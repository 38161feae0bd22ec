"""Spans and counters inside the serving path.

``span(name, **meta)`` marks a stretch of host work. With tracing enabled
it is a ``jax.profiler.TraceAnnotation``: the span lands on the profiler's
host plane, on the same clock as the device planes, with ``meta`` as its
arguments (``frames=`` and ``streams=`` on a batch of egress frames,
``rows=``/``bucket=`` on prefills). Disabled, it returns one shared null context: nothing is
allocated for it and the profiler is never touched. ``enable()`` and
``disable()`` switch it; tracing starts disabled.

``count(name, n)`` adds to a plain dict of integers that is always kept;
``counters()`` returns a copy of it.

While tracing is enabled, a ``jax.monitoring`` listener (registered at the
first ``enable()``) counts each jaxpr trace under ``traces.<span>`` and each
compile or persistent-cache fetch under ``compiles.<span>``, where
``<span>`` is the innermost program span open on the calling thread, or
``outside``.

An operator's use::

    jax.profiler.start_trace(log_dir)
    obs.enable()
    ...                      # serve
    obs.disable()
    jax.profiler.stop_trace()
    obs.counters(), trust_domain.channel.stats
"""

from __future__ import annotations

import threading
from typing import Dict, List

import jax

# every span the program opens, outermost first where they nest
SPANS = (
    "step", "step.admit", "step.prefill", "step.insert", "step.sampling",
    "step.decode", "decode.prepare", "decode.wait", "step.emit",
    "step.kv_events", "kv.seal_slot", "kv.restore_slot",
    "frame.seal", "frame.open", "ingress.seal", "ingress.open",
    "seal.kernel", "seal.mac", "unseal.verify", "unseal.kernel",
)
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")

_enabled = False
_listening = False
_counts: Dict[str, int] = {}
_local = threading.local()


class _Null:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NULL = _Null()


def _stack() -> List[str]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "_annotation")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self._annotation = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self):
        _stack().append(self.name)
        self._annotation.__enter__()

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        _stack().pop()
        return False


def span(name: str, **meta):
    """A context manager over one stretch of host work (see the module
    docstring)."""
    if not _enabled:
        return NULL
    return _Span(name, meta)


def enable() -> None:
    global _enabled, _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listening = True
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def count(name: str, n: int = 1) -> None:
    _counts[name] = _counts.get(name, 0) + n


def counters() -> Dict[str, int]:
    return dict(_counts)


def _on_event(event: str, duration: float, **_) -> None:
    if not _enabled:
        return
    if event == TRACE_EVENT:
        kind = "traces"
    elif event in COMPILE_EVENTS:
        kind = "compiles"
    else:
        return
    stack = _stack()
    count(f"{kind}.{stack[-1] if stack else 'outside'}")
