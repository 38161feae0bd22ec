"""The program's own spans in a profiler trace, and the numbers they give.

``repro.obs`` opens spans inside the serving path (``step``, ``step.emit``,
``frame.seal``, ``seal.mac``, ...) as profiler annotations on the host
plane, on the trace's one clock, when its tracing is enabled. ``load``
reads them from the ``.xplane.pb`` as ``[name, start_ns, end_ns, meta]``
(``program_spans``). The result merges into ``trace_reduce.load``'s
record; every reader takes that record and returns None when it holds no
program spans (a trace of a program without them, or taken with their
tracing off).

The counter readers take the change of ``repro.obs.counters()`` merged
with the trust domain's ``ChannelStats`` over a stretch of the run.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Sequence

from bench import trace_reduce

# spans under which the host does the trust domain's crypto
FRAME = ("frame.seal", "frame.open")
TEE_PREFIXES = ("frame.", "ingress.", "seal.", "unseal.")


def load(trace_dir: str) -> dict:
    """``program_spans`` of the newest trace under ``trace_dir``; an empty
    dict when the program has no spans."""
    import jax
    try:
        from repro.obs import SPANS
    except ImportError:
        return {}
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    names = set(SPANS)
    spans: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.end_ns, dict(e.stats)]
                          for e in line.events if e.name in names]
    spans.sort(key=lambda s: s[1])
    return {"program_spans": spans}


# ---------------------------------------------------------------------------
# span arithmetic
# ---------------------------------------------------------------------------

def spans_named(rec: dict, w, names: Sequence[str]) -> List[list]:
    """Program spans named in ``names`` that lie inside the window."""
    return [s for s in rec["program_spans"]
            if s[0] in names and s[1] >= w[0] and s[2] <= w[1]]


def family_time(rec: dict, w, names: Sequence[str],
                within: Sequence[str] = ()) -> float:
    """Seconds in program spans named in ``names`` inside the window; with
    ``within``, only those nested in a span named there."""
    spans = spans_named(rec, w, names)
    if within:
        outer = spans_named(rec, w, within)
        starts = [s for _, s, _, _ in outer]

        def nested(s, e):
            i = bisect.bisect_right(starts, s) - 1
            return i >= 0 and outer[i][2] >= e
        spans = [x for x in spans if nested(x[1], x[2])]
    return sum(e - s for _, s, e, _ in spans) * 1e-9


def idle_by_program_span(rec: dict, w, busy_union=None) -> Dict[str, float]:
    """Idle device time split by the innermost program span open over it
    (or ``other``), in seconds: ``trace_reduce.idle_by_span`` over the
    program's spans in place of the benchmark's."""
    spans = {"host_spans": [s[:3] for s in rec["program_spans"]],
             "device_ops": rec.get("device_ops", [])}
    return trace_reduce.idle_by_span(spans, w, busy_union)


def idle_within(gaps, spans) -> float:
    """Seconds of the idle stretches ``gaps`` inside the given spans."""
    starts = [a for a, _ in gaps]
    return sum(trace_reduce.overlap(gaps, s[1], s[2], starts)
               for s in spans) * 1e-9


# ---------------------------------------------------------------------------
# per-layer readers
# ---------------------------------------------------------------------------

def egress_mac_ms_per_token(rec: dict, w, tokens: int) -> Optional[float]:
    """HMAC time of the egress frames (sealing and the host's check), per
    output token."""
    if "program_spans" not in rec or not tokens:
        return None
    t = family_time(rec, w, ("seal.mac", "unseal.verify"), within=FRAME)
    return 1e3 * t / tokens if t > 0 else None


def egress_kernel_ms_per_token(rec: dict, w, tokens: int) -> Optional[float]:
    """Time of the egress frames' ChaCha20 kernels, dispatch, device time
    and read-back, per output token."""
    if "program_spans" not in rec or not tokens:
        return None
    t = family_time(rec, w, ("seal.kernel", "unseal.kernel"), within=FRAME)
    return 1e3 * t / tokens if t > 0 else None


def tee_ms_per_token(rec: dict, w, tokens: int) -> Optional[float]:
    """Time in egress frames and ingress messages, per output token: the
    in-program twin of the benchmark's ``tee_host_ms_per_token``."""
    if "program_spans" not in rec or not tokens:
        return None
    t = family_time(rec, w, FRAME + ("ingress.seal", "ingress.open"))
    return 1e3 * t / tokens if t > 0 else None


def step_prep_ms(rec: dict, w, busy_union) -> Optional[float]:
    """Per ``step`` span, the host time with the device idle outside
    ``step.emit``, in ms, averaged over the window's steps."""
    if "program_spans" not in rec:
        return None
    steps = spans_named(rec, w, ("step",))
    if not steps:
        return None
    gaps = trace_reduce.idle(busy_union, w)
    emit = spans_named(rec, w, ("step.emit",))
    return 1e3 * (idle_within(gaps, steps) - idle_within(gaps, emit)) \
        / len(steps)


def emit_idle_ms(rec: dict, w, busy_union) -> Optional[float]:
    """Idle device time under ``step.emit``, in ms per ``step`` span."""
    if "program_spans" not in rec:
        return None
    steps = spans_named(rec, w, ("step",))
    if not steps:
        return None
    gaps = trace_reduce.idle(busy_union, w)
    return 1e3 * idle_within(gaps, spans_named(rec, w, ("step.emit",))) \
        / len(steps)


def idle_in_tee_share(rec: dict, w, busy_union) -> Optional[float]:
    """Share of the window with the device idle under an innermost span of
    the trust domain's crypto (frame, ingress, seal, unseal), in %."""
    if "program_spans" not in rec:
        return None
    gaps = idle_by_program_span(rec, w, busy_union)
    tee = sum(v for k, v in gaps.items() if k.startswith(TEE_PREFIXES))
    return 100.0 * tee / ((w[1] - w[0]) * 1e-9)


def counter_delta(before: dict, after: dict) -> Dict[str, float]:
    """What each counter (``repro.obs`` and ``ChannelStats`` alike) moved by
    between two snapshots."""
    return {k: v - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float))}


def egress_cipher_bytes_per_token(delta: dict) -> Optional[float]:
    """Ciphertext bytes the egress frames moved and MAC'd, per token."""
    if not delta.get("tokens_out") or "cipher_bytes_out" not in delta:
        return None
    return delta["cipher_bytes_out"] / delta["tokens_out"]


def traces_per_kstep(delta: dict) -> Optional[float]:
    """jaxpr traces counted by ``repro.obs`` per 1000 engine steps."""
    steps = delta.get("engine.steps", 0)
    if not steps:
        return None
    traces = sum(v for k, v in delta.items() if k.startswith("traces."))
    return 1e3 * traces / steps


def traces_by_span(delta: dict) -> Dict[str, float]:
    return {k[len("traces."):]: v for k, v in delta.items()
            if k.startswith("traces.") and v}
