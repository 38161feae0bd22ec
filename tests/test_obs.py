"""Spans and counters inside the serving path (``repro.obs``): a disabled
span costs nothing, an enabled one lands in a profiler trace nested where
the work is, and the counters agree with what the engine and the channel
did."""

import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import smoke_config
from repro.core import TrustDomain
from repro.models import build_model
from repro.runtime import Engine, GenerationRequest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import program_spans, readers  # noqa: E402

TILE_BYTES = 65536   # one ChaCha20 tile: what an ingress message pads to
BLOCK_BYTES = 64     # one ChaCha20 block: what a one-token frame pads to

# each span's innermost enclosing program span, as the engine nests them
# (None: opened outside any program span, e.g. ingress at submit)
PARENTS = {
    "step": {None},
    "step.admit": {"step"},
    "step.prefill": {"step.admit"},
    "step.insert": {"step.admit"},
    "step.sampling": {"step"},
    "step.decode": {"step"},
    "decode.prepare": {"step.decode"},
    "decode.wait": {"step.decode"},
    "step.emit": {"step"},
    "step.kv_events": {"step", "kv.seal_slot", "kv.restore_slot"},
    "frame.seal": {"step.emit", "step.admit"},
    "frame.open": {"step.emit", "step.admit"},
    "ingress.seal": {None},
    "ingress.open": {None},
    "seal.kernel": {"frame.seal", "ingress.seal"},
    "seal.mac": {"frame.seal", "ingress.seal"},
    "unseal.verify": {"frame.open", "ingress.open"},
    "unseal.kernel": {"frame.open", "ingress.open"},
}


@pytest.fixture(scope="module")
def small_model():
    cfg = smoke_config("deepseek-7b")
    model = build_model(cfg)
    return model, model.init_params(jax.random.key(0))


def tdx_engine(model, params):
    return Engine(model, params, max_slots=2, max_len=64, prefill_len=8,
                  kv_backend="paged", kv_decode="kernel",
                  trust_domain=TrustDomain("tdx"))


def requests():
    return [GenerationRequest(prompt=np.arange(1, 9, dtype=np.int32),
                              max_new_tokens=4),
            GenerationRequest(prompt=np.arange(3, 8, dtype=np.int32),
                              max_new_tokens=3)]


@pytest.fixture(scope="module")
def traced_run(small_model):
    """Two requests through a tiny tdx engine under a CPU profiler trace
    with the program's tracing on; the engine is warmed first."""
    model, params = small_model
    eng = tdx_engine(model, params)
    for g in requests():
        eng.generate(g)
    stats = eng.td.channel.stats
    before = {**obs.counters(), "frames": stats.messages_out,
              "cipher_out": stats.cipher_bytes_out,
              "messages_in": stats.messages_in,
              "cipher_in": stats.cipher_bytes_in}
    trace_dir = tempfile.mkdtemp(prefix="obs_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    obs.enable()
    try:
        reqs = [eng.submit(g) for g in requests()]
        steps = 0
        while not eng.idle:
            eng.step()
            steps += 1
    finally:
        obs.disable()
        jax.profiler.stop_trace()
    return {"engine": eng, "reqs": reqs, "steps": steps, "before": before,
            "rec": program_spans.load(trace_dir)}


def test_disabled_span_is_the_shared_null_context(monkeypatch):
    def forbidden(*a, **kw):
        raise AssertionError("a disabled span built a TraceAnnotation")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    obs.disable()
    a = obs.span("frame.seal", stream=3)
    b = obs.span("step")
    assert a is obs.NULL and b is obs.NULL
    with a:
        with b:
            pass


def test_disabled_span_costs_under_a_microsecond():
    """Enter and exit, on this thread's CPU clock (time the thread spends
    descheduled on a busy host does not count)."""
    obs.disable()
    n = 20_000
    best = float("inf")
    for _ in range(7):
        t = time.thread_time()
        for _ in range(n):
            with obs.span("frame.seal", stream=7):
                pass
        best = min(best, (time.thread_time() - t) / n)
    assert best < 1e-6, best


def test_retrace_counts_against_innermost_span():
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(3))
    before = obs.counters()
    obs.enable()
    try:
        with obs.span("step"):
            with obs.span("step.decode"):
                f(jnp.ones(5))          # a new shape: traced again
            f(jnp.ones(5))              # cached: no trace
    finally:
        obs.disable()
    after = obs.counters()
    grew = {k: after[k] - before.get(k, 0) for k in after
            if k.startswith("traces.") and after[k] != before.get(k, 0)}
    assert grew.get("traces.step.decode", 0) >= 1
    assert "traces.step" not in grew
    # disabled: traces go uncounted
    f(jnp.ones(7))
    assert obs.counters() == after


def test_program_spans_nest_as_the_engine_does(traced_run):
    spans = traced_run["rec"]["program_spans"]
    seen = {s[0] for s in spans}
    for name in ("step", "step.admit", "step.prefill", "step.insert",
                 "step.sampling", "step.decode", "decode.prepare",
                 "decode.wait", "step.emit", "step.kv_events", "frame.seal",
                 "frame.open", "ingress.seal", "ingress.open", "seal.kernel",
                 "seal.mac", "unseal.verify", "unseal.kernel"):
        assert name in seen, name
    assert seen <= set(obs.SPANS)
    # one host thread: each span's parent is the innermost enclosing one
    for i, (name, s, e, _) in enumerate(spans):
        enclosing = [x for j, x in enumerate(spans)
                     if j != i and x[1] <= s and e <= x[2]]
        parent = min(enclosing, key=lambda x: x[2] - x[1])[0] \
            if enclosing else None
        assert parent in PARENTS[name], (name, parent)
    steps = [s for s in spans if s[0] == "step"]
    assert len(steps) == traced_run["steps"]


def test_frame_spans_carry_their_stream(traced_run):
    """One ``frame.seal`` and one ``frame.open`` per batch: per step that
    emitted, and per prefill whose first tokens left; their ``frames`` add
    up to one frame per token."""
    spans = traced_run["rec"]["program_spans"]
    streams = {r.stream_id for r in traced_run["reqs"]}
    tokens = sum(len(r.output) for r in traced_run["reqs"])
    prefill = [s for s in spans if s[0] == "step.prefill"]
    assert [(s[3]["rows"], s[3]["bucket"]) for s in prefill] == [(2, 8)]
    batches = traced_run["steps"] + len(prefill)
    for name in ("frame.seal", "frame.open"):
        frames = [s for s in spans if s[0] == name]
        assert len(frames) == batches, name
        assert {int(sid) for s in frames
                for sid in str(s[3]["streams"]).split()} == streams
        assert sum(s[3]["frames"] for s in frames) == tokens
    for name in ("seal.kernel", "unseal.kernel"):   # one trip per batch
        assert len([s for s in spans if s[0] == name
                    and not any(i[0].startswith("ingress.") and i[1] <= s[1]
                                and s[2] <= i[2] for i in spans)]) == batches
    now, was = obs.counters(), traced_run["before"]
    assert now["egress.batches"] - was.get("egress.batches", 0) == batches
    assert now["egress.frames"] - was.get("egress.frames", 0) == tokens


def test_engine_counters_match_the_engine(traced_run):
    now, was = obs.counters(), traced_run["before"]

    def moved(k):
        return now.get(k, 0) - was.get(k, 0)
    assert moved("engine.steps") == traced_run["steps"]
    # every output token but the prefill's first is one decoded row
    assert moved("engine.rows_decoded") == sum(
        len(r.output) - 1 for r in traced_run["reqs"])
    assert moved("engine.prefill_rows") == 2
    assert moved("engine.prefill_pad_rows") == 0
    assert moved("engine.prefill_pad_tokens") == 0 + 3


def test_cipher_bytes_are_whole_tiles_per_message(traced_run):
    stats = traced_run["engine"].td.channel.stats
    was = traced_run["before"]
    frames = stats.messages_out - was["frames"]
    assert frames == sum(len(r.output) for r in traced_run["reqs"])
    assert stats.cipher_bytes_out - was["cipher_out"] == frames * BLOCK_BYTES
    assert (stats.cipher_bytes_in - was["cipher_in"]
            == (stats.messages_in - was["messages_in"]) * TILE_BYTES)
    assert stats.bytes_out < stats.cipher_bytes_out


def test_compiled_programs_keep_the_benchmarks_module_names(small_model):
    """The benchmark's readers find prefill and decode by module name."""
    model, params = small_model
    eng = tdx_engine(model, params)
    calls = []
    decode_fn = eng.kv._decode_fn

    def record(*args):
        calls.append(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
            if isinstance(a, jax.Array) else a, args))
        return decode_fn(*args)
    eng.kv._decode_fn = record
    eng.generate(requests()[0])

    def module(lowered):
        text = lowered.as_text()
        return text.split("module @", 1)[1].split(" ", 1)[0]
    tokens = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    prefill = module(eng._prefill_fn.lower(eng.params, tokens,
                                           eng.kv.fresh_prefill_cache(1)))
    decode = module(decode_fn.lower(*calls[0]))
    assert readers.is_prefill_module(prefill), prefill
    assert readers.is_decode_module(decode), decode
    assert not readers.is_decode_module(prefill)
    assert not readers.is_prefill_module(decode)
