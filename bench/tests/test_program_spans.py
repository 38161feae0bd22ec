"""The readers of the program's own spans and counters, on a small recorded
trace (times in ns; every value worked out by hand in the comments)."""

import json
from pathlib import Path

import pytest

from bench import program_spans as ps
from bench import trace_reduce as tr

DATA = Path(__file__).parent / "data"
REC = json.loads((DATA / "small_trace_program.json").read_text())
# a trace of a program without spans of its own
BARE = json.loads((DATA / "small_trace.json").read_text())


@pytest.fixture
def win():
    w = tr.window(REC)
    assert w == (1000, 21000)
    busy = tr.busy(REC, w)
    # idle: 3000-8000, 9000-11000, 14000-20000 (13000 in all)
    assert tr.idle(busy, w) == [(3000, 8000), (9000, 11000), (14000, 20000)]
    return w, busy


def test_idle_by_innermost_program_span(win):
    w, busy = win
    got = ps.idle_by_program_span(REC, w, busy)
    assert got == pytest.approx({k: v * 1e-9 for k, v in {
        "step.sampling": 500, "decode.prepare": 500,
        "decode.wait": 3000 + 500,          # 4000-7000, 14000-14500
        "seal.kernel": 600 + 200,           # a frame's and ingress's
        "seal.mac": 300 + 100 + 200,
        "frame.seal": 100 + 500 + 300,      # around its children
        "unseal.kernel": 400 + 1000, "frame.open": 100 + 1000,
        "step.emit": 500 + 500 + 1000, "ingress.seal": 100,
        "other": 200 + 400 + 1000}.items()})
    assert sum(got.values()) == pytest.approx(13000e-9)


def test_idle_in_tee_share(win):
    w, busy = win
    # seal.kernel 800 + seal.mac 600 + frame.seal 900 + unseal.kernel 1400
    # + frame.open 1100 + ingress.seal 100 = 4900 of a 20000 window
    assert ps.idle_in_tee_share(REC, w, busy) == pytest.approx(24.5)


def test_egress_mac_and_kernel_per_token(win):
    w, _ = win
    # inside frame.* only (the ingress message's seal.* is left out):
    # mac 300 + 200 + 200 ns, kernels 600 + 1200 + 1000 ns, 2 tokens
    assert ps.egress_mac_ms_per_token(REC, w, 2) == pytest.approx(700e-6 / 2)
    assert ps.egress_kernel_ms_per_token(REC, w, 2) == pytest.approx(
        2800e-6 / 2)
    # frames 1000 + 1500 + 1000 + 2000 and the ingress message 400
    assert ps.tee_ms_per_token(REC, w, 2) == pytest.approx(5900e-6 / 2)


def test_step_prep_and_emit_idle(win):
    w, busy = win
    # two steps inside the window (the third runs past its end); idle in
    # them 6000 + 5000, of which under step.emit 2000 + 4500
    assert ps.step_prep_ms(REC, w, busy) == pytest.approx(
        (11000 - 6500) * 1e-6 / 2)
    assert ps.emit_idle_ms(REC, w, busy) == pytest.approx(6500e-6 / 2)
    # the two add up to the idle time inside the steps; the benchmark's
    # step_host_ms reads its own span around each call, idle 300 ns longer
    # (10000-10100, 10900-11000, 19000-19100)
    own = tr.span_self_times(REC, w, "engine.step", busy)
    assert sum(own) / len(own) * 1e3 == pytest.approx(
        ps.step_prep_ms(REC, w, busy) + ps.emit_idle_ms(REC, w, busy)
        + 300e-6 / 2)


def test_counter_readers():
    before = {"engine.steps": 10, "tokens_out": 5,
              "cipher_bytes_out": 5 * 65536, "traces.step.prefill": 3}
    after = {"engine.steps": 2010, "tokens_out": 105,
             "cipher_bytes_out": 105 * 65536, "traces.step.prefill": 9,
             "traces.outside": 1, "collective_s": 0.5}
    d = ps.counter_delta(before, after)
    assert d["engine.steps"] == 2000 and d["traces.outside"] == 1
    assert ps.egress_cipher_bytes_per_token(d) == 65536
    assert ps.traces_per_kstep(d) == pytest.approx(1e3 * 7 / 2000)
    assert ps.traces_by_span(d) == {"step.prefill": 6, "outside": 1}
    assert ps.egress_cipher_bytes_per_token({}) is None
    assert ps.traces_per_kstep({}) is None


def test_readers_read_nothing_without_program_spans():
    w = tr.window(BARE)
    busy = tr.busy(BARE, w)
    assert ps.egress_mac_ms_per_token(BARE, w, 5) is None
    assert ps.egress_kernel_ms_per_token(BARE, w, 5) is None
    assert ps.tee_ms_per_token(BARE, w, 5) is None
    assert ps.step_prep_ms(BARE, w, busy) is None
    assert ps.emit_idle_ms(BARE, w, busy) is None
    assert ps.idle_in_tee_share(BARE, w, busy) is None


def test_trace_program_on_the_cpu(tiny_root):
    """``bench/trace_program.py`` end to end on a tiny confidential cell:
    the program's spans and counters give every number."""
    from bench import trace_program
    from repro import obs
    out = trace_program.run("tiny.open", 2 ** 33 + 5, 3.0, root=tiny_root,
                            require_tpu=False, log=lambda s: None)
    assert obs.span("step") is obs.NULL     # tracing is off again
    p = out["program"]
    assert p["egress_cipher_bytes_per_token"] == 64     # one ChaCha20 block
    for k in ("egress_mac_ms_per_token", "egress_kernel_ms_per_token",
              "tee_ms_per_token", "step_prep_ms", "emit_idle_ms",
              "idle_in_tee_share", "traces_per_kstep"):
        assert p[k] is not None and p[k] >= 0, k
    assert (p["egress_mac_ms_per_token"] + p["egress_kernel_ms_per_token"]
            <= p["tee_ms_per_token"])
    assert "tee_host_ms_per_token.chat" in out["per_layer"]
