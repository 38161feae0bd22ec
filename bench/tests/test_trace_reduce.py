"""The trace reduction on a small recorded trace (times in ns)."""

import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

REC = json.loads((Path(__file__).parent / "data" / "small_trace.json")
                 .read_text())


def test_window_busy_and_idle():
    w = tr.window(REC)
    assert w == (1000, 11000)
    busy = tr.busy(REC, w)
    # ops 900-2000 (clipped to 1000), 1500-2500 (overlaps), 4000-5000,
    # 4500-4800 (inside), 9000-12000 (clipped to 11000)
    assert busy == [(1000, 2500), (4000, 5000), (9000, 11000)]
    assert tr.total(busy) == 4500
    assert tr.idle(busy, w) == [(2500, 4000), (5000, 9000)]


def test_kernel_time_by_name():
    w = tr.window(REC)
    t = tr.time_by_name(REC["device_ops"], w,
                        lambda n: n.startswith("paged_attention"))
    assert t == 1000 + 300
    top = tr.top_ops(REC, w, n=2)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(3000e-9)


def test_gap_attribution_innermost_span():
    w = tr.window(REC)
    got = tr.idle_by_span(REC, w)
    # idle 2500-4000: engine.step 2000-6000 holds tee.egress 3000-3500
    # idle 5000-9000: engine.step to 6000, gen.wait 6000-8500, other after
    assert got == pytest.approx({"engine.step": 1000e-9 + 1000e-9,
                                 "tee.egress": 500e-9,
                                 "gen.wait": 2500e-9, "other": 500e-9})
    assert sum(got.values()) == pytest.approx(
        tr.total(tr.idle(tr.busy(REC, w), w)) * 1e-9)


def test_span_self_time():
    w = tr.window(REC)
    own = tr.span_self_times(REC, w, "engine.step")
    # 2000-6000 with busy 2000-2500 and 4000-5000 inside
    assert own == pytest.approx([2500e-9])
