"""Window arithmetic: rates count every token in the window, in-flight
requests included; latencies count from when a request was due."""

import numpy as np
import pytest

from bench import harness, loadgen


def rec(due, times, in_window=True):
    spec = loadgen.Spec(0, np.zeros(4, np.int32), 8, True, 0,
                        in_window=in_window)
    r = harness.Rec(spec, 16, due=due, submit=due)
    r.times = list(times)
    r.first = times[0] if times else None
    return r


def run_data(kind, recs, seconds=10.0):
    cell = harness.Cell("c", 1, "cfg", {}, "t", {"kind": kind}, [], [], None)
    return harness.RunData(cell, seconds, 100.0, 100.0 + seconds, 4, recs,
                           [], [], {})


def test_tokens_per_s_counts_in_flight_and_not_completions():
    recs = [rec(95.0, [96.0, 99.0, 100.5, 101.0]),   # 2 tokens inside
            rec(99.0, [105.0, 106.0, 109.9, 110.0, 111.0]),  # 3 inside
            rec(108.0, [109.0])]                      # in flight at close
    out = harness.end_to_end(run_data("closed", recs), 5.0)
    assert out["tokens_per_s"] == pytest.approx(6 / 10.0)
    assert out["setup_s"] == 5.0


def test_ttft_from_due_and_itl_over_window_gaps():
    recs = [rec(101.0, [101.5, 101.6, 101.8]),
            rec(105.0, [106.0, 106.2]),
            rec(99.0, [99.5, 100.5], in_window=False),   # lead request
            rec(111.0, [111.2, 111.3])]                   # after the close
    out = harness.end_to_end(run_data("open", recs), 1.0)
    assert out["ttft_p75_ms"] == pytest.approx(
        np.percentile([500.0, 1000.0], 75))
    gaps = [100.0, 200.0, 200.0, 1000.0]   # later token inside the window
    assert out["itl_p50_ms"] == pytest.approx(np.percentile(gaps, 50))
    assert out["itl_p95_ms"] == pytest.approx(np.percentile(gaps, 95))
