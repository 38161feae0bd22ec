"""The harness end to end on the CPU at tiny sizes, with its look for a
chip skipped: new cells from new files only, refusal off the TPU, and the
check failing when the timed path is broken underneath."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.conftest import REPO, TINY_LIMIT, TINY_MEAN_LIMIT, make_root

SEED = 2 ** 32 + 12345


def run(root, cell, trace=False, seconds=2.0, seed=SEED):
    return harness.run(cell, seed, seconds, trace, root=root,
                       require_tpu=False, log=lambda s: None)


def test_refuses_off_the_tpu(tiny_root):
    with pytest.raises(harness.NoDevice):
        harness.run("tiny.open", 1, 1.0, False, root=tiny_root,
                    log=lambda s: None)


def test_command_prints_no_result_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "ds7b.batch.tdx", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().endswith("}")


def test_command_fails_with_only_the_benchmarks_files(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "ds7b.batch.tdx", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


def test_new_config_traffic_and_metric_run_from_new_files(tiny_root):
    """The tiny configuration and mixes are new files under
    bench/configs and bench/traffic; this adds a metric the same way."""
    (tiny_root / "bench/metrics/tokens_seen.tiny.py").write_text(
        'def read(run):\n'
        '    return sum(s.tokens for s in run.window_steps()) or None\n')
    m = json.loads((tiny_root / "BENCHMARK.json").read_text())
    m["per_layer"].append({"name": "tokens_seen.tiny", "unit": "tokens",
                           "better": "higher", "source": "program_counter",
                           "layer": "device", "moves": "tokens_per_s",
                           "workloads": ["tiny.closed"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(m))
    traced = run(tiny_root, "tiny.closed", trace=True)
    assert traced["correct"] and traced["failed"] == 0
    assert traced["metrics"]["tokens_seen.tiny"]["value"] > 0
    assert traced["metrics"]["batch_occupancy.batch"]["value"] > 0
    assert set(traced["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes", "busy_s",
                                     "window_s"}
    assert list(traced)[-1] == "checks" and "breakdown" in traced
    plain = run(tiny_root, "tiny.closed")
    assert set(plain["metrics"]) == {"tokens_per_s", "setup_s"}


def test_open_loop_cell_reports_its_latencies(tiny_root):
    out = run(tiny_root, "tiny.open")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"ttft_p75_ms", "itl_p50_ms",
                                   "itl_p95_ms", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["logit_gap"]["value"] <= TINY_LIMIT


def test_altered_token_is_not_correct(tiny_root, monkeypatch):
    """A token altered where the engine produces it."""
    from repro.runtime.engine import Engine
    emit = Engine._emit_token
    monkeypatch.setattr(Engine, "_emit_token", lambda self, slot, tok: emit(
        self, slot, (int(tok) + 1) % self._vocab))
    out = run(tiny_root, "tiny.closed")
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > TINY_LIMIT


def test_altered_egress_is_not_correct(tiny_root, monkeypatch):
    """A token altered on its way out of the trust domain."""
    from repro.core.confidential import TrustDomain
    egress = TrustDomain.egress_tokens
    monkeypatch.setattr(TrustDomain, "egress_tokens",
                        lambda self, sid, toks: [t ^ 1 for t in
                                                 egress(self, sid, toks)])
    out = run(tiny_root, "tiny.open")
    assert not out["correct"]
    assert out["checks"]["egress_mismatch"]["value"] > 0


@pytest.mark.parametrize("cell", ["tiny.closed", "tiny.open"])
def test_float8_control_is_not_correct(tiny_root, cell):
    """The float8 control in the program's place fails the same limit."""
    out = harness.run(cell, SEED, 2.0, False, root=tiny_root,
                      require_tpu=False, log=lambda s: None, control=True)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > TINY_LIMIT
    assert out["checks"]["egress_mismatch"]["value"] == 0


def test_mean_gap_check_passes_the_program_and_fails_the_control(tmp_path):
    """A configuration that compares the mean gap over the checked tokens
    in place of the widest one."""
    root = make_root(tmp_path)
    path = root / "bench/configs/tiny.json"
    cfg = json.loads(path.read_text())
    cfg["checks"] = {"logit_gap_mean": TINY_MEAN_LIMIT,
                     "min_checked_tokens": 10}
    path.write_text(json.dumps(cfg))
    program = run(root, "tiny.closed")
    assert program["correct"], program["checks"]
    assert set(program["checks"]) == {"logit_gap_mean", "egress_mismatch",
                                      "checked_tokens"}
    control = harness.run("tiny.closed", SEED, 2.0, False, root=root,
                          require_tpu=False, log=lambda s: None, control=True)
    assert not control["correct"]
    assert control["checks"]["logit_gap_mean"]["value"] > TINY_MEAN_LIMIT
