"""Fixtures for the benchmark's own tests: CPU, tiny sizes.

``make_root`` copies ``bench/`` and ``BENCHMARK.json`` into a temporary
checkout and adds, as new files only, a tiny configuration, a traffic mix
and a cell that uses them, plus a peaks entry for the CPU so the harness
can run here with its look for a chip skipped.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

TINY_MODEL = dict(hidden_size=64, head_dim=16, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  num_hidden_layers=2, vocab_size=256)
TINY_ENGINE = {"max_slots": 4, "max_len": 64, "page_size": 16,
               "num_pages": 16, "prefill_buckets": [16, 32],
               "kv_alloc": "reserve"}
LENGTHS = {"prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                      "min": 4, "max": 32},
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 4, "max": 16}}
TINY_MIXES = {
    "tiny_open": dict(kind="open", rate_per_s=8, lead_s=0.5, greedy_every=2,
                      sampling={"temperature": 0.7, "top_p": 0.9},
                      check_requests=4, **LENGTHS),
    "tiny_closed": dict(kind="closed", pool=16, queue_depth=2, lead_s=0.5,
                        greedy_every=1, check_requests=4, **LENGTHS),
}
# the widest logit gap at these widths on the CPU, over 11 seeds of each
# tiny cell: the bf16 program 0.0-0.0055, the float8 control 0.0098-0.079
TINY_LIMIT = 0.01
# the mean gap over every checked token, the same runs of ``tiny.closed``:
# the program 0.0-0.00018, the control 0.0017-0.0087
TINY_MEAN_LIMIT = 0.001


def make_root(tmp: Path) -> Path:
    """A checkout with cells ``tiny.open`` and ``tiny.closed`` added."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = json.loads((REPO / "bench/configs/mistral-nemo-12b-20l.json")
                     .read_text())
    cfg.update(TINY_MODEL, engine=TINY_ENGINE,
               checks={"logit_gap": TINY_LIMIT, "min_checked_tokens": 10})
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    for name, mix in TINY_MIXES.items():
        (tmp / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
    peaks = json.loads((REPO / "bench/peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
                               "source": "placeholder for CPU tests"}
    (tmp / "bench/peaks.json").write_text(json.dumps(peaks))
    m = json.loads((REPO / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"})
    for kind, e2e in (("open", ("ttft_p75_ms", "itl_p50_ms", "itl_p95_ms")),
                      ("closed", ("tokens_per_s",))):
        cell = f"tiny.{kind}"
        m["workloads"].append({"name": cell, "config": "tiny",
                               "traffic": f"tiny_{kind}", "chips": 1,
                               "why": "test"})
        for x in m["end_to_end"]:
            if x["name"] in e2e:
                x["workloads"].append(cell)
        suffix = ".chat" if kind == "open" else ".batch"
        for x in m["per_layer"]:
            if x["name"].endswith(suffix):
                x["workloads"].append(cell)
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    return tmp


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    return make_root(tmp_path_factory.mktemp("checkout"))
