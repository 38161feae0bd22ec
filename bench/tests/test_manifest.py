"""BENCHMARK.json against the benchmark contract, and against the files
it names."""

import json
import re

import pytest

from bench import harness
from bench.tests.conftest import REPO

M = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = M["end_to_end"] + M["per_layer"]


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["bench"]
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    cells = len(M["workloads"])
    assert 1 <= cells <= 24 and 1 <= len(M["configs"]) <= 24
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(1, cells // 2)
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_text():
    names = [x["name"] for x in M["configs"] + M["workloads"] + METRICS]
    names += [w["traffic"] for w in M["workloads"]]
    names += [k for c in M["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in M[group]}) == len(M[group])
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for text in ([w["why"] for w in M["workloads"] + M["configs"]]
                 + [m["layer"] for m in M["per_layer"]]
                 + [c["source"] for c in M["configs"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_end_to_end_metrics():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in M["workloads"]:
        here = [m for m in M["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(here) >= 2


def test_every_per_layer_metric_has_moves_workloads_and_a_reader():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    cells = {w["name"] for w in M["workloads"]}
    for m in M["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["workloads"]
        for w in m["workloads"]:
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])
        assert callable(harness.load_metric(REPO, m["name"]).read)
    for w in cells:
        assert any(w in m["workloads"] for m in M["per_layer"])


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_configuration_files(entry):
    cfg = json.loads((REPO / entry["file"]).read_text())
    assert entry["file"].startswith("bench/configs/")
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg["reduced"]) <= set(cfg["published"])
    for k, v in cfg["published"].items():
        assert cfg[k] != v, k
    assert cfg["tee"] in ("tdx", "none")
    e = cfg["engine"]
    assert e["max_len"] % e["page_size"] == 0
    assert max(e["prefill_buckets"]) < e["max_len"]
    compared = [k for k in harness.GAP_NUMBERS if k in cfg["checks"]]
    assert compared and all(cfg["checks"][k] > 0 for k in compared)
    harness.model_config(harness.Cell(entry["name"], 1, entry["name"], cfg,
                                      "", {}, [], [], REPO))


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cells_load_and_pool_holds_slots_longest(cell):
    c = harness.load_cell(cell["name"], REPO)
    e = c.config["engine"]
    p = c.mix["prompt"]["max"]
    bucket = harness.bucket_for(e["prefill_buckets"], p)
    need = max(bucket, p) + c.mix["output"]["max"] - 1
    assert need <= e["max_len"]
    # slots bound the batch: the pool holds every slot's longest request
    assert e["num_pages"] >= e["max_slots"] * -(-need // e["page_size"])
    assert max(e["prefill_buckets"]) >= p
