"""The families' seeded weights and the plain reference, on the CPU at tiny
sizes; the dense family pinned to what it read before families were split
out of the harness."""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from bench.tests.conftest import REPO, TINY_MODEL

CFG = dict(json.loads((REPO / "bench/configs/mistral-nemo-12b-20l.json")
                      .read_text()), **TINY_MODEL)
DENSE = harness.load_family(REPO, "dense")
CONFIGS = json.loads((REPO / "BENCHMARK.json").read_text())["configs"]


def test_layers_drawn_alone_equal_the_stacked_tree():
    tree = DENSE.program_params(7, CFG)
    key = jax.random.key(7)
    for i in range(CFG["num_hidden_layers"]):
        alone = DENSE.layer(key, CFG, i, jnp.bfloat16,
                            DENSE.layer_kind(CFG, i))
        stacked = jax.tree.map(lambda a: a[i], tree["layers"]["slot_0"])
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool((a == b).all()), alone, stacked))


@pytest.mark.parametrize("entry", CONFIGS, ids=lambda c: c["name"])
def test_weights_make_the_programs_tree(entry):
    """Every configuration's family draws the program's tree."""
    from repro.models.model import Model
    cfg = json.loads((REPO / entry["file"]).read_text())
    cell = harness.Cell(entry["name"], 1, entry["name"], cfg, "", {}, [], [],
                        REPO)
    want = Model(harness.model_config(cell)).abstract_params()
    got = jax.eval_shape(lambda: cell.family.program_params(0, cfg))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(want)]


def f32_program(seed):
    """The program's own full forward in float32 on the same weights."""
    from repro.models.model import Model
    cfg = dict(CFG, dtype="float32")
    cell = harness.Cell("t", 1, "t", cfg, "", {}, [], [], REPO)
    model = Model(dataclasses.replace(harness.model_config(cell),
                                      dtype="float32"))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          DENSE.program_params(seed, CFG))
    return model, params


def test_reference_agrees_with_the_programs_float32_forward():
    model, params = f32_program(3)
    seq = np.random.default_rng(0).integers(1, 256, 8).astype(np.int32)
    # greedy decoding by the program's full forward: each new token is the
    # program's first choice given everything before it
    with jax.default_matmul_precision("highest"):
        for _ in range(10):
            logits, _ = model._impl.forward(params, jnp.asarray(seq[None]))
            seq = np.append(seq, np.int32(jnp.argmax(logits[0, -1])))
    res = reference.gaps(DENSE, 3, CFG, [seq], [8])
    assert len(res[0]["served"]) == 10
    assert max(res[0]["served"]) < 1e-4


def test_control_reads_a_gap_the_reference_does_not():
    seq = np.random.default_rng(1).integers(1, 256, 40).astype(np.int32)
    res = reference.gaps(DENSE, 3, CFG, [seq], [1], control=True)[0]
    assert len(res["control"]) == len(res["served"]) == 39
    assert max(res["control"]) > 0.0
    assert min(res["control"]) >= 0.0


# Golden readings of the dense family, taken on the CPU from
# bench/weights.py, bench/costs.py and bench/reference.py as they stood
# before the family moved to bench/families/dense: an edit that moves one
# moves what the benchmark reads.

def test_dense_weights_are_bit_identical_to_the_golden_tree():
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(DENSE.program_params(7, CFG)):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == \
        "74d63cca69be3fdf36b0c3c7b2564019f3cfafecde5acee69c45cfebd177458b"


GOLDEN_COSTS = {
    "mistral-nemo-12b-20l": dict(
        param_bytes=12247787520, kv_bytes_per_token=81920,
        decode_step_bytes=12306360320,
        paged_attention_cost=(11665408.0, 2965504),
        token_flops=(12345835520.0, 11003658240.0),
        prefill_flops=2188966625280.0, paged_attention_layers=20),
    "deepseek-7b": dict(
        param_bytes=12981870592, kv_bytes_per_token=491520,
        decode_step_bytes=13333307392,
        paged_attention_cost=(11665408.0, 11714560),
        token_flops=(13129072640.0, 12290211840.0),
        prefill_flops=2439220428800.0, paged_attention_layers=30),
}


@pytest.mark.parametrize("name", sorted(GOLDEN_COSTS))
def test_dense_costs_equal_the_golden_counts(name):
    costs = DENSE.costs
    cfg = json.loads((REPO / f"bench/configs/{name}.json").read_text())
    want, ctx = GOLDEN_COSTS[name], [1, 200, 511]
    assert costs.param_bytes(cfg) == want["param_bytes"]
    assert costs.kv_bytes_per_token(cfg) == want["kv_bytes_per_token"]
    assert DENSE.decode_step_bytes(cfg, ctx) == want["decode_step_bytes"]
    assert DENSE.paged_attention_cost(cfg, ctx) == want["paged_attention_cost"]
    assert (DENSE.token_flops(cfg, 300.5, True),
            DENSE.token_flops(cfg, 300.5, False)) == want["token_flops"]
    assert DENSE.prefill_flops(cfg, 200) == want["prefill_flops"]
    assert DENSE.paged_attention_layers(cfg) == want["paged_attention_layers"]


def test_dense_reference_gaps_equal_the_golden_gaps():
    seq = np.random.default_rng(5).integers(1, 256, 16).astype(np.int32)
    res = reference.gaps(DENSE, 3, CFG, [seq], [4], control=True)[0]
    assert res["served"] == [
        0.26520732045173645, 0.6372271180152893, 0.4511606693267822,
        0.4346941411495209, 0.8635419607162476, 0.46903660893440247,
        0.7138844132423401, 0.26503467559814453, 0.1887216567993164,
        0.14650419354438782, 0.3839399516582489, 0.3988369107246399]
    assert res["control"] == [0.0, 0.0, 0.02706509828567505] + [0.0] * 9
