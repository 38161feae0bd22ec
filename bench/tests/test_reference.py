"""The seeded weights and the plain reference, on the CPU at tiny sizes."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, weights
from bench.tests.conftest import REPO, TINY_MODEL

CFG = dict(json.loads((REPO / "bench/configs/mistral-nemo-12b-20l.json")
                      .read_text()), **TINY_MODEL)


def test_layers_drawn_alone_equal_the_stacked_tree():
    tree = weights.program_params(7, CFG)
    key = jax.random.key(7)
    for i in range(CFG["num_hidden_layers"]):
        alone = weights.layer(key, CFG, i, jnp.bfloat16)
        stacked = jax.tree.map(lambda a: a[i], tree["layers"]["slot_0"])
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool((a == b).all()), alone, stacked))


@pytest.mark.parametrize("name", ["mistral-nemo-12b-20l", "deepseek-7b"])
def test_weights_make_the_programs_tree(name):
    from repro.models.model import Model
    cfg = json.loads((REPO / f"bench/configs/{name}.json").read_text())
    cell = harness.Cell(name, 1, name, cfg, "", {}, [], [], REPO)
    want = Model(harness.model_config(cell)).abstract_params()
    got = jax.eval_shape(lambda: weights.program_params(0, cfg))
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
                          weights.program_params(seed, CFG))
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
    res = reference.gaps(3, CFG, [seq], [8])
    assert len(res[0]["served"]) == 10
    assert max(res[0]["served"]) < 1e-4


def test_control_reads_a_gap_the_reference_does_not():
    seq = np.random.default_rng(1).integers(1, 256, 40).astype(np.int32)
    res = reference.gaps(3, CFG, [seq], [1], control=True)[0]
    assert len(res["control"]) == len(res["served"]) == 39
    assert max(res["control"]) > 0.0
    assert min(res["control"]) >= 0.0
