"""Distributed layer: sharding parity, overlap collectives, pipeline,
compression, elastic rescale. Multi-device tests run in subprocesses with
forced host device counts so this process keeps its single-device view."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

# the subprocess-based classes below each jit-compile in an 8-device
# subprocess and carry pytest.mark.slow; TestCompression runs in-process
# and stays in the fast tier.
_slow = pytest.mark.slow

from repro.distributed.compression import (compress_decompress,
                                           compressed_bytes,
                                           make_grad_compressor)


class TestCompression:
    @given(seed=st.integers(0, 1000), scale=st.floats(1e-4, 10.0))
    @settings(max_examples=15, deadline=None)
    def test_error_feedback_bounded_bias(self, seed, scale):
        """With error feedback, accumulated compressed grads track the true
        accumulation (residual never grows unboundedly)."""
        err = jnp.zeros((128,))
        acc_t = jnp.zeros((128,))
        acc_c = jnp.zeros((128,))
        for i in range(30):
            g = jax.random.normal(jax.random.key(seed * 100 + i), (128,)) * scale
            acc_t += g
            deq, err = compress_decompress(g, err)
            acc_c += deq
        # residual bounded by one quantization step of the last grad
        denom = float(jnp.linalg.norm(acc_t)) + 1e-9
        assert float(jnp.linalg.norm(acc_c - acc_t)) / denom < 0.05

    def test_wire_bytes_4x_smaller(self):
        grads = {"a": jnp.zeros((1024, 1024), jnp.float32)}
        raw, wire = compressed_bytes(grads)
        assert raw / wire > 3.9

    def test_transform_stateful(self):
        tr, get_state = make_grad_compressor()
        g = {"w": jnp.asarray([0.001, 0.5, -0.3])}
        out = tr(g)
        assert get_state() is not None
        assert out["w"].shape == (3,)


class TestShardingRules:
    pytestmark = _slow
    def test_specs_cover_all_archs(self, subproc):
        out = subproc("""
import jax
from jax.sharding import PartitionSpec as P
from repro.configs import smoke_config
from repro.models import build_model
from repro.distributed import sharding
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
for name in ["deepseek-7b", "qwen3-32b", "rwkv6-3b", "dbrx-132b",
             "deepseek-v3-671b", "jamba-v0.1-52b", "chameleon-34b",
             "whisper-small", "mistral-nemo-12b", "deepseek-67b"]:
    cfg = smoke_config(name)
    m = build_model(cfg)
    specs = sharding.param_specs(cfg, m.abstract_params(), mesh)
    for path, s in jax.tree_util.tree_flatten_with_path(specs)[0]:
        assert isinstance(s, P), (name, path)
    c = sharding.cache_specs(cfg, m.abstract_cache(4, 16), mesh)
print("OK")
""", devices=8)
        assert "OK" in out

    def test_sharded_train_step_matches_single_device(self, subproc):
        out = subproc("""
import jax, jax.numpy as jnp
from repro.configs import smoke_config
from repro.models import build_model
from repro.distributed import sharding
from repro.train.optimizer import AdamWConfig
from repro.train.train_loop import init_train_state, make_train_step, abstract_train_state
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg = smoke_config("qwen3-32b")
m = build_model(cfg)
opt = AdamWConfig(lr=1e-3)
state = init_train_state(m, opt, jax.random.key(0))
batch = {"tokens": jnp.ones((4, 32), jnp.int32), "labels": jnp.ones((4, 32), jnp.int32)}
step = make_train_step(m, opt)
s1, m1 = jax.jit(step)(state, batch)
sspecs = sharding.state_specs(cfg, abstract_train_state(m, opt), mesh)
bspecs = sharding.batch_specs(cfg, jax.eval_shape(lambda: batch), mesh)
with mesh:
    f = jax.jit(step, in_shardings=(sharding.to_named(mesh, sspecs),
                                    sharding.to_named(mesh, bspecs)),
                out_shardings=(sharding.to_named(mesh, sspecs), None))
    s2, m2 = f(state, batch)
assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, (m1["loss"], m2["loss"])
d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)))
assert d < 2e-2, d
print("OK")
""", devices=8)
        assert "OK" in out


class TestOverlap:
    pytestmark = _slow
    def test_ring_collective_matmuls(self, subproc):
        out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from jax import shard_map
from repro.distributed.overlap import all_gather_matmul, matmul_reduce_scatter
mesh = make_mesh((8,), ("model",))
x = jax.random.normal(jax.random.key(1), (64, 32))
w = jax.random.normal(jax.random.key(2), (32, 48))
y = shard_map(lambda a, b: all_gather_matmul(a, b, "model"), mesh=mesh,
              in_specs=(P("model", None), P(None, None)),
              out_specs=P(None, None), check_vma=False)(x, w)
assert jnp.allclose(y, x @ w, atol=1e-4)
xk = jax.random.normal(jax.random.key(3), (64, 128))
wk = jax.random.normal(jax.random.key(4), (128, 48))
y2 = shard_map(lambda a, b: matmul_reduce_scatter(a, b, "model"), mesh=mesh,
               in_specs=(P(None, "model"), P("model", None)),
               out_specs=P("model", None), check_vma=False)(xk, wk)
assert jnp.allclose(y2, xk @ wk, atol=1e-3)
print("OK")
""", devices=8)
        assert "OK" in out


class TestPipeline:
    pytestmark = _slow
    def test_gpipe_matches_sequential_and_trains(self, subproc):
        out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed.pipeline import make_gpipe
S, d = 4, 16
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("pipe", "data"))
ws = jax.random.normal(jax.random.key(5), (S, d, d)) * 0.3
stage = lambda w, x: jnp.tanh(x @ w)
pipe = make_gpipe(mesh, "pipe", stage, P("pipe", None, None),
                  P(None, None, None), P(None, None, None))
mb = jax.random.normal(jax.random.key(6), (6, 8, d))
out = pipe(ws, mb)
ref = mb
for i in range(S):
    ref = jnp.tanh(ref @ ws[i])
assert jnp.allclose(out, ref, atol=1e-4)
g = jax.grad(lambda w: jnp.sum(pipe(w, mb) ** 2))(ws)
gr = jax.grad(lambda w: jnp.sum(
    jnp.tanh(jnp.tanh(jnp.tanh(jnp.tanh(mb @ w[0]) @ w[1]) @ w[2]) @ w[3]) ** 2))(ws)
assert jnp.allclose(g, gr, atol=1e-3), float(jnp.max(jnp.abs(g - gr)))
print("OK")
""", devices=8)
        assert "OK" in out


class TestElastic:
    pytestmark = _slow
    def test_save_mesh_a_restore_mesh_b(self, subproc, tmp_path):
        out = subproc(f"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs import smoke_config
from repro.models import build_model
from repro.distributed import sharding
from repro.train.checkpoint import save_checkpoint, restore_checkpoint
cfg = smoke_config("deepseek-7b")
m = build_model(cfg)
params = m.init_params(jax.random.key(0))
from repro.launch.mesh import make_mesh
mesh_a = make_mesh((2, 4), ("data", "model"))
specs_a = sharding.to_named(mesh_a, sharding.param_specs(cfg, m.abstract_params(), mesh_a))
pa = jax.tree.map(jax.device_put, params, specs_a)
save_checkpoint(r"{tmp_path}", 1, pa)
# "rescale": restore onto a differently-shaped mesh
mesh_b = make_mesh((4, 2), ("data", "model"))
specs_b = sharding.to_named(mesh_b, sharding.param_specs(cfg, m.abstract_params(), mesh_b))
pb = restore_checkpoint(r"{tmp_path}", 1, jax.eval_shape(lambda: params), shardings=specs_b)
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(pb)):
    assert bool(jnp.all(a == b))
print("OK")
""", devices=8)
        assert "OK" in out
