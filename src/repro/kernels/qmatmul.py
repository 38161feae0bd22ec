"""int8 x int8 -> int32 tiled matmul Pallas kernel (the AMX -> MXU adaptation).

The paper's Insight 3/8: AMX int8/bf16 tiles double CPU inference speed and
shrink relative TEE overhead. The TPU analogue is the MXU's native int8 path:
we tile (M, K) x (K, N) into 128-aligned VMEM blocks, accumulate in an int32
VMEM scratch across the K grid dimension, and apply the (folded
activation x per-output-channel weight) scale on the final K step so the
output leaves VMEM once, in bf16.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics — sequential
accumulation); M, N parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


def _qmm_kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _emit():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * scale_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"))
def qmatmul(x_q: jax.Array, w_q: jax.Array, scale: jax.Array, *,
            bm: int = 128, bn: int = 128, bk: int = 128,
            out_dtype=jnp.bfloat16, interpret: bool | None = None) -> jax.Array:
    """x_q: int8 [M, K]; w_q: int8 [K, N]; scale: f32 [1, N]
    (activation scale already folded in). Returns [M, N] ``out_dtype``.

    M, K, N must be multiples of the block sizes (ops.py pads).
    """
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2 and scale.shape == (1, n)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    k_steps = k // bk
    return pl.pallas_call(
        functools.partial(_qmm_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((1, bn), lambda i, j, s: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=(interpret_default() if interpret is None
                   else interpret),
    )(x_q, w_q, scale)
