"""Causal flash attention Pallas kernel (blocked online softmax).

Prefill is the compute hot-spot of the serving path (paper Fig 7: self-attn
dominates block time). This kernel tiles Q and KV into VMEM blocks and keeps
the running (max, sum, acc) online-softmax state in VMEM scratch across the
KV grid dimension, so the S x S score matrix is never materialized in HBM —
the standard memory-roofline win, re-tiled for (8,128)-lane VMEM.

Layout: q/k/v are [heads_batched, seq, head_dim] (fold batch*heads outside).
Grid: (bh, q_blocks, kv_blocks), kv innermost sequential. Causal blocks where
kv_start > q_end are skipped via ``pl.when`` (their tiles still stream, but
no compute is issued — block-level masking handles the diagonal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, kv_steps: int, bq: int, bkv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip blocks entirely above the diagonal
    @pl.when(ki * bkv <= qi * bq + bq - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bkv, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

        m_prev = m_ref[...]                        # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # [bq, bkv]
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)           # [bkv, d]
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bkv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """Causal attention. q/k/v: [bh, s, d] with s % bq == s % bkv == 0."""
    bh, s, d = q.shape
    assert k.shape == v.shape == (bh, s, d)
    assert s % bq == 0 and s % bkv == 0, (s, bq, bkv)
    scale = 1.0 / np.sqrt(d)
    kv_steps = s // bkv
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, kv_steps=kv_steps,
                          bq=bq, bkv=bkv),
        grid=(bh, s // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=(interpret_default() if interpret is None
                   else interpret),
    )(q, k, v)
