"""Compute/communication overlap: ring collective matmuls (shard_map).

XLA hides some collective latency, but the big TP wins come from *structural*
overlap: decomposing all-gather->matmul and matmul->reduce-scatter into a
ring of (chunk matmul || ppermute) steps so the ICI transfer of chunk i+1
runs under the MXU work of chunk i. These are the beyond-paper optimizations
applied in the §Perf hillclimb for collective-bound cells.

Both functions are written for use inside ``shard_map`` (manual collectives)
and are verified against their unoverlapped one-shot equivalents.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp



def _ring_perm(axis_name: str, shift: int = 1):
    n = jax.lax.axis_size(axis_name)
    return [(i, (i + shift) % n) for i in range(n)]


def all_gather_matmul(x_local: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """y = all_gather(x, axis) @ w, overlapped.

    x_local: [m_l, k] (this rank's rows); w: [k, n] (replicated or local TP
    shard). Returns [m_l * p, n]. Each ring step matmuls the chunk currently
    held while the next chunk is in flight.
    """
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m_l = x_local.shape[0]
    out = jnp.zeros((m_l * p, w.shape[1]), jnp.promote_types(x_local.dtype, w.dtype))
    chunk = x_local
    for step in range(p):
        src = (idx - step) % p            # whose rows we currently hold
        y = chunk @ w                      # compute...
        if step + 1 < p:
            chunk = jax.lax.ppermute(chunk, axis_name, _ring_perm(axis_name))
        out = jax.lax.dynamic_update_slice(out, y.astype(out.dtype),
                                           (src * m_l, 0))  # ...while data moves
    return out


def matmul_reduce_scatter(x: jax.Array, w_local: jax.Array,
                          axis_name: str) -> jax.Array:
    """y_local = reduce_scatter(x @ w, axis) over the contraction shards.

    x: [m, k_l]; w_local: [k_l, n] (both K-sharded). Full result would be
    sum_p x_p @ w_p, [m, n]; each rank keeps rows [idx*m_l, (idx+1)*m_l).
    Ring: a partial-sum buffer travels the ring, each rank adding its local
    contribution for the buffer's eventual owner while computing the next.
    """
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = x.shape[0]
    assert m % p == 0, (m, p)
    m_l = m // p

    def local_chunk(owner):
        start = owner * m_l
        return jax.lax.dynamic_slice(x, (start, 0), (m_l, x.shape[1])) @ w_local

    # buffer starts as our contribution for rank (idx+p-1); after p-1 hops,
    # each rank adding its own contribution, it arrives at its owner complete.
    buf = local_chunk((idx + p - 1) % p)
    for step in range(p - 1):
        buf = jax.lax.ppermute(buf, axis_name, _ring_perm(axis_name))
        buf = buf + local_chunk((idx + p - 2 - step) % p)
    return buf
