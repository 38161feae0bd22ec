"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

For the multi-pod mesh the ``pod`` axis can run as pipeline stages instead of
extra data parallelism: stage s holds layers [s*L/S, (s+1)*L/S) and
microbatches flow through a (compute || ppermute) schedule with the classic
(S-1) bubble. Backward falls out of jax autodiff (the transpose of ppermute
is the reverse permute), so the same function trains.

This is an opt-in config (DESIGN.md §4); the dry-run's default multi-pod
mapping keeps ``pod`` as hierarchical DP.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Params = Any


def _ring(axis_name: str):
    n = jax.lax.axis_size(axis_name)
    return [(i, (i + 1) % n) for i in range(n)]


def gpipe_local(stage_fn: Callable[[Params, jax.Array], jax.Array],
                stage_params: Params, microbatches: jax.Array,
                axis_name: str) -> jax.Array:
    """Runs inside shard_map. ``microbatches``: [M, mb, ...] (same on every
    rank; only rank 0 consumes them). Returns [M, mb, ...] outputs valid on
    the LAST stage (zeros elsewhere).
    """
    s = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    carry = jnp.zeros_like(microbatches[0])
    outputs = jnp.zeros((m,) + microbatches.shape[1:], microbatches.dtype)

    for t in range(m + s - 1):
        inject = microbatches[min(t, m - 1)]
        x_in = jnp.where(idx == 0, inject, carry)
        y = stage_fn(stage_params, x_in)
        # emit: last stage finishes microbatch t-(s-1) at time t
        mb_idx = t - (s - 1)
        if mb_idx >= 0:
            emit = jnp.where(idx == s - 1, y, 0).astype(outputs.dtype)
            outputs = outputs.at[mb_idx].set(emit)
        # shift activations to the next stage
        carry = jax.lax.ppermute(y, axis_name, _ring(axis_name))
    return outputs


def make_gpipe(mesh: Mesh, axis_name: str,
               stage_fn: Callable[[Params, jax.Array], jax.Array],
               param_spec: P, in_spec: P, out_spec: P):
    """Wrap gpipe_local in shard_map for the given mesh axis.

    ``param_spec`` shards the stacked stage params [S, ...] over the axis;
    inputs/outputs are replicated ([M, mb, ...] everywhere, with the result
    broadcast from the last stage via psum of the zero-padded emits).
    """

    def pipelined(stacked_params: Params, microbatches: jax.Array) -> jax.Array:
        def local(params_local, mb):
            params_one = jax.tree.map(lambda x: x[0], params_local)
            out = gpipe_local(stage_fn, params_one, mb, axis_name)
            # broadcast final outputs to all ranks (only last stage nonzero)
            return jax.lax.psum(out, axis_name)

        return jax.shard_map(local, mesh=mesh,
                             in_specs=(param_spec, in_spec),
                             out_specs=out_spec,
                             check_vma=False)(stacked_params, microbatches)

    return pipelined
