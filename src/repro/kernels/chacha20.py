"""ChaCha20 keystream + XOR Pallas kernel — on-device unseal of sealed tensors.

This is the TPU-native analogue of TDX/SGX inline memory encryption
(DESIGN.md §2): sealed weights/KV pages live in HBM as ciphertext and are
decrypted on the way into compute. ChaCha20 (RFC 8439) is integer-only
(add/xor/rotl on uint32) and vectorizes perfectly on the VPU: each lane
computes an independent 64-byte block.

Data layout: a sealed buffer is a uint32 array of shape [16, N] — word w of
block b at [w, b] — so the lane dimension is the block counter and the kernel
is a pure elementwise pipeline with (16, BLOCKS)-shaped VMEM tiles. The host
packs bytes into this layout once at seal time (core/sealing.py), i.e.
ciphertext is stored on disk in the kernel-friendly layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default

# 128-lane multiple; 1024 blocks/tile = 64 KiB keystream per tile, well under
# VMEM while giving the VPU long vectors.
BLOCKS_PER_TILE = 1024

CONSTANTS = (0x61707865, 0x3320646E, 0x79622D32, 0x6B206574)


def _rotl(x: jax.Array, n: int) -> jax.Array:
    return (x << jnp.uint32(n)) | (x >> jnp.uint32(32 - n))


def _quarter(state, a, b, c, d):
    sa, sb, sc, sd = state[a], state[b], state[c], state[d]
    sa = sa + sb
    sd = _rotl(sd ^ sa, 16)
    sc = sc + sd
    sb = _rotl(sb ^ sc, 12)
    sa = sa + sb
    sd = _rotl(sd ^ sa, 8)
    sc = sc + sd
    sb = _rotl(sb ^ sc, 7)
    state[a], state[b], state[c], state[d] = sa, sb, sc, sd


def chacha_block_words(key_words, nonce_words, counters):
    """Vectorized ChaCha20 block fn. counters: uint32 array (any shape).

    Returns a list of 16 uint32 arrays shaped like ``counters``.
    Shared by the Pallas kernel body and the jnp reference (ref.py), so the
    round structure has a single source of truth; the *kernel* is the tiled
    pallas_call wrapping below.
    """
    shape = counters.shape
    full = lambda v: jnp.full(shape, v, jnp.uint32)
    init = ([full(c) for c in CONSTANTS]
            + [jnp.broadcast_to(w.astype(jnp.uint32), shape) for w in key_words]
            + [counters.astype(jnp.uint32)]
            + [jnp.broadcast_to(w.astype(jnp.uint32), shape) for w in nonce_words])
    state = list(init)
    for _ in range(10):  # 10 double rounds = 20 rounds
        _quarter(state, 0, 4, 8, 12)
        _quarter(state, 1, 5, 9, 13)
        _quarter(state, 2, 6, 10, 14)
        _quarter(state, 3, 7, 11, 15)
        _quarter(state, 0, 5, 10, 15)
        _quarter(state, 1, 6, 11, 12)
        _quarter(state, 2, 7, 8, 13)
        _quarter(state, 3, 4, 9, 14)
    return [s + i for s, i in zip(state, init)]


def _xor_kernel(base_ref, key_ref, nonce_ref, data_ref, out_ref):
    """One tile: data (16, BLOCKS) uint32 XOR keystream for counters
    [base + pid*BLOCKS, ...)."""
    pid = pl.program_id(0)
    lane = jax.lax.broadcasted_iota(jnp.uint32, (1, data_ref.shape[1]), 1)
    counters = (base_ref[0]
                + pid.astype(jnp.uint32) * jnp.uint32(data_ref.shape[1]) + lane)
    key_words = [key_ref[0, i] for i in range(8)]
    nonce_words = [nonce_ref[0, i] for i in range(3)]
    words = chacha_block_words(key_words, nonce_words, counters)
    ks = jnp.concatenate(words, axis=0)  # (16, BLOCKS)
    out_ref[...] = data_ref[...] ^ ks


def chacha20_xor_blocked(key: jax.Array, nonce: jax.Array, data: jax.Array,
                         counter_base=0,
                         interpret: bool | None = None) -> jax.Array:
    """XOR ``data`` (uint32 [16, N], N multiple of BLOCKS_PER_TILE) with the
    ChaCha20 keystream for counters ``counter_base ..`` (mod 2**32).
    Involution: applying twice returns the input. ``counter_base`` (an int
    or a uint32 scalar) is an operand, not a compile-time constant, so a
    tensor XORed in chunks compiles once per chunk shape."""
    return _xor_blocked(key, nonce, data,
                        jnp.asarray(counter_base, jnp.uint32).reshape(1),
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _xor_blocked(key, nonce, data, base, *, interpret):
    assert data.dtype == jnp.uint32 and data.shape[0] == 16, data.shape
    n = data.shape[1]
    assert n % BLOCKS_PER_TILE == 0, n
    return pl.pallas_call(
        _xor_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                        # counter base
            grid=(n // BLOCKS_PER_TILE,),
            in_specs=[
                pl.BlockSpec((1, 8), lambda i, base: (0, 0)),   # key words
                pl.BlockSpec((1, 3), lambda i, base: (0, 0)),   # nonce words
                pl.BlockSpec((16, BLOCKS_PER_TILE), lambda i, base: (0, i)),
            ],
            out_specs=pl.BlockSpec((16, BLOCKS_PER_TILE),
                                   lambda i, base: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct(data.shape, jnp.uint32),
        interpret=(interpret_default() if interpret is None
                   else interpret),
    )(base, key.reshape(1, 8), nonce.reshape(1, 3), data)
