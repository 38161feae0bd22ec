"""Pure-jnp oracles for every Pallas kernel in this package.

Tests sweep shapes/dtypes and assert_allclose kernel-vs-oracle; the oracles
themselves are validated against external ground truth where it exists
(ChaCha20: RFC 8439 test vectors).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.chacha20 import chacha_block_words


# ---------------------------------------------------------------------------
# chacha20
# ---------------------------------------------------------------------------

def chacha20_keystream_ref(key_words: jax.Array, nonce_words: jax.Array,
                           n_blocks: int, counter_base: int = 0) -> jax.Array:
    """Keystream as uint32 [16, n_blocks] (word w of block b at [w, b])."""
    counters = jnp.arange(counter_base, counter_base + n_blocks, dtype=jnp.uint32)
    words = chacha_block_words([key_words[i] for i in range(8)],
                               [nonce_words[i] for i in range(3)], counters)
    return jnp.stack(words, axis=0)


def chacha20_xor_ref(key_words: jax.Array, nonce_words: jax.Array,
                     data: jax.Array, counter_base: int = 0) -> jax.Array:
    """Oracle for chacha20_xor_blocked: data uint32 [16, N]."""
    ks = chacha20_keystream_ref(key_words, nonce_words, data.shape[1], counter_base)
    return data ^ ks


def chacha20_keystream_bytes_ref(key: bytes, nonce: bytes, n_bytes: int,
                                 counter_base: int = 0) -> bytes:
    """Byte-level RFC 8439 keystream (little-endian serialization), for
    checking against published test vectors."""
    kw = jnp.asarray(np.frombuffer(key, np.uint32))
    nw = jnp.asarray(np.frombuffer(nonce, np.uint32))
    nblocks = (n_bytes + 63) // 64
    ks = np.asarray(chacha20_keystream_ref(kw, nw, nblocks, counter_base))
    # [16, N] -> per-block LE bytes
    out = ks.T.astype("<u4").tobytes()
    return out[:n_bytes]


# ---------------------------------------------------------------------------
# qmatmul
# ---------------------------------------------------------------------------

def qmatmul_ref(x_q: jax.Array, w_q: jax.Array, scale: jax.Array,
                out_dtype=jnp.bfloat16) -> jax.Array:
    """int8 [M,K] x int8 [K,N] * scale [1,N] -> out_dtype [M,N]."""
    acc = jax.lax.dot_general(x_q, w_q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * scale).astype(out_dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal MHA. q/k/v: [bh, s, d]."""
    bh, s, d = q.shape
    scale = 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(mask[None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", probs, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

def paged_attention_ref(q, k_pool, v_pool, table, valid) -> np.ndarray:
    """Oracle for paged_attention: gather each slot's pages dense, then
    masked GQA softmax attention in f32 numpy — the same math the gather
    decode path's sdpa performs. q [slots, h, hd]; pools [pages+1, ps, hk,
    hd]; table [slots, max_pages]; valid [slots]. Idle rows (valid 0) are
    zeros."""
    b, h, hd = q.shape
    hk = k_pool.shape[2]
    g = h // hk
    out = np.zeros((b, h, hd), np.float32)
    for i in range(b):
        n = int(valid[i])
        if n == 0:
            continue
        phys = np.asarray(table[i])
        k = np.concatenate([np.asarray(k_pool[p]) for p in phys])[:n]
        v = np.concatenate([np.asarray(v_pool[p]) for p in phys])[:n]
        qg = np.asarray(q[i], np.float32).reshape(hk, g, hd)
        s = np.einsum("kgd,nkd->kgn", qg, k.astype(np.float32)) / np.sqrt(hd)
        s = s - s.max(axis=-1, keepdims=True)
        p = np.exp(s)
        p /= p.sum(axis=-1, keepdims=True)
        out[i] = np.einsum("kgn,nkd->kgd",
                           p, v.astype(np.float32)).reshape(h, hd)
    return out
