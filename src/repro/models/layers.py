"""Core transformer layers: norms, RoPE, GQA attention, MLPs.

Pure-functional: each layer is an ``init_*`` returning a param pytree and an
``apply``-style function. Parameters carry no metadata; their sharding specs
are produced structurally by :mod:`repro.distributed.sharding` walking the
same tree layout.

Compute dtype is bf16 by default with f32 softmax/norm accumulation, matching
the paper's AMX-bf16 operating point (Insight 3/8).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = Any  # nested dict pytree


# ---------------------------------------------------------------------------
# per-layer parameter hook
# ---------------------------------------------------------------------------

_LAYER_PARAMS_HOOK: contextvars.ContextVar = contextvars.ContextVar(
    "layer_params_hook", default=None)


@contextlib.contextmanager
def layer_params_hook(hook: Callable[[str, Params], Params]):
    """While a step is traced under this context, every scanned layer's
    parameter slice passes through ``hook(block_name, layer_params)`` before
    use. A mesh plan uses it to all-gather FSDP-sharded weights one layer at
    a time, inside the layer scan."""
    token = _LAYER_PARAMS_HOOK.set(hook)
    try:
        yield
    finally:
        _LAYER_PARAMS_HOOK.reset(token)


def layer_params(block: str, lp: Params) -> Params:
    """``lp`` as the active :func:`layer_params_hook` sees it (unchanged
    when none is active)."""
    hook = _LAYER_PARAMS_HOOK.get()
    return lp if hook is None else hook(block, lp)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size: Optional[int] = None, dtype=jnp.bfloat16):
    """Truncated-normal fan-in init (matches Llama-family practice)."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.bfloat16) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype=jnp.bfloat16) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # [head_dim/2]


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: [..., seq, head_dim]; positions: broadcastable to [..., seq]."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, optional qk-norm) — reference jnp path
#
# The Pallas flash kernel (kernels/flash_attention.py) is the TPU-targeted
# implementation; this path is the oracle and the dry-run/smoke path.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    rope_theta: float = 10000.0
    causal: bool = True
    use_rope: bool = True
    # >0: full-sequence attention runs in q-chunks of this size so the
    # [b, h, s, s] score matrix is never materialized (flash-style memory
    # behaviour expressed in XLA ops; §Perf iteration)
    chunk: int = 0


def init_attention(key, cfg: AttentionConfig, dtype=jnp.bfloat16) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, hk, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(kq, (d, h, hd), in_axis_size=d, dtype=dtype),
        "wk": dense_init(kk, (d, hk, hd), in_axis_size=d, dtype=dtype),
        "wv": dense_init(kv, (d, hk, hd), in_axis_size=d, dtype=dtype),
        "wo": dense_init(ko, (h, hd, d), in_axis_size=h * hd, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _qkv(params: Params, cfg: AttentionConfig, x: jax.Array, positions: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if cfg.use_rope:
        q = apply_rope(q.swapaxes(1, 2), positions[:, None, :], cfg.rope_theta).swapaxes(1, 2)
        k = apply_rope(k.swapaxes(1, 2), positions[:, None, :], cfg.rope_theta).swapaxes(1, 2)
    return q, k, v


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
         q_positions: Optional[jax.Array] = None,
         kv_valid_len: Optional[jax.Array] = None) -> jax.Array:
    """Scaled dot-product attention with GQA broadcast.

    q: [b, sq, h, hd]; k/v: [b, skv, hk, hd]. h must be a multiple of hk.
    ``q_positions``: absolute positions of queries [b, sq] (for causal masking
    against a cache); ``kv_valid_len``: [b] number of valid cache entries.
    """
    b, sq, h, hd = q.shape
    hk = k.shape[2]
    group = h // hk
    qg = q.reshape(b, sq, hk, group, hd)
    scale = 1.0 / np.sqrt(hd)
    # bf16 inputs + f32 accumulation: never materialize f32 copies of the
    # KV tensors (the MXU-native dataflow; §Perf iteration 1)
    logits = jnp.einsum("bqhgk,bshk->bhgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    skv = k.shape[1]
    kv_pos = jnp.arange(skv)[None, :]  # [1, skv]
    mask = jnp.ones((b, sq, skv), dtype=bool)
    if causal:
        qp = q_positions if q_positions is not None else jnp.broadcast_to(
            jnp.arange(sq)[None, :], (b, sq))
        mask &= kv_pos[:, None, :] <= qp[:, :, None]
    if kv_valid_len is not None:
        mask &= kv_pos[:, None, :] < kv_valid_len[:, None, None]
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqs,bshk->bqhgk", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def sdpa_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *, chunk: int,
                 causal: bool, q_positions: jax.Array) -> jax.Array:
    """Q-chunked attention: peak score memory is [b, h, chunk, s] instead of
    [b, h, s, s]; causal chunks only read keys up to their last position.
    Python loop => concrete HLO (costs stay countable in the dry-run)."""
    b, s = q.shape[:2]
    outs = []
    for start in range(0, s, chunk):
        end = min(start + chunk, s)
        kv_end = end if causal else s
        outs.append(sdpa(q[:, start:end], k[:, :kv_end], v[:, :kv_end],
                         causal=causal, q_positions=q_positions[:, start:end]))
    return jnp.concatenate(outs, axis=1)


def attention_forward(params: Params, cfg: AttentionConfig, x: jax.Array,
                      positions: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence (training / prefill-without-cache) attention."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q, k, v = _qkv(params, cfg, x, positions)
    if cfg.chunk and s > cfg.chunk:
        out = sdpa_chunked(q, k, v, chunk=cfg.chunk, causal=cfg.causal,
                           q_positions=positions)
    else:
        out = sdpa(q, k, v, causal=cfg.causal, q_positions=positions)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def init_kv_cache(batch: int, max_len: int, num_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> Params:
    return {
        "k": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
    }


def attention_prefill(params: Params, cfg: AttentionConfig, x: jax.Array,
                      cache: Params, positions: jax.Array):
    """Prefill: run full attention AND write k/v into the cache at [0, s)."""
    q, k, v = _qkv(params, cfg, x, positions)
    s = x.shape[1]
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
    }
    if cfg.chunk and s > cfg.chunk:
        out = sdpa_chunked(q, k, v, chunk=cfg.chunk, causal=cfg.causal,
                           q_positions=positions)
    else:
        out = sdpa(q, k, v, causal=cfg.causal, q_positions=positions)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), cache


def attention_decode(params: Params, cfg: AttentionConfig, x: jax.Array,
                     cache: Params, positions: jax.Array):
    """One-token decode: x [b,1,d], positions [b,1] absolute position.

    Appends to cache at ``positions`` then attends over the valid prefix.
    """
    q, k, v = _qkv(params, cfg, x, positions)

    def write(buf, new):
        def upd(buf_b, new_b, pos_b):
            return jax.lax.dynamic_update_slice(buf_b, new_b.astype(buf_b.dtype), (pos_b, 0, 0))
        return jax.vmap(upd)(buf, new, positions[:, 0])

    cache = {"k": write(cache["k"], k), "v": write(cache["v"], v)}
    valid = positions[:, 0] + 1
    out = sdpa(q, cache["k"], cache["v"], causal=True,
               q_positions=positions, kv_valid_len=valid)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), cache


# ---------------------------------------------------------------------------
# cross-attention (enc-dec decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg: AttentionConfig, dtype=jnp.bfloat16) -> Params:
    return init_attention(key, dataclasses.replace(cfg, qk_norm=False), dtype)


def cross_attention(params: Params, cfg: AttentionConfig, x: jax.Array,
                    enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """x: [b, sq, d]; enc_k/enc_v: precomputed [b, skv, hk, hd]."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    out = sdpa(q, enc_k, enc_v, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def encode_kv(params: Params, enc_out: jax.Array):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"])
    return k, v


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int, dtype=jnp.bfloat16) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype=dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype=dtype),
        "w_down": dense_init(k3, (d_ff, d_model), in_axis_size=d_ff, dtype=dtype),
    }


def swiglu(params: Params, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, params["w_down"])


def init_gelu_mlp(key, d_model: int, d_ff: int, dtype=jnp.bfloat16) -> Params:
    k1, k2 = jax.random.split(key, 2)
    return {
        "w_in": dense_init(k1, (d_model, d_ff), dtype=dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": dense_init(k2, (d_ff, d_model), in_axis_size=d_ff, dtype=dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(params: Params, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, params["w_in"]) + params["b_in"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", h, params["w_out"]) + params["b_out"]


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype=jnp.bfloat16) -> Params:
    return {"table": embed_init(key, (vocab, d_model), dtype)}


def embed(params: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params: Params, x: jax.Array) -> jax.Array:
    """Tied unembedding; returns f32 logits for loss stability."""
    return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                      params["table"].astype(jnp.float32))
