"""Plain reference forward for the dense (Llama-style) family.

Float32 ``jax.numpy`` with every matrix product at ``HIGHEST`` precision,
no kernels, no cache, no batching tricks: RMSNorm, rotary attention with
grouped key/value heads under a causal mask, SwiGLU feed-forward, and the
output head tied to the embedding (the configuration lists the tie under
``assumed``). It imports nothing of the program: the weights are made
again from the seed by :mod:`bench.weights`, one layer at a time, so the
reference fits on the chip once the program's state is freed.

:func:`gaps` runs every sequence through the layers together (right
padding does not reach earlier positions under a causal mask) and, at each
checked position, reads how far the served token's logit lies below the
reference's best. With ``control=True`` a second stream runs the same
forward with every matrix product's operands rounded to float8 (e4m3, one
scale per tensor for weights and per row for activations): the precision
below the configuration's bfloat16. At each checked position it reads the
reference's gap for the token the float8 forward puts first.
"""

from __future__ import annotations

import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis=None):
    """Round to float8 e4m3 with one scale per ``axis`` slice (per tensor
    when None), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(spec, a, w, low):
    if low:
        a, w = _q8(a, axis=-1), _q8(w)
    return jnp.einsum(spec, a, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [b, t, heads, hd]; rotate-half rotary embedding at positions 0..t-1."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(lw, x, eps, theta, low):
    b, t, _ = x.shape
    a = lw["attn"]
    hk, hd = a["wk"].shape[1], a["wk"].shape[2]
    h = a["wq"].shape[1]
    g = h // hk
    n = _rms(x, lw["pre_norm"]["scale"], eps)
    q = _rope(_mm("btd,dhk->bthk", n, a["wq"], low), theta)
    k = _rope(_mm("btd,dhk->bthk", n, a["wk"], low), theta)
    v = _mm("btd,dhk->bthk", n, a["wv"], low)
    q = q.reshape(b, t, hk, g, hd)
    if low:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    s = jnp.einsum("bqhgk,bshk->bhgqs", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if low:
        p = _q8(p, -1)
    o = jnp.einsum("bhgqs,bshk->bqhgk", p, v, precision=HI).reshape(b, t, h, hd)
    x = x + _mm("bthk,hkd->btd", o, a["wo"], low)
    n = _rms(x, lw["post_norm"]["scale"], eps)
    f = lw["ffn"]
    up = jax.nn.silu(_mm("btd,df->btf", n, f["w_gate"], low)) \
        * _mm("btd,df->btf", n, f["w_up"], low)
    return x + _mm("btf,fd->btd", up, f["w_down"], low)


def gaps(seed: int, cfg: dict, seqs: Sequence[np.ndarray],
         starts: Sequence[int], control: bool = False,
         chunk: int = 256) -> List[dict]:
    """For each sequence ``s`` (token ids as fed to the model) and each
    position ``p >= starts[i] - 1`` with a next token, the gap
    ``max(ref_logits[p]) - ref_logits[p][s[p + 1]]``. Returns one dict per
    sequence: ``served`` gaps, and with ``control`` the ``control`` gaps
    (the reference's gap for the float8 forward's first choice)."""
    dtype = weights.DTYPES[cfg["dtype"]]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    key = jax.random.key(seed)
    t = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    toks = jnp.asarray(toks)

    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    make_layer = jax.jit(lambda k, i: f32(weights.layer(k, cfg, i, dtype)))
    run_layer = jax.jit(_layer, static_argnums=(2, 3, 4))
    table = f32(jax.jit(lambda k: weights.embedding(k, cfg, dtype))(key))
    x = jnp.take(table, toks, axis=0)
    xs = {False: x, True: x} if control else {False: x}
    for i in range(cfg["num_hidden_layers"]):
        lw = make_layer(key, i)
        xs = {low: run_layer(lw, xv, eps, theta, low) for low, xv in xs.items()}
        del lw
    fn = f32(jax.jit(lambda k: weights.final_norm(k, cfg, dtype))(key))
    xs = {low: jax.jit(_rms, static_argnums=2)(xv, fn, eps)
          for low, xv in xs.items()}

    @jax.jit
    def read(h, hc, nxt, table):
        ref = jnp.einsum("pd,vd->pv", h, table, precision=HI)
        best = ref.max(-1)
        out = {"served": best - jnp.take_along_axis(ref, nxt[:, None], 1)[:, 0]}
        if hc is not None:
            pick = jnp.argmax(_mm("pd,vd->pv", hc, table, True), -1)
            out["control"] = best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
        return out

    results = []
    for i, s in enumerate(seqs):
        pos = np.arange(starts[i] - 1, len(s) - 1)
        res = {"served": [], "control": []}
        for c0 in range(0, len(pos), chunk):
            p = pos[c0:c0 + chunk]
            pad = chunk - len(p)
            pp = np.concatenate([p, np.full(pad, p[-1])])
            h = xs[False][i, pp]
            hc = xs[True][i, pp] if control else None
            out = read(h, hc, jnp.asarray(np.asarray(s)[pp + 1]), table)
            for k, v in out.items():
                res[k].extend(np.asarray(v)[:len(p)].tolist())
        results.append(res)
    return results
