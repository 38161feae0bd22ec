"""The plain reference's driver and precision, shared by every family.

Float32 ``jax.numpy`` with every matrix product at ``HIGHEST`` precision,
no kernels, no cache, no batching tricks. The layer equations, the
embedding, the final norm and the output head are the family's
(``bench/families/<family>/``, named by the configuration); this module
imports nothing of the program and takes nothing it made: the family draws
the weights again from the seed, one layer at a time, so the reference
fits on the chip once the program's state is freed.

:func:`gaps` runs every sequence through the layers together (right
padding does not reach earlier positions under a causal mask) and, at each
checked position, reads how far the served token's logit lies below the
reference's best. With ``control=True`` a second stream runs the same
forward with every matrix product's operands rounded to float8 (e4m3, one
scale per tensor for weights and per row for activations; a family routes
its products through :func:`mm`): the precision below the configuration's
bfloat16. At each checked position it reads the reference's gap for the
token the float8 forward puts first.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def q8(x, axis=None):
    """Round to float8 e4m3 with one scale per ``axis`` slice (per tensor
    when None), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(spec, a, w, low):
    """A matrix product at ``HIGHEST``; with ``low`` its operands in float8."""
    if low:
        a, w = q8(a, axis=-1), q8(w)
    return jnp.einsum(spec, a, w, precision=HI)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [b, t, heads, hd]; rotate-half rotary embedding at positions 0..t-1."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gaps(family, seed: int, cfg: dict, seqs: Sequence[np.ndarray],
         starts: Sequence[int], control: bool = False,
         chunk: int = 256) -> List[dict]:
    """For each sequence ``s`` (token ids as fed to the model) and each
    position ``p >= starts[i] - 1`` with a next token, the gap
    ``max(ref_logits[p]) - ref_logits[p][s[p + 1]]``, by the forward of
    ``family`` (a loaded ``bench/families/<family>``). Returns one dict per
    sequence: ``served`` gaps, and with ``control`` the ``control`` gaps
    (the reference's gap for the float8 forward's first choice)."""
    dtype = jnp.dtype(cfg["dtype"])
    key = jax.random.key(seed)
    t = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    toks = jnp.asarray(toks)

    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    # the layer index is traced and its kind static: the layers of one kind
    # share one program for their weights and one for their forward
    make_layer = jax.jit(lambda k, i, kind:
                         f32(family.layer(k, cfg, i, dtype, kind)),
                         static_argnums=2)
    run_layer = jax.jit(lambda lw, x, i, kind, low:
                        family.layer_forward(lw, x, cfg, i, kind, low),
                        static_argnums=(3, 4))
    table = f32(jax.jit(lambda k: family.embedding(k, cfg, dtype))(key))
    x = family.embed(table, toks)
    xs = {False: x, True: x} if control else {False: x}
    for i in range(cfg["num_hidden_layers"]):
        kind = family.layer_kind(cfg, i)
        lw = make_layer(key, i, kind)
        xs = {low: run_layer(lw, xv, i, kind, low) for low, xv in xs.items()}
        del lw
    fn = f32(jax.jit(lambda k: family.final_norm(k, cfg, dtype))(key))
    norm = jax.jit(lambda xv, fn: family.norm(xv, fn, cfg))
    xs = {low: norm(xv, fn) for low, xv in xs.items()}

    @jax.jit
    def read(h, hc, nxt, table):
        ref = family.logits(h, table, False)
        best = ref.max(-1)
        out = {"served": best - jnp.take_along_axis(ref, nxt[:, None], 1)[:, 0]}
        if hc is not None:
            pick = jnp.argmax(family.logits(hc, table, True), -1)
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
