"""The dense family's layer equations for the plain reference: float32,
every matrix product through ``bench.reference.mm`` so the float8 control
rounds its operands."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import HI, mm, q8, rms, rope


def layer_kind(cfg: dict, i: int) -> str:
    """Every layer of the family is of one kind."""
    return "dense"


def layer_forward(lw, x, cfg: dict, i, kind: str, low: bool):
    """Layer ``i`` (traced; unused, as every layer is of one kind) on
    ``x [b, t, d]``."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, t, _ = x.shape
    a = lw["attn"]
    hk, hd = a["wk"].shape[1], a["wk"].shape[2]
    h = a["wq"].shape[1]
    g = h // hk
    n = rms(x, lw["pre_norm"]["scale"], eps)
    q = rope(mm("btd,dhk->bthk", n, a["wq"], low), theta)
    k = rope(mm("btd,dhk->bthk", n, a["wk"], low), theta)
    v = mm("btd,dhk->bthk", n, a["wv"], low)
    q = q.reshape(b, t, hk, g, hd)
    if low:
        q, k, v = q8(q, -1), q8(k, -1), q8(v, -1)
    s = jnp.einsum("bqhgk,bshk->bhgqs", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if low:
        p = q8(p, -1)
    o = jnp.einsum("bhgqs,bshk->bqhgk", p, v, precision=HI).reshape(b, t, h, hd)
    x = x + mm("bthk,hkd->btd", o, a["wo"], low)
    n = rms(x, lw["post_norm"]["scale"], eps)
    f = lw["ffn"]
    up = jax.nn.silu(mm("btd,df->btf", n, f["w_gate"], low)) \
        * mm("btd,df->btf", n, f["w_up"], low)
    return x + mm("btf,fd->btd", up, f["w_down"], low)


def embed(table, toks):
    return jnp.take(table, toks, axis=0)


def norm(x, scale, cfg: dict):
    return rms(x, scale, cfg["rms_norm_eps"])


def logits(h, table, low: bool):
    """The output head, tied to the embedding (the configuration lists the
    tie under ``assumed``)."""
    return mm("pd,vd->pv", h, table, low)
