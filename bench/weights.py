"""Seeded weights for the dense (Llama-style) family, made by the benchmark.

The program is handed these weights; the plain reference makes the same
ones again from the same seed, a layer at a time, and never takes them
from the program. Each layer's leaves come from ``fold_in(key, layer)``,
so layer ``i`` is the same whether it is drawn alone (the reference) or
under ``vmap`` with the others (the program's stacked tree, drawn on the
device in one jitted call in the served dtype).

Sizes come from the configuration file's Hugging Face style keys.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, h=h, hk=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"])


def _normal(key, shape, scale, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)


def _scale(key, n, dtype):
    """RMSNorm gains near 1, so the norms are exercised."""
    return 1 + _normal(key, (n,), 0.1, dtype)


def layer(key, cfg: dict, i, dtype) -> dict:
    """Layer ``i``'s leaves, in the program's per-layer layout."""
    m = dims(cfg)
    d, h, hk, hd, ff = m["d"], m["h"], m["hk"], m["hd"], m["ff"]
    k = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 0), i), 9)
    return {
        "pre_norm": {"scale": _scale(k[0], d, dtype)},
        "post_norm": {"scale": _scale(k[1], d, dtype)},
        "attn": {
            "wq": _normal(k[2], (d, h, hd), 1 / math.sqrt(d), dtype),
            "wk": _normal(k[3], (d, hk, hd), 1 / math.sqrt(d), dtype),
            "wv": _normal(k[4], (d, hk, hd), 1 / math.sqrt(d), dtype),
            "wo": _normal(k[5], (h, hd, d), 1 / math.sqrt(h * hd), dtype),
        },
        "ffn": {
            "w_gate": _normal(k[6], (d, ff), 1 / math.sqrt(d), dtype),
            "w_up": _normal(k[7], (d, ff), 1 / math.sqrt(d), dtype),
            "w_down": _normal(k[8], (ff, d), 1 / math.sqrt(ff), dtype),
        },
    }


def embedding(key, cfg: dict, dtype):
    m = dims(cfg)
    return _normal(jax.random.fold_in(key, 1), (m["vocab"], m["d"]), 0.02, dtype)


def final_norm(key, cfg: dict, dtype):
    return _scale(jax.random.fold_in(key, 2), dims(cfg)["d"], dtype)


def program_params(seed: int, cfg: dict, block: str = "layers"):
    """The whole tree in the program's layout, drawn on the device in one
    jitted call: ``{embedding: {table}, final_norm: {scale},
    <block>: {slot_0: <layer leaves stacked on a leading layer axis>}}``."""
    dtype = DTYPES[cfg["dtype"]]
    n = dims(cfg)["layers"]

    def make(key):
        stacked = jax.vmap(lambda i: layer(key, cfg, i, dtype))(jnp.arange(n))
        return {"embedding": {"table": embedding(key, cfg, dtype)},
                "final_norm": {"scale": final_norm(key, cfg, dtype)},
                block: {"slot_0": stacked}}

    return jax.jit(make)(jax.random.key(seed))
