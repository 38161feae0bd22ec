"""Operations and bytes from shapes, for the dense (Llama-style) family.

Nothing here is measured: every count follows from the configuration's
sizes and the context lengths of the rows a step serves. A multiply-add
counts as two operations. Bytes are what the algorithm must move at the
least: each weight once per step, each live key and value once per step.
"""

from __future__ import annotations

from typing import Sequence

from bench.weights import dims

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def layer_matmul_params(cfg: dict) -> int:
    m = dims(cfg)
    d, h, hk, hd, ff = m["d"], m["h"], m["hk"], m["hd"], m["ff"]
    return d * h * hd * 2 + d * hk * hd * 2 + 3 * d * ff


def param_bytes(cfg: dict) -> int:
    """Every weight once: the layers, the norms and the tied table (the
    output head reads all of it on every step)."""
    m = dims(cfg)
    n = m["layers"] * (layer_matmul_params(cfg) + 2 * m["d"]) \
        + m["vocab"] * m["d"] + m["d"]
    return n * ITEMSIZE[cfg["dtype"]]


def kv_bytes_per_token(cfg: dict) -> int:
    m = dims(cfg)
    return 2 * m["layers"] * m["hk"] * m["hd"] * ITEMSIZE[cfg["dtype"]]


def attn_flops(cfg: dict, ctx: float) -> float:
    """Scores and weighted values of one query over ``ctx`` keys, all layers."""
    m = dims(cfg)
    return 4.0 * m["layers"] * m["h"] * m["hd"] * ctx


def token_flops(cfg: dict, ctx: float, logits: bool) -> float:
    """One token's forward pass at context ``ctx``; ``logits`` when the
    output head runs for it (decode tokens and a prefill's last)."""
    m = dims(cfg)
    f = 2.0 * m["layers"] * layer_matmul_params(cfg) + attn_flops(cfg, ctx)
    return f + (2.0 * m["vocab"] * m["d"] if logits else 0.0)


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A prompt's useful work: causal attention averages half its length."""
    return (prompt_len * token_flops(cfg, (prompt_len + 1) / 2, False)
            + 2.0 * dims(cfg)["vocab"] * dims(cfg)["d"])


def decode_step_bytes(cfg: dict, ctx: Sequence[int]) -> float:
    """A decode step over rows at contexts ``ctx``: every weight, every
    live key and value read, one key and value written per row."""
    kv = kv_bytes_per_token(cfg)
    return param_bytes(cfg) + kv * (sum(ctx) + len(ctx))


def paged_attention_cost(cfg: dict, ctx: Sequence[int]):
    """(operations, bytes) of one layer's decode attention over live rows
    at contexts ``ctx``: the live keys and values read once, each query
    read and each output written once."""
    m = dims(cfg)
    it = ITEMSIZE[cfg["dtype"]]
    flops = 4.0 * m["h"] * m["hd"] * sum(ctx)
    nbytes = (2 * m["hk"] * m["hd"] * it * sum(ctx)
              + 2 * len(ctx) * m["h"] * m["hd"] * it)
    return flops, nbytes
