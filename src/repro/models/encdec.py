"""Encoder-decoder transformer (Whisper backbone).

The audio conv frontend is a STUB per the assignment: ``input_specs`` feed
precomputed frame embeddings [b, frames, d_model] straight into the encoder.
Positional encoding uses RoPE as a stand-in for Whisper's sinusoidal/learned
tables (noted in DESIGN.md §8); LayerNorm + GELU match Whisper.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers

Params = Any


def _attn_cfg(cfg: ModelConfig, causal: bool) -> layers.AttentionConfig:
    return layers.AttentionConfig(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim_,
        qk_norm=False, rope_theta=cfg.rope_theta, causal=causal)


def _init_enc_layer(cfg: ModelConfig, key, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "pre_norm": layers.init_layernorm(cfg.d_model, dtype),
        "attn": layers.init_attention(k1, _attn_cfg(cfg, False), dtype),
        "post_norm": layers.init_layernorm(cfg.d_model, dtype),
        "mlp": layers.init_gelu_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def _init_dec_layer(cfg: ModelConfig, key, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm1": layers.init_layernorm(cfg.d_model, dtype),
        "self_attn": layers.init_attention(k1, _attn_cfg(cfg, True), dtype),
        "norm2": layers.init_layernorm(cfg.d_model, dtype),
        "cross_attn": layers.init_cross_attention(k2, _attn_cfg(cfg, False), dtype),
        "norm3": layers.init_layernorm(cfg.d_model, dtype),
        "mlp": layers.init_gelu_mlp(k3, cfg.d_model, cfg.d_ff, dtype),
    }


class EncDecLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def init_params(self, key) -> Params:
        cfg = self.cfg
        dtype = cfg.jnp_dtype
        k1, k2, k3 = jax.random.split(key, 3)
        enc = jax.vmap(lambda k: _init_enc_layer(cfg, k, dtype))(
            jax.random.split(k1, cfg.encoder_layers))
        dec = jax.vmap(lambda k: _init_dec_layer(cfg, k, dtype))(
            jax.random.split(k2, cfg.decoder_layers))
        return {
            "embedding": layers.init_embedding(k3, cfg.vocab_size, cfg.d_model, dtype),
            "enc_layers": enc,
            "dec_layers": dec,
            "enc_final_norm": layers.init_layernorm(cfg.d_model, dtype),
            "dec_final_norm": layers.init_layernorm(cfg.d_model, dtype),
        }

    def abstract_params(self) -> Params:
        return jax.eval_shape(lambda: self.init_params(jax.random.key(0)))

    # -- encoder -----------------------------------------------------------
    def encode(self, params: Params, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        b, s, _ = frames.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

        def body(x, lp):
            lp = layers.layer_params("enc_layers", lp)
            h = layers.layernorm(lp["pre_norm"], x, cfg.norm_eps)
            x = x + layers.attention_forward(lp["attn"], _attn_cfg(cfg, False),
                                             h, positions)
            h = layers.layernorm(lp["post_norm"], x, cfg.norm_eps)
            return x + layers.gelu_mlp(lp["mlp"], h), None

        if cfg.parallel.remat != "none":
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, frames, params["enc_layers"])
        return layers.layernorm(params["enc_final_norm"], x, cfg.norm_eps)

    # -- teacher-forced decoder (training) -----------------------------------
    def forward(self, params: Params, frames: jax.Array, tokens: jax.Array):
        cfg = self.cfg
        enc_out = self.encode(params, frames)
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        x = layers.embed(params["embedding"], tokens)

        def body(x, lp):
            lp = layers.layer_params("dec_layers", lp)
            h = layers.layernorm(lp["norm1"], x, cfg.norm_eps)
            x = x + layers.attention_forward(lp["self_attn"], _attn_cfg(cfg, True),
                                             h, positions)
            h = layers.layernorm(lp["norm2"], x, cfg.norm_eps)
            ek, ev = layers.encode_kv(lp["cross_attn"], enc_out)
            x = x + layers.cross_attention(lp["cross_attn"], _attn_cfg(cfg, False),
                                           h, ek, ev)
            h = layers.layernorm(lp["norm3"], x, cfg.norm_eps)
            return x + layers.gelu_mlp(lp["mlp"], h), None

        if cfg.parallel.remat != "none":
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["dec_layers"])
        x = layers.layernorm(params["dec_final_norm"], x, cfg.norm_eps)
        return layers.unembed(params["embedding"], x), jnp.zeros((), jnp.float32)

    def loss(self, params: Params, batch: Dict[str, jax.Array]):
        logits, aux = self.forward(params, batch["frames"], batch["tokens"])
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss + aux, {"ce_loss": loss, "aux_loss": aux}

    # -- serving -------------------------------------------------------------
    def init_cache(self, batch: int, enc_len: int) -> Params:
        cfg = self.cfg
        L, hk, hd = cfg.decoder_layers, cfg.num_kv_heads, cfg.head_dim_
        dt = cfg.jnp_dtype
        return {
            "pos": jnp.zeros((batch,), jnp.int32),
            "self_k": jnp.zeros((L, batch, cfg.max_target_len, hk, hd), dt),
            "self_v": jnp.zeros((L, batch, cfg.max_target_len, hk, hd), dt),
            "cross_k": jnp.zeros((L, batch, enc_len, hk, hd), dt),
            "cross_v": jnp.zeros((L, batch, enc_len, hk, hd), dt),
        }

    def abstract_cache(self, batch: int, enc_len: int) -> Params:
        return jax.eval_shape(lambda: self.init_cache(batch, enc_len))

    def prefill(self, params: Params, frames: jax.Array, tokens: jax.Array,
                cache: Params):
        """Encode frames, precompute cross-KV, prefill decoder self-KV."""
        cfg = self.cfg
        enc_out = self.encode(params, frames)
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        x = layers.embed(params["embedding"], tokens)

        def body(x, xs):
            lp, csl = xs
            lp = layers.layer_params("dec_layers", lp)
            h = layers.layernorm(lp["norm1"], x, cfg.norm_eps)
            sa, kv = layers.attention_prefill(
                lp["self_attn"], _attn_cfg(cfg, True), h,
                {"k": csl["self_k"], "v": csl["self_v"]}, positions)
            x = x + sa
            h = layers.layernorm(lp["norm2"], x, cfg.norm_eps)
            ek, ev = layers.encode_kv(lp["cross_attn"], enc_out)
            x = x + layers.cross_attention(lp["cross_attn"], _attn_cfg(cfg, False),
                                           h, ek, ev)
            h = layers.layernorm(lp["norm3"], x, cfg.norm_eps)
            x = x + layers.gelu_mlp(lp["mlp"], h)
            return x, {"self_k": kv["k"], "self_v": kv["v"],
                       "cross_k": ek.astype(csl["cross_k"].dtype),
                       "cross_v": ev.astype(csl["cross_v"].dtype)}

        xs = (params["dec_layers"],
              {"self_k": cache["self_k"], "self_v": cache["self_v"],
               "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]})
        x, new = jax.lax.scan(body, x, xs)
        x = layers.layernorm(params["dec_final_norm"], x[:, -1:], cfg.norm_eps)
        logits = layers.unembed(params["embedding"], x)
        new["pos"] = jnp.full((b,), t, jnp.int32)
        return logits[:, 0], new

    def decode_step(self, params: Params, tokens: jax.Array, cache: Params):
        cfg = self.cfg
        b = tokens.shape[0]
        positions = cache["pos"][:, None]
        x = layers.embed(params["embedding"], tokens)

        def body(x, xs):
            lp, csl = xs
            lp = layers.layer_params("dec_layers", lp)
            h = layers.layernorm(lp["norm1"], x, cfg.norm_eps)
            sa, kv = layers.attention_decode(
                lp["self_attn"], _attn_cfg(cfg, True), h,
                {"k": csl["self_k"], "v": csl["self_v"]}, positions)
            x = x + sa
            h = layers.layernorm(lp["norm2"], x, cfg.norm_eps)
            x = x + layers.cross_attention(lp["cross_attn"], _attn_cfg(cfg, False),
                                           h, csl["cross_k"], csl["cross_v"])
            h = layers.layernorm(lp["norm3"], x, cfg.norm_eps)
            x = x + layers.gelu_mlp(lp["mlp"], h)
            return x, {"self_k": kv["k"], "self_v": kv["v"],
                       "cross_k": csl["cross_k"], "cross_v": csl["cross_v"]}

        xs = (params["dec_layers"],
              {"self_k": cache["self_k"], "self_v": cache["self_v"],
               "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]})
        x, new = jax.lax.scan(body, x, xs)
        x = layers.layernorm(params["dec_final_norm"], x, cfg.norm_eps)
        logits = layers.unembed(params["embedding"], x)
        new["pos"] = cache["pos"] + 1
        return logits[:, 0], new
