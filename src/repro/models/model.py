"""Unified model facade: one API over all 10 architecture families.

``build_model(cfg)`` returns a :class:`Model` with uniform entry points used
by the trainer, the serving engine, and the dry-run launcher:

  loss(params, batch)            train_4k cells
  prefill(params, batch, cache)  prefill_32k cells
  decode_step(params, tok, cache) decode_32k / long_500k cells

``*_specs`` methods return ShapeDtypeStruct stand-ins (no allocation) for the
dry-run path.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.encdec import EncDecLM
from repro.models.transformer import DecoderLM

Params = Any


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.is_encdec = cfg.family == "encdec"
        self._impl = EncDecLM(cfg) if self.is_encdec else DecoderLM(cfg)

    # -- params --------------------------------------------------------------
    def init_params(self, key) -> Params:
        """Seeded parameters, drawn under ``jit``: the device then holds
        the outputs and none of the eager f32 draws behind them."""
        return jax.jit(self._impl.init_params)(key)

    def abstract_params(self) -> Params:
        return self._impl.abstract_params()

    # -- training ------------------------------------------------------------
    def loss(self, params: Params, batch: Dict[str, jax.Array]):
        return self._impl.loss(params, batch)

    # -- serving -------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> Params:
        return self._impl.init_cache(batch, max_len)

    def abstract_cache(self, batch: int, max_len: int) -> Params:
        return self._impl.abstract_cache(batch, max_len)

    def prefill(self, params: Params, batch: Dict[str, jax.Array], cache: Params):
        if self.is_encdec:
            return self._impl.prefill(params, batch["frames"], batch["tokens"], cache)
        return self._impl.prefill(params, batch["tokens"], cache)

    def decode_step(self, params: Params, tokens: jax.Array, cache: Params):
        return self._impl.decode_step(params, tokens, cache)

    # -- dry-run input specs ---------------------------------------------------
    def train_batch_specs(self, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        if self.is_encdec:
            t = cfg.max_target_len
            return {
                "frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), cfg.jnp_dtype),
                "tokens": jax.ShapeDtypeStruct((b, t), jnp.int32),
                "labels": jax.ShapeDtypeStruct((b, t), jnp.int32),
            }
        if cfg.embedding_inputs:
            return {
                "tokens": jax.ShapeDtypeStruct((b, s, cfg.d_model), cfg.jnp_dtype),
                "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
            }
        return {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }

    def prefill_batch_specs(self, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        if self.is_encdec:
            return {
                "frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), cfg.jnp_dtype),
                "tokens": jax.ShapeDtypeStruct((b, cfg.max_target_len), jnp.int32),
            }
        return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}

    def decode_token_specs(self, shape: ShapeConfig) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)

    def cache_specs(self, shape: ShapeConfig):
        """Abstract cache sized for the cell: seq_len entries already valid."""
        return self.abstract_cache(shape.global_batch, shape.seq_len)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
