"""The dense (Llama-style) decoder family: RMSNorm, rotary attention with
grouped key/value heads under a causal mask, SwiGLU, and the output head
tied to the embedding.

A family is a directory ``bench/families/<family>/`` that a configuration
file names under ``"family"``; ``bench/harness.py::load_family`` loads it
by path and uses only the names in ``harness.FAMILY_PARTS``:

- ``model_config(cfg)``: the program's ``ModelConfig`` for the file's keys;
- ``program_params(seed, cfg)``: the program's tree, drawn on the device in
  one jitted call in the served dtype;
- the plain reference's parts, driven by ``bench/reference.py::gaps``:
  layer ``i``'s kind (``layer_kind``, from the Python int ``i``: layers of
  one kind share a weights tree and a forward), its weights drawn alone
  (``layer``) and its float32 forward (``layer_forward``), both given ``i``
  traced and the kind, the embedding (``embedding``, ``embed``), the final
  norm (``final_norm``, ``norm``) and the output head (``logits``);
- the counts the per-layer readers use (``bench/readers.py``):
  ``decode_step_bytes``, ``paged_attention_cost``,
  ``paged_attention_layers``, ``token_flops``, ``prefill_flops``.
"""

from __future__ import annotations

import dataclasses

from .costs import (decode_step_bytes, paged_attention_cost,  # noqa: F401
                    paged_attention_layers, prefill_flops, token_flops)
from . import weights
from .reference import (embed, layer_forward, layer_kind,  # noqa: F401
                        logits, norm)
from .weights import embedding, final_norm, program_params  # noqa: F401

# configuration-file keys -> the program's ModelConfig fields
MODEL_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
              "num_attention_heads": "num_heads",
              "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
              "dtype": "dtype", "tie_word_embeddings": "tie_embeddings"}


def model_config(cfg: dict):
    """The registry's model under ``cfg["model"]``, with the file's sizes."""
    from repro.configs import get_config
    over = {MODEL_KEYS[k]: cfg[k] for k in MODEL_KEYS if k in cfg}
    return dataclasses.replace(get_config(cfg["model"]), **over)


def layer(key, cfg: dict, i, dtype, kind: str):
    """Layer ``i``'s leaves drawn alone, as ``program_params`` draws them."""
    return weights.layer(key, cfg, i, dtype)
