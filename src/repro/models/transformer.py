"""Decoder-only LM trunk covering dense / moe / hybrid / ssm / vlm families.

Layer stacks are ``lax.scan``-ed over parameters stacked on a leading layer
axis (keeps compiled HLO compact for 95-layer cells and makes remat policy a
single ``jax.checkpoint`` on the scan body).

Heterogeneous stacks are handled structurally:
  * deepseek-v3: ``first_k_dense`` dense-FFN layers scanned separately from
    the MoE remainder,
  * jamba: a *group* of ``attn_period`` layers (7 mamba + 1 attention,
    alternating dense/MoE FFN) is the scan unit, scanned over groups.

Caches are pytrees with a leading stacked-layer (or group) axis so decode is
the same scan. ``cache["pos"]`` holds per-sequence absolute positions.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers, mla, moe, ssm

Params = Any


# ---------------------------------------------------------------------------
# layer-slot helpers
# ---------------------------------------------------------------------------

def _attn_cfg(cfg: ModelConfig, use_rope: Optional[bool] = None) -> layers.AttentionConfig:
    return layers.AttentionConfig(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim_,
        qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
        use_rope=(cfg.family != "hybrid") if use_rope is None else use_rope,
        chunk=cfg.parallel.attention_chunk,
    )


def _mla_cfg(cfg: ModelConfig) -> mla.MLAConfig:
    m = cfg.mla
    return mla.MLAConfig(d_model=cfg.d_model, num_heads=cfg.num_heads,
                         q_lora_rank=m.q_lora_rank, kv_lora_rank=m.kv_lora_rank,
                         rope_dim=m.rope_dim, nope_dim=m.nope_dim,
                         v_head_dim=m.v_head_dim, rope_theta=cfg.rope_theta,
                         chunk=cfg.parallel.attention_chunk)


def _mamba_cfg(cfg: ModelConfig) -> ssm.MambaConfig:
    s = cfg.ssm
    return ssm.MambaConfig(d_model=cfg.d_model, d_state=s.d_state,
                           d_conv=s.d_conv, expand=s.expand, chunk=s.chunk)


def _rwkv_cfg(cfg: ModelConfig) -> ssm.RWKV6Config:
    s = cfg.ssm
    return ssm.RWKV6Config(d_model=cfg.d_model, head_dim=s.head_dim,
                           lora_rank=s.lora_rank, d_ff=cfg.d_ff)


def _moe_cfg(cfg: ModelConfig) -> moe.MoEConfig:
    m = cfg.moe
    return moe.MoEConfig(num_experts=m.num_experts, top_k=m.top_k,
                         d_ff_expert=m.d_ff_expert,
                         num_shared_experts=m.num_shared_experts,
                         capacity_factor=m.capacity_factor, gating=m.gating)


# ---------------------------------------------------------------------------
# single-layer init / forward for each (mixer, ffn) slot combination
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, mixer: str, ffn: str, key, dtype) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Dict[str, Params] = {}
    if mixer == "rwkv":
        p["pre_norm"] = layers.init_layernorm(cfg.d_model, dtype)
        p["post_norm"] = layers.init_layernorm(cfg.d_model, dtype)
    else:
        p["pre_norm"] = layers.init_rmsnorm(cfg.d_model, dtype)
        p["post_norm"] = layers.init_rmsnorm(cfg.d_model, dtype)

    if mixer == "attn":
        p["attn"] = layers.init_attention(k1, _attn_cfg(cfg), dtype)
    elif mixer == "mla":
        p["mla"] = mla.init_mla(k1, _mla_cfg(cfg), dtype)
    elif mixer == "mamba":
        p["mamba"] = ssm.init_mamba(k1, _mamba_cfg(cfg), dtype)
    elif mixer == "rwkv":
        p["tmix"] = ssm.init_rwkv6_time_mix(k1, _rwkv_cfg(cfg), dtype)
    else:
        raise ValueError(mixer)

    if ffn == "swiglu":
        p["ffn"] = layers.init_swiglu(k2, cfg.d_model, cfg.d_ff, dtype)
    elif ffn == "moe":
        p["moe"] = moe.init_moe(k2, cfg.d_model, _moe_cfg(cfg), dtype)
    elif ffn == "cmix":
        p["cmix"] = ssm.init_rwkv6_channel_mix(k2, _rwkv_cfg(cfg), dtype)
    else:
        raise ValueError(ffn)
    return p


def _layer_fwd(cfg: ModelConfig, mixer: str, ffn: str, lp: Params,
               x: jax.Array, positions: jax.Array, mode: str,
               cache_sl: Optional[Params]) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """One layer. mode: 'train' | 'prefill' | 'decode'. Returns (x, cache', aux)."""
    aux = jnp.zeros((), jnp.float32)
    norm = layers.layernorm if mixer == "rwkv" else layers.rmsnorm
    h = norm(lp["pre_norm"], x, cfg.norm_eps)
    new_cache = dict(cache_sl) if cache_sl is not None else None

    if mixer == "attn":
        acfg = _attn_cfg(cfg)
        if mode == "train":
            mix = layers.attention_forward(lp["attn"], acfg, h, positions)
        elif mode == "prefill":
            mix, kv = layers.attention_prefill(lp["attn"], acfg, h,
                                               {"k": cache_sl["k"], "v": cache_sl["v"]}, positions)
            new_cache.update(kv)
        else:
            mix, kv = layers.attention_decode(lp["attn"], acfg, h,
                                              {"k": cache_sl["k"], "v": cache_sl["v"]}, positions)
            new_cache.update(kv)
    elif mixer == "mla":
        mcfg = _mla_cfg(cfg)
        if mode == "train":
            mix = mla.mla_forward(lp["mla"], mcfg, h)
        elif mode == "prefill":
            mix, c = mla.mla_prefill(lp["mla"], mcfg, h,
                                     {"ckv": cache_sl["ckv"], "krope": cache_sl["krope"]}, positions)
            new_cache.update(c)
        else:
            mix, c = mla.mla_decode(lp["mla"], mcfg, h,
                                    {"ckv": cache_sl["ckv"], "krope": cache_sl["krope"]}, positions)
            new_cache.update(c)
    elif mixer == "mamba":
        scfg = _mamba_cfg(cfg)
        if mode == "train":
            mix = ssm.mamba_forward(lp["mamba"], scfg, h)
        elif mode == "prefill":
            mix, st = ssm.mamba_prefill(lp["mamba"], scfg, h)
            new_cache.update(st)
        else:
            mix, st = ssm.mamba_step(lp["mamba"], scfg, h,
                                     {"conv": cache_sl["conv"], "ssm": cache_sl["ssm"]})
            new_cache.update(st)
    elif mixer == "rwkv":
        rcfg = _rwkv_cfg(cfg)
        b = h.shape[0]
        if mode == "train":
            x_last = jnp.zeros((b, cfg.d_model), h.dtype)
            state = jnp.zeros((b, rcfg.num_heads, rcfg.head_dim, rcfg.head_dim), jnp.float32)
            mix, _, _ = ssm.rwkv6_time_mix(lp["tmix"], rcfg, h, x_last, state)
        else:  # prefill and decode share the segment-continuation form
            mix, x_last, state = ssm.rwkv6_time_mix(
                lp["tmix"], rcfg, h, cache_sl["tmix_x"], cache_sl["wkv"])
            new_cache.update({"tmix_x": x_last, "wkv": state})
    else:
        raise ValueError(mixer)

    x = x + mix
    h = norm(lp["post_norm"], x, cfg.norm_eps)

    if ffn == "swiglu":
        out = layers.swiglu(lp["ffn"], h)
    elif ffn == "moe":
        out, aux = moe.moe_forward(lp["moe"], _moe_cfg(cfg), h)
    elif ffn == "cmix":
        rcfg = _rwkv_cfg(cfg)
        b = h.shape[0]
        if mode == "train":
            x_last = jnp.zeros((b, cfg.d_model), h.dtype)
            out, _ = ssm.rwkv6_channel_mix(lp["cmix"], rcfg, h, x_last)
        else:
            out, x_last = ssm.rwkv6_channel_mix(lp["cmix"], rcfg, h, cache_sl["cmix_x"])
            new_cache.update({"cmix_x": x_last})
    else:
        raise ValueError(ffn)
    return x + out, new_cache, aux


# ---------------------------------------------------------------------------
# stack descriptors: a model is a sequence of scanned blocks
# ---------------------------------------------------------------------------

def _blocks(cfg: ModelConfig):
    """Returns [(block_name, n_repeats, [(mixer, ffn), ...per-slot...])]."""
    if cfg.family in ("dense", "vlm"):
        return [("layers", cfg.num_layers, [("attn", "swiglu")])]
    if cfg.family == "ssm":  # rwkv6
        return [("layers", cfg.num_layers, [("rwkv", "cmix")])]
    if cfg.family == "moe":
        mixer = "mla" if cfg.mla else "attn"
        fk = cfg.moe.first_k_dense
        blocks = []
        if fk:
            blocks.append(("dense_layers", fk, [(mixer, "swiglu")]))
        blocks.append(("moe_layers", cfg.num_layers - fk, [(mixer, "moe")]))
        return blocks
    if cfg.family == "hybrid":  # jamba group: attn at slot attn_period-1, moe on odd slots
        slots = []
        for i in range(cfg.attn_period):
            mixer = "attn" if i == cfg.attn_period - 1 else "mamba"
            ffn = "moe" if (cfg.moe_period and i % cfg.moe_period == cfg.moe_period - 1) else "swiglu"
            slots.append((mixer, ffn))
        return [("groups", cfg.num_layers // cfg.attn_period, slots)]
    raise ValueError(cfg.family)


def _init_block(cfg: ModelConfig, slots, n: int, key, dtype) -> Params:
    """Stacked params [n, ...] for a block of `slots` layers."""
    def init_one(k):
        ks = jax.random.split(k, len(slots))
        return {f"slot_{i}": _init_layer(cfg, m, f, ks[i], dtype)
                for i, (m, f) in enumerate(slots)}
    return jax.vmap(init_one)(jax.random.split(key, n))


def _init_cache_slot(cfg: ModelConfig, mixer: str, ffn: str, batch: int,
                     max_len: int, dtype) -> Params:
    c: Dict[str, Any] = {}
    if mixer == "attn":
        c.update(layers.init_kv_cache(batch, max_len, cfg.num_kv_heads,
                                      cfg.head_dim_, dtype))
    elif mixer == "mla":
        c.update(mla.init_mla_cache(batch, max_len, _mla_cfg(cfg), dtype))
    elif mixer == "mamba":
        c.update(ssm.init_mamba_state(batch, _mamba_cfg(cfg), dtype))
    elif mixer == "rwkv":
        rcfg = _rwkv_cfg(cfg)
        c["tmix_x"] = jnp.zeros((batch, cfg.d_model), dtype)
        c["wkv"] = jnp.zeros((batch, rcfg.num_heads, rcfg.head_dim, rcfg.head_dim), jnp.float32)
    if ffn == "cmix":
        c["cmix_x"] = jnp.zeros((batch, cfg.d_model), dtype)
    return c


def _stack_cache(cfg: ModelConfig, slots, n: int, batch: int, max_len: int, dtype):
    one = {f"slot_{i}": _init_cache_slot(cfg, m, f, batch, max_len, dtype)
           for i, (m, f) in enumerate(slots)}
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), one)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class DecoderLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.blocks = _blocks(cfg)

    # -- params ------------------------------------------------------------
    def init_params(self, key) -> Params:
        cfg = self.cfg
        dtype = cfg.jnp_dtype
        keys = jax.random.split(key, len(self.blocks) + 2)
        p: Dict[str, Params] = {
            "embedding": layers.init_embedding(keys[0], cfg.vocab_size,
                                               cfg.d_model, dtype),
            "final_norm": (layers.init_layernorm(cfg.d_model, dtype)
                           if cfg.family == "ssm"
                           else layers.init_rmsnorm(cfg.d_model, dtype)),
        }
        if cfg.family == "ssm":
            p["ln0"] = layers.init_layernorm(cfg.d_model, dtype)
        for i, (name, n, slots) in enumerate(self.blocks):
            p[name] = _init_block(cfg, slots, n, keys[i + 1], dtype)
        return p

    def abstract_params(self) -> Params:
        return jax.eval_shape(lambda: self.init_params(jax.random.key(0)))

    # -- block scan --------------------------------------------------------
    def _run_block(self, name: str, slots, bp: Params, x: jax.Array,
                   positions: jax.Array, mode: str, cache_blk):
        cfg = self.cfg

        def body(carry, xs):
            h, aux = carry
            lp, csl = xs
            lp = layers.layer_params(name, lp)
            new_csl = {} if csl is not None else None
            for i, (m, f) in enumerate(slots):
                sl = csl[f"slot_{i}"] if csl is not None else None
                h, new_sl, a = _layer_fwd(cfg, m, f, lp[f"slot_{i}"], h,
                                          positions, mode, sl)
                aux = aux + a
                if new_csl is not None:
                    new_csl[f"slot_{i}"] = new_sl
            return (h, aux), new_csl

        if cfg.parallel.remat == "full":
            body = jax.checkpoint(body)
        elif cfg.parallel.remat == "dots_saveable":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots)

        # §Perf: decode with the cache as scan CARRY — each layer reads and
        # writes only its own [1, ...] slice in place (XLA aliases the
        # dynamic-update-slice), instead of streaming the whole stacked
        # cache through xs/ys (2x full-cache HBM traffic per token).
        if (mode == "decode" and cache_blk is not None
                and cfg.parallel.decode_cache_carry and cfg.parallel.scan_layers):
            def carry_body(carry, lp):
                h, cache_full, i, aux = carry
                csl = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                    cache_full)
                (h, aux), new_csl = body((h, aux), (lp, csl))
                cache_full = jax.tree.map(
                    lambda full, sl: jax.lax.dynamic_update_index_in_dim(
                        full, sl.astype(full.dtype), i, 0),
                    cache_full, new_csl)
                return (h, cache_full, i + 1, aux), None

            (x, new_cache, _, aux), _ = jax.lax.scan(
                carry_body, (x, cache_blk, jnp.int32(0),
                             jnp.zeros((), jnp.float32)), bp)
            return x, aux, new_cache

        if cfg.parallel.scan_layers:
            (x, aux), new_cache = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)),
                (bp, cache_blk) if cache_blk is not None else (bp, None))
            return x, aux, new_cache
        # unrolled path (debug / tiny models / cost-analysis lowerings)
        aux = jnp.zeros((), jnp.float32)
        n = jax.tree.leaves(bp)[0].shape[0]
        if (mode == "decode" and cache_blk is not None
                and cfg.parallel.decode_cache_carry):
            # mirror the carry semantics: in-place per-layer slice updates
            new_cache = cache_blk
            for j in range(n):
                lp = jax.tree.map(lambda a: a[j], bp)
                csl = jax.tree.map(lambda a: a[j], new_cache)
                (x, aux), ncs = body((x, aux), (lp, csl))
                new_cache = jax.tree.map(
                    lambda full, sl: full.at[j].set(sl.astype(full.dtype)),
                    new_cache, ncs)
            return x, aux, new_cache
        new_layers = []
        for j in range(n):
            lp = jax.tree.map(lambda a: a[j], bp)
            csl = (jax.tree.map(lambda a: a[j], cache_blk)
                   if cache_blk is not None else None)
            (x, aux), ncs = body((x, aux), (lp, csl))
            new_layers.append(ncs)
        new_cache = (jax.tree.map(lambda *xs: jnp.stack(xs), *new_layers)
                     if cache_blk is not None else None)
        return x, aux, new_cache

    # -- embedding ---------------------------------------------------------
    def _embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        cfg = self.cfg
        if cfg.embedding_inputs:
            x = tokens  # already [b, s, d]
        else:
            x = layers.embed(params["embedding"], tokens)
        if cfg.family == "ssm":
            x = layers.layernorm(params["ln0"], x, cfg.norm_eps)
        return x

    def _unembed(self, params: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        norm = layers.layernorm if cfg.family == "ssm" else layers.rmsnorm
        x = norm(params["final_norm"], x, cfg.norm_eps)
        return layers.unembed(params["embedding"], x)

    # -- public entry points -------------------------------------------------
    def _trunk(self, params: Params, tokens: jax.Array):
        """Embed + all blocks; returns (hidden [b,s,d], aux)."""
        b, s = tokens.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        x = self._embed(params, tokens)
        aux = jnp.zeros((), jnp.float32)
        for name, n, slots in self.blocks:
            x, a, _ = self._run_block(name, slots, params[name], x,
                                      positions, "train", None)
            aux = aux + a
        return x, aux

    def forward(self, params: Params, tokens: jax.Array):
        """Training/teacher-forced full-sequence pass -> (logits, aux)."""
        x, aux = self._trunk(params, tokens)
        return self._unembed(params, x), aux

    def _ce_chunk(self, params: Params, x: jax.Array, labels: jax.Array):
        """Summed masked NLL + token count for a hidden-state chunk."""
        logits = self._unembed(params, x)
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * mask), jnp.sum(mask)

    def loss(self, params: Params, batch: Dict[str, jax.Array]):
        x, aux = self._trunk(params, batch["tokens"])
        labels = batch["labels"]
        chunk = self.cfg.parallel.loss_chunk
        s = x.shape[1]
        if chunk and s > chunk:
            # never materialize the full [b, s, vocab] logits (§Perf)
            tot = jnp.zeros((), jnp.float32)
            cnt = jnp.zeros((), jnp.float32)
            for start in range(0, s, chunk):
                end = min(start + chunk, s)
                t, c = self._ce_chunk(params, x[:, start:end],
                                      labels[:, start:end])
                tot, cnt = tot + t, cnt + c
        else:
            tot, cnt = self._ce_chunk(params, x, labels)
        loss = tot / jnp.maximum(cnt, 1.0)
        return loss + aux, {"ce_loss": loss, "aux_loss": aux}

    def init_cache(self, batch: int, max_len: int) -> Params:
        cfg = self.cfg
        cache = {"pos": jnp.zeros((batch,), jnp.int32)}
        for name, n, slots in self.blocks:
            cache[name] = _stack_cache(cfg, slots, n, batch, max_len, cfg.jnp_dtype)
        return cache

    def abstract_cache(self, batch: int, max_len: int) -> Params:
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    def prefill(self, params: Params, tokens: jax.Array, cache: Params):
        """tokens: [b, s] (or [b, s, d] embeddings). Fills cache[0, s)."""
        b, s = tokens.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        x = self._embed(params, tokens)
        new_cache = {"pos": jnp.full((b,), s, jnp.int32)}
        aux = jnp.zeros((), jnp.float32)
        for name, n, slots in self.blocks:
            x, a, nc = self._run_block(name, slots, params[name], x,
                                       positions, "prefill", cache[name])
            new_cache[name] = nc
            aux = aux + a
        logits = self._unembed(params, x[:, -1:, :])
        return logits[:, 0], new_cache

    def decode_step(self, params: Params, tokens: jax.Array, cache: Params):
        """tokens: [b, 1] -> (logits [b, vocab], cache')."""
        b = tokens.shape[0]
        positions = cache["pos"][:, None]  # [b,1] absolute position of new token
        x = self._embed(params, tokens)
        new_cache = {"pos": cache["pos"] + 1}
        for name, n, slots in self.blocks:
            x, _, nc = self._run_block(name, slots, params[name], x,
                                       positions, "decode", cache[name])
            new_cache[name] = nc
        logits = self._unembed(params, x)
        return logits[:, 0], new_cache
