"""Bring-up check: confidential serving at Llama2-7B's published widths on
the TPU, through the calls a user makes.

    python chip_smoke.py                # one chip: the whole confidential path
    python chip_smoke.py --four-chips   # four chips: the mesh path only

One chip. The weights are seeded with ``jax.random.key(0)`` under ``jit``.
A ``TrustDomain("tdx")`` seals them, quotes, and the verifier checks the
quote; the plaintext is then dropped and ``load_sealed`` decrypts the
ciphertext back onto the device a chunk at a time. Every unsealed leaf must
match the SHA-256 digest taken before the plaintext went. An ``Engine``
(paged KV, kernel decode) serves six requests whose priority mix forces a
sealed preemption, so a restore is admitted as ciphertext and decoded by the
compiled ``paged_attention_unseal``. On the served pool, the compiled
``paged_attention`` is checked against the dense-gather reference
(``kernels/ref.py``); once the engine has restored pages as ciphertext,
the fused kernel is checked on them, through the engine's own sidecars,
against the reference over their plaintext as it was when sealed.

Four chips. The same model, all 32 layers, serves the same requests on a
single-device plan, then on ``mesh="dp=4"`` (tokens must be identical) and
``mesh="dp=1,tp=4"`` (prefill logits within a stated tolerance).

Earlier lines report the device, seconds per phase with compile apart,
``peak_bytes_in_use``, tokens served, preemptions and fused-restore pages.
The last line is ``{"ok": true, "device": {...}}``. A failed phase exits
non-zero with no such line. Off the TPU it exits non-zero at once.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import hashlib
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

# engine shape, shared by both paths: 4 slots x 320 positions of 16-token
# pages (the prompts' largest bucket is 256, plus 16 new tokens)
ENGINE = dict(max_slots=4, max_len=320, page_size=16,
              prefill_buckets=(128, 256))
LOW = (200, 96, 160, 64)     # prompt lengths, priority 0
HIGH = (256, 128)            # prompt lengths, priority 5: they preempt
NEW_TOKENS = 16
# tp=4 splits every contraction into four partial sums that are reduced
# across chips, so bf16 rounding happens in another order than on one
# chip; through the layers that moves a logit by a few bf16 ulps of the
# largest logit, well under this bound
TP_LOGIT_TOL = 5e-2          # max |dlogit| / max |logit|

# lowering and XLA compile (tracing nests, so it is left in "run")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def require(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


class Phases:
    """Per-phase wall seconds, with the compile seconds JAX reports inside
    the phase split out, and the device's peak memory after it."""

    def __init__(self):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.compile_s += duration

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0, c0 = time.perf_counter(), self.compile_s
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        print(f"[phase] {name}: {wall:.2f} s = {comp:.2f} s compile + "
              f"{wall - comp:.2f} s run; peak_bytes_in_use "
              f"{peak_bytes()}", flush=True)


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def leaf_digests(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        raw = np.ascontiguousarray(np.asarray(leaf)).view(np.uint8)
        out[jax.tree_util.keystr(path)] = hashlib.sha256(
            raw.reshape(-1)).hexdigest()
    return out


def requests(vocab: int, lens, priority: int, seed: int):
    from repro.runtime import GenerationRequest
    rng = np.random.default_rng(seed)
    return [GenerationRequest(
        prompt=rng.integers(1, vocab, n).astype(np.int32),
        max_new_tokens=NEW_TOKENS, priority=priority) for n in lens]


def kv_budget_line(cfg, kv) -> str:
    per_token = (2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim_
                 * np.dtype(cfg.jnp_dtype).itemsize)
    pool = (kv.num_pages + 1) * kv.page_size * per_token
    prefill = ENGINE["max_slots"] * ENGINE["max_len"] * per_token
    return (f"KV budget: pool {kv.num_pages}+1 pages x {kv.page_size} "
            f"tokens = {pool / 1e9:.3f} GB, updated in place by the decode "
            f"step; a prefill of up to {ENGINE['max_slots']} rows adds a "
            f"dense {ENGINE['max_len']}-position cache of "
            f"{prefill / 1e9:.3f} GB")


def timed(fn, *args, reps: int = 10):
    """fn(*args) once (compiling it), then the median wall seconds of
    ``reps`` more calls, each waited for."""
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return out, float(np.median(times))


def check_pool_kernels(kv, layer: int, plain_pages=None) -> None:
    """On the live pool at ``layer``, the compiled kernels against the
    dense-gather reference over the plaintext pool, each timed. With no
    ciphertext-resident pages: ``paged_attention``, and
    ``paged_attention_unseal`` with every sidecar flag clear. After the
    engine has restored pages as ciphertext: ``paged_attention_unseal``
    through the engine's own crypt sidecars and key, with the reference over
    the pool with each such page's plaintext (``plain_pages``: phys ->
    (k, v)) put back."""
    import jax.numpy as jnp
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_unseal)
    from repro.kernels.ref import paged_attention_ref

    blk = kv.blocks["layers"]["slot_0"]
    k, v = blk["k"][layer], blk["v"][layer]
    table = np.asarray(kv.table)
    valid = np.asarray(kv.pos)
    b, h = table.shape[0], kv.model.cfg.num_heads
    q = jax.random.normal(jax.random.key(1), (b, h, k.shape[-1]),
                          jnp.float32).astype(k.dtype)
    q32, k32, v32 = (np.array(a, np.float32) for a in (q, k, v))
    for phys, (pk, pv) in (plain_pages or {}).items():
        k32[phys], v32[phys] = pk, pv
    ref = paged_attention_ref(q32, k32, v32, table, valid)
    live = valid > 0
    # each output element is a softmax-weighted mean of v. The kernels may
    # round the weights to bf16 for the MXU (2^-9 of each term) and round
    # the output to bf16 (2^-9): within 2^-7 of the weighted mean of |v|.
    # A wrong keystream, nonce or layer offset decrypts to garbage, far
    # outside it.
    bound = 2.0 ** -7 * paged_attention_ref(q32, k32, np.abs(v32), table,
                                            valid)[live]
    args = (q, k, v, jnp.asarray(table), jnp.asarray(valid))
    n_cipher = len(plain_pages or {})
    runs = []
    if not n_cipher:
        runs.append(("paged_attention", paged_attention))
    runs.append((f"paged_attention_unseal ({n_cipher} pages the engine "
                 f"restored as ciphertext)", functools.partial(
                     paged_attention_unseal, layer=jnp.int32(layer),
                     key_words=(kv._crypt_key.key_words if kv._crypt_key
                                else jnp.zeros(8, jnp.uint32)),
                     k_crypt=jnp.asarray(kv._crypt[kv._k_path]),
                     v_crypt=jnp.asarray(kv._crypt[kv._v_path]),
                     blocks_per_page=kv._fused_bpp)))
    for name, fn in runs:
        out, sec = timed(fn, *args)
        err = np.abs(np.asarray(out, np.float32) - ref)[live]
        print(f"{name} (layer {layer}, {int(live.sum())} live slots, "
              f"{int(valid.sum())} positions): max |err| {err.max():.3e}, "
              f"worst err/bound {(err / bound).max():.3f} "
              f"(bound 2^-7 sum p|v|); {sec * 1e6:.1f} us per call "
              f"(wall, median of 10)", flush=True)
        require((err <= bound).all(), f"{name} exceeds its tolerance")


def snapshot_full_pages(engine, layer: int, snaps: dict) -> None:
    """Host copies of each running request's mapped full pages at
    ``layer``, by request and page ordinal: what a preemption about to
    happen would seal. A page a partial eviction has unmapped keeps the
    copy taken while it was mapped."""
    kv = engine.kv
    blk = kv.blocks["layers"]["slot_0"]
    k, v = np.asarray(blk["k"][layer]), np.asarray(blk["v"][layer])
    for slot, req in engine.scheduler.running.items():
        pages = snaps.setdefault(id(req), {})
        for j in range(int(kv.pos[slot]) // kv.page_size):
            phys = int(kv.table[slot, j])
            if phys:
                pages[j] = (k[phys], v[phys])


def restored_plaintext(engine, snaps: dict) -> dict:
    """phys -> plaintext (k, v) of every ciphertext-resident page, from
    the snapshot of the request whose page it is."""
    kv = engine.kv
    plain = {}
    for slot, req in engine.scheduler.running.items():
        for j in range(int(kv.pos[slot]) // kv.page_size):
            phys = int(kv.table[slot, j])
            if phys in kv._cipher_pages:
                require(j in snaps.get(id(req), {}),
                        f"restored page {j} was not full when sealed")
                plain[phys] = snaps[id(req)][j]
    require(set(plain) == kv._cipher_pages,
            "a ciphertext-resident page maps to no running request")
    return plain


def check_restored_bits(kv, layer: int, plain_pages: dict) -> None:
    """The pool's ciphertext-resident pages at ``layer``, decrypted on the
    host with the reference keystream from the engine's own sidecars and
    key, are exactly the plaintext that was sealed: admission kept every
    bit, whatever the kernel then does with them."""
    from repro.kernels.ref import chacha20_keystream_bytes_ref
    blk = kv.blocks["layers"]["slot_0"]
    paths = {"k": kv._k_path, "v": kv._v_path}
    bad = total = 0
    for leaf, i in (("k", 0), ("v", 1)):
        pool = np.asarray(blk[leaf][layer])
        for phys, pages in plain_pages.items():
            ct = np.ascontiguousarray(pool[phys]).view(np.uint8).reshape(-1)
            ks = chacha20_keystream_bytes_ref(
                kv._crypt_key.key, kv._crypt[paths[leaf]][phys, :3].tobytes(),
                ct.size, counter_base=layer * kv._fused_bpp)
            want = np.ascontiguousarray(pages[i]).view(np.uint8).reshape(-1)
            bad += int(((ct ^ np.frombuffer(ks, np.uint8)) != want).sum())
            total += ct.size
    print(f"restored ciphertext pages, host-decrypted at layer {layer}: "
          f"{bad} of {total} bytes differ from the sealed plaintext",
          flush=True)
    require(bad == 0, "restored ciphertext does not decrypt to what was "
                      "sealed")


def one_chip(cfg, phases: Phases) -> None:
    """The confidential path on one device: init, seal, attest, load,
    serve with a sealed preemption, kernel checks."""
    from repro.core import TrustDomain
    from repro.models import build_model
    from repro.runtime import Engine

    model = build_model(cfg)
    with phases("init (weights from jax.random.key(0) under jit)"):
        params = model.init_params(jax.random.key(0))
        jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"params: {len(jax.tree.leaves(params))} leaves, "
          f"{n_bytes / 1e9:.3f} GB", flush=True)
    with phases("digest plaintext (sha256 per leaf)"):
        digests = leaf_digests(params)

    td = TrustDomain("tdx")
    with phases("seal"):
        sealed = td.seal_params(params)
    require(all(isinstance(st.ciphertext, np.ndarray)
                for st in sealed.values()),
            "sealed parameters must rest on the host")
    with phases("attest (quote -> verify)"):
        verifier = td.make_verifier(cfg.name)
        verifier.verify(td.quote(verifier.challenge(), cfg.name))
    del params                         # the plaintext leaves the device
    with phases("load_sealed (chunked unseal on device)"):
        params = td.load_sealed(sealed, model.abstract_params())
        jax.block_until_ready(params)
    del sealed
    with phases("digest unsealed"):
        same = leaf_digests(params) == digests
    print(f"unsealed parameters bit-identical to the originals: {same} "
          f"({len(digests)} leaves)", flush=True)
    require(same, "unsealed parameters differ from the originals")

    with phases("engine (paged KV pool, kernel decode)"):
        engine = Engine(model, params, trust_domain=td, kv_backend="paged",
                        kv_decode="kernel", **ENGINE)
    kv = engine.kv
    print(kv_budget_line(cfg, kv), flush=True)
    require(kv.decode_mode == "kernel" and kv.supports_fused,
            "the served pool must decode with the fused kernels")
    vocab = min(cfg.vocab_size, 32000)
    with phases(f"serve: {len(LOW)} priority-0 requests fill the slots"):
        reqs = [engine.submit(g) for g in requests(vocab, LOW, 0, seed=1)]
        for _ in range(64):
            if len(engine.scheduler.running) == len(LOW):
                break
            engine.step()
    require(len(engine.scheduler.running) == len(LOW),
            "the low-priority wave never filled the slots")
    layer = cfg.num_layers - 1
    with phases("kernel check on the live pool"):
        check_pool_kernels(kv, layer)
    with phases(f"serve: {len(HIGH)} priority-5 requests preempt; step "
                f"until a restore is admitted as ciphertext"):
        reqs += [engine.submit(g) for g in requests(vocab, HIGH, 5, seed=2)]
        snaps: dict = {}
        for _ in range(10_000):
            if kv._cipher_pages or not engine.scheduler.running:
                break
            snapshot_full_pages(engine, layer, snaps)
            engine.step()
    require(kv._cipher_pages, "no restore left pages ciphertext-resident")
    with phases("fused kernel check on the engine's restored pages"):
        plain = restored_plaintext(engine, snaps)
        check_restored_bits(kv, layer, plain)
        check_pool_kernels(kv, layer, plain)
    with phases("serve: run to the end"):
        stats = engine.run(max_steps=10_000)
    require(all(r.finished and len(r.output) == NEW_TOKENS for r in reqs),
            "a request did not finish with all its tokens")
    require(all(0 <= t < cfg.vocab_size for r in reqs for t in r.output),
            "a served token is outside the vocabulary")
    print(f"served {stats.total_requests} requests / {stats.total_tokens} "
          f"tokens; preemptions {stats.preemptions}; fused-restore pages "
          f"{kv.fused_restore_pages} ({kv.fused_restore_bytes} B admitted "
          f"as ciphertext)", flush=True)
    require(stats.preemptions > 0, "no sealed preemption happened")
    require(kv.fused_restore_pages > 0,
            "no restore was admitted as ciphertext")


def serve_tokens(model, params, **engine_kw):
    """Greedy tokens for the four low-priority requests, and the engine."""
    from repro.runtime import Engine
    engine = Engine(model, params, kv_backend="paged", kv_decode="gather",
                    **ENGINE, **engine_kw)
    reqs = [engine.submit(g) for g in requests(
        min(model.cfg.vocab_size, 32000), LOW, 0, seed=1)]
    engine.run(max_steps=10_000)
    require(all(r.finished for r in reqs), "a request did not finish")
    return [list(map(int, r.output)) for r in reqs], engine


def prefill_logits(engine, tokens: np.ndarray) -> np.ndarray:
    """Last-position logits of one prefill through the engine's own plan."""
    cache = engine.model.init_cache(tokens.shape[0], engine.max_len)
    logits, _ = engine.plan.compile_prefill()(engine.params, tokens, cache)
    return np.asarray(logits, np.float32)


def four_chips(cfg, phases: Phases, n_devices: int = 4) -> None:
    """Single-device plan first, then dp=4 (tokens identical) and
    dp=1,tp=4 (prefill logits within TP_LOGIT_TOL). The weights wait on
    the host between plans, so no device holds a whole copy next to its
    shard."""
    from repro.models import build_model

    require(len(jax.devices()) >= n_devices,
            f"needs {n_devices} devices, jax sees {len(jax.devices())}")
    model = build_model(cfg)
    with phases("init (weights from jax.random.key(0) under jit), then "
                "to the host"):
        params = jax.device_get(model.init_params(jax.random.key(0)))
    rng = np.random.default_rng(3)
    probe = rng.integers(1, min(cfg.vocab_size, 32000),
                         (n_devices, 128)).astype(np.int32)
    with phases("single-device plan (device 0)"):
        single, engine = serve_tokens(
            model, jax.device_put(params, jax.devices()[0]))
        ref_logits = prefill_logits(engine, probe)
    del engine                         # frees device 0
    with phases(f"mesh dp={n_devices}"):
        dp_tokens, engine = serve_tokens(model, params,
                                         mesh=f"dp={n_devices}")
    spans = {d.id for leaf in jax.tree.leaves(engine.params)
             for d in leaf.devices()}
    del engine
    same = dp_tokens == single
    print(f"dp={n_devices}: params span devices {sorted(spans)}; tokens "
          f"identical to the single-device run: {same}", flush=True)
    require(len(spans) == n_devices, "dp params did not span the mesh")
    require(same, f"dp={n_devices} tokens differ from the single device")
    with phases(f"mesh dp=1,tp={n_devices}"):
        tp_tokens, engine = serve_tokens(model, params,
                                         mesh=f"dp=1,tp={n_devices}")
        tp_logits = prefill_logits(engine, probe)
    del engine
    rel = float(np.abs(tp_logits - ref_logits).max()
                / np.abs(ref_logits).max())
    match = sum(a == b for x, y in zip(tp_tokens, single)
                for a, b in zip(x, y))
    total = sum(len(x) for x in single)
    print(f"tp={n_devices}: prefill logits max |dlogit| / max |logit| = "
          f"{rel:.3e} (tolerance {TP_LOGIT_TOL}); greedy tokens equal to "
          f"the single device {match}/{total}", flush=True)
    require(rel <= TP_LOGIT_TOL, f"tp={n_devices} logits beyond tolerance")
    peaks = [(d.id, (d.memory_stats() or {}).get("peak_bytes_in_use"))
             for d in jax.devices()]
    print(f"peak_bytes_in_use per device (id, bytes): {peaks}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path (dp=4, tp=4) and its "
                         "single-device comparison, on four chips")
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but jax's platform is "
                         f"{backend!r} ({jax.devices()[0].device_kind})")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}"
          f"; bytes_limit {(dev.memory_stats() or {}).get('bytes_limit')}",
          flush=True)
    cfg = get_config("llama2-7b")
    print(f"llama2-7b at published widths: d_model {cfg.d_model}, heads "
          f"{cfg.num_heads}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}; {cfg.num_layers} layers (published depth)",
          flush=True)
    phases = Phases()
    if args.four_chips:
        four_chips(cfg, phases)
    else:
        one_chip(cfg, phases)
    print(f"peak_bytes_in_use {peak_bytes()}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
