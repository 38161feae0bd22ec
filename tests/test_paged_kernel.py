"""Table-walking Pallas paged-attention decode kernel + fused in-kernel
unseal: kernel-level parity against the dense-gather reference
(kernels/ref.py), fused-decrypt
parity against unseal-then-attend, backend/engine wiring, and the
ciphertext-resident restore lifecycle (MAC gate, materialization on host
consumption, decoded-token equality with the gather reference)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.sealing import (IntegrityError, SealingKey, seal_tensor,
                                verify_mac)
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_unseal,
                                           supports_fused_unseal)
from repro.kernels.ref import paged_attention_ref


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def make_pool(rng, *, slots=3, pages=4, ps=8, h=4, hk=2, hd=16,
              dtype=np.float32):
    """Random pool + a table where every slot maps a distinct shuffled set
    of physical pages and valids include a partial tail and an idle row."""
    npages = slots * pages
    k_pool = rng.normal(size=(npages + 1, ps, hk, hd)).astype(dtype)
    v_pool = rng.normal(size=(npages + 1, ps, hk, hd)).astype(dtype)
    order = rng.permutation(npages) + 1
    table = order.reshape(slots, pages).astype(np.int32)
    valid = np.array([pages * ps, 2 * ps + 3, 0][:slots] +
                     [ps] * max(0, slots - 3), np.int32)[:slots]
    q = rng.normal(size=(slots, h, hd)).astype(dtype)
    return q, k_pool, v_pool, table, valid


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("h,hk", [(4, 4), (4, 2), (8, 1)])
    def test_matches_dense_oracle(self, h, hk):
        rng = np.random.default_rng(h * 10 + hk)
        q, kp, vp, table, valid = make_pool(rng, h=h, hk=hk)
        out = paged_attention(jnp.asarray(q), jnp.asarray(kp),
                              jnp.asarray(vp), jnp.asarray(table),
                              jnp.asarray(valid))
        expect = paged_attention_ref(q, kp, vp, table, valid)
        live = valid > 0
        np.testing.assert_allclose(np.asarray(out)[live], expect[live],
                                   atol=2e-5, rtol=2e-5)

    def test_partial_tail_page_masked(self):
        """Garbage beyond ``valid`` in the tail page must not reach a
        logit: corrupting those positions leaves the output unchanged."""
        rng = np.random.default_rng(0)
        q, kp, vp, table, valid = make_pool(rng, slots=1, pages=2)
        valid[:] = 11                                    # page 1 holds 3
        base = np.asarray(paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(valid)))
        kp2, vp2 = kp.copy(), vp.copy()
        tail = table[0, 1]
        kp2[tail, 3:] = 1e6
        vp2[tail, 3:] = -1e6
        out = np.asarray(paged_attention(
            jnp.asarray(q), jnp.asarray(kp2), jnp.asarray(vp2),
            jnp.asarray(table), jnp.asarray(valid)))
        np.testing.assert_array_equal(out, base)

    def test_bf16_pool(self):
        rng = np.random.default_rng(3)
        q, kp, vp, table, valid = make_pool(rng)
        to16 = lambda a: jnp.asarray(a).astype(jnp.bfloat16)
        out = paged_attention(to16(q), to16(kp), to16(vp),
                              jnp.asarray(table), jnp.asarray(valid))
        expect = paged_attention_ref(np.asarray(to16(q), np.float32),
                                     np.asarray(to16(kp), np.float32),
                                     np.asarray(to16(vp), np.float32),
                                     table, valid)
        live = valid > 0
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[live], expect[live], atol=3e-2)

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_emulation_matches_pallas_interpret(self, dtype):
        """The jnp page-walk stand-in (emulate=True, the default under
        interpret) must be bit-identical to the Pallas kernel's interpret
        output — it is what engine tests and CPU benches actually run."""
        rng = np.random.default_rng(11)
        q, kp, vp, table, valid = make_pool(rng)
        args = [jnp.asarray(a).astype(dtype) for a in (q, kp, vp)]
        args += [jnp.asarray(table), jnp.asarray(valid)]
        emu = paged_attention(*args, interpret=True)
        pallas = paged_attention(*args, interpret=True, emulate=False)
        np.testing.assert_array_equal(np.asarray(emu), np.asarray(pallas))


# ---------------------------------------------------------------------------
# fused in-kernel unseal
# ---------------------------------------------------------------------------

def seal_page_linear(key, name, page):
    """Seal one [L, ps, hk, hd] page the way the backend does and return
    (ciphertext bits laid out in the page's plaintext shape, nonce words).
    Mirrors restore's _admit_cipher_page."""
    from repro.core.sealing import ciphertext_page_bytes, nonce_words_for
    st = seal_tensor(key, name, page)
    raw = ciphertext_page_bytes(st)
    bits = np.frombuffer(raw, page.dtype).reshape(page.shape)
    return st, bits, nonce_words_for(key, name)


class TestFusedUnseal:
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_in_kernel_decrypt_matches_unseal_then_attend(self, dtype):
        """The acceptance-criteria parity: a pool where some pages are
        ciphertext-resident (crypt flag live) attends identically to the
        same pool fully host-decrypted — per layer, bit-exactly."""
        rng = np.random.default_rng(7)
        L, ps, hk, hd, h, slots = 2, 8, 2, 16, 4, 2
        q, kp, vp, table, valid = make_pool(
            rng, slots=slots, pages=2, ps=ps, h=h, hk=hk, hd=hd)
        kp = jnp.asarray(kp).astype(dtype)
        vp = jnp.asarray(vp).astype(dtype)
        q = jnp.asarray(q).astype(dtype)
        page_bytes = ps * hk * hd * jnp.dtype(dtype).itemsize
        assert supports_fused_unseal(dtype, page_bytes)
        bpp = page_bytes // 64

        key = SealingKey.generate(b"fused")
        npages = kp.shape[0]
        k_crypt = np.zeros((npages, 4), np.uint32)
        v_crypt = np.zeros((npages, 4), np.uint32)
        kp_c, vp_c = np.asarray(kp).copy(), np.asarray(vp).copy()
        # make slot 0's first page ciphertext-resident; everything else
        # stays plaintext (the flag-dead path must be bit-exact identity)
        phys = int(table[0, 0])
        # the sealed blob packs the page's L layers contiguously — here the
        # kernel is called per layer, so seal an L-stacked page and place
        # each layer's ciphertext
        for pool, crypt, leaf in ((kp_c, k_crypt, "k"),
                                  (vp_c, v_crypt, "v")):
            stacked = np.stack([np.asarray(pool[phys])] * L)
            # distinct per-layer contents
            for l in range(L):
                stacked[l] += l
            st, bits, nonce = seal_page_linear(
                key, f"t['{leaf}']/p0", stacked)
            verify_mac(key, st)
            crypt[phys, :3] = nonce
            crypt[phys, 3] = 1
            pool[phys] = bits[0]          # layer 0 resident this call
        plain_kp = np.asarray(kp).copy()
        plain_vp = np.asarray(vp).copy()

        fused = paged_attention_unseal(
            q, jnp.asarray(kp_c), jnp.asarray(vp_c), jnp.asarray(table),
            jnp.asarray(valid), jnp.int32(0), key.key_words,
            jnp.asarray(k_crypt), jnp.asarray(v_crypt),
            blocks_per_page=bpp)
        ref = paged_attention(q, jnp.asarray(plain_kp),
                              jnp.asarray(plain_vp), jnp.asarray(table),
                              jnp.asarray(valid))
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))

        # and the jnp stand-in decrypts bit-identically to the Pallas
        # interpreter on the same mixed cipher/plaintext pool
        pallas = paged_attention_unseal(
            q, jnp.asarray(kp_c), jnp.asarray(vp_c), jnp.asarray(table),
            jnp.asarray(valid), jnp.int32(0), key.key_words,
            jnp.asarray(k_crypt), jnp.asarray(v_crypt),
            blocks_per_page=bpp, emulate=False)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(pallas))

    def test_layer_counter_offset(self):
        """Layer l decrypts with counter_base = l * blocks_per_page: layer
        1's ciphertext under layer index 1 must equal its plaintext."""
        rng = np.random.default_rng(11)
        L, ps, hk, hd, h = 3, 8, 2, 16, 4
        q, kp, vp, table, valid = make_pool(
            rng, slots=1, pages=1, ps=ps, h=h, hk=hk, hd=hd)
        bpp = ps * hk * hd * 4 // 64
        key = SealingKey.generate(b"layers")
        phys = int(table[0, 0])
        stacked = rng.normal(size=(L, ps, hk, hd)).astype(np.float32)
        _, bits, nonce = seal_page_linear(key, "t['k']/p0", stacked)
        crypt = np.zeros((kp.shape[0], 4), np.uint32)
        crypt[phys, :3], crypt[phys, 3] = nonce, 1
        for l in range(L):
            kp_l = kp.copy()
            kp_l[phys] = bits[l]
            fused = paged_attention_unseal(
                jnp.asarray(q), jnp.asarray(kp_l), jnp.asarray(vp),
                jnp.asarray(table), jnp.asarray(valid), jnp.int32(l),
                key.key_words, jnp.asarray(crypt),
                jnp.asarray(np.zeros_like(crypt)), blocks_per_page=bpp)
            kp_p = kp.copy()
            kp_p[phys] = stacked[l]
            ref = paged_attention(jnp.asarray(q), jnp.asarray(kp_p),
                                  jnp.asarray(vp), jnp.asarray(table),
                                  jnp.asarray(valid))
            np.testing.assert_array_equal(np.asarray(fused),
                                          np.asarray(ref))

    @pytest.mark.parametrize("ps,hk,hd,dtype", [
        (8, 12, 128, np.float32),    # a page row spans two keystream rows
        (8, 4, 128, np.float32),     # four page rows share one
    ])
    def test_multi_row_keystream(self, ps, hk, hd, dtype):
        """Pages of more than 128 ChaCha20 blocks: the kernel computes the
        page's keystream once, block index on the lanes, and gathers each
        row's words from it. Kernel and emulation both decrypt every
        layer's ciphertext to exactly what the plaintext pool attends."""
        rng = np.random.default_rng(5)
        L, h = 2, hk
        q, kp, vp, table, valid = make_pool(
            rng, slots=1, pages=2, ps=ps, h=h, hk=hk, hd=hd)
        q, kp, vp = (np.asarray(jnp.asarray(a).astype(dtype))
                     for a in (q, kp, vp))
        bpp = ps * hk * hd * jnp.dtype(dtype).itemsize // 64
        assert bpp > 128
        key = SealingKey.generate(b"rows")
        phys = int(table[0, 1])
        stacked = np.asarray(jnp.asarray(
            rng.normal(size=(L, ps, hk, hd))).astype(dtype))
        _, bits, nonce = seal_page_linear(key, "t['v']/p1", stacked)
        crypt = np.zeros((kp.shape[0], 4), np.uint32)
        crypt[phys, :3], crypt[phys, 3] = nonce, 1
        for l in range(L):
            vp_c, vp_p = vp.copy(), vp.copy()
            vp_c[phys], vp_p[phys] = bits[l], stacked[l]
            ref = paged_attention(jnp.asarray(q), jnp.asarray(kp),
                                  jnp.asarray(vp_p), jnp.asarray(table),
                                  jnp.asarray(valid))
            for emulate in (True, False):
                fused = paged_attention_unseal(
                    jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp_c),
                    jnp.asarray(table), jnp.asarray(valid), jnp.int32(l),
                    key.key_words, jnp.asarray(np.zeros_like(crypt)),
                    jnp.asarray(crypt), blocks_per_page=bpp,
                    emulate=emulate)
                np.testing.assert_array_equal(np.asarray(fused),
                                              np.asarray(ref))

    def test_eligibility_predicate(self):
        assert supports_fused_unseal(jnp.float32, 8192)
        assert supports_fused_unseal(jnp.bfloat16, 4096)
        assert not supports_fused_unseal(jnp.float32, 8192 + 32)  # not 64B
        assert not supports_fused_unseal(jnp.int8, 8192)          # dtype


# ---------------------------------------------------------------------------
# backend + engine wiring
# ---------------------------------------------------------------------------

_MODEL_CACHE = {}


def tiny_model():
    from repro.configs import smoke_config
    from repro.models import build_model
    if "m" not in _MODEL_CACHE:
        cfg = smoke_config("deepseek-7b")
        model = build_model(cfg)
        _MODEL_CACHE["m"] = (model, model.init_params(jax.random.key(0)))
    return _MODEL_CACHE["m"]


@pytest.fixture(scope="module")
def kernel_engine_pair():
    """Decoded outputs of the same workload under gather and kernel decode
    (module-scoped: compiled engines are expensive under interpret)."""
    from repro.runtime import Engine, GenerationRequest, SamplingParams
    model, params = tiny_model()
    rng = np.random.default_rng(0)
    specs = [(list(rng.integers(1, 250, 6)), 10, i) for i in range(3)]

    def run(kv_decode):
        eng = Engine(model, params, max_slots=2, max_len=64,
                     prefill_buckets=(4, 8), kv_backend="paged",
                     page_size=8, kv_decode=kv_decode)
        reqs = [eng.submit(GenerationRequest(
                    prompt=np.asarray(p, np.int32), max_new_tokens=m,
                    params=SamplingParams(temperature=0.9, top_k=16,
                                          seed=s)))
                for p, m, s in specs]
        eng.run(max_steps=10_000)
        return [list(map(int, r.output)) for r in reqs], eng
    return run("gather"), run("kernel")


class TestKernelDecodeMode:
    def test_decoded_tokens_match_gather(self, kernel_engine_pair):
        (g_out, _), (k_out, k_eng) = kernel_engine_pair
        assert g_out == k_out
        assert k_eng.kv.decode_mode == "kernel"

    def test_slot_backend_rejects_kernel(self):
        from repro.runtime.kvcache import make_backend
        model, _ = tiny_model()
        with pytest.raises(ValueError, match="kv_decode"):
            make_backend("slot", model, max_slots=2, max_len=32,
                         decode="kernel")

    def test_bad_mode_rejected(self):
        from repro.runtime.kvcache import make_backend
        model, _ = tiny_model()
        with pytest.raises(ValueError):
            make_backend("paged", model, max_slots=2, max_len=32,
                         decode="fast")

    def test_sharded_plan_rejects_kernel(self):
        from repro.runtime.plan import ShardedPlan
        from repro.runtime.kvcache import make_backend
        model, _ = tiny_model()
        plan = ShardedPlan.from_spec(model, "dp=2")
        with pytest.raises(ValueError, match="single-device"):
            make_backend("paged", model, max_slots=2, max_len=32,
                         plan=plan, decode="kernel")


# ---------------------------------------------------------------------------
# ciphertext-resident restore lifecycle
# ---------------------------------------------------------------------------

def seal_restore_cycle(kv_decode, *, tamper=False, after=None):
    """Prefill+decode a request, whole-slot seal it, release, restore into
    a fresh slot, then decode 6 more steps greedily straight against the
    backend. Returns (tokens, backend)."""
    from repro.runtime import Engine, GenerationRequest, SamplingParams
    model, params = tiny_model()
    eng = Engine(model, params, max_slots=2, max_len=64,
                 prefill_buckets=(4, 8), kv_backend="paged", page_size=8,
                 kv_decode=kv_decode)
    kv = eng.kv
    rng = np.random.default_rng(42)
    prompt = np.asarray(list(rng.integers(1, 250, 20)), np.int32)
    eng.submit(GenerationRequest(
        prompt=prompt, max_new_tokens=24,
        params=SamplingParams(temperature=0.9, top_k=16, seed=7)))
    for _ in range(14):
        eng.step()
    key = SealingKey.generate(b"cycle")
    slot = next(s for s in range(2) if eng._active_mask[s])
    last = int(eng._last_token[slot])
    sealed = kv.seal(key, slot, "ckpt")
    pos = int(kv.pos[slot])
    kv.release(slot)
    s2 = kv.acquire(999, 64)
    if tamper:
        name = next(n for n in sealed if n.endswith("/p0"))
        ct = np.array(sealed[name].ciphertext)
        ct[0, 0] ^= 1
        sealed[name].ciphertext = jnp.asarray(ct)
        with pytest.raises(IntegrityError):
            kv.restore(key, sealed, s2, "ckpt", pos)
        return None, kv
    kv.restore(key, sealed, s2, "ckpt", pos)
    if after is not None:
        after(kv, s2)
    toks, out = np.zeros(2, np.int32), []
    toks[s2] = last
    for _ in range(6):
        nt = kv.decode(eng.params, toks, None, 0, [s2])
        toks[s2] = nt[s2]
        out.append(int(nt[s2]))
    return out, kv


class TestFusedRestore:
    def test_restore_admits_ciphertext_and_matches_gather(self):
        g, gkv = seal_restore_cycle("gather")
        k, kkv = seal_restore_cycle("kernel")
        assert g == k
        assert gkv.fused_restore_pages == 0
        # pos=22, page_size=8 -> pages 0 and 1 are full (fused), page 2 is
        # the partial tail (host path)
        assert kkv.fused_restore_pages == 2
        assert kkv.fused_restore_bytes > 0
        assert len(kkv._cipher_pages) == 2

    def test_tampered_page_fails_mac_before_admission(self):
        _, kv = seal_restore_cycle("kernel", tamper=True)
        assert not kv._cipher_pages      # nothing was admitted

    def test_materialize_on_reseal(self):
        """Sealing a slot holding ciphertext-resident pages host-decrypts
        them first; the re-sealed blobs restore to the same plaintext."""
        events = {}

        def reseal(kv, slot):
            key2 = SealingKey.generate(b"second")
            kv.seal(key2, slot, "ckpt2")
            events["cipher_after"] = set(kv._cipher_pages)
            events["ev"] = [e for e in kv.drain_events()
                            if e[0] == "materialize"]
        out, kv = seal_restore_cycle("kernel", after=reseal)
        assert events["cipher_after"] == set()
        assert len(events["ev"]) == 2            # both fused pages
        # decode after materialization still agrees with gather
        g, _ = seal_restore_cycle("gather")
        assert out == g

    def test_gather_mode_never_goes_fused(self):
        _, kv = seal_restore_cycle("gather")
        assert kv.decode_mode == "gather"
        assert not kv._cipher_pages
        assert kv.fused_restore_pages == 0
