"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles in
kernels/ref.py, external ground truth (RFC 8439), and property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.chacha20 import BLOCKS_PER_TILE, chacha20_xor_blocked
from repro.kernels.flash_attention import flash_attention
from repro.kernels.qmatmul import qmatmul
from repro.quant import quantize_int8, dequantize, qmatmul_ref


# ---------------------------------------------------------------------------
# chacha20
# ---------------------------------------------------------------------------

class TestChaCha20:
    def test_rfc8439_keystream_vector(self):
        """RFC 8439 §2.4.2 — the canonical test vector, counter=1."""
        key = bytes(range(32))
        nonce = bytes([0, 0, 0, 0, 0, 0, 0, 0x4A, 0, 0, 0, 0])
        ks = ref.chacha20_keystream_bytes_ref(key, nonce, 114, counter_base=1)
        plaintext = (b"Ladies and Gentlemen of the class of '99: If I could "
                     b"offer you only one tip for the future, sunscreen would be it.")
        cipher = bytes(a ^ b for a, b in zip(plaintext, ks))
        expected = bytes.fromhex(
            "6e2e359a2568f98041ba0728dd0d6981e97e7aec1d4360c20a27afccfd9fae0b"
            "f91b65c5524733ab8f593dabcd62b3571639d624e65152ab8f530c359f0861d8"
            "07ca0dbf500d6a6156a38e088a22b65e52bc514d16ccf806818ce91ab7793736"
            "5af90bbf74a35be6b40b8eedf2785e42874d")
        assert cipher == expected

    @pytest.mark.parametrize("n_tiles", [1, 2, 5])
    def test_kernel_matches_ref(self, n_tiles):
        n = n_tiles * BLOCKS_PER_TILE
        rng = np.random.default_rng(n_tiles)
        data = jnp.asarray(rng.integers(0, 2**32, (16, n), dtype=np.uint32))
        kw = jnp.asarray(rng.integers(0, 2**32, 8, dtype=np.uint32))
        nw = jnp.asarray(rng.integers(0, 2**32, 3, dtype=np.uint32))
        out = chacha20_xor_blocked(kw, nw, data, counter_base=7)
        expect = ref.chacha20_xor_ref(kw, nw, data, counter_base=7)
        assert jnp.all(out == expect)

    @given(seed=st.integers(0, 2**31 - 1), nbytes=st.integers(1, 5000))
    @settings(max_examples=15, deadline=None)
    def test_pack_seal_roundtrip_property(self, seed, nbytes):
        rng = np.random.default_rng(seed)
        raw = rng.integers(0, 256, nbytes, dtype=np.uint8)
        blocked, n = ops.pack_u32(raw)
        kw = jnp.asarray(rng.integers(0, 2**32, 8, dtype=np.uint32))
        nw = jnp.asarray(rng.integers(0, 2**32, 3, dtype=np.uint32))
        sealed = ops.seal_u32(kw, nw, blocked)
        # involution
        opened = ops.unseal_u32(kw, nw, sealed)
        back = ops.place_rows(jnp.zeros((n,), jnp.uint8), opened, 0, n)
        assert np.array_equal(np.asarray(back), raw)
        # ciphertext differs from plaintext (overwhelmingly likely)
        if nbytes > 8:
            assert not np.array_equal(np.asarray(sealed), np.asarray(blocked))

    def test_counter_continuation_across_tiled_calls(self):
        """Two tiled calls entering the stream at counter_base 0 and N must
        reproduce one contiguous single-call keystream — the fused-unseal
        decode kernel relies on mid-stream counter entry (layer l decrypts
        at counter_base = l * blocks_per_page)."""
        rng = np.random.default_rng(5)
        n = 2 * BLOCKS_PER_TILE
        data = jnp.asarray(rng.integers(0, 2**32, (16, n), dtype=np.uint32))
        kw = jnp.asarray(rng.integers(0, 2**32, 8, dtype=np.uint32))
        nw = jnp.asarray(rng.integers(0, 2**32, 3, dtype=np.uint32))
        whole = chacha20_xor_blocked(kw, nw, data)
        lo = chacha20_xor_blocked(kw, nw, data[:, :BLOCKS_PER_TILE])
        hi = chacha20_xor_blocked(kw, nw, data[:, BLOCKS_PER_TILE:],
                                  counter_base=BLOCKS_PER_TILE)
        assert jnp.array_equal(whole, jnp.concatenate([lo, hi], axis=1))
        # and the ref agrees block-for-block at an arbitrary entry point
        ks = ref.chacha20_keystream_ref(kw, nw, 8)
        ks_mid = ref.chacha20_keystream_ref(kw, nw, 3, counter_base=5)
        assert jnp.array_equal(ks[:, 5:], ks_mid)

    def test_counter_wraps_uint32(self):
        """The 32-bit block counter wraps modulo 2**32 (RFC 8439 keeps the
        counter a single u32 word): counter_base at the top of the range
        continues into 0, 1, ... rather than overflowing."""
        kw = jnp.arange(8, dtype=jnp.uint32)
        nw = jnp.arange(3, dtype=jnp.uint32)
        top = (1 << 32) - 2
        wrapped = ref.chacha20_keystream_ref(kw, nw, 4, counter_base=top)
        # blocks at counters [2**32-2, 2**32-1, 0, 1]
        lo = ref.chacha20_keystream_ref(kw, nw, 2, counter_base=0)
        assert jnp.array_equal(wrapped[:, 2:], lo)
        assert not jnp.array_equal(wrapped[:, :2], lo)
        # kernel path agrees with the ref across the wrap
        data = jnp.zeros((16, BLOCKS_PER_TILE), jnp.uint32)
        out = chacha20_xor_blocked(kw, nw, data, counter_base=top)
        expect = ref.chacha20_xor_ref(kw, nw, data, counter_base=top)
        assert jnp.array_equal(out, expect)

    def test_keystream_differs_across_nonces_and_counters(self):
        kw = jnp.arange(8, dtype=jnp.uint32)
        n1 = jnp.arange(3, dtype=jnp.uint32)
        n2 = n1 + 1
        ks1 = ref.chacha20_keystream_ref(kw, n1, 4)
        ks2 = ref.chacha20_keystream_ref(kw, n2, 4)
        ks3 = ref.chacha20_keystream_ref(kw, n1, 4, counter_base=4)
        assert not jnp.array_equal(ks1, ks2)
        assert not jnp.array_equal(ks1, ks3)


# ---------------------------------------------------------------------------
# qmatmul
# ---------------------------------------------------------------------------

class TestQMatmul:
    @pytest.mark.parametrize("m,k,n,bm,bn,bk", [
        (128, 128, 128, 128, 128, 128),
        (256, 384, 128, 128, 128, 128),
        (128, 256, 256, 64, 128, 64),
        (512, 128, 384, 128, 128, 128),
    ])
    def test_kernel_exact_vs_ref(self, m, k, n, bm, bn, bk):
        kx, kw = jax.random.split(jax.random.key(m + n))
        xq = jax.random.randint(kx, (m, k), -127, 128, jnp.int8)
        wq = jax.random.randint(kw, (k, n), -127, 128, jnp.int8)
        scale = jax.random.uniform(kx, (1, n), jnp.float32, 0.01, 1.0)
        out = qmatmul(xq, wq, scale, bm=bm, bn=bn, bk=bk)
        expect = ref.qmatmul_ref(xq, wq, scale)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(expect, np.float32))

    @pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32])
    def test_out_dtypes(self, out_dtype):
        xq = jnp.ones((128, 128), jnp.int8)
        wq = jnp.ones((128, 128), jnp.int8)
        scale = jnp.full((1, 128), 0.5, jnp.float32)
        out = qmatmul(xq, wq, scale, out_dtype=out_dtype)
        assert out.dtype == out_dtype
        assert float(out[0, 0]) == 64.0

    @pytest.mark.parametrize("m,k,n", [(100, 200, 300), (7, 130, 129), (1, 64, 32)])
    def test_qmm_wrapper_close_to_float(self, m, k, n):
        x = jax.random.normal(jax.random.key(0), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.key(1), (k, n), jnp.float32)
        out = ops.qmm(x, quantize_int8(w))
        oracle = x @ w
        rel = float(jnp.max(jnp.abs(out.astype(jnp.float32) - oracle))
                    / (jnp.max(jnp.abs(oracle)) + 1e-9))
        assert rel < 0.05, rel

    @given(seed=st.integers(0, 1000))
    @settings(max_examples=10, deadline=None)
    def test_quantize_roundtrip_error_bound(self, seed):
        w = jax.random.normal(jax.random.key(seed), (64, 96), jnp.float32)
        q = quantize_int8(w)
        back = dequantize(q, jnp.float32)
        # per output channel, max error <= scale/2 (+ rounding slack)
        err = jnp.max(jnp.abs(back - w), axis=0)
        bound = q.scale[0] * 0.5 + 1e-6
        assert bool(jnp.all(err <= bound))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

class TestFlashAttention:
    @pytest.mark.parametrize("bh,s,d,bq,bkv", [
        (2, 128, 64, 64, 64),
        (4, 256, 64, 128, 64),
        (1, 256, 128, 64, 128),
        (8, 128, 32, 128, 128),
    ])
    def test_matches_ref(self, bh, s, d, bq, bkv):
        ks = jax.random.split(jax.random.key(s + d), 3)
        q = jax.random.normal(ks[0], (bh, s, d), jnp.float32)
        k = jax.random.normal(ks[1], (bh, s, d), jnp.float32)
        v = jax.random.normal(ks[2], (bh, s, d), jnp.float32)
        out = flash_attention(q, k, v, bq=bq, bkv=bkv)
        expect = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16(self):
        ks = jax.random.split(jax.random.key(9), 3)
        q, k, v = (jax.random.normal(kk, (2, 128, 64), jnp.bfloat16) for kk in ks)
        out = flash_attention(q, k, v, bq=64, bkv=64)
        expect = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   atol=2e-2, rtol=2e-2)

    def test_causality(self):
        """Changing future K/V must not affect earlier outputs."""
        ks = jax.random.split(jax.random.key(3), 3)
        q, k, v = (jax.random.normal(kk, (1, 128, 32), jnp.float32) for kk in ks)
        out1 = flash_attention(q, k, v, bq=64, bkv=64)
        k2 = k.at[:, 100:].set(99.0)
        v2 = v.at[:, 100:].set(-99.0)
        out2 = flash_attention(q, k2, v2, bq=64, bkv=64)
        np.testing.assert_allclose(np.asarray(out1[:, :100]),
                                   np.asarray(out2[:, :100]), atol=1e-6)

    def test_mha_wrapper_gqa(self):
        b, s, h, hk, hd = 2, 128, 8, 2, 32
        ks = jax.random.split(jax.random.key(4), 3)
        q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, hk, hd), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, hk, hd), jnp.float32)
        out = ops.mha_flash(q, k, v, bq=64, bkv=64)
        # oracle via repeat + ref
        kr = jnp.repeat(k, h // hk, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, hd)
        vr = jnp.repeat(v, h // hk, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, hd)
        qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
        expect = ref.flash_attention_ref(qr, kr, vr).reshape(b, h, s, hd).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("s", [1, 7, 129, 200, 250])
    def test_mha_wrapper_odd_lengths(self, s):
        """Non-block-multiple sequence lengths (s=200 with bq=128 used to
        trip flash_attention's s % bq assert): padded to the block
        multiple, padded kv masked causally, output sliced back."""
        b, h, hd = 2, 4, 32
        ks = jax.random.split(jax.random.key(s), 3)
        q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, h, hd), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, h, hd), jnp.float32)
        out = ops.mha_flash(q, k, v)
        qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
        kr = k.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
        vr = v.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
        expect = ref.flash_attention_ref(qr, kr, vr).reshape(
            b, h, s, hd).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=2e-5, rtol=2e-5)
