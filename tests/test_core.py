"""Confidential core: sealing, attestation, bounce buffers, overhead model."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.core import (
    AttestationError, BounceBuffer, IntegrityError, PROFILES, RooflineTerms,
    SealingKey, TrustDomain, predict, seal_tensor, unseal_tensor,
)
from repro.core.overheads import sweep_batch


class TestSealing:
    @pytest.mark.parametrize("dtype,shape", [
        (np.float32, (10, 100)), (np.int8, (1000,)), (np.uint32, (3, 5, 7)),
        (np.float32, ()), ("bfloat16", (64, 64)),
    ])
    def test_roundtrip(self, dtype, shape):
        key = SealingKey.generate(b"test-seed")
        if dtype == "bfloat16":
            arr = jnp.ones(shape, jnp.bfloat16) * 1.5
        else:
            arr = jnp.asarray(np.random.default_rng(0).random(shape).astype(dtype)
                              if np.dtype(dtype).kind == "f"
                              else np.ones(shape, dtype))
        sealed = seal_tensor(key, "t", arr)
        back = unseal_tensor(key, sealed)
        assert back.dtype == arr.dtype and back.shape == arr.shape
        assert bool(jnp.all(back == arr))

    def test_tamper_detection_ciphertext(self):
        key = SealingKey.generate(b"k")
        sealed = seal_tensor(key, "w", jnp.arange(100, dtype=jnp.float32))
        ct = np.asarray(sealed.ciphertext).copy()
        ct[5, 17] ^= 1
        sealed.ciphertext = jnp.asarray(ct)
        with pytest.raises(IntegrityError):
            unseal_tensor(key, sealed)

    def test_tamper_detection_header(self):
        key = SealingKey.generate(b"k")
        sealed = seal_tensor(key, "w", jnp.arange(100, dtype=jnp.float32))
        sealed.shape = (50,)  # metadata tamper
        with pytest.raises(IntegrityError):
            unseal_tensor(key, sealed)

    def test_wrong_key_rejected(self):
        sealed = seal_tensor(SealingKey.generate(b"a"), "w",
                             jnp.ones((8,), jnp.float32))
        with pytest.raises(IntegrityError):
            unseal_tensor(SealingKey.generate(b"b"), sealed)

    def test_per_tensor_nonces_differ(self):
        """Same plaintext, different tensor names -> different ciphertext."""
        key = SealingKey.generate(b"k")
        x = jnp.ones((256,), jnp.float32)
        c1 = seal_tensor(key, "a", x).ciphertext
        c2 = seal_tensor(key, "b", x).ciphertext
        assert not bool(jnp.all(c1 == c2))

    @pytest.mark.parametrize("dtype,shape", [
        ("bfloat16", (64, 4096)), ("float32", (33, 512, 32)),
        ("int8", (100, 2048)), ("bfloat16", (37, 8, 2048)),
    ])
    def test_chunked_device_seal_matches_whole(self, dtype, shape,
                                               monkeypatch):
        """A device tensor over CHUNK_BYTES seals and unseals a chunk of
        rows at a time: same ciphertext and MAC as sealing its bytes whole
        on the host, and a bit-exact round trip, with the ciphertext at
        rest on the host."""
        from repro.core import sealing
        key = SealingKey.generate(b"chunks")
        x = (jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
             * 0.37).astype(dtype)
        whole = seal_tensor(key, "w", np.asarray(x))
        monkeypatch.setattr(sealing, "CHUNK_BYTES", 64 * 1024)
        assert len(sealing._row_chunks(shape, x.dtype.itemsize)) > 1
        chunked = seal_tensor(key, "w", x)
        assert isinstance(chunked.ciphertext, np.ndarray)
        assert np.array_equal(chunked.ciphertext, whole.ciphertext)
        assert chunked.mac == whole.mac
        back = unseal_tensor(key, chunked)
        assert back.dtype == x.dtype and back.shape == x.shape
        assert np.array_equal(np.asarray(back).view(np.uint8),
                              np.asarray(x).view(np.uint8))

    @pytest.mark.parametrize("dtype,shape", [
        ("float32", ()), ("int32", (3,)), ("bfloat16", (5, 7)),
        ("uint8", (130,)),
    ])
    def test_small_device_seal_matches_host(self, dtype, shape):
        """A small device tensor (one chunk, or a scalar) seals to the same
        bytes as its host copy, and both unseal bit-exactly."""
        key = SealingKey.generate(b"small")
        x = (jnp.arange(int(np.prod(shape)), dtype=jnp.float32)
             .reshape(shape) * 3.1).astype(dtype)
        dev, host = (seal_tensor(key, "t", a) for a in (x, np.asarray(x)))
        assert np.array_equal(dev.ciphertext, host.ciphertext)
        assert dev.mac == host.mac
        for st_ in (dev, host):
            back = unseal_tensor(key, st_)
            assert back.dtype == x.dtype and back.shape == x.shape
            assert np.asarray(back).tobytes() == np.asarray(x).tobytes()

    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 2000))
    @settings(max_examples=10, deadline=None)
    def test_roundtrip_property(self, seed, n):
        rng = np.random.default_rng(seed)
        arr = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        key = SealingKey.generate(seed.to_bytes(4, "little"))
        assert bool(jnp.all(unseal_tensor(key, seal_tensor(key, "x", arr)) == arr))


class TestAttestation:
    def _domain(self):
        td = TrustDomain("tdx")
        td.seal_params({"w": jnp.ones((4, 4), jnp.float32)})
        return td

    def test_quote_verifies_and_releases_key(self):
        td = self._domain()
        v = td.make_verifier("cfg")
        nonce = v.challenge()
        q = td.quote(nonce, "cfg")
        released = v.release_key(q, td.sealing_key.key)
        assert released == td.sealing_key.key

    def test_replay_rejected(self):
        td = self._domain()
        v = td.make_verifier("cfg")
        nonce = v.challenge()
        q = td.quote(nonce, "cfg")
        v.verify(q)
        with pytest.raises(AttestationError):
            v.verify(q)

    def test_measurement_binds_model(self):
        """Different sealed model -> different measurement -> rejected."""
        td = self._domain()
        v = td.make_verifier("cfg")
        td.seal_params({"w": jnp.zeros((4, 4), jnp.float32)})  # swap model
        nonce = v.challenge()
        with pytest.raises(AttestationError):
            v.verify(td.quote(nonce, "cfg"))

    def test_config_binds_measurement(self):
        td = self._domain()
        v = td.make_verifier("cfg-A")
        nonce = v.challenge()
        with pytest.raises(AttestationError):
            v.verify(td.quote(nonce, "cfg-B"))

    def test_forged_quote_rejected(self):
        td = self._domain()
        v = td.make_verifier("cfg")
        nonce = v.challenge()
        q = td.quote(nonce, "cfg")
        forged = dataclasses.replace(q, signature="00" * 32)
        with pytest.raises(AttestationError):
            v.verify(forged)


class TestBounce:
    def test_roundtrip_and_stats(self):
        bb = BounceBuffer(SealingKey.generate(b"io"))
        toks = np.arange(100, dtype=np.int32)
        out, sealed = bb.roundtrip(toks)
        assert np.array_equal(out, toks)
        assert bb.stats.messages_in == 1 and bb.stats.bytes_in >= 400
        # ciphertext on the wire differs from the plaintext bytes
        assert not np.array_equal(
            np.asarray(sealed.ciphertext).ravel()[:25].astype(np.int64),
            toks[:25].astype(np.int64))

    def test_sequence_numbers_make_unique_ciphertexts(self):
        bb = BounceBuffer(SealingKey.generate(b"io"))
        t = np.ones(64, np.int32)
        s1 = bb.host_send(t)
        s2 = bb.host_send(t)
        assert not bool(np.array_equal(np.asarray(s1.ciphertext),
                                       np.asarray(s2.ciphertext)))


class TestOverheadModel:
    def test_all_profiles_positive(self):
        t = RooflineTerms(compute_s=0.01, memory_s=0.04, collective_s=0.001)
        for name in PROFILES:
            assert predict(t, name).overhead > 0

    def test_memory_bound_worse_than_compute_bound_tdx(self):
        """Insight 9: TDX overhead is lowest when compute-bound."""
        mem_bound = RooflineTerms(compute_s=0.01, memory_s=0.09)
        comp_bound = RooflineTerms(compute_s=0.09, memory_s=0.01)
        assert (predict(mem_bound, "tdx").overhead
                > predict(comp_bound, "tdx").overhead)

    def test_batch_sweep_overhead_decreases(self):
        """Fig 9/11 shape: overhead monotonically falls as batch grows."""
        ovs = sweep_batch("tdx", compute_per_token_s=1e-4, memory_s=0.04,
                          batches=[1, 8, 64, 512])
        vals = list(ovs.values())
        assert all(a >= b for a, b in zip(vals, vals[1:]))

    def test_numa_and_hugepages_penalties(self):
        """Fig 5/6 + Insight 7: broken placement costs real performance."""
        t = RooflineTerms(compute_s=0.01, memory_s=0.05)
        base = predict(t, "tdx").overhead
        no_numa = predict(t, "tdx", numa_bound=False).overhead
        no_huge = predict(t, "tdx", hugepages_fixed=False).overhead
        assert no_numa > base and no_huge > base
        # SGX multi-socket catastrophe (~230%)
        sgx_numa = predict(t, "sgx", numa_bound=False).overhead
        assert sgx_numa > 1.0

    def test_paper_calibration_bands(self):
        """Single-socket inference-like terms land in the paper's bands."""
        t = RooflineTerms(compute_s=0.012, memory_s=0.045, collective_s=0.002)
        assert 0.04 < predict(t, "tdx").overhead < 0.12      # 5.51-10.68%
        assert 0.03 < predict(t, "sgx").overhead < 0.09      # 4.80-6.15%
        assert 0.01 < predict(t, "vm").overhead < 0.06       # 1.82-5.38%
        # cGPU at GPU-scale step times: 4.4-8%
        tg = RooflineTerms(compute_s=0.002, memory_s=0.0045, collective_s=0.0)
        assert 0.03 < predict(tg, "cgpu").overhead < 0.10


class TestTrustDomain:
    def test_non_confidential_passthrough(self):
        td = TrustDomain("none")
        toks = np.arange(10, dtype=np.int32)
        assert td.ingress(toks) is toks
        assert td.predict_overhead(RooflineTerms(0.1, 0.1)) is None

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            TrustDomain("sgx2")

    def test_audit_log_records_boundary_crossings(self):
        td = TrustDomain("tdx")
        td.seal_params({"w": jnp.ones((4,), jnp.float32)})
        td.ingress(np.ones(4, np.int32))
        kinds = [e.kind for e in td.audit]
        assert "seal" in kinds and "ingress" in kinds
