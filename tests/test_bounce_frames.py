"""Egress frames sealed and opened in batches: one device trip each way for
every frame due together, each frame still its own stream seq, nonce, MAC
and whole ChaCha20 blocks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import TrustDomain
from repro.core import bounce
from repro.core.bounce import BounceBuffer
from repro.core.sealing import (IntegrityError, SealingKey, _mac,
                                nonce_words_for, verify_mac)
from repro.kernels import chacha20, ops
from repro.models import build_model
from repro.runtime import Engine, GenerationRequest

RNG = np.random.default_rng(15)
# tokens per frame: one batch of a coalesced 40, one of three sizes, a
# step's 16 frames of 1-40 tokens, and three that need more than 128 blocks
BATCHES = {"1": [40], "3": [1, 17, 33],
           "16": RNG.integers(1, 41, 16).tolist(), "3-long": [1000] * 3}


@pytest.fixture
def trips(monkeypatch):
    """Each call of the frame program, by its lanes' shape."""
    shapes = []
    xor = bounce.xor_lanes

    def counted(key, lanes):
        shapes.append(lanes.shape)
        return xor(key, lanes)
    monkeypatch.setattr(bounce, "xor_lanes", counted)
    return shapes


def send(bb, sizes, first_stream=3):
    toks = [RNG.integers(0, 2 ** 31 - 1, n, dtype=np.int32) for n in sizes]
    frames = bb.device_send_frames(
        [(first_stream + i, t) for i, t in enumerate(toks)])
    return toks, frames


@pytest.mark.parametrize("batch", list(BATCHES))
def test_frames_are_the_first_blocks_of_the_whole_seal(batch, trips):
    key = SealingKey.generate(b"frames")
    bb = BounceBuffer(key)
    toks, frames = send(bb, BATCHES[batch])
    for t, f in zip(toks, frames):
        blocked, n_bytes = ops.pack_u32(t.view(np.uint8))
        whole = np.asarray(ops.seal_u32(
            key.key_words, jnp.asarray(nonce_words_for(key, f.sealed.name)),
            blocked))
        blocks = -(-4 * t.size // 64)
        assert f.sealed.ciphertext.shape == (16, blocks)
        np.testing.assert_array_equal(f.sealed.ciphertext,
                                      whole[:, :blocks])
        assert f.sealed.n_bytes == n_bytes
        verify_mac(key, f.sealed)
    opened = bb.host_recv_frames(frames)
    for t, o in zip(toks, opened):
        np.testing.assert_array_equal(o, t)
    assert len(trips) == 2                      # one trip each way
    assert bb.stats.cipher_bytes_out == 64 * sum(
        -(-4 * n // 64) for n in BATCHES[batch])
    assert bb.stats.tokens_out == sum(BATCHES[batch])


@pytest.mark.parametrize("part", ["mac", "ciphertext"])
def test_tampered_frame_mid_batch_opens_nothing(part, trips):
    bb = BounceBuffer(SealingKey.generate(b"tamper"))
    toks, frames = send(bb, [1, 5, 1])
    mid = frames[1].sealed
    if part == "mac":
        bad_sealed = dataclasses.replace(mid, mac=bytes(32))
    else:
        ct = mid.ciphertext.copy()
        ct[0, 0] ^= 1
        bad_sealed = dataclasses.replace(mid, ciphertext=ct)
    bad = [frames[0], dataclasses.replace(frames[1], sealed=bad_sealed),
           frames[2]]
    with pytest.raises(IntegrityError):
        bb.host_recv_frames(bad)
    assert len(trips) == 1                      # nothing was decrypted
    # no stream's seq advanced: the authentic batch opens whole
    for t, o in zip(toks, bb.host_recv_frames(frames)):
        np.testing.assert_array_equal(o, t)


def test_frames_of_a_wrong_size_are_refused():
    """A frame is whole blocks of its own bytes: a tile-padded one is not."""
    key = SealingKey.generate(b"size")
    bb = BounceBuffer(key)
    f = bb.device_send_frame(0, np.arange(3, dtype=np.int32))
    padded = np.zeros((16, 1024), np.uint32)
    padded[:, :1] = f.sealed.ciphertext
    st = dataclasses.replace(f.sealed, ciphertext=padded)
    st.mac = _mac(key, st.header(), padded)
    with pytest.raises(IntegrityError):
        bb.host_recv_frame(dataclasses.replace(f, sealed=st))
    assert bb.host_recv_frame(f).tolist() == [0, 1, 2]


def test_replay_reorder_and_closed_streams_through_batches():
    bb = BounceBuffer(SealingKey.generate(b"replay"))
    a = bb.device_send_frames([(3, [10]), (4, [20])])
    b = bb.device_send_frames([(3, [11]), (3, [12]), (4, [21])])
    assert [(f.stream_id, f.seq) for f in b] == [(3, 1), (3, 2), (4, 1)]
    with pytest.raises(IntegrityError):         # stream 3 out of order
        bb.host_recv_frames([a[1], b[2], a[0], b[1], b[0]])
    with pytest.raises(IntegrityError):
        bb.host_recv_frames([a[0], b[1]])       # stream 3 seq 1 skipped
    assert [o.tolist() for o in bb.host_recv_frames(a)] == [[10], [20]]
    with pytest.raises(IntegrityError):
        bb.host_recv_frames(a)                  # verbatim replay
    with pytest.raises(IntegrityError):
        bb.host_recv_frames([b[0], b[2], a[0]])  # replay at a batch's end
    assert [o.tolist() for o in bb.host_recv_frames(b)] == [[11], [12], [21]]
    bb.close_stream(3)
    with pytest.raises(IntegrityError):
        bb.host_recv_frames([b[0]])
    with pytest.raises(IntegrityError):         # a closed stream sends none
        bb.device_send_frames([(4, [22]), (3, [13])])
    f = bb.device_send_frame(4, [22])
    assert f.seq == 2                           # the refused batch took none
    assert bb.host_recv_frame(f).tolist() == [22]


def test_frame_program_traces_once_for_batches_of_1_to_16(monkeypatch):
    traces = []

    def body(key, lanes):
        traces.append(lanes.shape)
        return chacha20.xor_lanes(key, lanes)
    monkeypatch.setattr(bounce, "xor_lanes", jax.jit(body))
    bb = BounceBuffer(SealingKey.generate(b"shapes"))
    for k in range(1, 17):
        frames = bb.device_send_frames([(s, [s, k]) for s in range(k)])
        assert [o.tolist() for o in bb.host_recv_frames(frames)] == [
            [s, k] for s in range(k)]
    assert traces == [(20, bounce.FRAME_LANES)]


def test_staged_frames_cross_in_one_batch(trips):
    io = SealingKey.generate(b"staged")
    staged, plain = TrustDomain("tdx", io_key=io), TrustDomain("tdx", io_key=io)
    frames = [(s, [7 * s, 7 * s + 1]) for s in range(3)]
    staged.stage_egress(frames)
    got = [staged.egress_tokens(s, t) for s, t in frames]
    staged.finish_egress()
    assert len(trips) == 2
    assert got == [plain.egress_tokens(s, t) for s, t in frames]
    assert len(trips) == 2 + 2 * len(frames)
    assert got == [t for _, t in frames]
    # the same frames crossed: streams, seqs and sizes agree frame by frame
    log = [e.detail for e in staged.audit if e.kind == "egress_frame"]
    assert log == [e.detail for e in plain.audit if e.kind == "egress_frame"]
    # a staged frame left unrequested at the end of the step is caught
    staged.stage_egress(frames[:2])
    staged.egress_tokens(*frames[0])
    with pytest.raises(RuntimeError, match=r"\[1\]"):
        staged.finish_egress()
    # ... and so are tokens other than those staged
    staged.stage_egress(frames[:1])
    with pytest.raises(ValueError):
        staged.egress_tokens(0, [1])


def test_engine_step_makes_one_trip_each_way(trips):
    cfg = smoke_config("deepseek-7b")
    model = build_model(cfg)
    eng = Engine(model, model.init_params(jax.random.key(0)), max_slots=2,
                 max_len=64, prefill_len=8, trust_domain=TrustDomain("tdx"))
    reqs = [eng.submit(GenerationRequest(prompt=np.arange(1, 9, dtype=np.int32),
                                         max_new_tokens=4))
            for _ in range(2)]
    eng.step()          # both admitted: the first tokens, then a decode step
    assert len(trips) == 4
    stats = eng.td.channel.stats
    frames = stats.messages_out
    del trips[:]
    assert eng.step() == 2
    assert len(trips) == 2 and stats.messages_out == frames + 2
    eng.run()
    for r in reqs:
        assert r.egress_frames == r.egress_tokens == len(r.output) == 4
