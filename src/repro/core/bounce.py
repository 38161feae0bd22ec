"""Encrypted bounce buffer for host<->device token I/O.

NVIDIA cGPUs route every PCIe transfer through an encrypted+authenticated
bounce buffer (paper §V-A) — the main cGPU overhead source, amortized by
batch/input size (Insight 10). We implement the same structure for the
host<->TPU boundary: prompts enter and tokens leave the trust domain only as
ciphertext; the device side unseals with the ChaCha20 Pallas kernel.

The channel keeps byte/crypto counters so benchmarks can attribute boundary
costs exactly (fig04/fig11 harnesses).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import numpy as np

from repro import obs
from repro.core.sealing import (IntegrityError, SealingKey, SealedTensor,
                                _mac, nonce_words_for, seal_tensor,
                                unseal_to_host, verify_mac)
from repro.kernels.chacha20 import xor_lanes

# lanes of the frame program: up to this many blocks (a step's 16 one-token
# frames and more) share one compiled shape; beyond, a power of two of it
FRAME_LANES = 128


@dataclasses.dataclass
class ChannelStats:
    """Boundary-crossing counters. ``messages_out`` counts *crossings*
    (frames — the unit Insight 10's fixed cost is paid per); ``tokens_out``
    counts the tokens those frames carried. With per-token streaming the two
    are equal; a coalescing FramePolicy drives messages_out/tokens_out
    toward 1/N, which is exactly the amortization curve serve_bench plots.
    ``bytes_*`` count the plaintext; ``cipher_bytes_*`` the padded
    ciphertext each message moved and MAC'd: whole 64 KiB ChaCha20 tiles
    for ingress, whole 64-byte blocks for egress frames."""
    messages_in: int = 0
    messages_out: int = 0
    bytes_in: int = 0
    bytes_out: int = 0
    cipher_bytes_in: int = 0
    cipher_bytes_out: int = 0
    tokens_out: int = 0
    # sealed-KV traffic (preemption evictions/restores): ciphertext that
    # leaves/re-enters the domain outside the token channel. Counted apart
    # from messages so crossings_per_token stays a pure egress metric.
    seal_events: int = 0
    seal_bytes: int = 0
    restore_events: int = 0
    restore_bytes: int = 0
    # cross-device collective traffic inside the domain (mesh-spanning
    # engines): bytes each device moved over the interconnect per decode
    # step, and the *measured* time those collectives took on the real mesh
    # (a shard_map all-gather probe, not the closed-form roofline estimate).
    # This is the traffic the encrypted-interconnect tax (link_tax) applies
    # to — overheads.predict(collective_s=stats.collective_s / steps) prices
    # it from observation instead of the model.
    collective_steps: int = 0
    collective_bytes: int = 0
    collective_s: float = 0.0
    # persistent sealed-page store (prefix-cache tier): hits re-enter the
    # domain as content-named ciphertext (also counted in restore_*, the
    # boundary they cross); evictions are host-side forgetting — no
    # crossing, tracked for the retention experiments.
    store_hits: int = 0
    store_restored_bytes: int = 0
    store_evictions: int = 0

    @property
    def crossings_per_token(self) -> float:
        return self.messages_out / self.tokens_out if self.tokens_out else 0.0

    @property
    def seal_bytes_per_event(self) -> float:
        return self.seal_bytes / self.seal_events if self.seal_events else 0.0

    @property
    def collective_s_per_step(self) -> float:
        return (self.collective_s / self.collective_steps
                if self.collective_steps else 0.0)

    def reset(self):
        self.messages_in = self.messages_out = 0
        self.bytes_in = self.bytes_out = 0
        self.cipher_bytes_in = self.cipher_bytes_out = 0
        self.tokens_out = 0
        self.seal_events = self.seal_bytes = 0
        self.restore_events = self.restore_bytes = 0
        self.collective_steps = self.collective_bytes = 0
        self.collective_s = 0.0
        self.store_hits = self.store_restored_bytes = 0
        self.store_evictions = 0


@dataclasses.dataclass
class TokenFrame:
    """One streamed egress message: the token(s) a request released together.

    Frames are the unit the paper's cGPU fixed cost is paid per (Insight 10):
    streaming one token per frame maximizes boundary crossings, which is
    exactly what ``ChannelStats`` must see to price the deployment honestly;
    a coalescing FramePolicy packs N tokens into one frame to amortize it.
    ``(stream_id, seq)`` is bound into the sealed tensor's name, so the nonce
    is unique per frame and the host side can detect replay or reordering.
    Its ciphertext is its own whole ChaCha20 blocks, ``[16, ceil(n/64)]``
    words for ``n`` bytes: the first blocks of the tensor sealed whole.
    """
    stream_id: int
    seq: int
    sealed: SealedTensor

    @staticmethod
    def frame_name(stream_id: int, seq: int) -> str:
        return f"egress/s{stream_id}/{seq}"


class BounceBuffer:
    """Symmetric encrypted channel. ``host_*`` runs outside the trust domain,
    ``device_*`` inside. Sequence numbers make each message's nonce unique."""

    def __init__(self, key: SealingKey):
        self.key = key
        self.stats = ChannelStats()
        self._seq_in = 0
        self._seq_out = 0
        self._stream_seq: Dict[int, int] = {}   # stream id -> next send seq
        self._stream_recv: Dict[int, int] = {}  # stream id -> next expected seq
        self._next_stream = 0                   # ids never reused (nonce safety)
        # closed streams, compact: ids below the watermark are closed;
        # out-of-order closures wait in the set until it advances. The set
        # stays small while streams close roughly in open order — one
        # never-closed stream pins the watermark and the set tracks every
        # later closure, so abandon streams with close_stream, not silence.
        self._closed_lo = 0
        self._closed_set: set = set()
        self._key_words = None   # the cipher key on the device, for frames

    # host -> device
    def host_send(self, tokens: np.ndarray) -> SealedTensor:
        name = f"ingress/{self._seq_in}"
        self._seq_in += 1
        with obs.span("ingress.seal"):
            sealed = seal_tensor(self.key, name, tokens)
        self.stats.messages_in += 1
        self.stats.bytes_in += sealed.n_bytes
        self.stats.cipher_bytes_in += sealed.ciphertext.nbytes
        return sealed

    def device_recv(self, sealed: SealedTensor) -> np.ndarray:
        with obs.span("ingress.open"):
            return unseal_to_host(self.key, sealed)

    # device -> host
    def device_send(self, tokens: np.ndarray) -> SealedTensor:
        name = f"egress/{self._seq_out}"
        self._seq_out += 1
        sealed = seal_tensor(self.key, name, tokens)
        self.stats.messages_out += 1
        self.stats.bytes_out += sealed.n_bytes
        self.stats.cipher_bytes_out += sealed.ciphertext.nbytes
        return sealed

    def host_recv(self, sealed: SealedTensor) -> np.ndarray:
        return unseal_to_host(self.key, sealed)

    def open_stream(self) -> int:
        """Allocate a channel-global stream id. The channel — not the caller —
        owns the namespace: per-engine request ids restart at 0, and two
        engines sharing one TrustDomain must never land on the same
        ``egress/sN/M`` name (ChaCha20 nonce reuse)."""
        sid = self._next_stream
        self._next_stream += 1
        return sid

    def _stream_closed(self, stream_id: int) -> bool:
        return stream_id < self._closed_lo or stream_id in self._closed_set

    # device -> host, streaming: one frame per FramePolicy flush (1..N
    # tokens); the frames due together cross in one device trip each way
    def device_send_frames(self, frames: Sequence[Tuple[int, np.ndarray]]
                           ) -> List[TokenFrame]:
        """Seal ``frames`` (``(stream_id, tokens)`` pairs), each under its
        own stream seq, name, nonce and MAC, in one device call: one upload
        of every frame's blocks with each lane's nonce and counter, one
        XOR with the keystream, one read-back; then a MAC per frame."""
        for stream_id, _ in frames:
            if self._stream_closed(stream_id):
                raise IntegrityError(
                    f"stream {stream_id} is closed; sending would restart "
                    f"its seq at 0 and reuse a nonce")
        pending = []
        for stream_id, tokens in frames:
            tokens = np.asarray(tokens, np.int32)
            seq = self._stream_seq.get(stream_id, 0)
            self._stream_seq[stream_id] = seq + 1
            pending.append((stream_id, seq, tokens))
        obs.count("egress.batches")
        obs.count("egress.frames", len(pending))
        out = []
        with obs.span("frame.seal", frames=len(pending),
                      streams=_streams(sid for sid, _ in frames)):
            with obs.span("seal.kernel"):
                cts = self._xor_frames(
                    [(TokenFrame.frame_name(sid, seq), _blocks(toks))
                     for sid, seq, toks in pending])
            for (sid, seq, toks), ct in zip(pending, cts):
                st = SealedTensor(name=TokenFrame.frame_name(sid, seq),
                                  ciphertext=ct, mac=b"", shape=toks.shape,
                                  dtype=str(toks.dtype), n_bytes=toks.nbytes)
                with obs.span("seal.mac"):
                    st.mac = _mac(self.key, st.header(), ct)
                self.stats.messages_out += 1
                self.stats.bytes_out += st.n_bytes
                self.stats.cipher_bytes_out += ct.nbytes
                self.stats.tokens_out += int(toks.size)
                out.append(TokenFrame(sid, seq, st))
        return out

    def host_recv_frames(self, frames: Sequence[TokenFrame]
                         ) -> List[np.ndarray]:
        """Open ``frames``: every frame's stream, name, seq, size and MAC
        are checked before anything is decrypted, then one device call
        decrypts them all. A bad frame anywhere raises
        :class:`IntegrityError` and advances no stream's seq."""
        with obs.span("frame.open", frames=len(frames),
                      streams=_streams(f.stream_id for f in frames)):
            want: Dict[int, int] = {}
            for frame in frames:
                self._check_frame(frame, want)
            with obs.span("unseal.kernel"):
                pts = self._xor_frames([(f.sealed.name, f.sealed.ciphertext)
                                        for f in frames])
            out = [_unblock(pt, f.sealed) for pt, f in zip(pts, frames)]
            # advance only after every MAC verified: a forged frame must not
            # burn the seq and lock out the authentic one behind it.
            self._stream_recv.update(want)
        return out

    def device_send_frame(self, stream_id: int, tokens: np.ndarray) -> TokenFrame:
        return self.device_send_frames([(stream_id, tokens)])[0]

    def host_recv_frame(self, frame: TokenFrame) -> np.ndarray:
        return self.host_recv_frames([frame])[0]

    def _check_frame(self, frame: TokenFrame, want: Dict[int, int]) -> None:
        """One frame's checks before decryption; ``want`` holds the next seq
        of each stream seen earlier in the batch."""
        sid = frame.stream_id
        if self._stream_closed(sid):
            raise IntegrityError(
                f"stream {sid} is closed "
                f"(replayed frame from a finished request)")
        expect = TokenFrame.frame_name(sid, frame.seq)
        if frame.sealed.name != expect:
            raise IntegrityError(
                f"frame name mismatch: got '{frame.sealed.name}', "
                f"expected '{expect}'")
        # strict in-order receive per stream: a verbatim-replayed or
        # reordered frame carries a stale seq and is rejected even though
        # its MAC verifies.
        seq = want.get(sid, self._stream_recv.get(sid, 0))
        if frame.seq != seq:
            raise IntegrityError(
                f"stream {sid}: got frame seq {frame.seq}, "
                f"expected {seq} (replayed or reordered frame)")
        verify_mac(self.key, frame.sealed)
        shape = frame.sealed.ciphertext.shape
        if shape != (16, _n_blocks(frame.sealed.n_bytes)):
            raise IntegrityError(
                f"frame '{expect}' holds {shape} words, not the blocks of "
                f"its {frame.sealed.n_bytes} bytes")
        want[sid] = seq + 1

    def _xor_frames(self, frames: Sequence[Tuple[str, np.ndarray]]
                    ) -> List[np.ndarray]:
        """``(name, blocks [16, n])`` pairs XORed with each name's keystream
        (its nonce, block counters from 0) in one device call; frame by
        frame, the result's blocks. Seal and open alike."""
        tiles = max(1, -(-sum(b.shape[1] for _, b in frames) // FRAME_LANES))
        lanes = np.zeros((20, FRAME_LANES << (tiles - 1).bit_length()),
                         np.uint32)
        placed = []
        a = 0
        for name, blocks in frames:
            n = blocks.shape[1]
            lanes[:16, a:a + n] = blocks
            lanes[16:19, a:a + n] = nonce_words_for(self.key, name)[:, None]
            lanes[19, a:a + n] = np.arange(n, dtype=np.uint32)
            placed.append((a, n))
            a += n
        if self._key_words is None:
            self._key_words = jax.device_put(
                np.frombuffer(self.key.key, np.uint32))
        out = np.asarray(xor_lanes(self._key_words, lanes))
        return [np.ascontiguousarray(out[:, a:a + n]) for a, n in placed]

    def close_stream(self, stream_id: int) -> None:
        """Retire a finished stream: its per-stream seq state is dropped
        (bounded memory in a long-running server) while the closed-watermark
        keeps its frames permanently unreplayable and its id unsendable."""
        self._stream_seq.pop(stream_id, None)
        self._stream_recv.pop(stream_id, None)
        self._closed_set.add(stream_id)
        while self._closed_lo in self._closed_set:
            self._closed_set.discard(self._closed_lo)
            self._closed_lo += 1

    def roundtrip(self, tokens: np.ndarray) -> Tuple[np.ndarray, SealedTensor]:
        """Convenience: host->device one message (tests/benchmarks)."""
        sealed = self.host_send(tokens)
        return self.device_recv(sealed), sealed


def _n_blocks(n_bytes: int) -> int:
    return -(-n_bytes // 64)


def _blocks(array: np.ndarray) -> np.ndarray:
    """An array's bytes as whole ChaCha20 blocks, uint32 [16, n]: the
    blocked layout of ``ops.pack_u32`` without its padding to a tile."""
    raw = np.ascontiguousarray(array).reshape(-1).view(np.uint8)
    buf = np.zeros(_n_blocks(raw.size) * 64, np.uint8)
    buf[:raw.size] = raw
    return buf.view("<u4").reshape(-1, 16).T


def _unblock(blocks: np.ndarray, sealed: SealedTensor) -> np.ndarray:
    """Inverse of ``_blocks`` for the tensor ``sealed``'s header names."""
    raw = np.ascontiguousarray(blocks.T).astype("<u4").view(np.uint8)
    return (raw.reshape(-1)[:sealed.n_bytes].view(np.dtype(sealed.dtype))
            .reshape(sealed.shape))


def _streams(stream_ids) -> str:
    """A batch's streams, as its spans carry them: space-separated (the
    profiler reads ``"2,3"`` as the number 2)."""
    return " ".join(map(str, stream_ids))
