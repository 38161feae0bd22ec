"""Sealed tensors: encrypt-then-MAC at rest, Pallas-kernel decrypt on device.

The TPU-native analogue of TDX/SGX inline memory encryption (DESIGN.md §2):
model weights and KV pages are stored/moved as ChaCha20 ciphertext in the
kernel-friendly blocked layout and XOR-decrypted on the way into compute by
``kernels/chacha20.py``. Integrity is encrypt-then-MAC with HMAC-SHA256 over
(header || ciphertext) — a flipped ciphertext bit fails verification before
any plaintext is produced (the integrity property HE schemes lack, §II).

Nonces are derived per-tensor from (key id, tensor name) so no (key, nonce)
pair is ever reused across tensors; the block counter spans within a tensor.

Ciphertext rests on the host (numpy). A device tensor is sealed, and every
tensor unsealed, on the device a chunk of leading-axis rows (about
``CHUNK_BYTES``) at a time, at the chunk's own counter base: the device
holds the tensor plus one chunk, never a second copy of the tensor. A host
(numpy) tensor is packed on the host. Either way the ciphertext and MAC are
byte-identical to sealing the tensor's bytes whole.
"""

from __future__ import annotations

import dataclasses
import hashlib
import hmac
import math
import os
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.chacha20 import BLOCKS_PER_TILE

Params = Any

# device tensors seal/unseal in chunks of rows of about this size
CHUNK_BYTES = 64 << 20


class IntegrityError(Exception):
    """MAC verification failed — ciphertext or header was tampered with."""


@dataclasses.dataclass(frozen=True)
class SealingKey:
    key: bytes          # 32-byte ChaCha20 key
    mac_key: bytes      # 32-byte HMAC key (independent)

    @classmethod
    def generate(cls, seed: bytes | None = None) -> "SealingKey":
        if seed is not None:
            k = hashlib.sha256(b"seal" + seed).digest()
            m = hashlib.sha256(b"mac" + seed).digest()
            return cls(k, m)
        return cls(os.urandom(32), os.urandom(32))

    def derive(self, label: str) -> "SealingKey":
        """HKDF-style labeled subkey: a *key domain* carved out of this key.

        Both halves are derived independently (``expand(label, key)`` /
        ``expand(label, mac_key)``) so the MAC domain separates too: a blob
        sealed under ``k.derive("tenant/a")`` fails MAC verification — not
        merely decryption — under ``k.derive("tenant/b")`` or under ``k``
        itself. That is what makes cross-tenant restore fail *by integrity
        check* rather than by convention (the fleet's per-tenant KV
        isolation rests on this). Derivation is deterministic, so two
        attested workers handed the same master material derive the same
        tenant domain and sealed KV migrates between them."""
        lb = label.encode()
        return SealingKey(
            hashlib.sha256(b"derive/key|" + lb + b"|" + self.key).digest(),
            hashlib.sha256(b"derive/mac|" + lb + b"|" + self.mac_key).digest())

    @property
    def key_words(self) -> jax.Array:
        return jnp.asarray(np.frombuffer(self.key, np.uint32))

    def key_id(self) -> str:
        return hashlib.sha256(self.key).hexdigest()[:16]


def _nonce_for(key: SealingKey, name: str) -> bytes:
    return hashlib.sha256(key.key_id().encode() + b"|" + name.encode()).digest()[:12]


def nonce_words_for(key: SealingKey, name: str) -> np.ndarray:
    """The blob's ChaCha20 nonce as uint32[3] — what a ciphertext-resident
    page's crypt sidecar carries so the fused decode kernel can regenerate
    the exact keystream this name was sealed under."""
    return np.frombuffer(_nonce_for(key, name), np.uint32)


def shared_page_name(content_key: bytes, kpath: str) -> str:
    """The canonical sealed-tensor name for content-addressed KV pages
    (shared-page parking and the persistent page store). Derived from the
    page's content key alone, so identical content always seals under the
    same name — and therefore the same nonce AND the same plaintext, the
    pairing that makes a deterministic nonce safe to mint repeatedly: a
    re-seal of the same content can never put two plaintexts under one
    (key, nonce)."""
    return f"kvshared/{content_key.hex()}{kpath}"


@dataclasses.dataclass
class SealedTensor:
    name: str
    ciphertext: np.ndarray   # uint32 [16, N] blocked layout, on the host
    mac: bytes
    shape: Tuple[int, ...]
    dtype: str
    n_bytes: int

    def header(self) -> bytes:
        return f"{self.name}|{self.shape}|{self.dtype}|{self.n_bytes}".encode()


def _mac(key: SealingKey, sealed_header: bytes, ciphertext) -> bytes:
    h = hmac.new(key.mac_key, sealed_header, hashlib.sha256)
    h.update(np.ascontiguousarray(ciphertext).view(np.uint8).reshape(-1))
    return h.digest()


def _rows_shape(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """A tensor's shape as leading-axis rows (a scalar is one row)."""
    return tuple(shape) or (1,)


def _row_chunks(shape: Tuple[int, ...], itemsize: int):
    """Leading-axis row ranges of about CHUNK_BYTES, each but the last a
    whole number of kernel tiles (so every chunk starts on a tile of the
    tensor's blocked layout): ``[(start, rows, first_block), ...]``."""
    row_bytes = math.prod(shape[1:]) * itemsize
    if row_bytes == 0:
        return []
    tile_bytes = 64 * BLOCKS_PER_TILE
    step = tile_bytes // math.gcd(row_bytes, tile_bytes)
    k = max(step, CHUNK_BYTES // row_bytes // step * step)
    return [(a, min(k, shape[0] - a), a * row_bytes // 64)
            for a in range(0, shape[0], k)]


def seal_tensor(key: SealingKey, name: str, array) -> SealedTensor:
    nonce = jnp.asarray(np.frombuffer(_nonce_for(key, name), np.uint32))
    if isinstance(array, jax.Array):
        rows = array.reshape(_rows_shape(array.shape))
        n_bytes = array.nbytes
        blocks = -(-n_bytes // (64 * BLOCKS_PER_TILE)) * BLOCKS_PER_TILE
        ciphertext = np.empty((16, blocks), np.uint32)
        for start, n, c0 in _row_chunks(rows.shape, array.dtype.itemsize):
            ct = np.asarray(ops.seal_u32(key.key_words, nonce,
                                         ops.pack_rows(rows, start, n),
                                         counter_base=c0))
            ciphertext[:, c0:c0 + ct.shape[1]] = ct
        shape, dtype = array.shape, array.dtype
    else:
        arr = np.asarray(array)
        raw = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
        blocked, n_bytes = ops.pack_u32(raw)
        ciphertext = np.asarray(ops.seal_u32(key.key_words, nonce, blocked))
        shape, dtype = arr.shape, arr.dtype
    st = SealedTensor(name=name, ciphertext=ciphertext, mac=b"",
                      shape=tuple(shape), dtype=str(dtype), n_bytes=n_bytes)
    st.mac = _mac(key, st.header(), ciphertext)
    return st


def verify_mac(key: SealingKey, sealed: SealedTensor) -> None:
    """MAC-check a sealed tensor *without* decrypting it.

    The fused-unseal decode path (kernels/paged_attention.py) admits
    ciphertext directly into the KV pool and decrypts in-kernel, so the
    usual unseal_tensor gate never runs for those pages — this is the
    integrity gate that must pass before any kernel consumes the bits.
    Raises :class:`IntegrityError` on mismatch, like unseal_tensor.
    """
    expect = _mac(key, sealed.header(), sealed.ciphertext)
    if not hmac.compare_digest(expect, sealed.mac):
        raise IntegrityError(f"MAC mismatch for tensor '{sealed.name}'")


def ciphertext_page_bytes(sealed: SealedTensor) -> bytes:
    """Serialize blocked ciphertext to the linear RFC 8439 byte stream.

    ``[16, N].T.reshape(-1)`` is a pure permutation (linear word i is
    keystream word i%16 of counter block i//16), so the pool can hold the
    ciphertext *bit-for-bit* in the plaintext layout and the in-kernel
    keystream XOR (generated linearly per page) lines up word-for-word.
    """
    lin = np.asarray(sealed.ciphertext).T.reshape(-1)
    return lin.astype("<u4").tobytes()[:sealed.n_bytes]


def unseal_tensor(key: SealingKey, sealed: SealedTensor) -> jax.Array:
    """MAC-check, then decrypt onto the device."""
    verify_mac(key, sealed)
    nonce = jnp.asarray(np.frombuffer(_nonce_for(key, sealed.name), np.uint32))
    dtype = jnp.dtype(sealed.dtype)
    shape = _rows_shape(sealed.shape)
    ct = np.asarray(sealed.ciphertext)
    out = jnp.zeros(shape, dtype)
    per_tile = BLOCKS_PER_TILE * 64
    for start, rows, c0 in _row_chunks(shape, dtype.itemsize):
        chunk_bytes = rows * math.prod(shape[1:]) * dtype.itemsize
        c1 = c0 + -(-chunk_bytes // per_tile) * BLOCKS_PER_TILE
        blocked = ops.unseal_u32(key.key_words, nonce,
                                 np.ascontiguousarray(ct[:, c0:c1]),
                                 counter_base=c0)
        out = ops.place_rows(out, blocked, start, rows)
    return out.reshape(sealed.shape)


# ---------------------------------------------------------------------------
# pytrees
# ---------------------------------------------------------------------------

def seal_tree(key: SealingKey, tree: Params, prefix: str = "params",
              suffix: str = "") -> Dict[str, SealedTensor]:
    """``suffix`` lands after the leaf path in every derived name
    (``{prefix}{leaf}{suffix}``): sharded backends tag each seal with the
    addressable shard it was read from (``/s{shard}``), so two hosts sealing
    concurrently under one prefix can never collide in nonce space."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        name = prefix + jax.tree_util.keystr(path) + suffix
        out[name] = seal_tensor(key, name, leaf)
    return out


def unseal_tree(key: SealingKey, sealed: Dict[str, SealedTensor],
                treedef_like: Params, prefix: str = "params",
                suffix: str = "") -> Params:
    flat, treedef = jax.tree_util.tree_flatten_with_path(treedef_like)
    leaves = []
    for path, _ in flat:
        name = prefix + jax.tree_util.keystr(path) + suffix
        leaves.append(unseal_tensor(key, sealed[name]))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def sealed_nbytes(sealed: Dict[str, SealedTensor]) -> int:
    """Total plaintext bytes a sealed dict carries (the boundary-crossing
    payload a preemption moves; headers/MACs excluded for comparability)."""
    return sum(st.n_bytes for st in sealed.values())


def tree_digest(sealed: Dict[str, SealedTensor]) -> str:
    """Stable digest over all MACs — bound into the attestation measurement."""
    h = hashlib.sha256()
    for name in sorted(sealed):
        h.update(name.encode())
        h.update(sealed[name].mac)
    return h.hexdigest()
