"""jit'd public wrappers around the Pallas kernels.

Handles padding/packing so callers see natural shapes:
  * pack_u32 (host bytes) and pack_rows / place_rows (device, one chunk of
    rows) — arbitrary tensors <-> the blocked [16, N] layout
  * seal_u32 / unseal_u32 — XOR the blocked layout with the keystream
  * qmm — bf16 activations x QTensor weights with auto-padding to tiles
  * mha_flash — [b, s, h, d] attention with GQA head broadcast
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.chacha20 import BLOCKS_PER_TILE, chacha20_xor_blocked
from repro.kernels.flash_attention import flash_attention
from repro.kernels.qmatmul import qmatmul
from repro.quant.quantize import QTensor

# ---------------------------------------------------------------------------
# sealing: pack arbitrary arrays into the [16, N] blocked u32 layout
# ---------------------------------------------------------------------------

def pack_u32(raw: np.ndarray) -> Tuple[jax.Array, int]:
    """uint8 bytes -> (uint32 [16, N] blocked layout, original byte length)."""
    n_bytes = raw.size
    block_bytes = 64 * BLOCKS_PER_TILE
    padded = n_bytes + (-n_bytes) % block_bytes
    buf = np.zeros(padded, np.uint8)
    buf[:n_bytes] = raw
    words = buf.view("<u4").reshape(-1, 16).T  # [16, N]
    return jnp.asarray(np.ascontiguousarray(words)), n_bytes


# the same layout built and taken apart on the device, a chunk of leading-
# axis rows at a time (core/sealing.py's path for device tensors and for
# every unseal): element bytes little-endian, zero-padded to whole tiles.
_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


@functools.partial(jax.jit, static_argnames=("rows",))
def pack_rows(x: jax.Array, start, rows: int) -> jax.Array:
    """Rows ``[start, start + rows)`` of ``x`` -> their bytes as uint32
    [16, N] blocked layout, exactly what pack_u32 makes of those bytes.

    Built as [16 * per, N] (block index on the lanes) before pairing the
    sub-words, so no intermediate has a narrow minor dimension — the TPU
    pads one to 128 lanes, which multiplies a chunk's memory many times."""
    size = x.dtype.itemsize
    per = 4 // size
    chunk = jax.lax.dynamic_slice_in_dim(x, start, rows, 0).reshape(-1)
    sub = jax.lax.bitcast_convert_type(chunk, _UINT[size])
    per_block = 16 * per
    n_blocks = -(-sub.size // per_block)
    n_blocks += (-n_blocks) % BLOCKS_PER_TILE
    sub = jnp.pad(sub, (0, n_blocks * per_block - sub.size))
    cols = sub.reshape(n_blocks, per_block).T.astype(jnp.uint32)
    words = cols[0::per]
    for i in range(1, per):
        words = words | (cols[i::per] << jnp.uint32(8 * size * i))
    return words


@functools.partial(jax.jit, static_argnames=("rows",), donate_argnums=(0,))
def place_rows(out: jax.Array, blocked: jax.Array, start,
               rows: int) -> jax.Array:
    """Inverse of pack_rows: write the bytes ``blocked`` carries as rows
    ``[start, start + rows)`` of ``out``, in place (``out`` is donated)."""
    size = out.dtype.itemsize
    per = 4 // size
    shape = (rows,) + out.shape[1:]
    mask = jnp.uint32((1 << (8 * size)) - 1)
    cols = jnp.stack([(blocked >> jnp.uint32(8 * size * i)) & mask
                      for i in range(per)], axis=1)
    cols = cols.reshape(16 * per, -1).astype(_UINT[size])
    vals = jax.lax.bitcast_convert_type(cols.T.reshape(-1), out.dtype)
    return jax.lax.dynamic_update_slice_in_dim(
        out, vals[:math.prod(shape)].reshape(shape), start, 0)


def seal_u32(key_words: jax.Array, nonce_words: jax.Array,
             blocked: jax.Array, counter_base=0) -> jax.Array:
    """XOR blocked data with the keystream (seal == unseal: involution)."""
    return chacha20_xor_blocked(key_words, nonce_words, blocked,
                                counter_base=counter_base)


unseal_u32 = seal_u32  # stream-cipher involution


# ---------------------------------------------------------------------------
# quantized matmul
# ---------------------------------------------------------------------------

def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def qmm(x: jax.Array, w: QTensor, *, bm: int = 128, bn: int = 128,
        bk: int = 128) -> jax.Array:
    """bf16 [M, K] x QTensor([K, N]) -> bf16 [M, N] via the int8 MXU kernel.

    Dynamically quantizes activations per-tensor (AMX dataflow), folds the
    activation scale into the per-channel weight scale, pads to tile
    multiples, and un-pads the result.
    """
    m, kdim = x.shape
    k2, n = w.values.shape
    assert kdim == k2
    xf = x.astype(jnp.float32)
    xmax = jnp.max(jnp.abs(xf))
    xscale = jnp.where(xmax > 0, xmax / 127.0, 1.0)
    xq = jnp.clip(jnp.round(xf / xscale), -127, 127).astype(jnp.int8)

    xq = _pad_to(_pad_to(xq, 0, bm), 1, bk)
    wq = _pad_to(_pad_to(w.values, 0, bk), 1, bn)
    scale = _pad_to(w.scale.reshape(1, n) * xscale, 1, bn)
    out = qmatmul(xq, wq, scale, bm=bm, bn=bn, bk=bk)
    return out[:m, :n].astype(x.dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def mha_flash(q: jax.Array, k: jax.Array, v: jax.Array, *, bq: int = 128,
              bkv: int = 128) -> jax.Array:
    """Causal attention, [b, s, h, d] layout with GQA broadcast."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    if hk != h:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    bq_ = min(bq, s)
    bkv_ = min(bkv, s)
    # non-block-multiple sequence: zero-pad to a common block multiple. The
    # kernel's causal mask sends every padded kv position (k_pos >= s >
    # q_pos for all real rows) to NEG_INF, and padded query rows are
    # sliced away below, so padding is invisible to the result.
    s_pad = s + (-s) % int(np.lcm(bq_, bkv_))
    if s_pad != s:
        widths = ((0, 0), (0, s_pad - s), (0, 0))
        qf, kf, vf = (jnp.pad(a, widths) for a in (qf, kf, vf))
    out = flash_attention(qf, kf, vf, bq=bq_, bkv=bkv_)
    return out[:, :s].reshape(b, h, s, d).transpose(0, 2, 1, 3)
