"""Paged decode-attention Pallas kernel with optional fused in-kernel unseal.

The gather decode path (runtime/paged.py) rematerializes each sequence's
full KV per step: ``jnp.take`` over the ``[slots, max_pages]`` page table
builds the dense view ``model.decode_step`` expects, so per-step HBM
traffic grows with context length even though decode reads each KV element
exactly once. This kernel removes the gather: the page table rides in as a
scalar-prefetch operand and the BlockSpec index_map dereferences it
directly, so KV pages stream from the ``[num_pages+1, page_size, ...]``
pool into VMEM one page per grid step — vLLM-PagedAttention shape, re-tiled
on flash_attention.py's online-softmax VMEM scratch pattern.

``paged_attention_unseal`` goes one step further than a plaintext pool: a
per-page crypt sidecar (nonce words + live flag) lets sealed pages stay
*ciphertext-resident* in HBM after a restore. The kernel regenerates the
ChaCha20 keystream (chacha20.py's block function, counter_base derived from
the layer ordinal exactly as core/sealing.py laid the blocks out) and XORs
the page on the way into the attention dot — the TPU-native analogue of TDX
inline memory encryption. Restored pages then never round-trip plaintext KV
through HBM; MAC verification still happens on the host *before* the
ciphertext is admitted to the pool (see sealing.verify_mac).

Layout: one layer per call — q ``[slots, heads, head_dim]`` (the single
post-RoPE decode token per slot), pools ``[num_pages+1, page_size,
kv_heads, head_dim]`` (page 0 is the null scratch page), table ``[slots,
max_pages]`` int32, valid ``[slots]`` int32 (= pos + 1; the slot attends to
positions < valid). Grid (slots, max_pages), pages innermost sequential.
Pages wholly past ``valid`` skip compute via ``pl.when``; the in-page tail
is masked to NEG_INF like the causal diagonal in flash_attention.

Interpret-container stand-in (``emulate``): Pallas interpret mode copies
every operand block on every grid step, so a (slots, pages) grid over a
pooled operand costs O(grid x pool bytes) per call — quadratic in context
on the CPU containers this repo's tests and benches run in, drowning the
very gather the kernel exists to remove. ``emulate=True`` (the default
whenever ``interpret=True``) therefore runs the *same* page walk — same
table dereference, same ``_attend_page_math`` update per page, same
masking — as a ``lax.fori_loop`` over pages under ``vmap`` over slots,
touching each mapped page exactly once. Tests pin the emulation bit-exact
against the Pallas kernel's interpret output. ``interpret`` defaults from
the platform (:func:`repro.kernels.interpret_default`): on a TPU backend
both kernels always compile the real ``pallas_call``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default
from repro.kernels.chacha20 import chacha_block_words

NEG_INF = -1e30

# pool dtypes the in-kernel XOR path supports (bitcast to 32/16-bit words);
# anything else restores through the host-decrypt path instead.
FUSED_DTYPES = (jnp.float32, jnp.bfloat16)


def supports_fused_unseal(dtype, page_bytes: int) -> bool:
    """True when a page of this dtype can be decrypted in-kernel: the page
    must cover whole ChaCha20 blocks (64 B) and bitcast to uint words."""
    return page_bytes % 64 == 0 and jnp.dtype(dtype) in (
        jnp.dtype(d) for d in FUSED_DTYPES)


LANES = 128


def _page_words(key_words, nonce, layer, bpp: int, row0: int = 0,
                rows: int | None = None):
    """Keystream of one page's sealed bytes, each ChaCha20 block computed
    once: the 16 word planes of blocks ``row0*128 ..`` of the page (counter
    layer * bpp + k, as core/sealing.py packs a page's L layers
    contiguously, layer l at blocks [l*bpp, (l+1)*bpp)), block k at
    ``[k // 128 - row0, k % 128]`` — the block index on the lanes."""
    rows = -(-bpp // LANES) - row0 if rows is None else rows
    shape = (rows, LANES)
    k = ((jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row0) * LANES
         + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    return chacha_block_words(key_words, nonce,
                              layer.astype(jnp.uint32) * jnp.uint32(bpp)
                              + k.astype(jnp.uint32))


def _xor_bits(t: jax.Array, ks: jax.Array) -> jax.Array:
    """``t`` XOR ``ks`` (uint32, t's shape, one keystream unit per
    element in its low bits), keeping t's dtype."""
    bits_dtype = jnp.uint32 if t.dtype.itemsize == 4 else jnp.uint16
    bits = jax.lax.bitcast_convert_type(t, bits_dtype).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type((bits ^ ks).astype(bits_dtype),
                                        t.dtype)


def _unseal_tile(tile: jax.Array, crypt_row, key_words, layer,
                 bpp: int) -> jax.Array:
    """The emulation's whole-page unseal: decrypt iff the crypt flag is
    live; a plaintext page (flag 0) passes through bit-exactly."""
    def decrypt(t):
        words = _page_words(key_words, crypt_row[:3], layer, bpp)
        stream = jnp.stack([w.reshape(-1) for w in words], -1).reshape(-1)
        if t.dtype.itemsize == 2:       # little-endian halves of each word
            stream = jnp.stack([stream & jnp.uint32(0xFFFF),
                                stream >> jnp.uint32(16)], -1).reshape(-1)
        return _xor_bits(t, stream[:t.size].reshape(t.shape))

    return jax.lax.cond(crypt_row[3] > 0, decrypt, lambda t: t, tile)


def _unseal_into(src_ref, dst_ref, ks_ref, crypt_row, key_words, layer,
                 bpp: int) -> None:
    """The kernel's unseal: copy page ``src_ref[0]`` into VMEM scratch
    ``dst_ref``, decrypting it when its crypt flag is live.

    The page's keystream is computed once, block index on the lanes, into
    ``ks_ref`` [16 words, blocks // 128, 128]. Each page row then picks its
    elements' words with a lane gather: element e (row-major over the page)
    covers stream word w = e * itemsize // 4, word w % 16 of block w // 16,
    and a 2-byte element is the low (even e) or high (odd e) half of it.
    Same keystream and XOR as :func:`_unseal_tile`, so both produce the
    same bits."""
    live = crypt_row[3] > 0
    ps, shape = dst_ref.shape[0], dst_ref.shape[1:]
    itemsize = dst_ref.dtype.itemsize
    row_elems = int(np.prod(shape))
    n_rows = ks_ref.shape[1]
    chunk = 8 if n_rows % 8 == 0 else n_rows     # one vreg of blocks

    def first_row(p):          # ks row holding the page row's first word
        return p * row_elems * itemsize // 64 // LANES

    # ks rows one page row spans, at most
    span = max((((p + 1) * row_elems * itemsize - 1) // 64 // LANES)
               - first_row(p) + 1 for p in range(ps))

    @pl.when(live)
    def _cipher():
        for c0 in range(0, n_rows, chunk):
            words = _page_words(key_words, crypt_row[:3], layer, bpp,
                                row0=c0, rows=chunk)
            for n in range(16):
                ks_ref[n, c0:c0 + chunk] = words[n]
        e0 = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        for axis in range(1, len(shape)):
            e0 = e0 * shape[axis] + jax.lax.broadcasted_iota(
                jnp.int32, shape, axis)
        wide = shape[:-1] + (LANES,)

        def row(p, carry):
            e = e0 + p * row_elems
            w = e if itemsize == 4 else e >> 1
            blk = w >> 4
            r0 = first_row(p)
            dr = (blk >> 7) - r0
            lane = blk & (LANES - 1)
            word = w & 15
            ks = jnp.zeros(shape, jnp.uint32)
            for k in range(span):
                r = jnp.minimum(r0 + k, n_rows - 1)
                for n in range(16):
                    src = jnp.broadcast_to(ks_ref[n, pl.ds(r, 1), :], wide)
                    got = jnp.take_along_axis(src, lane, axis=-1,
                                              mode="promise_in_bounds")
                    ks = jnp.where((word == n) & (dr == k), got, ks)
            if itemsize == 2:
                ks = jnp.where((e & 1) == 1, ks >> jnp.uint32(16),
                               ks & jnp.uint32(0xFFFF))
            dst_ref[p] = _xor_bits(src_ref[0, p], ks)
            return carry
        jax.lax.fori_loop(0, ps, row, 0)

    @pl.when(jnp.logical_not(live))
    def _plain():
        dst_ref[...] = src_ref[0]


def _attend_page_math(q32, k, v, j, valid, m_prev, l_prev, acc_prev, *,
                      scale: float, page_size: int):
    """One online-softmax update over one KV page (GQA batched over kv
    heads), as a pure function — shared verbatim by the Pallas kernel body
    and the interpret-container jnp emulation so the two stay bit-aligned.
    q32 [h, hd] f32; k/v [page_size, hk, hd] (any dtype)."""
    h, hd = q32.shape
    hk = k.shape[1]
    g = h // hk
    qg = q32.reshape(hk, g, hd)
    kt = k.astype(jnp.float32).transpose(1, 0, 2)       # [hk, ps, hd]
    s = jax.lax.dot_general(qg, kt, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    k_pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (hk, g, page_size), 2)
    s = jnp.where(k_pos < valid, s, NEG_INF).reshape(h, page_size)

    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                               # [h, ps]
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    vt = v.astype(jnp.float32).transpose(1, 0, 2)        # [hk, ps, hd]
    pv = jax.lax.dot_general(p.reshape(hk, g, page_size), vt,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    return m_new, l_new, acc_prev * alpha + pv.reshape(h, hd)


def _attend_page(q32, k, v, j, valid, m_ref, l_ref, acc_ref, *,
                 scale: float, page_size: int):
    m_ref[...], l_ref[...], acc_ref[...] = _attend_page_math(
        q32, k, v, j, valid, m_ref[...], l_ref[...], acc_ref[...],
        scale=scale, page_size=page_size)


def _paged_kernel(table_ref, valid_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, page_size: int,
                  pages: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = valid_ref[i]

    @pl.when(j * page_size < valid)
    def _compute():
        _attend_page(q_ref[0].astype(jnp.float32), k_ref[0], v_ref[0],
                     j, valid, m_ref, l_ref, acc_ref,
                     scale=scale, page_size=page_size)

    @pl.when(j == pages - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_unseal_kernel(table_ref, valid_ref, layer_ref, key_ref,
                         kc_ref, vc_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, kd_ref, vd_ref, ks_ref, *,
                         scale: float, page_size: int, pages: int, bpp: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = valid_ref[i]
    layer = layer_ref[0]

    @pl.when(j * page_size < valid)
    def _compute():
        key_words = [key_ref[w] for w in range(8)]
        _unseal_into(k_ref, kd_ref, ks_ref,
                     [kc_ref[i, 4 * j + w] for w in range(4)],
                     key_words, layer, bpp)
        _unseal_into(v_ref, vd_ref, ks_ref,
                     [vc_ref[i, 4 * j + w] for w in range(4)],
                     key_words, layer, bpp)
        _attend_page(q_ref[0].astype(jnp.float32), kd_ref[...],
                     vd_ref[...], j, valid, m_ref, l_ref, acc_ref,
                     scale=scale, page_size=page_size)

    @pl.when(j == pages - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _emulated_walk(table, valid, q, k_pool, v_pool, unseal=None):
    """The kernel's page walk as plain jnp: vmap over slots, fori_loop over
    table columns, one dynamic page load per step, ``_attend_page_math``
    verbatim. Pages past ``valid`` still execute (loop bounds are static)
    but their carry update is where()-discarded — the same values the
    Pallas kernel's ``pl.when`` produces, at O(mapped pages) cost."""
    _, h, hd = q.shape
    _, ps, _, _ = k_pool.shape
    pages = table.shape[1]
    scale = 1.0 / np.sqrt(hd)

    def one_slot(qi, row, vi):
        q32 = qi.astype(jnp.float32)

        def body(j, carry):
            m, l, acc = carry
            phys = row[j]
            k, v = k_pool[phys], v_pool[phys]
            if unseal is not None:
                k, v = unseal(phys, k, v)
            m2, l2, a2 = _attend_page_math(q32, k, v, j, vi, m, l, acc,
                                           scale=scale, page_size=ps)
            live = j * ps < vi
            return (jnp.where(live, m2, m), jnp.where(live, l2, l),
                    jnp.where(live, a2, acc))

        init = (jnp.full((h, 1), NEG_INF, jnp.float32),
                jnp.zeros((h, 1), jnp.float32),
                jnp.zeros((h, hd), jnp.float32))
        m, l, acc = jax.lax.fori_loop(0, pages, body, init)
        return (acc / jnp.maximum(l, 1e-30)).astype(qi.dtype)

    return jax.vmap(one_slot)(q, table, valid)


def _specs(h, hd, ps, hk, n_prefetch):
    """Common BlockSpecs: q/out by slot, pools dereferenced through the
    prefetched page table (index_map args: grid indices then prefetch refs)."""
    q_spec = pl.BlockSpec((1, h, hd), lambda i, j, *refs: (i, 0, 0))
    pool_spec = pl.BlockSpec(
        (1, ps, hk, hd), lambda i, j, *refs: (refs[0][i, j], 0, 0, 0))
    return q_spec, pool_spec


@functools.partial(jax.jit, static_argnames=("interpret", "emulate"))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    table: jax.Array, valid: jax.Array, *,
                    interpret: bool | None = None,
                    emulate: bool | None = None) -> jax.Array:
    """Decode attention over a paged KV pool, no dense gather.

    q ``[slots, heads, head_dim]``; pools ``[num_pages+1, page_size,
    kv_heads, head_dim]``; table ``[slots, max_pages]`` int32 physical page
    ids (0 = null page); valid ``[slots]`` int32 attended prefix length.
    Returns ``[slots, heads, head_dim]`` in q's dtype. Rows whose table
    maps nowhere (idle slots) produce garbage the engine discards.

    ``emulate`` (default: follow ``interpret``) swaps the ``pallas_call``
    for the bit-aligned jnp page walk — see the module docstring. Pass
    ``emulate=False`` with ``interpret=True`` to force the Pallas
    interpreter (tests pin the two paths against each other).
    """
    b, h, hd = q.shape
    _, ps, hk, _ = k_pool.shape
    pages = table.shape[1]
    assert h % hk == 0, (h, hk)
    if interpret is None:
        interpret = interpret_default()
    if emulate is None:
        emulate = interpret
    if emulate:
        return _emulated_walk(table.astype(jnp.int32),
                              valid.astype(jnp.int32), q, k_pool, v_pool)
    scale = 1.0 / np.sqrt(hd)
    q_spec, pool_spec = _specs(h, hd, ps, hk, 2)
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, page_size=ps,
                          pages=pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, pages),
            in_specs=[q_spec, pool_spec, pool_spec],
            out_specs=pl.BlockSpec((1, h, hd), lambda i, j, *refs: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),    # running max
                pltpu.VMEM((h, 1), jnp.float32),    # running sum
                pltpu.VMEM((h, hd), jnp.float32),   # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), valid.astype(jnp.int32), q, k_pool, v_pool)


@functools.partial(jax.jit,
                   static_argnames=("blocks_per_page", "interpret",
                                    "emulate"))
def paged_attention_unseal(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, table: jax.Array,
                           valid: jax.Array, layer: jax.Array,
                           key_words: jax.Array, k_crypt: jax.Array,
                           v_crypt: jax.Array, *, blocks_per_page: int,
                           interpret: bool | None = None,
                           emulate: bool | None = None) -> jax.Array:
    """paged_attention over a pool whose pages may be ciphertext-resident.

    ``k_crypt``/``v_crypt`` ``[num_pages+1, 4]`` uint32 sidecars: words 0-2
    are the page blob's ChaCha20 nonce (core/sealing.py's
    sha256(key_id|name)[:12]), word 3 is the live flag (0 = plaintext page,
    XOR skipped bit-exactly). ``layer`` is the layer ordinal (int32 scalar
    or shape-[1]); counter_base = layer * blocks_per_page matches the
    sealed blob's contiguous [L, page] packing. ``key_words`` is the uint32
    [8] sealing key (SealingKey.key_words).
    """
    b, h, hd = q.shape
    _, ps, hk, _ = k_pool.shape
    pages = table.shape[1]
    assert h % hk == 0, (h, hk)
    page_bytes = ps * hk * hd * jnp.dtype(k_pool.dtype).itemsize
    assert page_bytes == blocks_per_page * 64, (page_bytes, blocks_per_page)
    assert supports_fused_unseal(k_pool.dtype, page_bytes), k_pool.dtype
    if interpret is None:
        interpret = interpret_default()
    if emulate is None:
        emulate = interpret
    if emulate:
        key = key_words.astype(jnp.uint32).reshape(8)
        lay = jnp.asarray(layer, jnp.int32).reshape(())
        kc = k_crypt.astype(jnp.uint32)
        vc = v_crypt.astype(jnp.uint32)

        def unseal(phys, k, v):
            return (_unseal_tile(k, list(kc[phys]), key, lay,
                                 blocks_per_page),
                    _unseal_tile(v, list(vc[phys]), key, lay,
                                 blocks_per_page))

        return _emulated_walk(table.astype(jnp.int32),
                              valid.astype(jnp.int32), q, k_pool, v_pool,
                              unseal=unseal)
    scale = 1.0 / np.sqrt(hd)
    q_spec, pool_spec = _specs(h, hd, ps, hk, 4)
    # the sidecar rows each grid step needs, gathered through the table into
    # scalar memory: [slots, pages * 4], row i words 4j..4j+3 = page (i, j)
    tbl = table.astype(jnp.int32)
    kc = k_crypt.astype(jnp.uint32)[tbl].reshape(b, pages * 4)
    vc = v_crypt.astype(jnp.uint32)[tbl].reshape(b, pages * 4)
    return pl.pallas_call(
        functools.partial(_paged_unseal_kernel, scale=scale, page_size=ps,
                          pages=pages, bpp=blocks_per_page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(b, pages),
            in_specs=[q_spec, pool_spec, pool_spec],
            out_specs=pl.BlockSpec((1, h, hd), lambda i, j, *refs: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, hd), jnp.float32),
                pltpu.VMEM((ps, hk, hd), k_pool.dtype),   # decrypted k page
                pltpu.VMEM((ps, hk, hd), v_pool.dtype),   # decrypted v page
                # one page's keystream, [16 words, blocks // 128, 128]
                pltpu.VMEM((16, -(-blocks_per_page // LANES), LANES),
                           jnp.uint32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(tbl, valid.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      key_words.astype(jnp.uint32).reshape(8), kc, vc, q, k_pool, v_pool)
