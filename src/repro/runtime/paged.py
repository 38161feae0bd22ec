"""Paged KV backend: a page-pool + page-table layout behind ``KVBackend``.

Layout. Every cache leaf with a sequence-length axis (attention ``k``/``v``,
MLA ``ckv``/``krope``) is stored as a static pool
``[L, num_pages + 1, page_size, ...]`` — physical page 0 is a reserved
*null page* (scratch for rows that are not appending) and pages
``1..num_pages`` are allocatable. An ``[max_slots, max_pages]`` int32 page
table maps each sequence's logical pages to physical ones (entry 0 =
unmapped). Leaves without a length axis (SSM conv/state, RWKV wkv rows)
stay slot-dense ``[L, max_slots, ...]``; per-sequence positions live
host-side and are threaded into each step.

Decode. One ``jnp.take`` over the page table gathers each sequence's pages
into exactly the dense ``[L, max_slots, max_len, ...]`` view the model's
``decode_step`` already expects — static shapes end to end (TPU/XLA-safe),
no model changes. Positions at or beyond a sequence's live length are
masked inside attention (``kv_valid_len``), so whatever the gather pulls
out of unmapped/null pages never reaches a logit, and outputs are
bit-identical to the slot-dense backend. Only the single appended position
is scattered back per step (``pool.at[:, write_phys, write_off]``); rows
that are not appending route their write to the null page.

Allocation. Two modes (``alloc=``):

  * ``"reserve"`` (default): admission reserves ``ceil(need / page_size)``
    pages — the request's own worst case — and physical pages are mapped
    lazily as positions are written, so reservations make append failure
    impossible (allocated <= reserved <= num_pages).
  * ``"ondemand"`` (vLLM-style; implied by ``prefix_sharing``): admission
    checks only the *prompt's* immediate page need against the free pool
    and decode-time appends are granted at step time. The pool may be
    oversubscribed against worst cases; when it runs dry mid-step the
    engine frees capacity by *capacity preemption* — evict-by-slack
    through the existing ``seal_tail_pages`` / whole-seal machinery.

Prefix sharing (``prefix_sharing=True``). A content index maps the
*cumulative* hash of the token ids up to each aligned page boundary to a
shared physical page with a per-page refcount. ``insert_prefill`` maps an
index hit instead of allocating+writing a copy (prefill KV rows are
bitwise row-count-invariant, so the resident page is exactly what this
request would have computed); the refcount equals the number of live table
mappings. A write into an indexed page (only ever the *tail* page a slot
appends into) triggers copy-on-write when other mappings remain, or simply
unregisters the page when the writer is its sole user.

Sealing. Preemption seals *per page*: each private page of each paged leaf
becomes its own ciphertext+MAC with a nonce derived from
``{prefix}{leaf}/p{ordinal}`` — sealed bytes scale with tokens used, not
capacity reserved. Shared (content-indexed) pages are refcount-aware: a
victim's sealed meta records the page's content key (and refcount) instead
of moving ciphertext, restore *re-links* the resident page, and the page's
data only crosses the boundary when its **last** reference drops — sealed
once, under its content-derived name (same content => same nonce => the
identical ciphertext, so repeated parking can never pair one nonce with two
plaintexts). ``seal_tail_pages``/``restore_tail_pages`` support partial
eviction of the (always private) tail.

Page store (``page_store=``). The persistent tier behind the content index
(:mod:`repro.runtime.pagestore`): parking's content-named ciphertext, but
retained past the last live/sealed reference. Aligned FULL pages publish to
the store whenever their data is already sealed (parking, last-sealed-ref
discard) or when their last mapping drops unsealed (release, sole-user
divergence — one fresh seal, under the same canonical name parking uses,
so the nonce-safety argument is unchanged and re-publishing resident
content is a membership no-op). ``insert_prefill`` index misses and
``restore``'s neither-resident-nor-parked case consult the store:
a hit MAC-verifies and decrypts the blobs *before* any page or refcount
moves, then maps the restored page exactly like a shared one. Store
residency also discounts ``admission_check``'s effective need (the live
index's discount, extended one tier down); entries are namespaced per
sealing-key domain, so another tenant's lookups are clean misses.

Decode modes (``decode=``). ``"gather"`` (default) is the dense-view path
above — bit-identical to slot-dense, any model family, any plan.
``"kernel"`` replaces the gather with ``kernels/paged_attention.py``: a
Pallas kernel walks the page table directly and streams KV pages into
VMEM, so per-step KV traffic is O(tokens attended), not O(max_len) dense
rematerialization. Kernel mode additionally keeps eligible restored pages
*ciphertext-resident*: a whole-slot restore MAC-checks each full private
page (``sealing.verify_mac``) and places the ciphertext bits straight into
the pool with a per-page crypt sidecar (nonce + live flag); the decode
kernel regenerates the ChaCha20 keystream in-VMEM and decrypts on the way
into the attention dot, so the restore never round-trips plaintext KV
through HBM. Any host-side consumer of a ciphertext page (seal, park,
page copy, partial eviction) first calls ``_materialize_page`` — pages a
slot appends into are always plaintext (appends target the partial tail,
which restores through the host path). Kernel mode requires a dense
attention family, a non-sharded plan, and (for the ciphertext-resident
part) a pool dtype/page size the in-kernel XOR supports; ineligible
configs still get the kernel attention path with host-decrypt restores.
"""

from __future__ import annotations

import hashlib
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sealing import (IntegrityError, SealedTensor, SealingKey,
                                ciphertext_page_bytes, nonce_words_for,
                                seal_tensor, shared_page_name, unseal_tensor,
                                verify_mac)
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_unseal,
                                           supports_fused_unseal)
from repro.kernels.ref import chacha20_keystream_bytes_ref
from repro.runtime import sampling
from repro.runtime.kvcache import KVBackend, host_upload, next_pow2
from repro.runtime.plan import ComputePlan

Cache = Any
Params = Any

# cache-leaf names that carry a [.., max_len, ..] sequence axis at dim 2
_LENGTH_LEAVES = ("k", "v", "ckv", "krope")


def _keystr(path) -> str:
    return jax.tree_util.keystr(path)


def _leaf_key(path) -> Optional[str]:
    return getattr(path[-1], "key", None) if path else None


def prefix_page_keys(tokens: np.ndarray, page_size: int, written_len: int,
                     salt: bytes = b"") -> List[bytes]:
    """Content keys for the pages covering ``tokens[:written_len]``: key j is
    the running hash of every token id up to the end of page j (KV at a
    position depends on *all* earlier tokens, so only a true prefix match
    may share), truncated chains for the final partial page. 16-byte sha256
    prefixes — collisions are negligible against 2^64 pages."""
    toks = np.ascontiguousarray(np.asarray(tokens, np.int32)[:written_len])
    h = hashlib.sha256(salt)
    keys = []
    for j in range(-(-int(written_len) // page_size)):
        h.update(toks[j * page_size:(j + 1) * page_size].tobytes())
        keys.append(h.digest()[:16])
    return keys


def _write_page(pool_leaf, phys, page):
    """Write ``page`` [L, 1, ...] at pool page ``phys``. A dynamic-update-
    slice, not a scatter: on the TPU a scatter into a bf16 pool changed the
    NaN payloads of resident ciphertext, which is full of NaN bit patterns;
    a dynamic-update-slice keeps every bit."""
    return jax.lax.dynamic_update_slice_in_dim(
        pool_leaf, page.astype(pool_leaf.dtype), phys, axis=1)


@partial(jax.jit, donate_argnums=(0,))
def _set_pages(pool_leaf, idx, pages):
    """Write restored pages into a donated pool leaf in place — restore
    cost stays O(pages moved), not O(pool) rebuilt per leaf."""
    for n in range(pages.shape[1]):
        pool_leaf = _write_page(pool_leaf, idx[n], pages[:, n:n + 1])
    return pool_leaf


@partial(jax.jit, donate_argnums=(0,))
def _set_row(dense_leaf, slot, row):
    start = (jnp.int32(0), slot.astype(jnp.int32)) + \
        (jnp.int32(0),) * (dense_leaf.ndim - 2)
    return jax.lax.dynamic_update_slice(
        dense_leaf, row.astype(dense_leaf.dtype), start)


class PagedKVBackend(KVBackend):
    """See module docstring; constructed via ``Engine(kv_backend="paged")``
    or ``kvcache.make_backend("paged", ...)``."""

    name = "paged"
    supports_partial = True

    def __init__(self, model, max_slots: int, max_len: int, *,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 plan: Optional[ComputePlan] = None,
                 prefix_sharing: bool = False, alloc: Optional[str] = None,
                 decode: str = "gather", page_store: Any = None,
                 store_budget_pages: Optional[int] = None):
        super().__init__(model, max_slots, max_len, plan)
        if decode not in ("gather", "kernel"):
            raise ValueError(f"decode must be 'gather' or 'kernel', "
                             f"got {decode!r}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_len % page_size != 0:
            raise ValueError(f"max_len={max_len} must be a multiple of "
                             f"page_size={page_size}")
        if alloc is None:
            alloc = "ondemand" if prefix_sharing else "reserve"
        if alloc not in ("reserve", "ondemand"):
            raise ValueError(f"alloc must be 'reserve' or 'ondemand', "
                             f"got {alloc!r}")
        if prefix_sharing and alloc != "ondemand":
            # COW converts a shared mapping into a private page at step
            # time, which no admission-time worst case can cover — sharing
            # therefore runs on step-time grants.
            raise ValueError("prefix_sharing requires alloc='ondemand'")
        self.on_demand = alloc == "ondemand"
        self.prefix_sharing = prefix_sharing
        self.supports_sharing = prefix_sharing
        # persistent sealed-page store (the prefix-cache tier). Accepts a
        # ready SealedPageStore (possibly shared between backends), True, or
        # a policy name; store_budget_pages alone implies an LRU store.
        if page_store is False:
            page_store = None
        if page_store is None and store_budget_pages is not None:
            page_store = "lru"
        if page_store is not None and not prefix_sharing:
            raise ValueError(
                "page_store requires prefix_sharing=True (the store is the "
                "tier behind the content index — without page keys there is "
                "nothing to address it by)")
        if page_store is True:
            page_store = "lru"
        if isinstance(page_store, str):
            from repro.runtime.pagestore import SealedPageStore
            page_store = SealedPageStore(budget_pages=store_budget_pages,
                                         policy=page_store)
        elif page_store is not None and store_budget_pages is not None:
            raise ValueError(
                "store_budget_pages configures a store the backend "
                "constructs; a ready SealedPageStore carries its own budget")
        self.page_store = page_store
        self.store_key: Optional[SealingKey] = None
        self.store_hits = 0             # pages served from the store
        self.store_restored_pages = 0
        self.store_restored_bytes = 0
        self.page_size = page_size
        self.max_pages = max_len // page_size
        if num_pages is None:
            num_pages = max_slots * self.max_pages   # dense-equivalent pool
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        # a pool smaller than max_pages is legal: request_capacity shrinks
        # to num_pages * page_size and submit rejects what cannot ever fit.
        self.num_pages = num_pages
        self._key_salt = f"{model.cfg.name}|{page_size}|{max_len}".encode()

        # classify leaves once (from shapes: the dense cache is never
        # allocated); paged leaves move to pool layout, the rest stay dense
        dense = model.abstract_cache(max_slots, max_len)
        dense.pop("pos")
        self._paged_paths = set()

        def build(path, leaf):
            shape = leaf.shape
            if (_leaf_key(path) in _LENGTH_LEAVES and leaf.ndim >= 3
                    and shape[2] == max_len):
                self._paged_paths.add(_keystr(path))
                shape = (shape[0], num_pages + 1, page_size) + shape[3:]
            return jnp.zeros(shape, leaf.dtype)
        self.blocks = jax.tree_util.tree_map_with_path(build, dense)
        if not self._paged_paths:
            raise ValueError(
                f"model {model.cfg.name} has no sequence-length KV leaves to "
                f"page; use kv_backend='slot' for pure-state families")
        # mesh placement: pool leaves replicate (pages are shared), dense
        # recurrent-state leaves shard their batch dim (see kvcache docs)
        self.blocks = self.plan.place_paged_cache(self.blocks,
                                                  self._paged_paths)

        # host-side sequence state
        self.pos = np.zeros(max_slots, np.int32)           # live KV positions
        self.table = np.zeros((max_slots, self.max_pages), np.int32)
        self._free_pages: List[int] = list(range(1, num_pages + 1))
        self._alloc = np.zeros(max_slots, np.int32)        # pages mapped
        self._reserved = np.zeros(max_slots, np.int32)     # pages promised
        self._reserve_free = num_pages
        # on-demand admission promises: pages pledged to an admitted-but-
        # not-yet-prefilled slot so a batched admission group cannot
        # overcommit the free list between acquire() and insert_prefill().
        self._promised = np.zeros(max_slots, np.int32)
        self._promised_total = 0

        # prefix-sharing state. _page_ref counts live table mappings per
        # physical page (private pages hold exactly 1); _index/_page_key is
        # the content index both ways; _sealed_refs counts sealed-out
        # requests whose meta references a content key; _parked holds the
        # content-named ciphertext of pages whose last live reference
        # dropped while sealed references remain.
        self._page_ref = np.zeros(num_pages + 1, np.int32)
        self._index: Dict[bytes, int] = {}
        self._page_key: Dict[int, bytes] = {}
        self._sealed_refs: Dict[bytes, int] = {}
        self._parked: Dict[bytes, Dict[str, SealedTensor]] = {}
        # content keys whose page only part-fills (registered past
        # written_len): never published to the store — store entries are
        # aligned FULL pages only. Partialness is a content property (the
        # chain hash covers the same truncated token run), so the flag is
        # stable across engines sharing a store.
        self._partial_keys: set = set()
        self._seal_key_cache: Optional[SealingKey] = None
        self._events: List[Tuple[str, int, int]] = []  # (kind, nbytes, n)
        self.shared_page_maps = 0     # mappings served by an index hit
        self.cow_copies = 0           # tail-page copy-on-write events
        self.pages_written = 0        # physical pages taken + written

        paged = self._paged_paths

        def _decode(params, tokens, blocks, table, pos, write_phys,
                    write_off, state, kmax):
            def gather(path, leaf):
                if _keystr(path) not in paged:
                    return leaf
                v = jnp.take(leaf, table, axis=1)  # [L, b, max_pages, ps, ..]
                return v.reshape(leaf.shape[0], table.shape[0], max_len,
                                 *leaf.shape[3:])
            view = jax.tree_util.tree_map_with_path(gather, blocks)
            cache = dict(view)
            cache["pos"] = pos
            logits, new_cache = model.decode_step(params, tokens, cache)
            if state is None:
                toks = sampling.greedy(logits)
            else:
                toks = sampling.sample(logits, state, kmax=kmax)
            new_cache.pop("pos")

            def scatter(path, pool, new_leaf):
                if _keystr(path) not in paged:
                    # slot-dense (recurrent-state) leaf: advance ONLY the
                    # rows that actually stepped — a paused (partially
                    # evicted) row's state must stay frozen exactly where
                    # its sealed tail left it. write_phys > 0 is precisely
                    # the stepped-rows mask.
                    mask = (write_phys > 0).reshape(
                        1, -1, *([1] * (new_leaf.ndim - 2)))
                    return jnp.where(mask, new_leaf.astype(pool.dtype), pool)
                # pull the one appended position per sequence out of the
                # dense view and write it to (write_phys, write_off)
                idx = pos.reshape(1, -1, 1, *([1] * (new_leaf.ndim - 3)))
                idx = jnp.broadcast_to(
                    idx, new_leaf.shape[:2] + (1,) + new_leaf.shape[3:])
                written = jnp.take_along_axis(new_leaf, idx, axis=2)[:, :, 0]
                return pool.at[:, write_phys, write_off].set(
                    written.astype(pool.dtype))
            new_blocks = jax.tree_util.tree_map_with_path(
                scatter, blocks, new_cache)
            return toks, new_blocks

        # fused-unseal (ciphertext-resident restore) state. Present in both
        # modes so accounting/stats code stays unconditional: _cipher_pages
        # is the set of physical pages whose pool bits are ciphertext,
        # _crypt maps each paged leaf to its [num_pages+1, 4] uint32 sidecar
        # (nonce words 0-2, live flag word 3), _crypt_key is the key every
        # resident ciphertext page was sealed under.
        self.decode_mode = decode
        self._cipher_pages: set = set()
        self._crypt: Dict[str, np.ndarray] = {}
        self._crypt_key: Optional[SealingKey] = None
        self.supports_fused = False
        self._fused_bpp = 0
        self.fused_restore_pages = 0
        self.fused_restore_bytes = 0

        if decode == "kernel":
            self._init_kernel_decode(model)
        else:
            self._decode_fn = self.plan.compile_decode(
                _decode, donate_argnums=(2,), static_argnums=(8,))

        def _splice(blocks, prefilled, page_rows, page_ord, phys,
                    dense_rows, dense_slots):
            def upd(path, pool, src):
                if _keystr(path) not in paged:
                    return pool.at[:, dense_slots].set(
                        src[:, dense_rows].astype(pool.dtype))
                pages = src.reshape(src.shape[0], src.shape[1],
                                    self.max_pages, page_size, *src.shape[3:])
                size = (pages.shape[0], 1, 1) + pages.shape[3:]
                for n in range(phys.shape[0]):
                    start = ((0, page_rows[n], page_ord[n])
                             + (0,) * (pages.ndim - 3))
                    page = jax.lax.dynamic_slice(pages, start, size)
                    pool = _write_page(pool, phys[n], page[:, 0])
                return pool
            return jax.tree_util.tree_map_with_path(upd, blocks, prefilled)

        self._splice_fn = self.plan.compile(_splice, donate_argnums=(0,))

        def _copy_page(blocks, src, dst):
            def upd(path, pool):
                if _keystr(path) not in paged:
                    return pool
                return _write_page(pool, dst, jax.lax.dynamic_slice_in_dim(
                    pool, src, 1, axis=1))
            return jax.tree_util.tree_map_with_path(upd, blocks)

        self._copy_page_fn = self.plan.compile(_copy_page,
                                               donate_argnums=(0,))

    # -- kernel decode mode ---------------------------------------------------
    def _init_kernel_decode(self, model) -> None:
        """Build the table-walking Pallas decode path (decode='kernel').

        The closure mirrors the dense family's ``decode_step`` math exactly
        (rmsnorm -> _qkv with RoPE -> attention -> wo -> rmsnorm -> swiglu,
        layer scan with the pool slices as carry) but replaces
        gather + sdpa with ``kernels/paged_attention.py`` reading the page
        table directly; when ciphertext-resident pages exist the fused
        variant decrypts them in-kernel against the crypt sidecars.
        """
        from repro.models import layers as model_layers
        from repro.models.transformer import _attn_cfg
        if self.plan.is_sharded:
            raise ValueError(
                "decode='kernel' requires a single-device plan (the paged-"
                "attention kernel reads the local pool; use decode='gather' "
                "on meshes)")
        impl = getattr(model, "_impl", model)   # Model facade -> DecoderLM
        blocks_desc = getattr(impl, "blocks", None)
        if (not blocks_desc or len(blocks_desc) != 1
                or blocks_desc[0][2] != [("attn", "swiglu")]):
            raise ValueError(
                f"decode='kernel' supports the dense attention family only "
                f"(one uniform attn+swiglu block); {model.cfg.name} has "
                f"{blocks_desc!r} — use decode='gather'")
        block_name = blocks_desc[0][0]
        self._k_path = next(p for p in self._paged_paths
                            if p.endswith("['k']"))
        self._v_path = next(p for p in self._paged_paths
                            if p.endswith("['v']"))

        # per-leaf page geometry + fused-unseal eligibility: pages must
        # cover whole ChaCha20 blocks and bitcast to uint words in-kernel,
        # and every leaf must share one blocks-per-page (k and v do).
        shapes: Dict[str, Tuple[tuple, Any]] = {}

        def grab(path, leaf):
            if _keystr(path) in self._paged_paths:
                shapes[_keystr(path)] = (leaf.shape, leaf.dtype)
            return leaf
        jax.tree_util.tree_map_with_path(grab, self.blocks)
        self._page_shape = {p: (s[0], self.page_size) + tuple(s[3:])
                            for p, (s, _) in shapes.items()}
        self._page_dtype = {p: d for p, (_, d) in shapes.items()}
        page_bytes = {p: int(np.prod(s[2:])) * np.dtype(d).itemsize
                      for p, (s, d) in shapes.items()}
        self.supports_fused = (
            len(set(page_bytes.values())) == 1
            and all(supports_fused_unseal(d, page_bytes[p])
                    for p, (_, d) in shapes.items()))
        self._fused_bpp = (next(iter(page_bytes.values())) // 64
                           if self.supports_fused else 0)
        for p in self._paged_paths:
            self._crypt[p] = np.zeros((self.num_pages + 1, 4), np.uint32)

        cfg = model.cfg
        acfg = _attn_cfg(cfg)
        bpp = self._fused_bpp
        mlayers = model_layers

        def _decode_kernel(params, tokens, blocks, table, pos, write_phys,
                           write_off, k_crypt, v_crypt, key_words, state,
                           kmax, use_cipher):
            x = impl._embed(params, tokens)            # [b, 1, d]
            positions = pos[:, None]
            valid = pos + 1
            slot0 = blocks[block_name]["slot_0"]
            kp, vp = slot0["k"], slot0["v"]

            def body(carry, lp):
                x, kp, vp, li = carry
                sl = lp["slot_0"]
                h = mlayers.rmsnorm(sl["pre_norm"], x, cfg.norm_eps)
                q, k, v = mlayers._qkv(sl["attn"], acfg, h, positions)
                kl = jax.lax.dynamic_index_in_dim(kp, li, 0, keepdims=False)
                vl = jax.lax.dynamic_index_in_dim(vp, li, 0, keepdims=False)
                # append this step's k/v before attending (idle rows route
                # to the null page), exactly like the gather path's
                # write-then-attend; one dynamic-update-slice per row, not
                # a scatter, so resident ciphertext keeps its bits (see
                # _set_pages)
                for i in range(k.shape[0]):
                    at = (write_phys[i], write_off[i], 0, 0)
                    kl = jax.lax.dynamic_update_slice(
                        kl, k[i:i + 1].astype(kl.dtype), at)
                    vl = jax.lax.dynamic_update_slice(
                        vl, v[i:i + 1].astype(vl.dtype), at)
                if use_cipher:
                    out = paged_attention_unseal(
                        q[:, 0], kl, vl, table, valid, li, key_words,
                        k_crypt, v_crypt, blocks_per_page=bpp)
                else:
                    out = paged_attention(q[:, 0], kl, vl, table, valid)
                x = x + jnp.einsum("bshk,hkd->bsd",
                                   out.astype(q.dtype)[:, None],
                                   sl["attn"]["wo"])
                h = mlayers.rmsnorm(sl["post_norm"], x, cfg.norm_eps)
                x = x + mlayers.swiglu(sl["ffn"], h)
                kp = jax.lax.dynamic_update_index_in_dim(kp, kl, li, 0)
                vp = jax.lax.dynamic_update_index_in_dim(vp, vl, li, 0)
                return (x, kp, vp, li + 1), None

            (x, kp, vp, _), _ = jax.lax.scan(
                body, (x, kp, vp, jnp.int32(0)), params[block_name])
            logits = impl._unembed(params, x)[:, 0]
            if state is None:
                toks = sampling.greedy(logits)
            else:
                toks = sampling.sample(logits, state, kmax=kmax)
            new_blocks = dict(blocks)
            new_blocks[block_name] = dict(blocks[block_name])
            new_blocks[block_name]["slot_0"] = dict(slot0, k=kp, v=vp)
            return toks, new_blocks

        self._decode_fn = self.plan.compile_decode(
            _decode_kernel, donate_argnums=(2,), static_argnums=(11, 12))

    # -- page accounting ------------------------------------------------------
    def pages_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.page_size)

    @property
    def free_page_reserve(self) -> int:
        """Pages an admission may still promise: unreserved pages in reserve
        mode, unpromised free physical pages on demand."""
        if self.on_demand:
            return len(self._free_pages) - self._promised_total
        return self._reserve_free

    @property
    def free_physical_pages(self) -> int:
        return len(self._free_pages)

    def allocated_pages(self, slot: int) -> int:
        return int(self._alloc[slot])

    @property
    def request_capacity(self) -> int:
        # the dense decode view is still [*, max_len, *]; a sequence also
        # cannot out-reserve the pool.
        return min(self.max_len, self.num_pages * self.page_size)

    def page_keys(self, tokens: np.ndarray, written_len: int
                  ) -> Optional[List[bytes]]:
        """Content keys for a prompt's prefill pages (None when sharing is
        off — callers pass the result straight back to admission hooks)."""
        if not self.prefix_sharing:
            return None
        return prefix_page_keys(tokens, self.page_size, written_len,
                                self._key_salt)

    def resident_pages(self, page_keys: Optional[Sequence[bytes]]) -> int:
        """How many of these content keys are resident in the LIVE index
        now. Deliberately excludes store residency: admission's page
        promises (:meth:`Engine._admit_need`) size physical takes from this
        count, and a store hit still takes a fresh physical page — only the
        *pricing* discount (:meth:`admission_check`) may see the store."""
        if not page_keys:
            return 0
        return sum(1 for k in page_keys if k in self._index)

    def store_resident_pages(self, page_keys: Optional[Sequence[bytes]]
                             ) -> int:
        """How many of these content keys the persistent store could serve
        beyond the live index — the admission discount's second tier (and
        the fleet's store-affinity placement signal)."""
        if not page_keys or self.page_store is None:
            return 0
        skey = self.store_key or self._seal_key_cache
        if skey is None:
            return 0
        return sum(1 for k in page_keys
                   if k not in self._index
                   and self.page_store.contains(skey, k))

    def admission_check(self, need: int, page_keys: Optional[Sequence[bytes]]
                        = None) -> Tuple[bool, int]:
        """(fits, effective_need). The capacity bound is NOT relaxed by
        sharing: every page of one sequence — shared or private — occupies
        its own simultaneous page-table mapping, so a single request can
        never exceed ``min(max_len, num_pages * page_size)`` however warm
        the index is. What sharing discounts is the *effective demand*
        (``need`` minus resident shared positions): the unit admission
        charges against the pool, which is what lets a RAG request whose
        context prefix is resident admit alongside traffic that would
        otherwise have reserved the pool away. Store-resident prefixes
        discount the same way — a store hit skips the prefill recompute,
        which is the cost effective demand prices — even though the
        restored page still occupies a fresh physical page."""
        resident = (self.resident_pages(page_keys)
                    + self.store_resident_pages(page_keys))
        eff = max(1, int(need) - resident * self.page_size)
        return need <= self.request_capacity, eff

    def can_admit(self, n_tokens: int) -> bool:
        return self.pages_for(n_tokens) <= self.free_page_reserve

    def can_restore(self, n_tokens: int,
                    n_pages: Optional[int] = None) -> bool:
        if not self.on_demand:
            return self.pages_for(n_tokens) <= self._reserve_free
        pages = n_pages if n_pages is not None else self.pages_for(n_tokens)
        # headroom: leave one page per active slot so the restore is not
        # immediately re-evicted by the next step's appends (thrash damping;
        # with nothing else active the pool is all the restore's).
        return pages + len(self.slots.active) <= len(self._free_pages)

    def _take_pages(self, n: int) -> List[int]:
        assert n <= len(self._free_pages), \
            "page allocation exceeded reservation/grant — accounting bug"
        taken, self._free_pages = self._free_pages[:n], self._free_pages[n:]
        for p in taken:
            self._page_ref[p] = 1
            self._clear_crypt(p)
        return taken

    def _drop_ref(self, phys: int) -> None:
        """One table mapping of ``phys`` goes away; the page is freed (and
        unregistered — parked first if sealed references remain) only when
        the LAST mapping drops."""
        phys = int(phys)
        self._page_ref[phys] -= 1
        assert self._page_ref[phys] >= 0, "double-free — refcount bug"
        if self._page_ref[phys] == 0:
            self._unregister(phys)
            self._clear_crypt(phys)
            self._free_pages.append(phys)

    def bind_store_key(self, key: SealingKey) -> None:
        """Fix the key domain this backend's store traffic lives under (the
        engine binds its TrustDomain sealing key at construction). Store
        entries are namespaced by key id, so two engines sharing one store
        object can never be offered each other's ciphertext — a cross-
        domain lookup is a clean miss, and the independent per-domain MAC
        key would reject the blob even if it were offered."""
        self.store_key = key

    def _content_key(self) -> Optional[SealingKey]:
        """The key content-named blobs (parking AND the store) seal under:
        the bound store key when present, else the last key seen — one
        selection for both tiers, so parked blobs and store entries are
        always interchangeable ciphertext."""
        return self.store_key or self._seal_key_cache

    def _seal_content_page(self, key: SealingKey, key_bytes: bytes,
                           phys: int) -> Dict[str, SealedTensor]:
        """Seal one resident page under its canonical content-derived name
        (same content => same name => same nonce AND plaintext)."""
        pages = self._page_arrays([phys])
        return {kpath: seal_tensor(key, shared_page_name(key_bytes, kpath),
                                   arr[:, 0])
                for kpath, arr in pages.items()}

    def _publish_store(self, skey: SealingKey, key_bytes: bytes,
                       blobs: Dict[str, SealedTensor]) -> None:
        """Hand content-named blobs to the persistent store; evictions the
        budget forces surface as events (no boundary crossing — the host
        simply forgets ciphertext)."""
        for e in self.page_store.publish(skey, key_bytes, blobs,
                                         tokens=self.page_size):
            self._events.append(("store_evict", e.n_bytes, len(e.blobs)))

    def _unregister(self, phys: int) -> None:
        key = self._page_key.pop(phys, None)
        if key is None:
            return
        del self._index[key]
        if self._sealed_refs.get(key, 0) > 0:
            self._park(key, phys)
        if (self.page_store is not None
                and key not in self._partial_keys):
            skey = self._content_key()
            if skey is not None and not self.page_store.contains(skey, key):
                # publish the dying page's content: reuse the parked blobs
                # when parking just sealed them (no second crossing), else
                # seal once here — a fresh "store_publish" boundary event.
                blobs = self._parked.get(key)
                if blobs is None:
                    blobs = self._seal_content_page(skey, key, phys)
                    nb = sum(b.n_bytes for b in blobs.values())
                    self._events.append(("store_publish", nb, len(blobs)))
                self._publish_store(skey, key, blobs)

    def _park(self, key_bytes: bytes, phys: int) -> None:
        """Last reference to a sealed-referenced shared page is dropping:
        move its data across the boundary ONCE, under its content-derived
        name (deterministic: same content => same nonce AND same plaintext,
        so a later identical parking can never violate nonce uniqueness)."""
        key = self._content_key()
        assert key is not None, \
            "sealed refs exist but no sealing key was ever seen"
        if key_bytes in self._parked:
            return
        blobs = self._seal_content_page(key, key_bytes, phys)
        self._parked[key_bytes] = blobs
        nb = sum(b.n_bytes for b in blobs.values())
        self._events.append(("park", nb, len(blobs)))

    def drain_events(self) -> List[Tuple[str, int, int]]:
        """Boundary traffic the backend generated outside an explicit
        seal/restore call (shared-page parking and re-materialization); the
        engine drains this into the TrustDomain accounting."""
        ev, self._events = self._events, []
        return ev

    # -- sequence lifecycle ---------------------------------------------------
    def acquire(self, rid: int, n_tokens: int) -> Optional[int]:
        need = self.pages_for(n_tokens) if n_tokens > 0 else 0
        if need > self.free_page_reserve:
            return None
        slot = self.slots.acquire(rid)
        if slot is None:
            return None
        if self.on_demand:
            # promise only the immediate (prompt) need the engine passed;
            # decode-time pages are granted at step time.
            self._promised[slot] = need
            self._promised_total += need
        else:
            self._reserved[slot] = need
            self._reserve_free -= need
        return slot

    def release(self, slot: int) -> None:
        n = int(self._alloc[slot])
        for j in range(n):
            self._drop_ref(self.table[slot, j])
        self.table[slot] = 0
        self._alloc[slot] = 0
        self._reserve_free += int(self._reserved[slot])
        self._reserved[slot] = 0
        self._promised_total -= int(self._promised[slot])
        self._promised[slot] = 0
        self.pos[slot] = 0
        self.slots.release(slot)

    # -- device compute -------------------------------------------------------
    def insert_prefill(self, prefilled: Cache, slots: List[int],
                       written_len: int,
                       page_keys: Optional[List[Optional[List[bytes]]]] = None
                       ) -> None:
        k = len(slots)
        rows = prefilled["pos"].shape[0]
        n_pages = self.pages_for(written_len)
        skey = self._content_key() if self.page_store is not None else None
        # phase 1: plan every slot's pages with NO state mutation. Index
        # misses consult the persistent store; a store hit's blobs are
        # MAC-verified and decrypted HERE, so a tampered store entry fails
        # the whole group before a single page or refcount moves. `pending`
        # tracks keys an earlier group member will register at commit —
        # later members share its page instead of double-registering.
        plans: List[List[Tuple[str, int, Optional[bytes], Any]]] = []
        pending: set = set()
        for i, slot in enumerate(slots):
            keys = page_keys[i] if page_keys else None
            plan = []
            for j in range(n_pages):
                key = keys[j] if keys else None
                if key is not None and (key in self._index
                                        or key in pending):
                    plan.append(("hit", j, key, None))
                    continue
                if key is not None and skey is not None:
                    blobs = self.page_store.lookup(skey, key)
                    if blobs is not None:
                        plain = {kpath: np.asarray(unseal_tensor(skey, st))
                                 for kpath, st in blobs.items()}
                        nb = sum(st.n_bytes for st in blobs.values())
                        plan.append(("store", j, key, (plain, nb)))
                        pending.add(key)
                        continue
                plan.append(("miss", j, key, None))
                if key is not None:
                    pending.add(key)
            plans.append(plan)
        # phase 2: commit — map hits, take pages for store hits and misses.
        src_rows, page_ord, phys = [], [], []
        store_writes: Dict[int, Dict[str, np.ndarray]] = {}
        for i, slot in enumerate(slots):
            store_js = [pl for pl in plans[i] if pl[0] == "store"]
            misses = [pl for pl in plans[i] if pl[0] == "miss"]
            # one batched take per slot (not one free-list reslice per page)
            taken = self._take_pages(len(store_js) + len(misses))
            for pl in plans[i]:
                kind, j, key = pl[0], pl[1], pl[2]
                if kind == "hit":
                    # shared: map the resident page, write nothing (keys
                    # pending at plan time committed in an earlier slot)
                    hit = self._index[key]
                    self._page_ref[hit] += 1
                    self.table[slot, j] = hit
                    self.shared_page_maps += 1
                elif kind == "store":
                    plain, nb = pl[3]
                    p = taken.pop(0)
                    self.table[slot, j] = p
                    self._index[key] = p
                    self._page_key[p] = key
                    store_writes[p] = plain
                    self.store_hits += 1
                    self.store_restored_pages += 1
                    self.store_restored_bytes += nb
                    self._events.append(("store_hit", nb, len(plain)))
                else:
                    p = taken.pop(0)
                    self.table[slot, j] = p
                    if key is not None:
                        self._index[key] = p
                        self._page_key[p] = key
                        if (j + 1) * self.page_size > written_len:
                            self._partial_keys.add(key)
                    src_rows.append(i)
                    page_ord.append(j)
                    phys.append(p)
            self._alloc[slot] = n_pages
            self.pos[slot] = written_len
            self._promised_total -= int(self._promised[slot])
            self._promised[slot] = 0
        # store-restored pages are intentionally NOT pages_written: that
        # counter is the prefill-write cost the warm epoch is supposed to
        # shrink (store_restored_pages counts the restores).
        self.pages_written += len(phys)
        if not phys:
            # every page of every group member was an index hit: route one
            # dummy write to the null scratch page (the same sink idle rows
            # use) so the splice shape machinery stays uniform.
            src_rows, page_ord, phys = [0], [0], [0]
        # pad the scatter lists to a power of two by repeating the last real
        # entry (an identical duplicate write — harmless) so compiled splice
        # shapes stay bounded; same for the dense-row scatter.
        pad = next_pow2(len(phys))
        src_rows += [src_rows[-1]] * (pad - len(src_rows))
        page_ord += [page_ord[-1]] * (pad - len(page_ord))
        phys += [phys[-1]] * (pad - len(phys))
        dense_rows = list(range(k)) + [k - 1] * (rows - k)
        dense_slots = list(slots) + [slots[-1]] * (rows - k)
        prefilled = dict(prefilled)
        prefilled.pop("pos")
        self.blocks = self._splice_fn(
            self.blocks, prefilled,
            host_upload(src_rows, jnp.int32), host_upload(page_ord, jnp.int32),
            host_upload(phys, jnp.int32), host_upload(dense_rows, jnp.int32),
            host_upload(dense_slots, jnp.int32))
        if store_writes:
            self._scatter_pages(store_writes)

    def step_page_need(self, slot: int) -> int:
        """Physical pages decode() will take for this slot's next append:
        1 for a fresh page when the append crosses a page boundary, 1 for a
        copy-on-write when the append lands in a page other live mappings
        still read. The engine sums this over the step's write slots and
        frees capacity (on-demand mode) before the decode call."""
        ordinal = int(self.pos[slot]) // self.page_size
        if ordinal >= int(self._alloc[slot]):
            return 1
        p = int(self.table[slot, ordinal])
        if p in self._page_key and self._page_ref[p] > 1:
            return 1
        return 0

    def _prepare_write(self, slot: int) -> Tuple[int, int]:
        """Resolve the physical (page, offset) for this slot's append,
        mapping a fresh page at a page boundary and resolving writes into
        indexed pages: copy-on-write while other mappings remain, plain
        unregistration (parking the content for sealed references first)
        when the writer is the sole user."""
        ordinal = int(self.pos[slot]) // self.page_size
        if ordinal >= int(self._alloc[slot]):
            assert ordinal == int(self._alloc[slot])
            assert self.on_demand or ordinal < int(self._reserved[slot])
            self.table[slot, ordinal] = self._take_pages(1)[0]
            self._alloc[slot] = ordinal + 1
            self.pages_written += 1
        p = int(self.table[slot, ordinal])
        if p in self._page_key:
            if self._page_ref[p] > 1:
                new = self._take_pages(1)[0]
                self.blocks = self._copy_page_fn(
                    self.blocks, jnp.int32(p), jnp.int32(new))
                self._page_ref[p] -= 1
                self.table[slot, ordinal] = new
                self.cow_copies += 1
                self.pages_written += 1
                p = new
            else:
                # sole live user about to diverge: the page leaves the
                # index (its registered content is about to change)
                self._unregister(p)
        # backstop: an append must never land in ciphertext. Restore only
        # admits FULL pages as ciphertext-resident (the next append maps a
        # fresh page), so this fires only if that invariant ever breaks.
        if p in self._cipher_pages:
            self._materialize_page(p)
        return p, int(self.pos[slot]) % self.page_size

    def decode(self, params, tokens, state, kmax,
               write_slots: Sequence[int]) -> np.ndarray:
        write_phys = np.zeros(self.max_slots, np.int32)   # default: null page
        write_off = np.zeros(self.max_slots, np.int32)
        for s in write_slots:
            write_phys[s], write_off[s] = self._prepare_write(s)
        if self.decode_mode == "kernel":
            use_cipher = bool(self._cipher_pages)
            key_words = (self._crypt_key.key_words if use_cipher
                         else jnp.zeros(8, jnp.uint32))
            next_tokens, self.blocks = self._decode_fn(
                params, host_upload(tokens[:, None]), self.blocks,
                host_upload(self.table), host_upload(self.pos),
                host_upload(write_phys), host_upload(write_off),
                host_upload(self._crypt[self._k_path]),
                host_upload(self._crypt[self._v_path]),
                key_words, state, kmax, use_cipher)
        else:
            next_tokens, self.blocks = self._decode_fn(
                params, host_upload(tokens[:, None]), self.blocks,
                host_upload(self.table), host_upload(self.pos),
                host_upload(write_phys), host_upload(write_off), state, kmax)
        for s in write_slots:
            self.pos[s] += 1
        return np.asarray(next_tokens)

    def cache_nbytes(self) -> int:
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree.leaves(self.blocks))

    # -- ciphertext-resident pages (fused-unseal restore path) ----------------
    def _admit_cipher_page(self, key: SealingKey, phys: int,
                           blobs: Dict[str, SealedTensor]
                           ) -> Dict[str, np.ndarray]:
        """Admit a MAC-verified sealed page into the pool as raw ciphertext
        bits (the linear RFC 8439 stream bit-cast into the page's plaintext
        layout) and arm its crypt sidecar rows (nonce words + live flag) so
        the fused decode kernel decrypts it in VMEM on every read."""
        writes: Dict[str, np.ndarray] = {}
        for kpath, st in blobs.items():
            raw = ciphertext_page_bytes(st)
            writes[kpath] = np.frombuffer(
                raw, self._page_dtype[kpath]).reshape(self._page_shape[kpath])
            self._crypt[kpath][phys, :3] = nonce_words_for(key, st.name)
            self._crypt[kpath][phys, 3] = 1
        self._cipher_pages.add(int(phys))
        self.fused_restore_pages += 1
        self.fused_restore_bytes += sum(st.n_bytes for st in blobs.values())
        return writes

    def _clear_crypt(self, phys: int) -> None:
        if phys in self._cipher_pages:
            self._cipher_pages.discard(phys)
            for kpath in self._crypt:
                self._crypt[kpath][phys] = 0

    def _materialize_page(self, phys: int) -> None:
        """Host-decrypt a ciphertext-resident page in place (XOR with the
        reference keystream regenerated from the crypt sidecar) so host
        consumers — seal, park, copy, append — see plaintext. The decode
        kernel's per-page counter for layer l starts at l*blocks_per_page,
        which is exactly the linear stream from counter 0, so one
        contiguous keystream covers all L layers of the blob."""
        if phys not in self._cipher_pages:
            return
        pages = self._page_arrays([phys], materialize=False)
        writes: Dict[str, np.ndarray] = {}
        nb = 0
        for kpath, arr in pages.items():
            page = np.ascontiguousarray(arr[:, 0])
            nonce = self._crypt[kpath][phys, :3].tobytes()
            ks = chacha20_keystream_bytes_ref(
                self._crypt_key.key, nonce, page.nbytes)
            plain = np.bitwise_xor(
                page.reshape(-1).view(np.uint8),
                np.frombuffer(ks, np.uint8)).view(page.dtype)
            writes[kpath] = plain.reshape(page.shape)
            nb += page.nbytes
        self._clear_crypt(phys)
        self._scatter_pages({phys: writes})
        self._events.append(("materialize", nb, len(writes)))

    # -- sealing --------------------------------------------------------------
    def _page_arrays(self, phys: Sequence[int], *,
                     materialize: bool = True) -> Dict[str, np.ndarray]:
        """Fetch the given physical pages of every paged leaf:
        keystr -> [L, n, page_size, ...].

        By default any ciphertext-resident page among ``phys`` is
        materialized (host-decrypted in place) first, so every host
        consumer — seal, park, copy — sees plaintext bits.
        """
        if materialize and self._cipher_pages:
            for p in phys:
                self._materialize_page(int(p))
        idx = host_upload(list(phys), jnp.int32)
        out = {}

        def pull(path, leaf):
            if _keystr(path) in self._paged_paths:
                out[_keystr(path)] = np.asarray(leaf[:, idx])
            return leaf
        jax.tree_util.tree_map_with_path(pull, self.blocks)
        return out

    def _seal_pages(self, key: SealingKey, prefix: str, ordinals: Sequence[int],
                    phys: Sequence[int],
                    suffix: str = "") -> Dict[str, SealedTensor]:
        sealed: Dict[str, SealedTensor] = {}
        if not ordinals:
            return sealed
        pages = self._page_arrays(phys)
        for kpath, arr in pages.items():
            for j, ordinal in enumerate(ordinals):
                name = f"{prefix}{kpath}/p{ordinal}{suffix}"
                sealed[name] = seal_tensor(key, name, arr[:, j])
        return sealed

    def _split_ordinals(self, slot: int) -> Tuple[List[int], List[int]]:
        """(shared, private) page ordinals of a slot: shared pages are the
        content-indexed ones (sealed by reference), private ones move as
        per-page ciphertext."""
        shared, private = [], []
        for j in range(int(self._alloc[slot])):
            p = int(self.table[slot, j])
            (shared if p in self._page_key else private).append(j)
        return shared, private

    def seal(self, key, slot, prefix, suffix="",
             detach=False) -> Dict[str, SealedTensor]:
        self._seal_key_cache = key
        n_alloc = int(self._alloc[slot])
        if detach:
            # by-VALUE seal for cross-pool migration: shared pages ship as
            # ordinary per-page ciphertext so the blob is self-contained —
            # a destination pool has neither this pool's content index nor
            # its parked blobs to resolve a by-reference entry against. The
            # copies restore as private pages; sharing re-forms (if at all)
            # through the destination's own content index. Source-side
            # residents and refcounts are untouched: co-sharers keep their
            # mappings, and no _sealed_refs entry is minted (there is
            # nothing for discard_sealed to release).
            shared, private = [], list(range(n_alloc))
        else:
            shared, private = self._split_ordinals(slot)
        # meta v2: [pos, n_alloc, n_shared, (ordinal, refcount) per shared
        # page]; the content keys ride in their own sealed blob. The
        # refcount is recorded at seal time (audit/diagnostic — the live
        # count changes legitimately while this request is out).
        meta = [int(self.pos[slot]), n_alloc, len(shared)]
        keys_cat = b""
        for j in shared:
            k = self._page_key[int(self.table[slot, j])]
            meta += [j, int(self._page_ref[int(self.table[slot, j])])]
            keys_cat += k
            self._sealed_refs[k] = self._sealed_refs.get(k, 0) + 1
        meta_name = f"{prefix}/meta{suffix}"
        sealed = {meta_name: seal_tensor(key, meta_name,
                                         np.asarray(meta, np.int32))}
        if shared:
            keys_name = f"{prefix}/sharedkeys{suffix}"
            sealed[keys_name] = seal_tensor(
                key, keys_name, np.frombuffer(keys_cat, np.uint8))
        sealed.update(self._seal_pages(
            key, prefix, private,
            [int(self.table[slot, j]) for j in private], suffix))

        def pull_dense(path, leaf):
            if _keystr(path) not in self._paged_paths:
                name = f"{prefix}{_keystr(path)}{suffix}"
                sealed[name] = seal_tensor(key, name,
                                           np.asarray(leaf[:, slot:slot + 1]))
            return leaf
        jax.tree_util.tree_map_with_path(pull_dense, self.blocks)
        return sealed

    def restore(self, key, sealed, slot, prefix, n_tokens, suffix="") -> None:
        # the reservation was re-made when the engine re-acquired the slot
        # (reserve mode); decrypt-then-commit: every MAC is verified before
        # any accounting state moves, so a tampered blob fails the restore
        # without leaking the slot, pages, or a refcount.
        self._seal_key_cache = key
        meta = np.asarray(unseal_tensor(key, sealed[f"{prefix}/meta{suffix}"]))
        pos, n_alloc, n_shared = int(meta[0]), int(meta[1]), int(meta[2])
        shared_ords = [int(meta[3 + 2 * i]) for i in range(n_shared)]
        keys: List[bytes] = []
        if n_shared:
            cat = bytes(np.asarray(unseal_tensor(
                key, sealed[f"{prefix}/sharedkeys{suffix}"])))
            keys = [cat[16 * i:16 * (i + 1)] for i in range(n_shared)]
        shared_set = set(shared_ords)
        private_ords = [j for j in range(n_alloc) if j not in shared_set]
        # fused-unseal eligibility (decode='kernel' on a fused-capable
        # pool): FULL private pages are MAC-gated here but admitted as
        # ciphertext — the decode kernel regenerates the keystream per page
        # and XORs in VMEM, so their plaintext never round-trips HBM. The
        # partial tail page stays on the host path (the next append writes
        # into it and appends must land in plaintext).
        fused_set = (
            {j for j in private_ords if (j + 1) * self.page_size <= pos}
            if self.decode_mode == "kernel" and self.supports_fused
            else set())
        if fused_set:
            if (self._crypt_key is not None and self._cipher_pages
                    and self._crypt_key.key != key.key):
                # one keystream key rides the decode step: flush residents
                # sealed under the previous key before switching
                for p in list(self._cipher_pages):
                    self._materialize_page(p)
            self._crypt_key = key
        # phase 1: MAC-verify everything this restore will need — fused
        # pages without decrypting (verify_mac), host-path pages by
        # decrypting; resident re-links get no blob to verify (the live
        # pool IS the authority), parked pages are verified below.
        fused_blobs: Dict[int, Dict[str, SealedTensor]] = {}
        private_pages: Dict[int, Dict[str, np.ndarray]] = {}
        for j in private_ords:
            blobs = {kpath: sealed[f"{prefix}{kpath}/p{j}{suffix}"]
                     for kpath in self._paged_paths}
            if j in fused_set:
                for st in blobs.values():
                    verify_mac(key, st)
                fused_blobs[j] = blobs
            else:
                private_pages[j] = {
                    kpath: np.asarray(unseal_tensor(key, st))
                    for kpath, st in blobs.items()}
        plans: List[Tuple[str, int, bytes, Any]] = []
        for j, k in zip(shared_ords, keys):
            if k in self._index:
                plans.append(("relink", j, k, None))
            elif k in self._parked:
                blobs = {kpath: np.asarray(unseal_tensor(key, st))
                         for kpath, st in self._parked[k].items()}
                plans.append(("remat", j, k, blobs))
            elif (self.page_store is not None
                  and self.page_store.contains(key, k)):
                # third tier: the persistent store outlived the parked blob
                # (e.g. a deadline abort discarded the last sealed ref).
                # MAC-gate here, in phase 1, like everything else.
                stored = self.page_store.lookup(key, k)
                blobs = {kpath: np.asarray(unseal_tensor(key, st))
                         for kpath, st in stored.items()}
                nb = sum(st.n_bytes for st in stored.values())
                plans.append(("storehit", j, k, (blobs, nb)))
            else:
                raise IntegrityError(
                    f"shared page (ordinal {j}) is neither resident, "
                    f"parked, nor store-resident — sealed state references "
                    f"lost content")
        dense_rows = {}

        def pull_names(path, leaf):
            kpath = _keystr(path)
            if kpath not in self._paged_paths:
                dense_rows[kpath] = np.asarray(unseal_tensor(
                    key, sealed[f"{prefix}{kpath}{suffix}"]))
            return leaf
        jax.tree_util.tree_map_with_path(pull_names, self.blocks)

        # phase 2: commit — map, write, and account.
        assert self.on_demand or n_alloc <= int(self._reserved[slot]), \
            "restore into a smaller reservation — accounting bug"
        n_fresh = len(private_ords) + sum(1 for p in plans
                                          if p[0] in ("remat", "storehit"))
        taken = self._take_pages(n_fresh)
        it = iter(taken)
        writes: Dict[int, Dict[str, np.ndarray]] = {}
        for j in private_ords:
            p = next(it)
            self.table[slot, j] = p
            if j in fused_set:
                writes[p] = self._admit_cipher_page(key, p, fused_blobs[j])
            else:
                writes[p] = private_pages[j]
        # NOTE: sealed references are NOT consumed here — a whole-slot
        # restore may still fail after this commit (the engine grafts
        # sealed-while-paused tail blobs afterwards), and an under-counted
        # _sealed_refs would let parked ciphertext an innocent co-sharer
        # still needs be deleted. The engine releases the references via
        # discard_sealed only once the entire restore has succeeded; a
        # rolled-back restore leaves refs (and parked blobs) untouched.
        for kind, j, k, blobs in plans:
            if kind == "relink":
                p = self._index[k]
                self._page_ref[p] += 1
                self.table[slot, j] = p
                self.shared_page_maps += 1
            elif kind == "storehit":
                plain, nb = blobs
                p = next(it)
                self.table[slot, j] = p
                self._index[k] = p
                self._page_key[p] = k
                writes[p] = plain
                self.store_hits += 1
                self.store_restored_pages += 1
                self.store_restored_bytes += nb
                self._events.append(("store_hit", nb, len(plain)))
            else:
                p = next(it)
                self.table[slot, j] = p
                self._index[k] = p
                self._page_key[p] = k
                writes[p] = blobs
                nb = sum(st.n_bytes for st in self._parked[k].values())
                self._events.append(("rematerialize", nb,
                                     len(self._parked[k])))
        self._alloc[slot] = n_alloc
        self.pos[slot] = pos
        # store-restored pages stay out of pages_written (same counter
        # contract as insert_prefill: pages_written is prefill/seal-path
        # write cost, store_restored_pages counts the restores)
        self.pages_written += len(writes) - sum(1 for p in plans
                                                if p[0] == "storehit")
        self._scatter_pages(writes)
        self._put_dense_rows(slot, dense_rows)

    def _scatter_pages(self, writes: Dict[int, Dict[str, np.ndarray]]) -> None:
        """Write host page arrays into the pool: one padded donated scatter
        per leaf (see the next_pow2 note on bounded compiled variants)."""
        if not writes:
            return
        phys = list(writes)
        pad = next_pow2(len(phys))
        idx = host_upload(phys + [phys[-1]] * (pad - len(phys)), jnp.int32)

        def put(path, leaf):
            kpath = _keystr(path)
            if kpath not in self._paged_paths:
                return leaf
            pages = np.stack([writes[p][kpath] for p in phys]
                             + [writes[phys[-1]][kpath]] * (pad - len(phys)),
                             axis=1)
            return _set_pages(leaf, idx, host_upload(pages))
        self.blocks = jax.tree_util.tree_map_with_path(put, self.blocks)

    def _put_dense_rows(self, slot: int,
                        rows: Dict[str, np.ndarray]) -> None:
        """Write every dense (recurrent-state) leaf's restored row in ONE
        tree traversal (one jitted row-scatter per dense leaf)."""
        if not rows:
            return

        def put(path, leaf):
            row = rows.get(_keystr(path))
            if row is None:
                return leaf
            return _set_row(leaf, jnp.int32(slot), host_upload(row))
        self.blocks = jax.tree_util.tree_map_with_path(put, self.blocks)

    def discard_sealed(self, key: SealingKey, sealed: Dict[str, SealedTensor],
                       prefix: str, suffix: str = "") -> None:
        """Release a sealed dict's shared-content references — called when
        the dict is spent: after a fully-successful restore, or when a
        sealed-out request is dropped unrestored (deadline abort). Parked
        ciphertext dies with its last reference instead of outliving every
        reader."""
        name = f"{prefix}/sharedkeys{suffix}"
        if name not in sealed:
            return
        cat = bytes(np.asarray(unseal_tensor(key, sealed[name])))
        for i in range(len(cat) // 16):
            k = cat[16 * i:16 * (i + 1)]
            if k in self._sealed_refs:
                self._sealed_refs[k] -= 1
                if self._sealed_refs[k] <= 0:
                    del self._sealed_refs[k]
                    blobs = self._parked.pop(k, None)
                    # store retention: a deadline abort dropping the last
                    # sealed reference must not take the content with it
                    # when a store tier exists — admission may already have
                    # discounted a waiting request against this key. The
                    # dying parked blob IS the store's canonical ciphertext
                    # (same name, same key), so hand it over — a membership
                    # no-op when the release path already published it.
                    if (blobs is not None and self.page_store is not None
                            and k not in self._partial_keys):
                        skey = self._content_key()
                        if skey is not None:
                            self._publish_store(skey, k, blobs)

    # -- partial eviction -----------------------------------------------------
    def evictable_tail_pages(self, slot: int) -> int:
        """How many tail pages ``seal_tail_pages`` may take: trailing
        *private* pages only (a shared page cannot be torn out of other
        readers' tables), and the victim always keeps one resident page."""
        n_alloc = int(self._alloc[slot])
        trailing = 0
        for j in range(n_alloc - 1, -1, -1):
            if int(self.table[slot, j]) in self._page_key:
                break
            trailing += 1
        return max(0, min(trailing, n_alloc - 1))

    def seal_tail_pages(self, key: SealingKey, slot: int, prefix: str,
                        n_pages: int,
                        suffix: str = "") -> Dict[str, SealedTensor]:
        """Seal and free the ``n_pages`` most recent pages of ``slot`` —
        a capacity loan: the pages AND their reservation go back to the
        pool for other traffic, while the victim keeps its slot, sampling
        row, and resident head pages. The victim must not decode until
        :meth:`restore_tail_pages` brings the delta back (the engine parks
        it out of the batch)."""
        self._seal_key_cache = key
        n_alloc = int(self._alloc[slot])
        if not (0 < n_pages < n_alloc):
            raise ValueError(
                f"partial eviction wants 0 < n_pages < allocated "
                f"({n_alloc}), got {n_pages}")
        if n_pages > self.evictable_tail_pages(slot):
            raise ValueError(
                f"partial eviction of {n_pages} pages would cross into the "
                f"shared prefix (only {self.evictable_tail_pages(slot)} "
                f"trailing private pages)")
        ordinals = list(range(n_alloc - n_pages, n_alloc))
        phys = [int(p) for p in self.table[slot, ordinals]]
        meta_name = f"{prefix}/pagemeta{suffix}"
        sealed = {meta_name: seal_tensor(
            key, meta_name, np.asarray([ordinals[0], n_pages], np.int32))}
        sealed.update(self._seal_pages(key, prefix, ordinals, phys, suffix))
        self.table[slot, ordinals] = 0
        self._alloc[slot] = n_alloc - n_pages
        for p in phys:
            self._drop_ref(p)
        if not self.on_demand:
            self._reserved[slot] -= n_pages
            self._reserve_free += n_pages
        return sealed

    def can_restore_tail(self, n_pages: int) -> bool:
        if not self.on_demand:
            return n_pages <= self._reserve_free
        # same thrash damping as can_restore: demand headroom while other
        # slots are live (the resume competes with their next appends).
        headroom = 1 if len(self.slots.active) > 1 else 0
        return n_pages + headroom <= len(self._free_pages)

    def restore_tail_pages(self, key: SealingKey,
                           sealed: Dict[str, SealedTensor], slot: int,
                           prefix: str, reserve: bool = True,
                           suffix: str = "") -> int:
        """Re-map and decrypt a partial eviction's pages; returns the page
        count. Physical placement is fresh — the table indirection makes
        relocation free. ``reserve=False`` skips re-reserving: used when the
        tail rides along a whole-slot restore whose ``acquire`` already
        reserved the sequence's full worst case."""
        self._seal_key_cache = key
        meta = np.asarray(unseal_tensor(
            key, sealed[f"{prefix}/pagemeta{suffix}"]))
        start, n_pages = int(meta[0]), int(meta[1])
        if reserve and not self.on_demand:
            assert self.can_restore_tail(n_pages), \
                "restore_tail without can_restore_tail — accounting bug"
            self._reserved[slot] += n_pages
            self._reserve_free -= n_pages
        ordinals = list(range(start, start + n_pages))
        # decrypt first (MAC gate), then map and write
        pages = {
            j: {kpath: np.asarray(unseal_tensor(
                    key, sealed[f"{prefix}{kpath}/p{j}{suffix}"]))
                for kpath in self._paged_paths}
            for j in ordinals}
        taken = self._take_pages(n_pages)
        writes = {}
        for j, p in zip(ordinals, taken):
            self.table[slot, j] = p
            writes[p] = pages[j]
        self._alloc[slot] = start + n_pages
        self.pages_written += n_pages
        self._scatter_pages(writes)
        return n_pages
