"""Pluggable KV-cache backends for continuous batching.

The engine sees one :class:`KVBackend` interface; the KV *layout* behind it
is a deployment choice (``Engine(kv_backend="slot"|"paged")``). Picking one:

**Slot-dense** (:class:`SlotDenseBackend`, the default) keeps a fixed
``[L, max_slots, max_len, ...]`` buffer, one slot per in-flight sequence —
the JetStream-style TPU-native layout: contiguous reads for the MXU/VPU,
static shapes for XLA, zero indirection on the decode hot path. It wins when
sequences actually use most of ``max_len`` (short-context chat at high
occupancy), when ``max_len`` is small enough that whole-slot sealing is
cheap, and when decode-step latency matters more than memory efficiency.

**Paged** (:class:`~repro.runtime.paged.PagedKVBackend`) keeps a static
``[num_pages, page_size, ...]`` pool plus an ``[slots, max_pages]`` int32
page table; decode gathers each slot's pages into the dense view the model
expects (``jnp.take`` over the table — still static shapes, TPU-safe) and
scatters back only the one appended position. Everything becomes
proportional to *tokens used, not capacity reserved*:

  * admission charges ``ceil(need / page_size)`` pages instead of an
    implicit whole ``max_len`` slot — long-context mixes where most
    requests are short admit far more concurrency from the same HBM;
  * sealed preemption seals per-page ciphertext (per-page nonces), so
    evicting a sequence that holds 3 pages moves 3 pages across the trust
    boundary, not ``max_len`` worth (the paper's Insight-10 boundary-cost
    model: crossings are fixed-cost dominated, so move less);
  * partial eviction can free just the tail pages of a victim and restore
    only that delta later.

It costs one gather per decode step and page-table bookkeeping. Prefer it
for long-context workloads (``max_len`` ≥ 1k), memory-constrained pools,
or whenever preemption/sealing traffic shows up in ``ChannelStats``.

``page_size`` guidance: small pages (8–16) track token usage tightly
(least waste, most seal granularity) but grow the page table and per-page
seal count; large pages (64–128) amortize per-page fixed costs toward
slot-dense behavior. 16–32 is a good default at ``max_len`` ≤ 4k; scale
page_size with context length so ``max_pages`` stays in the hundreds.

**Prefix sharing and on-demand allocation**
(``Engine(kv_backend="paged", prefix_sharing=True)``; paged only). A
content index maps the cumulative hash of the token ids up to each aligned
page boundary to one shared physical page with a per-page refcount;
requests whose (padded) prompts agree on a page-aligned prefix map the
same physical pages instead of storing copies. When sharing pays: any
workload where many requests open identically — RAG system prompts,
few-shot headers, agent scaffolds — provided the shared region is *page
aligned and position aligned* (prefill left-pads prompts into their
bucket, so equal-length prompts with a common head share; KV entries are
position-dependent, so a prefix at a different offset is different
content). Capacity multiplies: N requests over one B-page context cost
B + N·(suffix pages), not N·B — which in a TEE is the difference between
fitting in the attested enclave memory or paying sealed-eviction traffic.

COW cost model: shared *full* pages are never written again and cost
nothing; the final partial prompt page is written by the first decode
append, which triggers one page copy (copy-on-write) per sharer that
diverges while others still read the page — worst case ``ceil(one page)``
extra write per request, amortized against ``shared pages × page_size``
tokens of prefill KV never recomputed or stored. Sharing a page whose
writer was its sole reader degrades to an index unregistration (free).

Sealing semantics under sharing: a victim's *private* pages seal per-page
under its epoch prefix as usual; its *shared* pages seal **by reference**
— the sealed meta records each page's content key (and refcount), and
restore re-links the resident page (no ciphertext moved either way). The
page's data crosses the boundary only when its **last** live reference
drops while sealed references remain: it is then *parked* once under a
content-derived name (same content => same nonce AND same plaintext, so
re-parking identical content can never pair one nonce with two
plaintexts), and the first restore that needs it re-materializes it into
the pool. Net: sealed bytes per preemption shrink by the shared fraction,
and K victims sharing a prefix pay for its eviction at most once.

On-demand allocation (``alloc="ondemand"``, implied by sharing — COW
grants cannot be covered by any admission-time worst case): admission
checks only the prompt's immediate page need (minus resident shared
pages) against the free pool, and decode appends are granted at step
time. The pool may be oversubscribed against worst cases; when it runs
dry the engine frees capacity by evict-by-slack *capacity preemption*
(partial ``seal_tail_pages`` of the laxest victim's private tail, else a
whole-slot seal). ``alloc="reserve"`` (the default without sharing) keeps
the PR-3 worst-case reservations, under which appends can never fail.

**Persistent sealed-page store** (``Engine(kv_backend="paged",
prefix_sharing=True, page_store=True)``; see
:mod:`repro.runtime.pagestore`). Plain prefix sharing only helps while
some live mapping or sealed reference keeps a page alive: when the last
reference drops, the parked ciphertext dies with it and the next
recurring prompt re-prefills content the domain already produced and
named. The store is the tier *behind* the content index that retains
content-named ciphertext past the last reference: ``insert_prefill``
misses consult it and restore MAC-verified pages into the pool (mapped
and refcounted like any shared page), aligned full pages publish to it on
seal/park/release, and admission discounts store-resident prefixes the
same way it discounts live ones. When the store beats plain sharing:
recurring-but-not-overlapping traffic — cold-start RAG contexts, system
prompts across bursty sessions, tenant scaffolds with idle gaps — where
requests arrive after their predecessors fully drained, so the live index
is empty however hot the content. Plain sharing already covers the
simultaneous case for free; the store adds host memory (budgeted in
pages: ``store_budget_pages``, LRU or restore-vs-recompute ``cost``
retention) and one MAC-verified unseal per hit, worth paying exactly when
the ``overheads.predict``-priced sealed bytes across the boundary
undercut the prefill compute a hit avoids
(:func:`repro.core.overheads.store_restore_savings` — serve.py and
serve_bench.py print the breakeven line). Entries are namespaced per
sealing-key domain: a fleet tenant's entries fail MAC under any other
domain and are never even offered cross-tenant (the lookup is a clean
miss by key-id namespace).

**Gather vs kernel decode** (``Engine(kv_backend="paged",
kv_decode="gather"|"kernel")``; paged only). The default ``gather`` path
rematerializes each slot's full dense KV view per decode step (``jnp.take``
over the page table) and runs the model's stock ``decode_step`` — simple,
mesh-capable, and the differential reference. ``kernel`` replaces the
gather+SDPA with :mod:`repro.kernels.paged_attention`: a Pallas kernel that
walks the page table directly, streaming only each slot's *valid* pages
from the pool into VMEM with online softmax — per-step HBM traffic drops
from O(max_pages·page_size) rematerialized to O(context) streamed, so the
advantage grows with context length (the gather's rematerialization
dominates from roughly 512 tokens of context upward; at short contexts the
two are within noise). The kernel path additionally unlocks the
*fused-unseal restore*: sealed full pages restore as ciphertext bits and
decrypt in-VMEM against per-page nonces during attention
(``paged_attention_unseal``), so restored KV plaintext never round-trips
HBM. Kernel outputs are numerically close to gather (f32 online softmax),
not bitwise; decoded tokens agree at the bench operating points and the
differential harness pins a tight tolerance. Constraints: dense attention
family only (uniform attn+swiglu blocks), single-device plans (use
``gather`` on meshes).

**Sharded** (:class:`ShardedKVBackend`, implied by ``Engine(mesh=...)``)
is not a third layout — it wraps either of the above when the engine spans
a mesh (:class:`~repro.runtime.plan.ShardedPlan`). When to *shard* the
cache vs replicate it: the slot-dense cache shards cleanly (each data-shard
owns ``max_slots / dp`` whole sequences per
:func:`repro.distributed.sharding.cache_specs` — shard it whenever the
data-axis size divides ``max_slots`` (otherwise the batch dim falls back to
replication and every seal is tagged ``/s0``), which also keeps decode
outputs byte-identical to one device); the paged pool is *shared* by every
sequence, so it replicates for now (its dense recurrent-state leaves still
shard by batch) — prefer slot-dense for mesh serving until per-shard page
pools land (ROADMAP). Sealing under a mesh is per *addressable shard*:
every sealed name gains a ``/s{shard}`` suffix recording which data-shard
the ciphertext left, so concurrent hosts sealing under one prefix occupy
disjoint nonce namespaces and a preemption round-trips byte-identically
(restore reads the shard tag back out of the sealed names — the slot it
lands in may live on a different shard).

Cache pytrees follow the model layout contract: top-level key "pos" is
batch-major [b]; every other leaf is layer-stacked with batch at axis 1
([L, b, ...]). ``insert_slot``/``insert_rows``/``extract_slot`` are the
dense splice primitives both backends build on.
"""

from __future__ import annotations

import dataclasses
import re
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.sealing import (SealedTensor, SealingKey, seal_tree,
                                unseal_tree)
from repro.runtime import sampling
from repro.runtime.plan import ComputePlan, SingleDevicePlan

Cache = Any
Params = Any


@dataclasses.dataclass
class SlotState:
    """Slot bookkeeping + the ``[slots]``-shaped per-sequence sampling rows
    the jitted decode step consumes (each sequence samples with its own
    temperature/top-k/top-p/PRNG key). Owned by the KV backend — a backend
    maps sequences to whatever physical layout it likes, but every live
    sequence holds exactly one row here. The arrays are host-side numpy
    mirrors; the engine snapshots them into a ``sampling.SamplingState`` per
    step. A released row resets to greedy (temp 0, top_p 1) so stale
    settings can never leak into the next occupant."""
    free: List[int]
    active: dict  # slot -> request id
    temp: np.ndarray    # [slots] f32; <= 0 → greedy
    top_k: np.ndarray   # [slots] i32; 0 → unrestricted
    top_p: np.ndarray   # [slots] f32; >= 1 → unrestricted
    key: np.ndarray     # [slots, 2] u32 per-request base PRNG keys
    rep_pen: np.ndarray   # [slots] f32; 1.0 → no repetition penalty
    presence: np.ndarray  # [slots] f32; 0.0 → no presence penalty
    # [slots, vocab] i32 counts of *generated* tokens, tracked ONLY for
    # slots whose request actually penalizes (greedy/unpenalized rows stay
    # zero, so their churn never invalidates anything). Rebuilt from
    # Request.output after a sealed restore, so seeded requests re-sample
    # identically. Allocated lazily on the first penalized ``set_sampling``
    # (engines that never see a penalty never pay max_slots x vocab ints).
    # ``hist_version`` bumps on the BULK mutations (row rebuild/clear) so
    # the engine's device mirror knows when an incremental update stream
    # was broken and a re-upload is due — per-token ``note_token`` counts
    # are mirrored incrementally instead of re-shipping the whole matrix
    # every decode step.
    vocab: int = 0
    hist: Optional[np.ndarray] = None
    hist_version: int = 0
    # [slots, vocab] f32 additive logit-bias rows, lazily allocated like
    # ``hist`` on the first ``set_sampling(..., logit_bias=...)``. Bias is
    # static per request (no per-token stream), so the device mirror is
    # version-triggered only: ``bias_version`` bumps whenever any row
    # changes and the engine re-uploads the whole matrix then.
    bias: Optional[np.ndarray] = None
    bias_version: int = 0

    @classmethod
    def create(cls, max_slots: int, vocab: int = 0) -> "SlotState":
        return cls(free=list(range(max_slots)), active={},
                   temp=np.zeros(max_slots, np.float32),
                   top_k=np.zeros(max_slots, np.int32),
                   top_p=np.ones(max_slots, np.float32),
                   key=np.zeros((max_slots, 2), np.uint32),
                   rep_pen=np.ones(max_slots, np.float32),
                   presence=np.zeros(max_slots, np.float32),
                   vocab=vocab)

    def acquire(self, request_id: int) -> Optional[int]:
        if not self.free:
            return None
        slot = self.free.pop(0)
        self.active[slot] = request_id
        return slot

    def release(self, slot: int) -> None:
        rid = self.active.pop(slot, None)
        if rid is not None:
            self.free.append(slot)
            self.clear_sampling(slot)

    def set_sampling(self, slot: int, temp: float, top_k: int, top_p: float,
                     key: np.ndarray, rep_pen: float = 1.0,
                     presence: float = 0.0,
                     logit_bias: Optional[Dict[int, float]] = None) -> None:
        self.temp[slot] = temp
        self.top_k[slot] = top_k
        self.top_p[slot] = top_p
        self.key[slot] = key
        self.rep_pen[slot] = rep_pen
        self.presence[slot] = presence
        if self.penalized(slot) and self.hist is None and self.vocab > 0:
            self.hist = np.zeros((len(self.temp), self.vocab), np.int32)
        self._set_bias_row(slot, logit_bias)

    def _set_bias_row(self, slot: int,
                      logit_bias: Optional[Dict[int, float]]) -> None:
        """Densify a request's sparse bias map into its slot row. A request
        without a map keeps (or resets to) the zero row; the matrix itself
        only exists once some request has biased."""
        if logit_bias:
            if self.bias is None:
                self.bias = np.zeros((len(self.temp), self.vocab), np.float32)
            self.bias[slot] = 0.0
            for tok, val in logit_bias.items():
                self.bias[slot, int(tok)] = np.float32(val)
            self.bias_version += 1
        elif self.bias is not None and self.bias[slot].any():
            self.bias[slot] = 0.0
            self.bias_version += 1

    def penalized(self, slot: int) -> bool:
        """Does this slot's request use a non-neutral penalty? Only such
        slots have their token history tracked."""
        return bool(self.rep_pen[slot] != 1.0 or self.presence[slot] != 0.0)

    def clear_sampling(self, slot: int) -> None:
        self.temp[slot] = 0.0
        self.top_k[slot] = 0
        self.top_p[slot] = 1.0
        self.key[slot] = 0
        self.rep_pen[slot] = 1.0
        self.presence[slot] = 0.0
        if self.hist is not None and self.hist[slot].any():
            # bump only when the row actually changes: routine churn of
            # greedy/unpenalized slot-mates (whose rows are already zero)
            # must not force a full [slots, vocab] mirror re-upload.
            self.hist[slot] = 0
            self.hist_version += 1
        if self.bias is not None and self.bias[slot].any():
            self.bias[slot] = 0.0
            self.bias_version += 1

    def note_token(self, slot: int, token: int) -> bool:
        """Count one generated token into the penalty history — only for a
        penalized slot (others keep zero rows so their churn stays free).
        Incremental: does NOT bump hist_version — the caller mirrors the
        increment itself. Returns whether the token was counted."""
        if self.hist is None or not self.penalized(slot):
            return False
        self.hist[slot, int(token)] += 1
        return True

    def set_hist(self, slot: int, tokens: Sequence[int]) -> None:
        """Rebuild a slot's penalty history (sealed restore: the token list
        travels with the request, not with the cache). Unpenalized slots
        keep zero rows; no version bump when the row is unchanged (fresh
        admission into an already-clean row)."""
        if self.hist is None:
            return
        if not self.penalized(slot):
            if self.hist[slot].any():          # defensive: never stale
                self.hist[slot] = 0
                self.hist_version += 1
            return
        if not (len(tokens) or self.hist[slot].any()):
            return
        self.hist[slot] = 0
        for t in tokens:
            self.hist[slot, int(t)] += 1
        self.hist_version += 1

    @property
    def any_sampled(self) -> bool:
        return bool((self.temp > 0).any())

    @property
    def any_top_p(self) -> bool:
        return bool(((self.temp > 0) & (self.top_p < 1.0)).any())

    @property
    def any_rep_pen(self) -> bool:
        return bool(((self.temp > 0) & (self.rep_pen != 1.0)).any())

    @property
    def any_presence(self) -> bool:
        return bool(((self.temp > 0) & (self.presence != 0.0)).any())

    @property
    def any_bias(self) -> bool:
        return self.bias is not None and bool(self.bias.any())

    @property
    def max_top_k(self) -> int:
        return int(self.top_k.max()) if len(self.top_k) else 0

    @property
    def num_active(self) -> int:
        return len(self.active)


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (shape padding keeps compiled variants
    bounded by log2, not one per batch/scatter size)."""
    p = 1
    while p < n:
        p *= 2
    return p


def host_upload(x, dtype=None) -> jax.Array:
    """Host->device upload that always *copies* host-owned buffers.

    ``jnp.asarray`` on a numpy array may zero-copy it into the computation
    when its malloc'd address happens to satisfy the runtime's alignment
    bound. Whether that happens varies per allocation, and XLA:CPU kernels
    pick alignment-dependent code paths with different FMA grouping — so
    the same engine scenario can produce last-ulp logit differences from
    run to run, flipping near-tie sampled tokens (observed as bimodal
    outputs in the 8-device parity tests). Copying into a runtime-allocated
    buffer pins every upload to one alignment class, restoring the engine's
    byte-identical-replay contract. The arrays on these paths are small
    (slot tables, token columns, page indices), so the copy is noise next
    to the step itself; weights and KV pools never go through here.
    """
    if isinstance(x, jax.Array):
        return x if dtype is None else jnp.asarray(x, dtype)
    return jnp.array(np.ascontiguousarray(x), dtype)


def _is_pos(path) -> bool:
    return any(getattr(k, "key", None) == "pos" for k in path[:1])


@jax.jit
def insert_slot(batched: Cache, single: Cache, slot: jax.Array) -> Cache:
    """Write a b=1 cache into batch slot ``slot`` of the batched cache."""
    def upd(path, big, small):
        if _is_pos(path):
            return big.at[slot].set(small[0])
        # [L, 1, ...] into [L, B, ...] at axis 1
        start = (jnp.int32(0), slot.astype(jnp.int32)) + (jnp.int32(0),) * (big.ndim - 2)
        return jax.lax.dynamic_update_slice(big, small.astype(big.dtype), start)
    return jax.tree_util.tree_map_with_path(upd, batched, single)


@partial(jax.jit, donate_argnums=(0,))
def insert_rows(batched: Cache, src: Cache, slots: jax.Array) -> Cache:
    """Scatter the first k rows of a b>=k cache into batch slots ``slots``
    (int32 [k], distinct) in ONE donated call — a batched prefill group
    splices in with a single cache materialization instead of k full-cache
    copies through repeated ``insert_slot``."""
    k = slots.shape[0]
    def upd(path, big, small):
        if _is_pos(path):
            return big.at[slots].set(small[:k])
        # [L, k, ...] rows into [L, B, ...] at axis 1
        return big.at[:, slots].set(small[:, :k].astype(big.dtype))
    return jax.tree_util.tree_map_with_path(upd, batched, src)


@jax.jit
def extract_slot(batched: Cache, slot: jax.Array) -> Cache:
    """Inverse of insert_slot: pull slot ``slot`` out as a b=1 cache."""
    def get(path, big):
        if _is_pos(path):
            return jax.lax.dynamic_slice(big, (slot.astype(jnp.int32),), (1,))
        start = (jnp.int32(0), slot.astype(jnp.int32)) + (jnp.int32(0),) * (big.ndim - 2)
        sizes = (big.shape[0], 1) + big.shape[2:]
        return jax.lax.dynamic_slice(big, start, sizes)
    return jax.tree_util.tree_map_with_path(get, batched)


def cache_bytes(cache: Cache) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(cache))


# ---------------------------------------------------------------------------
# backend protocol
# ---------------------------------------------------------------------------

class KVBackend:
    """One live KV store behind the engine. A backend owns

      * the device cache (whatever physical layout),
      * the slot <-> sequence mapping and per-sequence sampling rows
        (:class:`SlotState`),
      * the jitted decode step over its layout, and
      * the seal/restore format a preemption moves across the boundary.

    The engine speaks tokens: every capacity question is asked in "KV
    positions this request may write" (``n_tokens``), and the backend maps
    that onto slots, pages, or whatever it accounts in.
    """

    name: str = "?"
    supports_partial = False   # page-granular (tail) eviction available?
    supports_sharing = False   # content-indexed prefix page sharing on?
    on_demand = False          # step-time page grants (vs admission reserve)

    def __init__(self, model, max_slots: int, max_len: int,
                 plan: Optional[ComputePlan] = None):
        self.model = model
        self.max_slots = max_slots
        self.max_len = max_len
        self.plan = plan or SingleDevicePlan(model)
        self.slots = SlotState.create(max_slots, model.cfg.vocab_size)

    # -- capacity -----------------------------------------------------------
    @property
    def request_capacity(self) -> int:
        """Most KV positions a single request may occupy."""
        return self.max_len

    def can_admit(self, n_tokens: int) -> bool:
        """Beyond a free slot, is there KV room for ``n_tokens`` positions?"""
        return True

    def can_restore(self, n_tokens: int,
                    n_pages: Optional[int] = None) -> bool:
        """Room to re-admit a sealed-out sequence of ``n_tokens`` positions
        (a free slot is checked separately via ``slots.free``). ``n_pages``
        is the page count the sequence actually held at seal time — the
        unit an on-demand paged pool gates on instead of the worst case."""
        return True

    def page_keys(self, tokens: np.ndarray,
                  written_len: int) -> Optional[List[bytes]]:
        """Content keys for a prompt's prefill pages, or None when the
        backend does no prefix sharing (the accounting hooks below accept
        None and fall back to unshared behavior)."""
        return None

    def resident_pages(self, page_keys: Optional[Sequence[Any]]) -> int:
        """How many of these content keys are resident in the sharing
        index right now (0 without sharing)."""
        return 0

    # persistent sealed-page store tier (paged + sharing backends only);
    # None means no store is attached and every store counter stays 0.
    page_store = None
    store_hits = 0
    store_restored_pages = 0
    store_restored_bytes = 0

    def store_resident_pages(self, page_keys: Optional[Sequence[Any]]
                             ) -> int:
        """How many of these content keys the persistent page store could
        serve beyond the live index (0 without a store)."""
        return 0

    @property
    def free_physical_pages(self) -> int:
        """Free pages an on-demand grant can draw on (page backends only;
        the engine consults this behind the ``on_demand`` flag)."""
        return 0

    def step_page_need(self, slot: int) -> int:
        """Pages the next decode step will take for this slot's append
        (fresh page / copy-on-write); the engine's step-time grant loop
        sums this over the batch in on-demand mode."""
        return 0

    def evictable_tail_pages(self, slot: int) -> int:
        """Tail pages a partial eviction may seal off this slot (page
        backends with ``supports_partial`` only)."""
        return 0

    def admission_check(self, need: int,
                        page_keys: Optional[Sequence[Any]] = None
                        ) -> Tuple[bool, int]:
        """(fits, effective_need): can ``need`` worst-case KV positions ever
        be served, and what does the request *effectively* demand once
        resident shared pages are discounted? Default: no sharing, the
        plain capacity bound."""
        return need <= self.request_capacity, need

    def prompt_budget(self, max_new_tokens: int,
                      buckets: Sequence[int]) -> int:
        """Longest prompt a submit will accept for ``max_new_tokens``,
        accounting for prefill-bucket padding: a short prompt still occupies
        its whole (left-padded) bucket in the cache. Prefix sharing does
        NOT raise this bound — every page of one sequence holds its own
        simultaneous table mapping whether shared or private — it lowers
        the *effective demand* admission charges (see
        :meth:`admission_check`)."""
        cand = self.request_capacity - max_new_tokens + 1  # last token: no KV
        if cand >= buckets[-1]:
            return cand
        fits = [b for b in buckets if b <= cand]
        return fits[-1] if fits else 0

    # -- sequence lifecycle ---------------------------------------------------
    def acquire(self, rid: int, n_tokens: int) -> Optional[int]:
        return self.slots.acquire(rid)

    def release(self, slot: int) -> None:
        self.slots.release(slot)

    # -- device compute -------------------------------------------------------
    def fresh_prefill_cache(self, rows: int) -> Cache:
        """A zeroed ``rows``-sequence dense cache for one prefill call (both
        backends prefill dense; the splice into backend storage differs)."""
        return self.model.init_cache(rows, self.max_len)

    def insert_prefill(self, prefilled: Cache, slots: List[int],
                       written_len: int,
                       page_keys: Optional[List[Any]] = None) -> None:
        """Splice a prefilled dense group into backend storage.
        ``page_keys`` (sharing backends) carries one entry per slot: the
        prompt's content keys, or None for a request that opted out."""
        raise NotImplementedError

    def drain_events(self) -> List[Tuple[str, int, int]]:
        """(kind, nbytes, n_tensors) boundary traffic generated outside an
        explicit seal/restore call — shared-page parking and
        re-materialization on the paged backend. The engine drains this
        into TrustDomain accounting; default backends generate none."""
        return []

    def discard_sealed(self, key: SealingKey, sealed: Dict[str, SealedTensor],
                       prefix: str, suffix: str = "") -> None:
        """A sealed dict is spent — restored in full, or dropped unrestored
        (deadline abort): release whatever references it holds (shared-page
        sealed refcounts on the sharing backend). Default: nothing."""

    def decode(self, params: Params, tokens: np.ndarray,
               state: Optional[sampling.SamplingState], kmax: int,
               write_slots: Sequence[int]) -> np.ndarray:
        """One batched decode+sample step over all ``max_slots`` rows.
        ``write_slots`` are the slots genuinely appending a KV position this
        step (active, not paused) — a backend may route other rows' writes
        to a scratch location. Returns the sampled token per row."""
        raise NotImplementedError

    def cache_nbytes(self) -> int:
        raise NotImplementedError

    # -- sealing --------------------------------------------------------------
    def seal(self, key: SealingKey, slot: int, prefix: str,
             suffix: str = "") -> Dict[str, SealedTensor]:
        """Encrypt slot ``slot``'s KV for eviction across the trust boundary.
        ``prefix`` must be unique per (stream, seal epoch) — it derives the
        nonces; ``suffix`` lands after the leaf path in every name (the
        sharded wrapper's per-shard ``/s{shard}`` tag). Does NOT release the
        slot."""
        raise NotImplementedError

    def restore(self, key: SealingKey, sealed: Dict[str, SealedTensor],
                slot: int, prefix: str, n_tokens: int,
                suffix: str = "") -> None:
        """Inverse of :meth:`seal` into freshly-acquired slot ``slot``."""
        raise NotImplementedError


class SlotDenseBackend(KVBackend):
    """The dense ``[L, max_slots, max_len, ...]`` layout (see module
    docstring for when it wins). Sealing moves the victim's whole
    ``max_len`` extent regardless of how many positions hold live tokens."""

    name = "slot"

    def __init__(self, model, max_slots: int, max_len: int,
                 plan: Optional[ComputePlan] = None):
        super().__init__(model, max_slots, max_len, plan)
        self.cache = self.plan.place_dense_cache(
            model.init_cache(max_slots, max_len))

        def _decode(params, tokens, cache, state, kmax):
            logits, cache = model.decode_step(params, tokens, cache)
            if state is None:     # all-greedy step: no sampling state at all
                return sampling.greedy(logits), cache
            return sampling.sample(logits, state, kmax=kmax), cache

        self._decode_fn = self.plan.compile_decode(
            _decode, donate_argnums=(2,), static_argnums=(4,))

    def insert_prefill(self, prefilled: Cache, slots: List[int],
                       written_len: int, page_keys=None) -> None:
        # one donated scatter for the whole group (not k full-cache copies)
        self.cache = insert_rows(self.cache, prefilled,
                                 host_upload(slots, jnp.int32))

    def decode(self, params, tokens, state, kmax, write_slots) -> np.ndarray:
        with obs.span("decode.prepare"):
            next_tokens, self.cache = self._decode_fn(
                params, host_upload(tokens[:, None]), self.cache, state, kmax)
        with obs.span("decode.wait"):
            return np.asarray(next_tokens)

    def cache_nbytes(self) -> int:
        return cache_bytes(self.cache)

    def seal(self, key, slot, prefix, suffix="") -> Dict[str, SealedTensor]:
        single = extract_slot(self.cache, jnp.int32(slot))
        return seal_tree(key, single, prefix=prefix, suffix=suffix)

    def restore(self, key, sealed, slot, prefix, n_tokens,
                suffix="") -> None:
        single_like = self.model.abstract_cache(1, self.max_len)
        single = unseal_tree(key, sealed, single_like, prefix=prefix,
                             suffix=suffix)
        self.cache = insert_slot(self.cache, single, jnp.int32(slot))


# sealed-name anatomy for the shard tag and partial-eviction meta blobs
_SUFFIX_RE = re.compile(r"/s(\d+)$")
_PAGEMETA_RE = re.compile(r"^(?P<prefix>.*)/pagemeta(?P<suffix>/s\d+)?$")


def tail_blob_names(sealed: Dict[str, SealedTensor]
                    ) -> List[Tuple[str, str]]:
    """(prefix, suffix) of every partial-eviction tail blob riding in a
    sealed dict (a paused victim that was whole-sealed carries its earlier
    tail under its own epoch prefix — and, under a mesh, shard suffix)."""
    out = []
    for name in sealed:
        m = _PAGEMETA_RE.match(name)
        if m:
            out.append((m.group("prefix"), m.group("suffix") or ""))
    return out


class ShardedKVBackend:
    """Mesh wrapper around either layout: compute/placement concerns already
    live in the backend's :class:`~repro.runtime.plan.ShardedPlan`; what the
    wrapper owns is keeping *sealing* correct per addressable shard. Every
    seal gains a ``/s{shard}`` name suffix recording which data-shard the
    slot's row was read from (concurrent hosts sealing under one prefix stay
    in disjoint nonce namespaces), and restore recovers the tag from the
    sealed names themselves — so a preemption round-trips byte-identically
    even when the sequence re-lands on a different shard. Everything else
    delegates to the wrapped backend."""

    def __init__(self, inner: KVBackend):
        self.inner = inner
        if not inner.plan.is_sharded:
            raise ValueError("ShardedKVBackend wants a backend built on a "
                             "ShardedPlan")

    def __getattr__(self, attr):
        return getattr(self.inner, attr)

    def _suffix_for(self, slot: int) -> str:
        shard = self.inner.plan.shard_of_slot(slot, self.inner.max_slots)
        return f"/s{shard}"

    @staticmethod
    def _detect_suffix(sealed: Dict[str, SealedTensor], prefix: str) -> str:
        for name in sealed:
            if name.startswith(prefix):
                m = _SUFFIX_RE.search(name)
                if m:
                    return m.group(0)
        return ""

    def seal(self, key, slot, prefix, suffix=None, detach=False):
        kw = {"detach": detach} if detach else {}
        return self.inner.seal(key, slot, prefix,
                               suffix=suffix or self._suffix_for(slot), **kw)

    def restore(self, key, sealed, slot, prefix, n_tokens, suffix=None):
        if suffix is None:
            suffix = self._detect_suffix(sealed, prefix)
        return self.inner.restore(key, sealed, slot, prefix, n_tokens,
                                  suffix=suffix)

    def seal_tail_pages(self, key, slot, prefix, n_pages, suffix=None):
        return self.inner.seal_tail_pages(
            key, slot, prefix, n_pages,
            suffix=suffix or self._suffix_for(slot))

    def restore_tail_pages(self, key, sealed, slot, prefix, reserve=True,
                           suffix=None):
        if suffix is None:
            suffix = self._detect_suffix(sealed, prefix)
        return self.inner.restore_tail_pages(key, sealed, slot, prefix,
                                             reserve=reserve, suffix=suffix)

    def discard_sealed(self, key, sealed, prefix, suffix=None):
        if suffix is None:
            suffix = self._detect_suffix(sealed, prefix)
        return self.inner.discard_sealed(key, sealed, prefix, suffix=suffix)


def make_backend(kind: str, model, *, max_slots: int, max_len: int,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 plan: Optional[ComputePlan] = None,
                 prefix_sharing: bool = False,
                 alloc: Optional[str] = None,
                 decode: str = "gather",
                 page_store: Any = None,
                 store_budget_pages: Optional[int] = None) -> KVBackend:
    """Factory behind ``Engine(kv_backend=...)``. With a sharded ``plan``
    the chosen layout is built on the mesh and wrapped for per-shard
    sealing. ``prefix_sharing``/``alloc``/``decode``/``page_store`` are
    paged-only (see the module docstring's prefix-sharing, store-tier, and
    decode-mode sections)."""
    if kind == "slot":
        if (prefix_sharing or alloc is not None or decode != "gather"
                or page_store or store_budget_pages is not None):
            raise ValueError("prefix_sharing / kv_alloc / kv_decode / "
                             "page_store need kv_backend='paged' (the dense "
                             "slot layout has no pages to share, grant, "
                             "table-walk, or store)")
        kv: KVBackend = SlotDenseBackend(model, max_slots, max_len, plan)
    elif kind == "paged":
        from repro.runtime.paged import PagedKVBackend
        kv = PagedKVBackend(model, max_slots, max_len,
                            page_size=page_size, num_pages=num_pages,
                            plan=plan, prefix_sharing=prefix_sharing,
                            alloc=alloc, decode=decode,
                            page_store=page_store,
                            store_budget_pages=store_budget_pages)
    else:
        raise ValueError(
            f"unknown kv backend {kind!r} (want 'slot' or 'paged')")
    if kv.plan.is_sharded:
        return ShardedKVBackend(kv)   # type: ignore[return-value]
    return kv
