"""Inference engine v6: prefill and decode are independently scheduled
phases, optionally on separate ComputePlans.

Dataflow per paper Fig 2's protected stack:
  prompt --(encrypted bounce buffer)--> bucketed batched prefill(slots)
  --> batched decode loop --> sampled tokens --(encrypted frames through the
  bounce buffer, 1..N tokens each per the request's FramePolicy)--> client.

**Two-phase serving.** Generation has two phases with opposite shapes —
prefill is one wide compute-bound call per request, decode is a thin
latency-bound step over every live row — and v6 schedules them
independently instead of letting a burst of long prompts stall every
in-flight decode (the dominant TTFT failure mode at load):

  * **Step-level continuous batching** (``Engine(continuous_batching=True)``,
    single plan): admission no longer fills a whole prefill bucket group
    before decode resumes. Each engine step has a token budget
    (``step_tokens``, default ``largest bucket + max_slots``) split between
    live decode rows (1 each) and prefill admissions (their bucket width);
    the slack/priority scheduler orders the prefill queue, and when the
    head's bucket doesn't fit the remaining budget a smaller queued request
    *backfills* the leftover (``Request.backfilled``) while the head keeps
    first claim on the next step's fresh budget. Chunked prompt tails
    interleave into decode steps exactly as before. Decoded bytes are
    unchanged — only admission timing moves.

  * **Disaggregated prefill** (``Engine(prefill_plan=...)``): prompts
    prefill on a dedicated :class:`~repro.runtime.plan.PrefillOnlyPlan`
    stream, dispatched asynchronously (jax's async dispatch overlaps it
    with the current decode step) and consumed one step later. The finished
    KV rows cross from the prefill plan to the decode plan through a
    **sealed handoff** — a ``seal_tree``/``unseal_tree`` pair under the
    request's ``kvhandoff/{stream}`` nonce namespace, accounted in
    ``TrustDomain``/``ChannelStats`` sealed bytes exactly like a preemption
    crossing. That prices the disaggregation boundary the way the paper's
    Insight 9-12 cost model prices every other data-movement boundary:
    per-request ``n_handoffs``/``handoff_bytes`` roll up into
    ``ServeStats.handoff_bytes``.

The serving API is the request-object model in :mod:`repro.runtime.api`
(per-request sampling — temperature/top-k/top-p, repetition/presence
penalties, logit-bias maps — coalesced egress frames, SLO admission).
Underneath sit three pluggable layers:

  * **ComputePlan** (:mod:`repro.runtime.plan`) — every device-facing
    concern (param placement, the jitted prefill/decode callables,
    host<->device transfer policy, collective accounting) goes through one
    seam. :class:`SingleDevicePlan` reproduces the v4 engine bit for bit;
    :class:`ShardedPlan` (``Engine(mesh="dp=8")``) spans a jax mesh: batch
    rows shard over the data axis, params place FSDP-style per
    ``distributed.sharding.param_specs`` (sharded at rest, all-gathered at
    use — real per-step interconnect traffic), the KV cache shards per
    ``cache_specs``, and outputs stay byte-identical to one device on
    dp-only meshes. The plan *measures* its collective path (HLO-parsed
    bytes/step + a shard_map all-gather probe on the real mesh) into
    ``ChannelStats.collective_bytes``/``collective_s`` — the measured input
    ``overheads.predict(collective_s=...)`` prices link_tax with, instead
    of the closed form the paper's §V-D4 Insight-12 estimate comes from.

  * **Pluggable KV layout** — the engine speaks
    :class:`~repro.runtime.kvcache.KVBackend`
    (``Engine(kv_backend="slot"|"paged")``): dense slots, or a page pool +
    table where admission charges ``ceil(need/page_size)`` pages and sealed
    preemption moves per-page ciphertext (bytes scale with tokens used;
    preemption can be *partial* — just a victim's tail pages). The paged
    layout additionally offers content-indexed **prefix sharing** with
    copy-on-write (``prefix_sharing=True``) and vLLM-style **on-demand**
    page grants with step-time capacity preemption
    (``kv_alloc="ondemand"``) — see the kvcache selection guide. Under a
    mesh the chosen layout is wrapped by
    :class:`~repro.runtime.kvcache.ShardedKVBackend`: seal/restore operate
    per addressable shard (``/s{shard}`` nonce suffixes), so preemption
    round-trips byte-identically however the cache is laid out.

  * **SLO enforcement** — the admission and sealed-restore queues order by
    *slack* (earliest absolute deadline, priority tiebreak) by default, so
    ``on_deadline="abort"`` — which terminates expired mid-flight requests
    and discards expired sealed ones — fires rarely rather than cheaply
    (``Engine(admission_order="priority")`` restores the v4 ordering).

All device compute is jitted once per shape; decode donates the cache.
``submit``/``generate``/``stream`` take a :class:`GenerationRequest`.
"""

from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, \
    Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.confidential import TrustDomain
from repro.core.sealing import (IntegrityError, seal_tree, sealed_nbytes,
                                unseal_tree)
from repro.models.model import Model
from repro.runtime import sampling
from repro.runtime.api import (FINISH_ABORTED, GenerationRequest,
                               RequestOutput)
from repro.runtime.kvcache import (KVBackend, SlotState, host_upload,
                                   make_backend, next_pow2, tail_blob_names)
from repro.runtime.plan import (ComputePlan, PrefillOnlyPlan, ShardedPlan,
                                SingleDevicePlan)
from repro.runtime.scheduler import Request, Scheduler, ServeStats

Params = Any


@dataclasses.dataclass
class PreemptedRequest:
    """A sealed-out request waiting for a slot: KV pages as ciphertext only.

    ``key``/``prefix`` override the engine defaults at restore time — set on
    cross-worker *migrants* (fleet drain/failure), whose blobs are sealed
    under a fleet-shared tenant key domain in a ``kvmigrate/{worker}/...``
    nonce namespace instead of this worker's own key and ``kvslot/`` space.
    ``None`` means the ordinary local-preemption defaults."""
    sealed: Dict[str, Any]
    req: Request
    key: Optional[Any] = None
    prefix: Optional[str] = None


@dataclasses.dataclass
class InflightPrefill:
    """A request prefilling on the dedicated prefill plan: the jitted call
    was dispatched at admission (jax's async dispatch overlaps it with this
    step's decode) but its KV rows have not yet crossed to the decode plan.
    The slot is already reserved; :meth:`Engine._handoff_ready` consumes it
    at the next step through the sealed plan-to-plan handoff."""
    req: Request
    slot: int
    bucket: int
    logits: jax.Array
    cache: Any


@dataclasses.dataclass
class PausedSlot:
    """A partially-evicted running slot (paged backend): its tail pages are
    ciphertext outside the pool, the head pages stay resident, and the slot
    sits out of the decode batch until the delta is restored."""
    sealed: Dict[str, Any]
    prefix: str
    n_pages: int


class _RateBucket:
    """Token bucket for one priority class: refills at ``rate`` tokens/s up
    to ``burst``; admission charges a request's whole ``max_new_tokens`` up
    front (the KV reservation it will hold). A request larger than the burst
    is admitted on a full bucket and overdraws it (level goes negative), so
    nothing starves while the long-run rate still holds."""

    def __init__(self, rate: float, burst: Optional[float] = None):
        if rate <= 0:
            raise ValueError(f"rate budget must be > 0 tokens/s, got {rate}")
        self.rate = float(rate)
        self.burst = float(burst) if burst is not None else max(self.rate, 1.0)
        self.level = self.burst
        self._t = time.monotonic()

    def _refill(self) -> None:
        now = time.monotonic()
        self.level = min(self.burst, self.level + (now - self._t) * self.rate)
        self._t = now

    def can(self, n: int) -> bool:
        self._refill()
        return self.level >= min(float(n), self.burst)

    def charge(self, n: int) -> None:
        self.level -= float(n)


class Engine:
    def __init__(self, model: Model, params: Params, *, max_slots: int = 4,
                 max_len: int = 512, trust_domain: Optional[TrustDomain] = None,
                 prefill_len: int = 64,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 batch_prefill: bool = True,
                 rate_budgets: Optional[Dict[int, float]] = None,
                 kv_backend: str = "slot", page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefix_sharing: bool = False,
                 kv_alloc: Optional[str] = None,
                 kv_decode: str = "gather",
                 page_store: Any = None,
                 store_budget_pages: Optional[int] = None,
                 mesh: Optional[str] = None,
                 plan: Optional[ComputePlan] = None,
                 admission_order: str = "slack",
                 continuous_batching: bool = False,
                 step_tokens: Optional[int] = None,
                 prefill_plan: Optional[Any] = None,
                 handoff_batch: int = 1,
                 reject_infeasible: bool = False,
                 step_time_hint_s: Optional[float] = None):
        """``prefill_buckets`` supersedes the v1 single static ``prefill_len``
        (kept as the default one-bucket config for compatibility). Buckets
        should be powers of two; each distinct (rows, bucket) prefill shape
        compiles once. ``batch_prefill=False`` restores v1's one-request-per-
        prefill-call behavior (the serve_bench baseline).

        ``rate_budgets`` maps priority -> tokens/s: admission charges each
        request's max_new_tokens against its class's token bucket and holds
        the class back (without starving others) once the budget is spent.
        Priorities absent from the map are unthrottled.

        ``kv_backend`` selects the KV layout: ``"slot"`` (dense, default) or
        ``"paged"`` (page pool + table; ``page_size``/``num_pages`` size it,
        ``num_pages=None`` matches the dense footprint). See the
        :mod:`repro.runtime.kvcache` docstring for when each wins.

        ``prefix_sharing`` (paged only) turns on content-indexed shared
        prompt pages with copy-on-write; ``kv_alloc`` picks the page
        allocation mode — ``"reserve"`` (worst-case admission reservations,
        the default) or ``"ondemand"`` (step-time grants with capacity
        preemption when the pool runs dry; implied by ``prefix_sharing``).
        Decoded outputs are byte-identical across all of these — only
        memory, sealing traffic, and scheduling change.

        ``page_store`` (paged + sharing only) attaches the persistent
        content-addressed sealed-page store — the prefix-cache tier that
        retains content-named page ciphertext after the last live/sealed
        reference drops (:mod:`repro.runtime.pagestore`). Pass ``True`` or
        a policy name (``"lru"``/``"cost"``), or a ready
        :class:`~repro.runtime.pagestore.SealedPageStore` instance (which
        may be shared between engines — entries are namespaced per sealing
        key, so sharing the object never shares ciphertext across trust
        domains). ``store_budget_pages`` bounds store residency; prefill
        misses restore MAC-verified store pages instead of recomputing,
        admission discounts store-resident prefixes via
        ``effective_kv_need``, and hits/evictions land in
        ``TrustDomain``/``ServeStats`` accounting.

        ``kv_decode`` (paged only) selects the decode attention path:
        ``"gather"`` (default) rematerializes the dense KV view per step;
        ``"kernel"`` runs the table-walking Pallas paged-attention kernel
        (streams valid pages only, decrypts fused-unseal restored pages
        in-VMEM). Kernel outputs are numerically close, not byte-identical
        — see the :mod:`repro.runtime.kvcache` docstring.

        ``mesh`` spans the engine across devices: ``"dp=4"`` shards the
        batch (and FSDP-places params) over 4 devices, ``"dp=4,tp=2"`` adds
        tensor parallelism over 2 more. Equivalently pass a ready
        :class:`~repro.runtime.plan.ComputePlan` as ``plan``. Default: one
        device, bit-identical to v4.

        ``admission_order``: ``"slack"`` (default) serves
        tightest-deadline-first with priority tiebreak; ``"priority"`` is
        the v4 priority-only order.

        ``continuous_batching`` replaces fill-a-bucket-then-decode admission
        with step-level interleaving: each step's token budget
        (``step_tokens``, default ``largest bucket + max_slots`` so a fresh
        step with a free slot can always admit the queue head) splits
        between live decode rows and prefill admissions, with queue-ordered
        backfill when the head's bucket doesn't fit the remainder.

        ``prefill_plan`` disaggregates: prompts prefill on their own plan
        (pass a ready :class:`~repro.runtime.plan.ComputePlan`, or
        ``"dedicated"`` for a fresh
        :class:`~repro.runtime.plan.PrefillOnlyPlan`) and the finished KV
        rows hand off to the decode plan through a sealed seal/restore pair
        priced in ``ChannelStats``. Mutually exclusive with
        ``continuous_batching`` — a dedicated prefill stream already
        decouples prefill from the decode step, so there is no shared
        per-step budget to split. Decoded outputs are byte-identical under
        every mode — admission timing and boundary accounting are all that
        move.

        ``handoff_batch`` (disaggregated engines only) amortizes the sealed
        prefill->decode handoff: up to N finished prefill rows cross the
        plan boundary per sealed crossing (Insight 10 — the fixed
        per-crossing cost divides by N). The default 1 keeps one crossing
        per row, byte- and accounting-identical to v6.

        ``reject_infeasible`` turns on admission-time deadline feasibility:
        a request whose ``deadline_s`` is provably unmeetable — serial
        decode steps it needs plus queued work ahead, priced at the
        *fastest* observed (or ``step_time_hint_s``-modeled, e.g.
        ``overheads.predict(...).t_tee_s``) step time — is refused at
        ingest with ``finish_reason="rejected"`` before its prompt crosses
        the boundary or holds a stream, instead of burning prefill compute
        only to be aborted mid-decode."""
        self.model = model
        if plan is not None and mesh is not None:
            raise ValueError("pass mesh= or plan=, not both")
        if plan is None:
            plan = (ShardedPlan.from_spec(model, mesh) if mesh is not None
                    else SingleDevicePlan(model))
        self.plan = plan
        self.params = self.plan.place_params(params)
        self.max_slots = max_slots
        self.max_len = max_len
        self.prefill_len = prefill_len
        if prefill_buckets is None:
            prefill_buckets = (prefill_len,)
        self.prefill_buckets = tuple(sorted({int(b) for b in prefill_buckets}))
        if not self.prefill_buckets or min(self.prefill_buckets) < 1:
            raise ValueError(f"bad prefill buckets {self.prefill_buckets}")
        if max(self.prefill_buckets) >= max_len:
            raise ValueError("largest prefill bucket must leave decode room "
                             f"({self.prefill_buckets} vs max_len={max_len})")
        self.batch_prefill = batch_prefill
        self.td = trust_domain or TrustDomain("none")
        self.scheduler = Scheduler(order=admission_order)
        self.kv: KVBackend = make_backend(kv_backend, model,
                                          max_slots=max_slots, max_len=max_len,
                                          page_size=page_size,
                                          num_pages=num_pages, plan=self.plan,
                                          prefix_sharing=prefix_sharing,
                                          alloc=kv_alloc, decode=kv_decode,
                                          page_store=page_store,
                                          store_budget_pages=store_budget_pages)
        if getattr(self.kv, "page_store", None) is not None:
            # fix the store's key domain to this engine's trust domain so
            # lookups/publishes run before any seal ever caches a key
            self.kv.bind_store_key(self.td.sealing_key)
        self._active_mask = np.zeros(max_slots, bool)
        self._last_token = np.zeros(max_slots, np.int32)
        self._due: List[Request] = []   # requests whose frame is due (_emit)
        self._preempted: List[PreemptedRequest] = []
        self._paused: Dict[int, PausedSlot] = {}
        self._buckets: Dict[int, _RateBucket] = {
            prio: _RateBucket(rate) for prio, rate in (rate_budgets or {}).items()}
        self._seed_rng = np.random.default_rng()
        self._prefill_fn = self.plan.compile_prefill()
        self._vocab = model.cfg.vocab_size
        # device mirror of slots.hist, maintained incrementally while some
        # slot penalizes (see _hist_device) — the [slots, vocab] matrix must
        # not be re-uploaded on every decode step. Per-token increments are
        # queued in _hist_pending and applied as ONE batched scatter per
        # step (a per-token .at[].add would copy the whole matrix per emit).
        self._hist_dev = None
        self._hist_dev_version = -1
        self._hist_pending: List[Tuple[int, int]] = []
        # device mirror of slots.bias — version-triggered only (bias rows
        # are static per request; there is no per-token increment stream)
        self._bias_dev = None
        self._bias_dev_version = -1
        # -- two-phase serving --------------------------------------------
        if continuous_batching and prefill_plan is not None:
            raise ValueError(
                "continuous_batching applies to single-plan engines; a "
                "dedicated prefill_plan already decouples prefill from the "
                "decode step")
        if step_tokens is not None and not continuous_batching:
            raise ValueError(
                "step_tokens only makes sense with continuous_batching=True")
        if isinstance(prefill_plan, str):
            if prefill_plan != "dedicated":
                raise ValueError(
                    f"prefill_plan must be a ComputePlan or 'dedicated', "
                    f"got {prefill_plan!r}")
            prefill_plan = PrefillOnlyPlan(model)
        self.prefill_plan = prefill_plan
        if prefill_plan is not None:
            self.prefill_params = prefill_plan.place_params(params)
            self._prefill_stream_fn = prefill_plan.compile_prefill()
        else:
            self.prefill_params = None
            self._prefill_stream_fn = None
        if continuous_batching:
            if step_tokens is None:
                step_tokens = self.prefill_buckets[-1] + max_slots
            if step_tokens < self.prefill_buckets[-1]:
                raise ValueError(
                    f"step_tokens={step_tokens} can never admit the largest "
                    f"prefill bucket ({self.prefill_buckets[-1]}) — the "
                    f"queue head would starve")
        self._continuous = continuous_batching or prefill_plan is not None
        self._step_tokens = step_tokens if continuous_batching else None
        self._budget_left: Optional[int] = None
        self._inflight: Dict[int, InflightPrefill] = {}
        self.backfills = 0   # out-of-order budget-backfill admissions
        self._handoff_batch = int(handoff_batch)
        if self._handoff_batch < 1:
            raise ValueError(f"handoff_batch must be >= 1, got {handoff_batch}")
        if self._handoff_batch > 1 and prefill_plan is None:
            raise ValueError(
                "handoff_batch only applies to disaggregated engines "
                "(prefill_plan=...) — there is no plan boundary to amortize")
        self.handoff_crossings = 0   # sealed plan-boundary crossings (each
                                     # carries up to handoff_batch rows)
        # -- admission-time deadline feasibility ---------------------------
        self._reject_infeasible = reject_infeasible
        self._step_time_hint_s = step_time_hint_s
        self._step_floor: Optional[float] = None  # fastest observed step
        # -- fleet migration -----------------------------------------------
        self._draining = False

    @property
    def slots(self) -> SlotState:
        """Per-sequence bookkeeping rows (owned by the KV backend)."""
        return self.kv.slots

    # -- request admission ----------------------------------------------------
    def submit(self, request: GenerationRequest) -> Request:
        """Admit one :class:`GenerationRequest`; returns the live
        :class:`Request` handle (``.finished``, ``.result()``)."""
        if not isinstance(request, GenerationRequest):
            raise TypeError(
                "submit takes a GenerationRequest (repro.runtime.api); the "
                "v2 kwargs form was removed in v4 — build a request object")
        if self._draining:
            raise RuntimeError(
                "engine is draining (drain()/export_sealed_state was "
                "called); route new work to another worker")
        gen = request
        gen.validate(self._vocab)
        # worst-case KV positions: the padded prefill bucket (or the full
        # prompt when chunked past it) plus one per decode *input* — the
        # final sampled token is emitted but never fed back, so it writes no
        # KV. Past the backend's capacity, writes would clamp onto the last
        # cache row and silently corrupt the sequence — reject up front,
        # BEFORE the prompt crosses the boundary (a rejected request must
        # not skew ChannelStats). On a prefix-sharing backend the capacity
        # check (and kv_need) is *effective*: pages whose content is already
        # resident in the index charge nothing against the pool, so a RAG
        # request whose context prefix is resident is not rejected for
        # memory it will never allocate.
        bucket = self._bucket_for(len(gen.prompt))
        need = max(bucket, len(gen.prompt)) + gen.max_new_tokens - 1
        keys = None
        if self.kv.supports_sharing and gen.share_prefix:
            keys = self.kv.page_keys(self._padded_bucket(gen.prompt, bucket),
                                     bucket)
        fits, eff_need = self.kv.admission_check(need, keys)
        if not fits:
            raise ValueError(
                f"request needs up to {need} KV positions "
                f"(prompt {len(gen.prompt)} + {gen.max_new_tokens} new) "
                f"but the {self.kv.name} backend serves at most "
                f"{self.kv.request_capacity} (max_len={self.max_len}); "
                f"shorten the prompt or raise max_len")
        # deadline feasibility, decided BEFORE the prompt crosses the
        # boundary or a stream is held: a rejected request must cost the
        # domain nothing (no ingress message, no egress stream, no slot).
        rejected = self._reject_if_infeasible(gen)
        if rejected is not None:
            return rejected
        gen.prompt = self.td.ingress(gen.prompt)
        req = self.scheduler.submit(gen)
        req.kv_need = eff_need
        req.page_keys = keys
        req.ingress_messages = 1 if self.td.confidential else 0
        # resolve the sampling seed NOW so the request is reproducible from
        # this point on (including across seal/restore preemption cycles).
        if not gen.params.is_greedy:
            req.seed = (gen.params.seed if gen.params.seed is not None
                        else int(self._seed_rng.integers(2 ** 31 - 1)))
        req.stream_id = self.td.open_stream()
        return req

    def prompt_budget(self, max_new_tokens: int) -> int:
        """Longest prompt submit() will accept for ``max_new_tokens``
        (backend-delegated: the slot-dense answer is bounded by ``max_len``
        and bucket padding, the paged one also by the page pool). Prefix
        sharing never raises this bound — a sequence's pages all hold
        simultaneous table mappings, shared or not; what sharing lowers is
        the *effective demand* a request charges at admission, which
        :meth:`effective_kv_need` reports."""
        return self.kv.prompt_budget(max_new_tokens, self.prefill_buckets)

    def effective_kv_need(self, prompt: np.ndarray,
                          max_new_tokens: int) -> Tuple[int, int]:
        """(worst_case, effective) KV positions this prompt would charge at
        admission right now: on a prefix-sharing engine the effective
        figure discounts pages of this prompt already resident in the
        content index — a resident RAG context stops counting against the
        pool, so such requests admit alongside traffic that would
        otherwise have reserved it away."""
        prompt = np.asarray(prompt, np.int32)
        bucket = self._bucket_for(len(prompt))
        need = max(bucket, len(prompt)) + max_new_tokens - 1
        keys = None
        if self.kv.supports_sharing:
            keys = self.kv.page_keys(self._padded_bucket(prompt, bucket),
                                     bucket)
        _, eff = self.kv.admission_check(need, keys)
        return need, eff

    def _bucket_for(self, prompt_len: int) -> int:
        """Smallest bucket that fits the prompt, else the largest bucket
        (the tail past it is chunked through decode steps)."""
        for b in self.prefill_buckets:
            if prompt_len <= b:
                return b
        return self.prefill_buckets[-1]

    @staticmethod
    def _padded_bucket(prompt: np.ndarray, bucket: int) -> np.ndarray:
        """The token content the prefill writes into the bucket region —
        left-padded exactly as _admit_batch lays it out (content keys must
        hash what the cache will actually hold)."""
        chunk = np.asarray(prompt[:bucket], np.int32)
        padded = np.zeros(bucket, np.int32)
        padded[bucket - len(chunk):] = chunk
        return padded

    def _admit_need(self, req: Request) -> int:
        """KV positions admission must cover *now*: the full effective worst
        case under reservation accounting; on demand, the prefill's own
        page demand (net of currently-resident shared pages) plus one page
        of append/CoW headroom — without it a fully-resident-prompt request
        admits into a dry pool only for its first decode append to evict it
        straight back out (admission churn, no forward progress)."""
        if not self.kv.on_demand:
            return req.kv_need
        bucket = self._bucket_for(len(req.prompt))
        resident = self.kv.resident_pages(req.page_keys)
        return max(0, bucket - resident * self.kv.page_size) \
            + self.kv.page_size

    # -- admission-time deadline feasibility -----------------------------------
    def _step_time_lower(self) -> Optional[float]:
        """A defensible lower bound on one engine step's wall time: the
        fastest step observed so far (first-step compile time can only
        *raise* individual samples, never lower the min) and/or the modeled
        hint (``overheads.predict(...).t_tee_s``), whichever is smaller.
        None until either exists — feasibility then never rejects."""
        cands = [c for c in (self._step_floor, self._step_time_hint_s)
                 if c is not None]
        return min(cands) if cands else None

    def _reject_if_infeasible(self, gen: GenerationRequest
                              ) -> Optional[Request]:
        """Refuse ``gen`` at ingest when its deadline is provably unmeetable:
        even at the fastest step time, the serial steps it needs (one
        prefill dispatch, chunked prompt-tail feeds, one decode step per
        output token after the prefill-produced first) plus the queue ahead
        of it (optimistically packed across all slots — a lower bound)
        already exceed ``deadline_s``. Returns the finished rejected
        :class:`Request` (``finish_reason="rejected"``), or None to admit
        normally. Estimation is deliberately one-sided: a request this
        rejects would have been aborted mid-decode after consuming prefill
        compute and sealed-KV bandwidth."""
        if not self._reject_infeasible or gen.deadline_s is None:
            return None
        lo = self._step_time_lower()
        if lo is None:
            return None
        bucket = self._bucket_for(len(gen.prompt))
        tail = max(0, len(gen.prompt) - bucket)
        own_steps = 1 + tail + (gen.max_new_tokens - 1)
        ahead = sum(r.max_new_tokens for _, _, r in self.scheduler.queue)
        ahead += sum(max(0, r.max_new_tokens - len(r.output))
                     for r in self.scheduler.running.values())
        ahead += sum(max(0, p.req.max_new_tokens - len(p.req.output))
                     for p in self._preempted)
        est = lo * (own_steps + ahead / max(1, self.max_slots))
        if est <= gen.deadline_s:
            return None
        req = self.scheduler.reject(gen)
        self.td._log("reject_infeasible",
                     f"rid={req.rid} est>={est:.4f}s "
                     f"deadline={gen.deadline_s}s step_lo={lo:.6f}s")
        return req

    # -- sampling plumbing -----------------------------------------------------
    def _base_key(self, req: Request) -> np.ndarray:
        return np.asarray(jax.random.PRNGKey(req.seed or 0), np.uint32)

    def _set_slot_sampling(self, slot: int, req: Request) -> None:
        p = req.gen.params
        if p.is_greedy:
            self.slots.clear_sampling(slot)
        else:
            self.slots.set_sampling(slot, p.temperature, p.top_k, p.top_p,
                                    self._base_key(req),
                                    p.repetition_penalty, p.presence_penalty,
                                    logit_bias=p.logit_bias)
            # penalty history follows the request, not the cache: rebuilt
            # from its output list (empty at first admission; the generated
            # prefix after a sealed restore), so a seeded penalized request
            # re-samples byte-identically across preemption.
            self.slots.set_hist(slot, req.output)

    def _static_kmax(self) -> int:
        """Pow2-rounded top_k bound → bounded set of compiled decode shapes."""
        k = self.slots.max_top_k
        return min(next_pow2(k), self._vocab) if k > 0 else 0

    def _sampling_state(self, steps: np.ndarray
                        ) -> Tuple[Optional[sampling.SamplingState], int]:
        """The per-step (state, kmax) pair for the jitted decode: ``None``
        state on all-greedy steps, and a ``top_p`` row only when some slot
        actually restricts (both are static pytree differences, so the
        nucleus sort and the sampling math compile only when used)."""
        s = self.slots
        rep = host_upload(s.rep_pen) if s.any_rep_pen else None
        pres = host_upload(s.presence) if s.any_presence else None
        if rep is None and pres is None:
            # no live penalties: drop the device mirror and its queue (also
            # on the all-greedy path below — _emit_token must not keep
            # feeding a queue nothing will ever drain).
            hist = None
            self._hist_dev = None
            self._hist_pending.clear()
        else:
            hist = self._hist_device()
        if not s.any_bias:
            bias = None
            self._bias_dev = None
            self._bias_dev_version = -1
        else:
            bias = self._bias_device()
        if not s.any_sampled:
            return None, 0
        top_p = host_upload(s.top_p) if s.any_top_p else None
        state = sampling.SamplingState(
            host_upload(s.temp), host_upload(s.top_k), host_upload(s.key),
            host_upload(steps), top_p=top_p, rep_pen=rep, presence=pres,
            hist=hist, bias=bias)
        return state, self._static_kmax()

    def _hist_device(self):
        """Device copy of the penalty history, kept in sync cheaply: bulk
        host mutations (row rebuild/clear — admission, restore, release)
        bump ``hist_version`` and trigger a full upload (which subsumes any
        queued increments — the host matrix is always authoritative);
        otherwise the per-token increments queued since the last step are
        applied as one batched scatter, so the decode hot path ships a few
        ints per step instead of [slots, vocab]."""
        if (self._hist_dev is None
                or self._hist_dev_version != self.slots.hist_version):
            self._hist_dev = host_upload(self.slots.hist)
            self._hist_dev_version = self.slots.hist_version
            self._hist_pending.clear()
        elif self._hist_pending:
            rows = host_upload([s for s, _ in self._hist_pending], jnp.int32)
            toks = host_upload([t for _, t in self._hist_pending], jnp.int32)
            self._hist_dev = self._hist_dev.at[rows, toks].add(1)
            self._hist_pending.clear()
        return self._hist_dev

    def _bias_device(self):
        """Device copy of the logit-bias rows. Unlike ``hist`` there is no
        incremental stream — bias is static per request — so a version check
        alone decides when the matrix re-uploads (admission/release of a
        biased request bumps ``bias_version``)."""
        if (self._bias_dev is None
                or self._bias_dev_version != self.slots.bias_version):
            self._bias_dev = host_upload(self.slots.bias)
            self._bias_dev_version = self.slots.bias_version
        return self._bias_dev

    # -- egress ----------------------------------------------------------------
    def _flush_egress(self, req: Request) -> None:
        """Release the request's buffered tokens as ONE encrypted frame (the
        FramePolicy flush); the on_token callback fires per token as it
        becomes visible outside the domain."""
        if not req.egress_buf:
            return
        toks, req.egress_buf = req.egress_buf, []
        if self.td.confidential:
            out = self.td.egress_tokens(req.stream_id, toks)
            req.egress_frames += 1
            req.egress_tokens += len(out)
        else:
            out = toks
        if req.on_token is not None:
            for t in out:
                req.on_token(req, int(t))

    def _emit_token(self, slot: int, tok: int) -> bool:
        """Record one sampled token (in-domain), mark the request's frame
        due per its FramePolicy (coalesce window, flush-on-finish), and
        check termination. Returns True if the request finished; ``_emit``
        sends the due frames and releases the finished."""
        req = self.scheduler.running[slot]
        self.scheduler.record_token(slot, int(tok))
        # penalty history (host), counted only for penalized slots; a
        # counted token is queued for the device mirror so both sides agree
        if (self.slots.note_token(slot, int(tok))
                and self._hist_dev is not None):
            self._hist_pending.append((slot, int(tok)))
        self._last_token[slot] = int(tok)
        done = req.done
        req.egress_buf.append(int(tok))
        if done or not self.td.confidential or len(req.egress_buf) >= req.coalesce:
            self._due.append(req)
        return done

    def _emit(self, tokens: Sequence[Tuple[int, int]]) -> None:
        """Emit ``(slot, token)`` pairs, in three passes: (a) each token is
        recorded and its request's frame marked due; (b) the due frames are
        staged, so they cross the trust boundary in one device trip each
        way, and each is requested in slot order, its ``on_token`` after
        it; (c) finished requests are released, a stream closed only after
        its last frame has left. Finishing checks right after recording: a
        max_new_tokens=1 request (or EOS as the very first token) releases
        its slot without paying for a wasted decode step."""
        finished = [slot for slot, tok in tokens if self._emit_token(slot, tok)]
        due, self._due = self._due, []
        self.td.stage_egress([(req.stream_id, req.egress_buf) for req in due])
        for req in due:
            self._flush_egress(req)
        self.td.finish_egress()
        for slot in finished:
            req = self.scheduler.finish(slot)
            self.kv.release(slot)
            self._active_mask[slot] = False
            self.td.close_stream(req.stream_id)

    # -- SLO admission ---------------------------------------------------------
    @property
    def _admit_filter(self):
        """Admissibility predicate for the scheduler queue — None when no
        rate budgets are configured, keeping the common path on the O(1)
        heap peek instead of a sorted scan."""
        return self._admissible if self._buckets else None

    def _admissible(self, req: Request) -> bool:
        bucket = self._buckets.get(req.priority)
        return bucket is None or bucket.can(req.max_new_tokens)

    def _charge_budget(self, req: Request) -> None:
        bucket = self._buckets.get(req.priority)
        if bucket is not None:
            bucket.charge(req.max_new_tokens)

    def _drop_expired(self) -> None:
        for req in self.scheduler.drop_expired():
            self.td.close_stream(req.stream_id)
            self.td._log("drop_deadline",
                         f"rid={req.rid} deadline={req.gen.deadline_s}s "
                         f"waited={req.t_done - req.t_submit:.3f}s")

    def _enforce_aborts(self) -> None:
        """``on_deadline="abort"``: terminate expired mid-flight requests.
        A running one flushes its partial tokens and frees its slot/pages; a
        sealed-out (preempted) one is discarded instead of restored — its
        ciphertext is simply dropped, which is what makes abort cheap: no
        boundary crossing, no decode steps, just bookkeeping."""
        now = time.monotonic()
        for slot in list(self.scheduler.running):
            req = self.scheduler.running[slot]
            if not req.abort_expired(now):
                continue
            self._flush_egress(req)
            req.finish_reason = FINISH_ABORTED
            self.scheduler.finish(slot)
            self.kv.release(slot)
            self._active_mask[slot] = False
            self._paused.pop(slot, None)   # a paused victim's sealed tail
            self.td.close_stream(req.stream_id)
            self.td._log("abort_deadline",
                         f"rid={req.rid} deadline={req.gen.deadline_s}s "
                         f"tokens={len(req.output)}")
        for p in list(self._preempted):
            if not p.req.abort_expired(now):
                continue
            self._preempted.remove(p)
            self._flush_egress(p.req)   # coalesced tokens sealed with it must
            p.req.finish_reason = FINISH_ABORTED     # still reach the client
            # its sealed state may reference shared pages: release those
            # refs so parked ciphertext does not outlive every reader (the
            # blob itself is just dropped — that is what makes abort cheap).
            # Only tampered/garbled blobs are tolerated here; accounting
            # bugs (asserts, refcount underflows) must still surface.
            try:
                self.kv.discard_sealed(
                    p.key or self.td.sealing_key, p.sealed,
                    p.prefix
                    or f"kvslot/{p.req.stream_id}/{p.req.seal_epoch - 1}")
            except (IntegrityError, ValueError):
                pass
            self.scheduler.finish_detached(p.req)
            self.td.close_stream(p.req.stream_id)
            self.td._log("abort_deadline",
                         f"rid={p.req.rid} sealed KV discarded unrestored")

    def _admit_batch(self) -> int:
        """Pop waiting requests sharing the head's prefill bucket (bounded by
        free slots, the backend's KV capacity, and per-priority rate budgets)
        and prefill them in one jitted call."""
        head = self.scheduler.peek_waiting(self._admit_filter)
        if (head is None or not self.slots.free
                or not self.kv.can_admit(self._admit_need(head))):
            return 0
        bucket = self._bucket_for(len(head.prompt))
        first = self.scheduler.next_waiting(self._admit_filter)
        self._charge_budget(first)
        slots = [self.kv.acquire(first.rid, self._admit_need(first))]
        assert slots[0] is not None, "admission raced KV accounting"
        group: List[Request] = [first]
        if self.batch_prefill:
            # group-mates must not jump the restore queue: a sealed-out
            # request with priority >= theirs gets the free slot first
            # (the head itself already outranked every sealed request, or
            # _admit_ready would have taken the restore branch).
            best_sealed = max((p.req.priority for p in self._preempted),
                              default=None)
            while self.slots.free:
                nxt = self.scheduler.peek_waiting(self._admit_filter)
                if nxt is None or self._bucket_for(len(nxt.prompt)) != bucket:
                    break
                if best_sealed is not None and nxt.priority <= best_sealed:
                    break
                if not self.kv.can_admit(self._admit_need(nxt)):
                    break
                nxt = self.scheduler.next_waiting(self._admit_filter)
                self._charge_budget(nxt)
                slot = self.kv.acquire(nxt.rid, self._admit_need(nxt))
                assert slot is not None, "admission raced KV accounting"
                group.append(nxt)
                slots.append(slot)

        # rows padded to a power of two so compiled prefill shapes stay
        # bounded: |buckets| x log2(max_slots) variants, not one per batch.
        rows = next_pow2(len(group))
        tokens = np.zeros((rows, bucket), np.int32)
        pad = 0
        for i, req in enumerate(group):
            chunk = req.prompt[:bucket]
            tokens[i, bucket - len(chunk):] = chunk   # left-pad short prompts
            pad += bucket - len(chunk)
        self._count_prefill(len(group), rows, pad)
        fresh = self.kv.fresh_prefill_cache(rows)
        with obs.span("step.prefill", rows=rows, bucket=bucket):
            logits, prefilled = self._prefill_fn(
                self.params, host_upload(tokens), fresh)
            first_np = self._first_tokens(logits, group, rows)

        group_keys = None
        if self.kv.supports_sharing:
            group_keys = [req.page_keys for req in group]
        with obs.span("step.insert"):
            self.kv.insert_prefill(prefilled, slots, bucket,
                                   page_keys=group_keys)
        self._emit([(slots[i], int(first_np[i])) for i, req in enumerate(group)
                    if self._start_decode(slots[i], req, bucket)])
        return len(group)

    @staticmethod
    def _count_prefill(real_rows: int, rows: int, pad_tokens: int) -> None:
        """Prefill rows, the rows added to reach a power of two, and the
        tokens of left padding up to the bucket."""
        obs.count("engine.prefill_rows", real_rows)
        obs.count("engine.prefill_pad_rows", rows - real_rows)
        obs.count("engine.prefill_pad_tokens", pad_tokens)

    def _start_decode(self, slot: int, req: Request, bucket: int) -> bool:
        """Common post-prefill setup: the request enters the decode phase —
        it joins the scheduler's running set, its sampling row is set, and
        either its chunked prompt tail starts feeding through decode steps
        or (True) its first sampled token is to be emitted."""
        self.scheduler.start(slot, req)
        req.phase = "decode"
        self._active_mask[slot] = True
        self._set_slot_sampling(slot, req)
        if len(req.prompt) > bucket:
            # chunked prefill: the tail is fed through the decode loop,
            # one token per step, before any sampling counts as output.
            req.pending_input = [int(t) for t in req.prompt[bucket:]]
            self._last_token[slot] = 0   # unused until the tail drains
            return False
        return True

    def _first_tokens(self, logits, group: List[Request], rows: int) -> np.ndarray:
        """Sample each group member's first token from its prefill logits
        with its own SamplingParams at token index 0 (same fold-in the
        decode loop would use), so prefill- and decode-produced tokens are
        governed by one policy."""
        if all(req.gen.params.is_greedy for req in group):
            return np.argmax(np.asarray(logits), axis=-1)
        temp = np.zeros(rows, np.float32)
        top_k = np.zeros(rows, np.int32)
        top_p = np.ones(rows, np.float32)
        key = np.zeros((rows, 2), np.uint32)
        bias = None
        for i, req in enumerate(group):
            p = req.gen.params
            if not p.is_greedy:
                temp[i], top_k[i], top_p[i] = p.temperature, p.top_k, p.top_p
                key[i] = self._base_key(req)
            if p.logit_bias:
                if bias is None:
                    bias = np.zeros((rows, self._vocab), np.float32)
                for tok, val in p.logit_bias.items():
                    bias[i, int(tok)] = np.float32(val)
        kmax = int(top_k.max())
        state = sampling.SamplingState(
            host_upload(temp), host_upload(top_k), host_upload(key),
            jnp.zeros(rows, jnp.int32),
            top_p=host_upload(top_p) if (top_p < 1.0).any() else None,
            bias=host_upload(bias) if bias is not None else None)
        return np.asarray(sampling.sample(
            logits, state, kmax=min(next_pow2(kmax), self._vocab) if kmax else 0))

    # -- two-phase admission (continuous batching / disaggregated prefill) ----
    def _admit_one(self, req: Request, bucket: int) -> None:
        """Admit a single request (rows=1 prefill, left-padded into its
        bucket exactly like a batch of one — the differential harness pins
        rows=1 and rows=N prefills bitwise identical). On a disaggregated
        engine the prefill is *dispatched* on the dedicated plan and parked
        in ``_inflight``; the sealed handoff consumes it next step."""
        self._charge_budget(req)
        slot = self.kv.acquire(req.rid, self._admit_need(req))
        assert slot is not None, "admission raced KV accounting"
        tokens = np.zeros((1, bucket), np.int32)
        chunk = req.prompt[:bucket]
        tokens[0, bucket - len(chunk):] = chunk   # left-pad short prompts
        self._count_prefill(1, 1, bucket - len(chunk))
        if self._prefill_stream_fn is not None:
            # jax dispatches this call asynchronously: the decode step the
            # engine runs next overlaps with it, and _handoff_ready blocks
            # on the result only when it crosses to the decode plan.
            fresh = self.model.init_cache(1, self.max_len)
            with obs.span("step.prefill", rows=1, bucket=bucket):
                logits, cache = self._prefill_stream_fn(
                    self.prefill_params, host_upload(tokens), fresh)
            req.phase = "prefill"
            self._inflight[slot] = InflightPrefill(req, slot, bucket,
                                                   logits, cache)
            return
        fresh = self.kv.fresh_prefill_cache(1)
        with obs.span("step.prefill", rows=1, bucket=bucket):
            logits, prefilled = self._prefill_fn(
                self.params, host_upload(tokens), fresh)
            first_np = self._first_tokens(logits, [req], 1)
        keys = [req.page_keys] if self.kv.supports_sharing else None
        with obs.span("step.insert"):
            self.kv.insert_prefill(prefilled, [slot], bucket, page_keys=keys)
        if self._start_decode(slot, req, bucket):
            self._emit([(slot, int(first_np[0]))])

    def _admit_continuous(self) -> int:
        """Step-level admission: pop waiting requests one at a time into
        free slots while the step-token budget (single-plan mode) and KV
        capacity allow. When the head's bucket doesn't fit the remaining
        budget, the best-ordered queued request that *does* fit backfills
        the leftover — the head keeps first claim on the next step's fresh
        budget, so nothing starves. Mirrors ``_admit_batch``'s group-mate
        guard: admissions beyond the first must outrank every sealed-out
        request, or they would jump the restore queue."""
        admitted = 0
        best_sealed = max((p.req.priority for p in self._preempted),
                          default=None)
        while self.slots.free:
            head = self.scheduler.peek_waiting(self._admit_filter)
            if head is None:
                break
            if (admitted and best_sealed is not None
                    and head.priority <= best_sealed):
                break
            bucket = self._bucket_for(len(head.prompt))
            fits_budget = (self._budget_left is None
                           or bucket <= self._budget_left)
            if fits_budget and self.kv.can_admit(self._admit_need(head)):
                req = self.scheduler.next_waiting(self._admit_filter)
                self._admit_one(req, bucket)
                if self._budget_left is not None:
                    self._budget_left -= bucket
                admitted += 1
                continue
            if self._budget_left is None:
                break   # KV-blocked without a budget: nothing to backfill on

            def fits(r, head_rid=head.rid):
                if r.rid == head_rid:
                    return False   # the head keeps next step's fresh budget
                if best_sealed is not None and r.priority <= best_sealed:
                    return False   # must not jump the restore queue
                if self._buckets and not self._admissible(r):
                    return False
                b = self._bucket_for(len(r.prompt))
                return (b <= self._budget_left
                        and self.kv.can_admit(self._admit_need(r)))

            cand = self.scheduler.next_backfill(fits)
            if cand is None:
                break
            cand.backfilled = True
            self.backfills += 1
            b = self._bucket_for(len(cand.prompt))
            self._admit_one(cand, b)
            self._budget_left -= b
            admitted += 1
        return admitted

    def _handoff_key(self, inf: InflightPrefill) -> tuple:
        """Handoff consumption order mirrors the admission queue's: tightest
        slack first (static absolute deadline, priority tiebreak) under the
        default order, pure priority otherwise — NOT slot order, which is an
        arrival-order artifact. A tight-deadline request admitted one slot
        later still gets its first token (and its decode phase) first."""
        r = inf.req
        if self.scheduler.order == "slack":
            return (r.abs_deadline, -r.priority, r.rid)
        return (-r.priority, r.rid)

    def _handoff_ready(self) -> None:
        """Consume prefill-stream work dispatched at the previous step:
        finished requests' KV rows cross from the prefill plan to the decode
        plan as seal/unseal pairs — the disaggregation boundary, accounted
        in ``ChannelStats`` sealed bytes exactly like a preemption — and the
        requests enter the decode phase. Up to ``handoff_batch`` rows ride
        each sealed crossing (slack-ordered groups), so the fixed
        per-crossing cost amortizes across the group (Insight 10)."""
        order = sorted(self._inflight.values(), key=self._handoff_key)
        self._inflight.clear()
        for i in range(0, len(order), self._handoff_batch):
            self._complete_handoff(order[i:i + self._handoff_batch])

    def _complete_handoff(self, group: List[InflightPrefill]) -> None:
        # Each row seals under its own kvhandoff/{stream} namespace (one
        # handoff per stream, ever — restores after preemption use kvslot/ —
        # so the stream id alone keeps nonces fresh), but the whole group
        # crosses the plan boundary as ONE message: one seal event and one
        # restore event carry the group's total payload.
        sealed_rows = []
        total_nb = total_tensors = 0
        for inf in group:
            prefix = f"kvhandoff/{inf.req.stream_id}"
            sealed = seal_tree(self.td.sealing_key, inf.cache, prefix=prefix)
            nb = sealed_nbytes(sealed)
            inf.req.n_handoffs += 1
            inf.req.handoff_bytes += nb
            total_nb += nb
            total_tensors += len(sealed)
            sealed_rows.append((inf, prefix, sealed))
        self.handoff_crossings += 1
        rids = ",".join(str(inf.req.rid) for inf in group)
        self.td.record_seal(total_nb, total_tensors,
                            f"handoff x{len(group)} rids={rids}")
        self.td.record_restore(total_nb, total_tensors,
                               f"handoff x{len(group)} rids={rids}")
        firsts = []
        for inf, prefix, sealed in sealed_rows:
            req, slot, bucket = inf.req, inf.slot, inf.bucket
            restored = unseal_tree(self.td.sealing_key, sealed,
                                   self.model.abstract_cache(1, self.max_len),
                                   prefix=prefix)
            keys = [req.page_keys] if self.kv.supports_sharing else None
            with obs.span("step.insert"):
                self.kv.insert_prefill(restored, [slot], bucket,
                                       page_keys=keys)
            first_np = self._first_tokens(inf.logits, [req], 1)
            if self._start_decode(slot, req, bucket):
                firsts.append((slot, int(first_np[0])))
        self._emit(firsts)

    def _preempt_for(self, incoming: Request) -> bool:
        """Free capacity for ``incoming`` by preempting the lowest-priority
        running slot it strictly outranks. On the paged backend, when only
        *pages* are short (a slot is free but the pool is not), a partial
        eviction seals just the shortfall off the victim's tail — the victim
        keeps its slot and resident pages and resumes via a delta restore.
        Otherwise the whole victim is sealed out. Returns True if capacity
        was freed."""
        if not self.scheduler.running:
            return False
        victim_slot = min(self.scheduler.running,
                          key=lambda s: (self.scheduler.running[s].priority,
                                         -self.scheduler.running[s].rid))
        victim = self.scheduler.running[victim_slot]
        if victim.priority >= incoming.priority:
            return False
        if (self.slots.free and victim_slot not in self._paused
                and self.kv.supports_partial):
            shortfall = (self.kv.pages_for(self._admit_need(incoming))
                         - self.kv.free_page_reserve)
            spare = self.kv.evictable_tail_pages(victim_slot)
            if 0 < shortfall <= spare:
                self.partial_preempt(victim_slot, shortfall)
                return True
        sealed, vreq = self.seal_slot(victim_slot)
        vreq.n_preemptions += 1
        self._preempted.append(PreemptedRequest(sealed, vreq))
        return True

    def _resume_paused(self) -> bool:
        """Restore a partially-evicted slot's sealed tail once the pool has
        room again — unless a strictly higher-priority request is still
        waiting for the pages (the reason the tail was sealed)."""
        for slot, paused in list(self._paused.items()):
            # every path that removes a paused slot from running (abort,
            # whole-seal) also pops self._paused, so the victim is live here.
            # The gate is the strongest waiting PRIORITY (not the slack-
            # ordered queue head — see Scheduler.peek_priority).
            victim = self.scheduler.running[slot]
            rival = self.scheduler.peek_priority(self._admit_filter)
            if rival is not None and rival.priority > victim.priority:
                continue
            if not self.kv.can_restore_tail(paused.n_pages):
                continue
            self.kv.restore_tail_pages(self.td.sealing_key, paused.sealed,
                                       slot, paused.prefix)
            self.td.record_restore(sealed_nbytes(paused.sealed),
                                   len(paused.sealed),
                                   f"slot={slot} rid={victim.rid} partial")
            del self._paused[slot]
            return True
        return False

    def _admit_ready(self) -> None:
        """Admission policy, run at the top of every step:
        1. drop queued requests whose drop-deadline has passed and abort
           mid-flight ones whose abort-deadline has (SLO),
        2. resume partially-evicted slots when the pool has room again,
        3. restore sealed-out requests while no waiting request outranks
           them (and the backend has KV room),
        4. batch-admit waiting requests into free slots (bucket-grouped,
           rate-budget and KV-capacity gated — an over-budget priority class
           is skipped without blocking the classes behind it),
        5. preempt a strictly lower-priority running request when the
           waiting head cannot get capacity otherwise — wholly, or just the
           page shortfall on the paged backend (preempted requests never
           trigger further preemption — bounded, no thrash)."""
        while True:
            self._drop_expired()
            self._enforce_aborts()
            if self._paused and self._resume_paused():
                continue
            if self._preempted and self.slots.free:
                # restore-vs-admit: only sealed requests that the strongest
                # WAITING PRIORITY does not outrank are restorable
                # (restoring one a waiting request outranks would just be
                # preempted right back — livelock; gating on the slack-
                # ordered queue head instead would let a deadline-bearing
                # low-priority head unlock restores a waiting high-priority
                # request should block). AMONG the eligible, the restore
                # queue orders like the waiting queue: tightest slack first
                # (static absolute deadlines), then priority — a sealed-out
                # deadline-bound victim gets back in while its deadline is
                # still meetable. Priority-only engines keep the v4
                # selection.
                rival = self.scheduler.peek_priority(self._admit_filter)
                eligible = [p for p in self._preempted
                            if rival is None
                            or p.req.priority >= rival.priority]
                if eligible:
                    if self.scheduler.order == "slack":
                        best = min(eligible,
                                   key=lambda p: (p.req.abs_deadline,
                                                  -p.req.priority,
                                                  p.req.rid))
                    else:
                        best = max(eligible,
                                   key=lambda p: (p.req.priority,
                                                  -p.req.rid))
                    if self.kv.can_restore(
                            best.req.kv_need,
                            n_pages=best.req.sealed_pages or None):
                        self._preempted.remove(best)
                        self.restore_slot(best.sealed, best.req,
                                          key=best.key, prefix=best.prefix)
                        continue
            if (self.scheduler.queue and self.slots.free
                    and (self._admit_continuous() if self._continuous
                         else self._admit_batch()) > 0):
                continue
            # preemption is a PRIORITY right, independent of queue order:
            # the strongest waiting request may evict strictly weaker
            # running work even when a tighter-deadline (lower-priority)
            # request holds the slack-ordered queue head.
            cand = self.scheduler.peek_priority(self._admit_filter)
            if (cand is not None
                    and (not self.slots.free
                         or not self.kv.can_admit(self._admit_need(cand)))
                    and self._preempt_for(cand)):
                continue
            return

    def _drain_kv_events(self) -> None:
        """Account boundary traffic the backend generated on its own:
        shared-page parking (a last live reference dropped while sealed
        references remain — the page crosses out once, content-named),
        re-materialization (the first restore that needed it brings it
        back), and the persistent store's publish/hit/evict traffic."""
        with obs.span("step.kv_events"):
            for kind, nb, n in self.kv.drain_events():
                if kind == "park":
                    self.td.record_seal(nb, n, "shared page parked (last ref)")
                elif kind == "store_publish":
                    self.td.record_seal(nb, n,
                                        "page published to sealed store")
                elif kind == "store_hit":
                    self.td.record_store_hit(nb, n)
                elif kind == "store_evict":
                    self.td.record_store_evict(nb, n)
                else:
                    self.td.record_restore(nb, n,
                                           "shared page rematerialized")

    def _grant_step_pages(self, live: List[int]) -> List[int]:
        """On-demand allocation: make sure the pool can grant every live
        slot's append (and copy-on-write) page this step. When it runs dry,
        free capacity by *evict-by-slack*: the laxest running victim
        (latest absolute deadline, weakest priority; pure weakest-priority
        under ``admission_order="priority"``) loses just its private tail
        pages through ``seal_tail_pages`` when that covers the shortfall,
        else its whole slot. Terminates: every round either frees pages or
        removes a victim from the batch, and a lone survivor's demand
        always fits (its pages are bounded by request_capacity <= pool).
        Returns the live set minus evicted/paused victims."""
        while True:
            live = [s for s in live if s in self.scheduler.running
                    and s not in self._paused]
            demand = sum(self.kv.step_page_need(s) for s in live)
            free = self.kv.free_physical_pages
            if demand <= free:
                return live
            # paused slots are eviction candidates too: a lone live slot
            # must be able to reclaim pages a paused victim still holds
            # (whole-seal grafts the paused tail blob along — tested).
            candidates = list(self.scheduler.running)
            assert len(candidates) > 1, \
                "single-slot page demand exceeded the pool — capacity bug"

            def laxness(slot):
                r = self.scheduler.running[slot]
                if self.scheduler.order == "slack":
                    return (r.abs_deadline, -r.priority, r.rid)
                return (-r.priority, r.rid)
            victim = max(candidates, key=laxness)
            shortfall = demand - free
            spare = self.kv.evictable_tail_pages(victim)
            if victim not in self._paused and shortfall <= spare:
                self.partial_preempt(victim, shortfall)
            else:
                sealed, vreq = self.seal_slot(victim)
                vreq.n_preemptions += 1
                self._preempted.append(PreemptedRequest(sealed, vreq))

    # -- serving loop ----------------------------------------------------------
    def step(self) -> int:
        """One engine iteration: prefill-stream handoffs, then
        admission/restoration/preemption, then one batched decode step.
        Returns number of *output* tokens produced (prompt-chunk feeding
        steps count zero)."""
        obs.count("engine.steps")
        with obs.span("step"):
            return self._step()

    def _step(self) -> int:
        t0 = time.monotonic()
        if self._inflight:
            self._handoff_ready()
        if self._step_tokens is not None:
            # fresh per-step budget: every live decode row (including slots
            # still feeding a chunked prompt tail) costs 1; admissions then
            # charge their prefill bucket against the remainder.
            live_now = sum(1 for s in self.slots.active
                           if s not in self._paused)
            self._budget_left = max(0, self._step_tokens - live_now)
        with obs.span("step.admit"):
            self._admit_ready()
        live = [s for s in self.slots.active
                if s not in self._paused and s not in self._inflight]
        if live and self.kv.on_demand:
            live = self._grant_step_pages(live)
        if not live:
            self._drain_kv_events()
            return 0
        feeding_prompt = {}   # slot -> tail still pending after this step?
        steps = np.zeros(self.max_slots, np.int32)
        for slot in live:
            req = self.scheduler.running.get(slot)
            if req is None:
                continue
            steps[slot] = len(req.output)   # fold-in index of the next token
            if req.pending_input:
                self._last_token[slot] = req.pending_input.pop(0)
                feeding_prompt[slot] = bool(req.pending_input)
        with obs.span("step.sampling"):
            state, kmax = self._sampling_state(steps)
        with obs.span("step.decode"):
            next_np = self.kv.decode(self.params, self._last_token, state,
                                     kmax, write_slots=live)
        obs.count("engine.rows_decoded", len(live))
        if self.plan.is_sharded:
            # account the step's cross-device collective traffic (bytes from
            # the compiled HLO, seconds from the plan's measured probe)
            n, cb, cs = self.plan.drain_collectives()
            if n:
                self.td.record_collective(cb, cs, steps=n)
        # a mid-prompt chunk's sample is discarded
        tokens = [(slot, int(next_np[slot])) for slot in live
                  if self._active_mask[slot] and slot not in self._paused
                  and not feeding_prompt.get(slot, False)]
        with obs.span("step.emit"):
            self._emit(tokens)
        self._drain_kv_events()
        # feasibility floor: only steps that actually decoded count (an
        # empty tick would fake an impossibly fast step and over-reject)
        dt = time.monotonic() - t0
        if self._step_floor is None or dt < self._step_floor:
            self._step_floor = dt
        return len(tokens)

    @property
    def idle(self) -> bool:
        return (self.scheduler.idle and not self._preempted
                and not self._inflight)

    def run(self, max_steps: int = 10_000) -> ServeStats:
        steps = 0
        while not self.idle and steps < max_steps:
            produced = self.step()
            steps += 1
            if produced == 0 and not self.slots.active and not self.idle:
                # every waiting request is rate-budget gated: yield briefly
                # so the token buckets refill instead of busy-spinning.
                time.sleep(1e-3)
        stats = self.scheduler.stats()
        stats.shared_pages = getattr(self.kv, "shared_page_maps", 0)
        stats.cow_copies = getattr(self.kv, "cow_copies", 0)
        stats.store_hits = getattr(self.kv, "store_hits", 0)
        stats.store_restored_bytes = getattr(self.kv, "store_restored_bytes", 0)
        # evictions come from the channel (event-accounted), not the store
        # object — a store shared between engines counts fleet-wide there
        stats.store_evictions = self.td.channel.stats.store_evictions
        return stats

    # -- sealed KV preemption ----------------------------------------------------
    # The KV cache holds user conversation state; when a slot is preempted
    # (priority eviction, host maintenance) its pages must not land anywhere
    # unencrypted — the at-rest property H100 HBM lacks (paper §V-D3). The
    # slot cache is sealed with the domain key and can be restored later.
    # The sealing *granularity* is the backend's: slot-dense moves the whole
    # [L, max_len, ...] extent, paged moves ceil(tokens/page_size) pages.

    def _seal_prefix(self, req: Request) -> str:
        # the nonce-deriving name must be unique across every seal the domain
        # ever performs: the channel-global stream id (never reused, unlike
        # per-engine rids) plus a per-request seal epoch — a request
        # preempted twice holds different KV contents each time, and a
        # stream cipher must never encrypt two plaintexts under one nonce.
        return f"kvslot/{req.stream_id}/{req.seal_epoch}"

    def seal_slot(self, slot: int, *, key=None,
                  prefix: Optional[str] = None) -> Tuple[Dict[str, Any],
                                                         Request]:
        """Evict a running slot: returns (sealed_cache_dict, request). Any
        not-yet-prefilled prompt tail travels on ``request.pending_input``
        and not-yet-flushed egress tokens stay buffered on the request.

        A partially-evicted (paused) slot can be whole-sealed too: only its
        resident remainder is encrypted now, and the already-sealed tail
        blob rides along in the returned dict (its distinct epoch prefix
        keeps the nonce namespaces apart); ``restore_slot`` reassembles
        both.

        ``key``/``prefix`` override the worker defaults for cross-worker
        migration: the blob seals under a fleet-shared tenant key domain in
        a caller-supplied (worker-name-embedding) nonce namespace. Callers
        overriding the key must not have a paused tail on the slot — that
        earlier blob is under THIS worker's key and cannot cross
        (``export_sealed_state`` reunites it first)."""
        with obs.span("kv.seal_slot"):
            paused = self._paused.pop(slot, None)
            req = self.scheduler.running.pop(slot)
            assert paused is None or key is None, \
                "cannot migration-seal a paused slot: its tail blob is local"
            # a key override means the blob leaves this worker: shared pages
            # must seal by VALUE (detach) — a by-reference entry resolves
            # against THIS pool's content index / parked blobs, which the
            # destination does not have
            detach = key is not None and getattr(self.kv, "supports_sharing",
                                                 False)
            key = key if key is not None else self.td.sealing_key
            prefix = prefix if prefix is not None else self._seal_prefix(req)
            if self.kv.supports_partial:
                # what an on-demand restore must find free: the resident pages
                # plus any earlier-sealed tail riding along (shared pages may
                # re-link for less — this is the conservative bound).
                req.sealed_pages = (self.kv.allocated_pages(slot)
                                    + (paused.n_pages if paused else 0))
            sealed = (self.kv.seal(key, slot, prefix, detach=True) if detach
                      else self.kv.seal(key, slot, prefix))
            req.seal_epoch += 1
            # the paused tail was recorded at its own seal
            nb = sealed_nbytes(sealed)
            req.sealed_bytes += nb
            self.td.record_seal(nb, len(sealed),
                                f"slot={slot} rid={req.rid} "
                                f"stream={req.stream_id} "
                                f"epoch={req.seal_epoch - 1}")
            if paused is not None:
                sealed.update(paused.sealed)
            self.kv.release(slot)
            self._active_mask[slot] = False
            self._drain_kv_events()
            return sealed, req

    def restore_slot(self, sealed, req: Request, *, key=None,
                     prefix: Optional[str] = None) -> int:
        """Re-admit a sealed-out request into a free slot. On-demand pools
        acquire without a pledge (the restore's page takes were gated by
        ``can_restore(n_pages=...)``); reservation pools re-reserve the
        effective worst case. ``key``/``prefix`` override the worker
        defaults when the blob is a cross-worker migrant (sealed under a
        fleet-shared tenant domain in a ``kvmigrate/`` namespace)."""
        with obs.span("kv.restore_slot"):
            slot = self.kv.acquire(req.rid,
                                   0 if self.kv.on_demand else req.kv_need)
            if slot is None:
                raise RuntimeError("no free slot/KV room to restore into")
            key = key if key is not None else self.td.sealing_key
            if prefix is None:
                prefix = f"kvslot/{req.stream_id}/{req.seal_epoch - 1}"
            try:
                self.kv.restore(key, sealed, slot, prefix, req.kv_need)
                # a sealed-while-paused eviction carries its earlier tail
                # blob under an older epoch prefix (and, under a mesh, shard
                # suffix); graft it back on top of the remainder (acquire()
                # above already reserved the full need).
                for gprefix, gsuffix in tail_blob_names(sealed):
                    self.kv.restore_tail_pages(
                        key, sealed, slot, gprefix,
                        reserve=False, suffix=gsuffix)
            except Exception:
                self.kv.release(slot)   # a failed (e.g. tampered) restore must
                raise                   # not leak the slot or its reservation
            # the WHOLE restore succeeded: only now are this sealed dict's
            # shared-page references spent (a rolled-back restore must leave
            # _sealed_refs and parked ciphertext intact for co-sharers)
            self.kv.discard_sealed(key, sealed, prefix)
            self.scheduler.running[slot] = req
            self._active_mask[slot] = True
            self._set_slot_sampling(slot, req)
            # next decode input: the prompt tail (if chunked prefill was cut
            # short) takes precedence in step(); otherwise the last output
            # token.
            self._last_token[slot] = req.output[-1] if req.output else 0
            self.td.record_restore(sealed_nbytes(sealed), len(sealed),
                                   f"slot={slot} rid={req.rid}")
            self._drain_kv_events()
            return slot

    def partial_preempt(self, slot: int, n_pages: int) -> None:
        """Page-granular preemption (paged backend only): seal the victim's
        ``n_pages`` tail pages and hand them (and their reservation) back to
        the pool. The victim stays admitted — slot, sampling row, and head
        pages intact — but sits out of the decode batch until
        ``_resume_paused`` restores the delta."""
        if not self.kv.supports_partial:
            raise RuntimeError(
                f"the {self.kv.name} backend cannot seal at page granularity;"
                f" use kv_backend='paged'")
        if slot in self._paused:
            raise RuntimeError(f"slot {slot} is already partially evicted")
        req = self.scheduler.running[slot]
        prefix = self._seal_prefix(req)
        sealed = self.kv.seal_tail_pages(self.td.sealing_key, slot, prefix,
                                         n_pages)
        req.seal_epoch += 1
        req.n_preemptions += 1
        nb = sealed_nbytes(sealed)
        req.sealed_bytes += nb
        self.td.record_seal(nb, len(sealed),
                            f"slot={slot} rid={req.rid} partial "
                            f"pages={n_pages}")
        self._paused[slot] = PausedSlot(sealed, prefix, n_pages)

    # -- fleet: drain + sealed-state migration ---------------------------------
    def drain(self) -> None:
        """Stop taking new work (subsequent ``submit`` raises); everything
        already accepted keeps stepping. Pair with
        :meth:`export_sealed_state` to move the remaining state to another
        worker instead of finishing it here."""
        self._draining = True

    def export_sealed_state(
            self, *,
            key_for: Optional[Callable[[Request], Any]] = None,
            namespace: str = "kvmigrate",
    ) -> Tuple[List[PreemptedRequest], List[Request]]:
        """Seal EVERY piece of live state out of this engine for adoption by
        another — the fleet drain/failure path. Returns ``(migrants,
        queued)``: migrants are :class:`PreemptedRequest` blobs sealed under
        ``key_for(req)`` (the fleet passes the request's *tenant* key
        domain, identical on every attested worker) in the
        ``{namespace}/{stream}/{epoch}`` nonce space — the caller's
        namespace must embed this worker's fleet-unique name, because the
        tenant key is shared and two workers' stream ids are not distinct
        from each other; queued requests carry no KV and move as-is.

        The export is staged so the pool always has room: pending prefill
        handoffs complete first (they become running rows), plain running
        slots migration-seal directly, a paused slot round-trips through
        this worker's own seal/restore to reunite its resident head with
        its locally-sealed tail before migrating whole, and already-
        preempted blobs restore into the (by then free) slots and re-seal
        under the export key. Every crossing is priced in ``ChannelStats``
        like any other seal/restore; per-request
        ``n_migrations``/``migrated_bytes`` roll up into
        ``ServeStats.migrations``/``migrated_bytes``."""
        self._draining = True
        if key_for is None:
            key_for = lambda req: self.td.sealing_key  # noqa: E731
        if self._inflight:
            self._handoff_ready()
        migrants: List[PreemptedRequest] = []

        def _migrate(slot: int) -> None:
            req = self.scheduler.running[slot]
            key = key_for(req)
            prefix = f"{namespace}/{req.stream_id}/{req.seal_epoch}"
            sealed, req = self.seal_slot(slot, key=key, prefix=prefix)
            nb = sealed_nbytes(sealed)
            req.n_migrations += 1
            req.migrated_bytes += nb
            self.td._log("migrate_out", f"rid={req.rid} {nb}B {prefix}")
            self.td.close_stream(req.stream_id)
            migrants.append(PreemptedRequest(sealed, req, key=key,
                                             prefix=prefix))

        while self.scheduler.running:
            slot = next((s for s in self.scheduler.running
                         if s not in self._paused), None)
            if slot is None:
                # every survivor is paused: its sealed tail is under THIS
                # worker's key and cannot cross. Whole-seal (grafts the
                # tail along) then restore — the standard reassembly path —
                # and migrate the reunited slot.
                slot = next(iter(self._paused))
                sealed, req = self.seal_slot(slot)
                slot = self.restore_slot(sealed, req)
            _migrate(slot)
        # already-sealed preempted blobs: local key/namespace — bring each
        # back through a now-free slot and re-seal under the export key
        while self._preempted:
            p = self._preempted.pop(0)
            slot = self.restore_slot(p.sealed, p.req, key=p.key,
                                     prefix=p.prefix)
            _migrate(slot)
        queued = [req for _, _, req in sorted(self.scheduler.queue)]
        self.scheduler.queue.clear()
        for req in queued:
            self.td.close_stream(req.stream_id)
        return migrants, queued

    def import_sealed_state(self, migrants: Sequence[PreemptedRequest],
                            queued: Sequence[Request] = ()) -> None:
        """Adopt another worker's exported state. Requests keep their object
        identity — the caller's handle finishes here, byte-identically
        (seeded sampling; output/penalty history travel on the request) —
        but get fresh rids (this scheduler's numbering) and fresh egress
        streams on this engine's channel. Migrants join the sealed-restore
        queue and re-enter through the ordinary slack/priority admission
        gates; their first local re-seal (if any) falls back to this
        worker's own key and ``kvslot/`` namespace."""
        for p in migrants:
            p.req.rid = self.scheduler._next_rid
            self.scheduler._next_rid += 1
            p.req.stream_id = self.td.open_stream()
            self.td._log("migrate_in", f"rid={p.req.rid} {p.prefix}")
            self._preempted.append(p)
        for req in queued:
            req.rid = self.scheduler._next_rid
            self.scheduler._next_rid += 1
            req.stream_id = self.td.open_stream()
            heapq.heappush(self.scheduler.queue,
                           (self.scheduler._key(req), req.rid, req))

    # -- convenience -----------------------------------------------------------
    def generate(self, request: GenerationRequest) -> RequestOutput:
        """Serve one request to completion: ``generate(GenerationRequest)
        -> RequestOutput``."""
        req = self.submit(request)
        self.run()
        return req.result()

    def stream(self, request: GenerationRequest, *,
               max_steps: int = 100_000) -> Iterator[int]:
        """Yields this request's tokens as they cross the trust boundary —
        per token with the default FramePolicy, in bursts of ``coalesce``
        when the request asked for frame coalescing. Other queued requests
        keep advancing in the same decode batch. The request is submitted
        eagerly (before the first token is pulled), so it joins the batch
        even if the caller iterates later. Any on_token the request carries
        still fires."""
        if not isinstance(request, GenerationRequest):
            raise TypeError(
                "stream takes a GenerationRequest (repro.runtime.api); the "
                "v2 kwargs form was removed in v4 — build a request object")
        buf: List[int] = []
        inner = request.on_token

        def _tap(r, t):
            buf.append(t)
            if inner is not None:
                inner(r, t)

        request.on_token = _tap
        req = self.submit(request)

        def _drain() -> Iterator[int]:
            steps = 0
            while not req.finished:
                if steps >= max_steps:
                    raise RuntimeError(f"stream exceeded {max_steps} steps")
                produced = self.step()
                steps += 1
                if produced == 0 and not self.slots.active and not self.idle:
                    # rate-budget gated (same as run()): let buckets refill
                    # instead of burning max_steps on empty iterations.
                    time.sleep(1e-3)
                while buf:
                    yield buf.pop(0)
            while buf:
                yield buf.pop(0)

        return _drain()
