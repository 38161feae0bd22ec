"""ComputePlan — the engine's device-facing seam.

Everything the engine does *on devices* goes through one object: parameter
placement, the jitted prefill/decode callables, cache placement, and the
accounting for what the placement costs in cross-device traffic. The engine
and the KV backends speak to the plan; the plan decides whether that compute
lands on one device or spans a mesh.

Three plans:

  * :class:`SingleDevicePlan` — today's behavior, bit for bit: every
    ``compile_*`` is a plain ``jax.jit``, every ``place_*`` is the identity.

  * :class:`PrefillOnlyPlan` — the dedicated prefill stream of a
    disaggregated engine (``Engine(prefill_plan=...)``): it compiles the
    prefill callable only and refuses ``compile_decode`` outright. Finished
    prefill KV rows never stay on this plan — they cross to the decode
    plan through the engine's sealed handoff (a seal/restore pair priced
    in ``ChannelStats`` exactly like a preemption), so the plan boundary
    is a *trust* boundary the paper's Insight 9–12 cost model can account.

  * :class:`ShardedPlan` — one engine spans a ``jax`` mesh built from
    :func:`repro.launch.mesh.make_host_mesh` (axes ``("data", "model")``):

      - **batch** rows shard over the ``data`` axis (each device decodes
        ``max_slots / dp`` sequences);
      - **params** are placed per
        :func:`repro.distributed.sharding.param_specs` with FSDP forced on:
        sharded at rest over ``data``, all-gathered at use. That gather is
        deliberate — it makes the interconnect carry real per-step traffic
        (the weight-streaming flow a confidential deployment must encrypt),
        and because the gather reconstructs *full* weights before any
        matmul, per-row compute is unchanged and outputs stay
        **byte-identical** to the single-device plan. With ``tp > 1`` the
        TP dims of ``param_specs`` additionally partition over ``model``;
        XLA then all-reduces partial products, which is numerically
        equivalent but (like every TP system) not bitwise — parity tests
        pin ``dp``-only meshes;
      - the **KV cache** is placed per
        :func:`repro.distributed.sharding.cache_specs` (slot-dense layout)
        or batch-sharded dense leaves + a replicated page pool (paged
        layout — per-shard pools are a ROADMAP follow-on).

    The collective path is *instrumented*: the first compiled decode step
    is lowered once more and its SPMD-partitioned HLO parsed with
    :func:`repro.roofline.analysis.parse_collectives` for the bytes each
    device moves per step, and an ``all_gather`` of that volume runs under
    ``jax.shard_map`` on the real mesh to
    *measure* the per-step collective time. Both flow into
    ``ChannelStats.collective_bytes`` / ``collective_s`` (per decode step),
    which ``overheads.predict(collective_s=...)`` accepts in place of its
    closed-form estimate — the measured-vs-modeled link_tax comparison
    ``serve_bench.py --mesh`` reports.
"""

from __future__ import annotations

import time
from types import SimpleNamespace
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

Params = Any
Cache = Any


def parse_mesh(spec: str) -> Tuple[int, int]:
    """``"dp=2,tp=1"`` (or just ``"dp=2"``) -> ``(dp, tp)``."""
    if not spec or not spec.strip():
        raise ValueError(
            "empty mesh spec: want 'dp=N' or 'dp=N,tp=M' (omit the mesh "
            "argument entirely for single-device)")
    dp, tp = 1, 1
    try:
        for part in spec.split(","):
            if not part.strip():
                continue
            k, v = part.split("=")
            k = k.strip()
            if k == "dp":
                dp = int(v)
            elif k == "tp":
                tp = int(v)
            else:
                raise ValueError(k)
    except ValueError:
        raise ValueError(
            f"bad mesh spec {spec!r}: want 'dp=N' or 'dp=N,tp=M'")
    if dp < 1 or tp < 1:
        raise ValueError(f"mesh axes must be >= 1, got dp={dp}, tp={tp}")
    return dp, tp


class ComputePlan:
    """Base seam; also the single-device implementation contract."""

    is_sharded = False
    name = "single"

    def __init__(self, model):
        self.model = model
        # per-step collective cost, drained by the engine into ChannelStats
        self._pending_steps = 0
        self.collective_bytes_per_step = 0
        self.collective_s_per_step = 0.0

    # -- placement ----------------------------------------------------------
    def place_params(self, params: Params) -> Params:
        return params

    def place_dense_cache(self, cache: Cache) -> Cache:
        return cache

    def place_paged_cache(self, blocks: Cache, paged_paths) -> Cache:
        return blocks

    # -- compiled callables --------------------------------------------------
    def compile_prefill(self):
        model = self.model

        def _prefill(params, tokens, cache):
            return model.prefill(params, {"tokens": tokens}, cache)

        return jax.jit(_prefill)

    def compile(self, fn, *, donate_argnums=(), static_argnums=()):
        """Non-decode device work (prefill splices, scatters)."""
        return jax.jit(fn, donate_argnums=donate_argnums,
                       static_argnums=static_argnums)

    def compile_decode(self, fn, *, donate_argnums=(), static_argnums=()):
        """The backend's batched decode step. Sharded plans additionally
        count each call's collective cost (see :meth:`drain_collectives`)."""
        return jax.jit(fn, donate_argnums=donate_argnums,
                       static_argnums=static_argnums)

    # -- collective accounting ----------------------------------------------
    def drain_collectives(self) -> Tuple[int, int, float]:
        """(steps, bytes, seconds) of collective cost accrued since the last
        drain. The engine feeds this into TrustDomain.record_collective."""
        n, self._pending_steps = self._pending_steps, 0
        return (n, n * self.collective_bytes_per_step,
                n * self.collective_s_per_step)

    def shard_of_slot(self, slot: int, max_slots: int) -> int:
        return 0


class SingleDevicePlan(ComputePlan):
    """Exactly the pre-plan engine: plain ``jax.jit``, no placement."""


class PrefillOnlyPlan(ComputePlan):
    """A plan compiled for the prefill phase only — the prefill half of a
    disaggregated ``Engine(prefill_plan=...)``. Prompts prefill here
    (asynchronously, via jax's dispatch queue) while the decode plan keeps
    stepping; the finished KV rows leave through the engine's sealed
    plan-to-plan handoff rather than by sharing device state, so this plan
    deliberately has no decode surface at all."""

    name = "prefill-only"

    def compile_decode(self, fn, *, donate_argnums=(), static_argnums=()):
        raise RuntimeError(
            "PrefillOnlyPlan compiles no decode step: it is the dedicated "
            "prefill stream of a disaggregated engine, and finished KV rows "
            "hand off to the decode plan through the sealed channel "
            "(Engine(prefill_plan=...))")


class ShardedPlan(ComputePlan):
    is_sharded = True
    name = "sharded"

    def __init__(self, model, *, dp: int = 1, tp: int = 1,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 probe_iters: int = 16):
        super().__init__(model)
        # imports deferred so a single-device engine never touches the
        # distributed stack (and plan.py stays import-cycle-free).
        from repro.launch.mesh import make_host_mesh

        if mesh is None:
            n = len(jax.devices())
            if dp * tp > n:
                raise ValueError(
                    f"mesh dp={dp},tp={tp} needs {dp * tp} devices but jax "
                    f"sees {n}: on an accelerator, run on a host with "
                    f"{dp * tp} chips or shrink the mesh; on the CPU, set "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{dp * tp} before jax initializes")
            mesh = make_host_mesh(data=dp, model=tp)
        self.mesh = mesh
        self.dp = int(mesh.shape["data"])
        self.tp = int(mesh.shape["model"])
        self.probe_iters = probe_iters
        # param_specs with FSDP forced on (see module docstring): the spec
        # table only reads cfg.parallel.{fsdp, dp_over_model}.
        self._spec_cfg = SimpleNamespace(parallel=SimpleNamespace(
            fsdp=True, dp_over_model=model.cfg.parallel.dp_over_model,
            zero1=False))
        self._analyzed = False

    @classmethod
    def from_spec(cls, model, spec: str) -> "ShardedPlan":
        dp, tp = parse_mesh(spec)
        return cls(model, dp=dp, tp=tp)

    def describe(self) -> str:
        return f"dp={self.dp},tp={self.tp} ({self.mesh.size} devices)"

    # -- placement ----------------------------------------------------------
    def _put(self, tree, spec_tree):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            tree, spec_tree, is_leaf=lambda x: isinstance(x, P))

    def _param_specs(self):
        from repro.distributed import sharding
        return sharding.param_specs(self._spec_cfg,
                                    self.model.abstract_params(), self.mesh)

    def place_params(self, params: Params) -> Params:
        return self._put(params, self._param_specs())

    def _gather_at_use(self, fn):
        """Wrap a step whose first argument is the params so that they are
        all-gathered over ``data`` inside it (TP dims stay partitioned).
        Every matmul then runs on whole weights, which keeps dp outputs
        identical to the single-device plan. Left to itself, the partitioner
        may keep FSDP weights sharded and all-reduce partial products, which
        reorders the sums (it does so on TPU).

        Stacked layer weights are gathered one layer at a time inside the
        layer scan (:func:`repro.models.layers.layer_params_hook`), so a
        device holds its FSDP shard plus one gathered layer, never the
        whole model; the rest (embedding, final norms) is gathered at step
        entry."""
        from repro.distributed.sharding import STACKED
        from repro.models.layers import layer_params_hook

        def drop_data(spec, lead=0):
            def axis(a):
                if isinstance(a, tuple):
                    a = tuple(x for x in a if x != "data")
                    return a or None
                return None if a == "data" else a
            return NamedSharding(self.mesh,
                                 P(*(axis(a) for a in tuple(spec)[lead:])))

        is_spec = lambda x: isinstance(x, P)        # noqa: E731
        specs = self._param_specs()
        per_layer = {k: jax.tree.map(lambda s: drop_data(s, 1), v,
                                     is_leaf=is_spec)
                     for k, v in specs.items() if k in STACKED}
        whole = {k: jax.tree.map(drop_data, v, is_leaf=is_spec)
                 for k, v in specs.items() if k not in STACKED}

        def gather_layer(block, lp):
            return jax.lax.with_sharding_constraint(lp, per_layer[block])

        def gathered(params, *args):
            params = dict(params)
            params.update(jax.lax.with_sharding_constraint(
                {k: params[k] for k in whole}, whole))
            with layer_params_hook(gather_layer):
                return fn(params, *args)
        return gathered

    def place_dense_cache(self, cache: Cache) -> Cache:
        from repro.distributed import sharding
        specs = sharding.cache_specs(self._spec_cfg, cache, self.mesh)
        return self._put(cache, specs)

    def place_paged_cache(self, blocks: Cache, paged_paths) -> Cache:
        """Pool leaves (pages shared by every sequence) replicate; the
        slot-dense remainder ([L, slots, ...] recurrent state) shards its
        batch dim over ``data`` when it divides. Prefix-sharing state
        (content index, per-page refcounts, parked ciphertext) is
        host-side and engine-global, so a replicated pool shares pages
        across every data-shard's sequences for free; per-shard pools
        (ROADMAP) will need the index keyed per shard. Sealing stays
        nonce-safe either way: per-epoch names carry the ``/s{shard}``
        suffix, and parked shared pages use content-derived names whose
        repeat sealing is deterministic (same plaintext, same ciphertext)."""
        def spec_for(path, leaf):
            if jax.tree_util.keystr(path) in paged_paths:
                return P(*([None] * leaf.ndim))
            if leaf.ndim >= 2 and leaf.shape[1] % self.dp == 0:
                return P(None, "data", *([None] * (leaf.ndim - 2)))
            return P(*([None] * leaf.ndim))

        specs = jax.tree_util.tree_map_with_path(spec_for, blocks)
        return self._put(blocks, specs)

    # -- compiled callables --------------------------------------------------
    def compile_prefill(self):
        model, plan = self.model, self

        def _prefill(params, tokens, cache):
            return model.prefill(params, {"tokens": tokens}, cache)

        jitted = jax.jit(self._gather_at_use(_prefill))

        def run(params, tokens, cache):
            rows = tokens.shape[0]
            if rows % plan.dp == 0:
                tokens = jax.device_put(
                    tokens, NamedSharding(plan.mesh, P("data", None)))
                cache = plan._put(cache, jax.tree_util.tree_map_with_path(
                    plan._prefill_cache_spec, cache))
            return jitted(params, tokens, cache)

        return run

    def _prefill_cache_spec(self, path, leaf):
        if any(getattr(k, "key", None) == "pos" for k in path[:1]):
            return P("data")
        return P(None, "data", *([None] * (leaf.ndim - 2)))

    def compile_decode(self, fn, *, donate_argnums=(), static_argnums=()):
        jitted = jax.jit(self._gather_at_use(fn),
                         donate_argnums=donate_argnums,
                         static_argnums=static_argnums)
        plan = self

        def run(*args):
            if not plan._analyzed:
                plan._analyze(jitted, args)
            out = jitted(*args)
            plan._pending_steps += 1
            return out

        return run

    # -- collective instrumentation ------------------------------------------
    def _analyze(self, jitted, args) -> None:
        """Parse the SPMD-partitioned HLO of the first compiled decode
        variant for per-device collective bytes/step, then *measure* that
        volume's all-gather time on the real mesh. One extra compile, once
        per engine; later sampling variants share the calibration (their
        collective profile is the same param gather)."""
        from repro.roofline.analysis import parse_collectives
        self._analyzed = True
        hlo = jitted.lower(*args).compile().as_text()
        self.collective_bytes_per_step = int(
            sum(op.moved_bytes for op in parse_collectives(hlo)))
        self.collective_s_per_step = self.measure_collective_s(
            self.collective_bytes_per_step)

    def measure_collective_s(self, nbytes: int, iters: Optional[int] = None
                             ) -> float:
        """Time a real collective of ``nbytes`` (per device) on this mesh:
        an ``all_gather`` under ``shard_map``, the measured stand-in for the
        decode step's gather traffic. Returns seconds per step."""
        if nbytes <= 0 or self.mesh.size < 2:
            return 0.0
        iters = iters or self.probe_iters
        n_dev = self.mesh.size
        axes = tuple(self.mesh.axis_names)
        elems = max(nbytes // 4, n_dev)
        elems -= elems % n_dev
        x = jax.device_put(
            jnp.zeros((elems,), jnp.float32),
            NamedSharding(self.mesh, P(axes)))

        def gather(local):
            return jax.lax.all_gather(local, axes, axis=0, tiled=True)

        f = jax.jit(jax.shard_map(gather, mesh=self.mesh, in_specs=P(axes),
                                  out_specs=P(None), check_vma=False))
        f(x).block_until_ready()           # compile outside the clock
        t0 = time.perf_counter()
        for _ in range(iters):
            f(x).block_until_ready()
        return (time.perf_counter() - t0) / iters

    def shard_of_slot(self, slot: int, max_slots: int) -> int:
        """Which data-shard (device index along ``data``) holds this slot's
        cache row — the ``/s{shard}`` suffix per-shard sealing records."""
        if max_slots % self.dp != 0:
            return 0               # cache fell back to replication
        return int(slot) // (max_slots // self.dp)
