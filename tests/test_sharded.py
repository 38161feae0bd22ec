"""Mesh-spanning engine (ComputePlan seam): single-device vs sharded parity,
per-shard sealing, measured collective accounting, and the
``overheads.predict`` measured-collective override.

Fast tier runs everything on an in-process 1-device mesh (the plan/wrapper
machinery is fully exercised — placement, suffixed sealing, HLO analysis —
without multi-device state). The 8-device byte-identity checks run in a
subprocess with a forced host device count (same harness as
tests/test_distributed.py) and carry ``pytest.mark.slow``.
"""

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import TrustDomain
from repro.core.bounce import ChannelStats
from repro.core.overheads import PROFILES, RooflineTerms, predict
from repro.models import build_model
from repro.runtime import (Engine, GenerationRequest, SamplingParams,
                           ShardedKVBackend, ShardedPlan, SingleDevicePlan,
                           parse_mesh)


@pytest.fixture(scope="module")
def small_model():
    cfg = smoke_config("deepseek-7b")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    return cfg, model, params


def make_engine(model, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_len", 8)
    return Engine(model, params, **kw)


PROMPT = np.arange(1, 9, dtype=np.int32)


def gen(prompt=PROMPT, **kw):
    return GenerationRequest(prompt=np.asarray(prompt, np.int32), **kw)


class TestPlanPlumbing:
    def test_default_plan_is_single_device(self, small_model):
        cfg, model, params = small_model
        eng = make_engine(model, params)
        assert isinstance(eng.plan, SingleDevicePlan)
        assert not isinstance(eng.kv, ShardedKVBackend)

    def test_mesh_engine_gets_sharded_plan_and_wrapper(self, small_model):
        cfg, model, params = small_model
        eng = make_engine(model, params, mesh="dp=1")
        assert isinstance(eng.plan, ShardedPlan)
        assert isinstance(eng.kv, ShardedKVBackend)
        assert eng.plan.dp == 1 and eng.plan.tp == 1

    def test_mesh_and_plan_are_exclusive(self, small_model):
        cfg, model, params = small_model
        with pytest.raises(ValueError, match="not both"):
            make_engine(model, params, mesh="dp=1",
                        plan=SingleDevicePlan(model))

    def test_parse_mesh(self):
        assert parse_mesh("dp=2") == (2, 1)
        assert parse_mesh("dp=2,tp=4") == (2, 4)
        assert parse_mesh("tp=2") == (1, 2)
        for bad in ("dp", "dp=0", "pp=2", "dp=2;tp=2", "", "  "):
            with pytest.raises(ValueError):
                parse_mesh(bad)

    def test_empty_mesh_string_rejected(self, small_model):
        """An empty --mesh (e.g. an unset shell variable) must fail loudly,
        not silently build a single-device engine."""
        cfg, model, params = small_model
        with pytest.raises(ValueError, match="empty mesh"):
            make_engine(model, params, mesh="")

    def test_oversized_mesh_rejected_with_hint(self, small_model):
        cfg, model, params = small_model
        with pytest.raises(ValueError, match="xla_force_host_platform"):
            make_engine(model, params, mesh="dp=4096")

    def test_accelerator_short_of_devices_raises_without_relaunch(
            self, monkeypatch):
        """Off the CPU the process holds its chips: too few devices is an
        error, never a child process."""
        from types import SimpleNamespace
        from repro.launch import mesh
        monkeypatch.setattr(mesh.jax, "devices",
                            lambda: [SimpleNamespace(platform="tpu")])
        monkeypatch.setattr(mesh.subprocess, "run", lambda *a, **k: 1 / 0)
        with pytest.raises(RuntimeError, match="4 chips"):
            mesh.ensure_host_devices(4)
        mesh.ensure_host_devices(1)            # enough devices: no-op

    def test_params_gathered_one_layer_at_a_time(self, small_model):
        """The gather-at-use constraints on stacked layer weights sit inside
        the layer scan, on one layer's slice: a dp device never holds the
        whole gathered model. Only unstacked leaves are gathered at entry."""
        cfg, model, params = small_model
        plan = ShardedPlan(model, dp=1)
        step = plan._gather_at_use(
            lambda p, t, c: model.prefill(p, {"tokens": t}, c))
        jaxpr = jax.make_jaxpr(step)(
            params, np.ones((2, 8), np.int32), model.init_cache(2, 16))

        def constrained(eqns, nested=True):
            shapes = []
            for e in eqns:
                if e.primitive.name == "sharding_constraint":
                    shapes += [v.aval.shape for v in e.invars]
                elif nested:
                    for sub in e.params.values():
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            shapes += constrained(sub.eqns)
            return shapes

        entry = constrained(jaxpr.eqns, nested=False)
        scan = next(e for e in jaxpr.eqns if e.primitive.name == "scan")
        in_scan = constrained(scan.params["jaxpr"].eqns)
        stacked = jax.tree.leaves(params["layers"])
        assert sorted(in_scan) == sorted(x.shape[1:] for x in stacked)
        assert len(entry) == len(jax.tree.leaves(params)) - len(stacked)
        assert not {x.shape for x in stacked} & set(entry)

    def test_collective_analysis_failure_raises(self, small_model,
                                                monkeypatch):
        """A failed HLO collective analysis stops the engine; it must not
        report 0 collective bytes as if it had measured them."""
        from repro.roofline import analysis
        cfg, model, params = small_model

        def broken(hlo):
            raise ValueError("unparseable HLO")
        monkeypatch.setattr(analysis, "parse_collectives", broken)
        eng = make_engine(model, params, mesh="dp=1")
        eng.submit(GenerationRequest(prompt=np.arange(1, 5, dtype=np.int32),
                                     max_new_tokens=2))
        with pytest.raises(ValueError, match="unparseable"):
            eng.run(max_steps=10)


class TestOneDeviceMeshParity:
    """Sharded-vs-single output parity moved into the differential harness
    (test_differential.py), which replays the canonical scenario on a REAL
    in-process dp=2 mesh — strictly stronger than the dp=1 smoke this class
    used to run. What stays here is the per-shard sealing machinery."""

    def test_seal_names_carry_shard_suffix_and_roundtrip(self, small_model):
        """Per-shard sealing: every sealed name ends in /s{shard}, and a
        preemption round-trips byte-identically through the tagged form."""
        cfg, model, params = small_model
        sp = SamplingParams(temperature=0.9, top_k=8, seed=3)
        ref = make_engine(model, params, max_slots=1).generate(
            gen(max_new_tokens=8, params=sp)).tokens
        eng = make_engine(model, params, max_slots=1, mesh="dp=1",
                          trust_domain=TrustDomain("tdx"))
        req = eng.submit(gen(max_new_tokens=8, params=sp))
        for _ in range(3):
            eng.step()
        sealed, evicted = eng.seal_slot(0)
        assert sealed and all(n.endswith("/s0") for n in sealed), \
            sorted(sealed)
        eng.restore_slot(sealed, evicted)
        eng.run(max_steps=50_000)
        assert req.output == ref

    def test_partial_eviction_tail_suffix_roundtrip(self, small_model):
        """Page-granular partial eviction under a mesh: the tail blob's
        names carry the shard tag and the delta restore finds them."""
        cfg, model, params = small_model
        common = dict(max_slots=1, kv_backend="paged", page_size=8)
        ref = make_engine(model, params, **common).generate(
            gen(np.arange(1, 25, dtype=np.int32), max_new_tokens=8)).tokens
        eng = make_engine(model, params, mesh="dp=1",
                          trust_domain=TrustDomain("tdx"), **common)
        req = eng.submit(gen(np.arange(1, 25, dtype=np.int32),
                             max_new_tokens=8))
        for _ in range(2):
            eng.step()
        eng.partial_preempt(0, 1)
        assert 0 in eng._paused
        assert any(n.endswith("/s0") for n in eng._paused[0].sealed)
        eng.run(max_steps=50_000)      # _resume_paused restores the delta
        assert req.output == ref

    def test_collective_counters_flow_into_channel_stats(self, small_model):
        """Even a 1-device mesh counts its decode steps (bytes are honestly
        zero — nothing crosses between devices)."""
        cfg, model, params = small_model
        td = TrustDomain("cgpu")
        eng = make_engine(model, params, mesh="dp=1", trust_domain=td)
        eng.generate(gen(max_new_tokens=5))
        assert td.channel.stats.collective_steps > 0
        assert td.channel.stats.collective_bytes == 0


class TestMeasuredLinkTax:
    def test_predict_collective_override(self):
        terms = RooflineTerms(compute_s=1e-3, memory_s=1e-3,
                              collective_s=1e-4)
        base = predict(terms, "cgpu")
        measured = predict(terms, "cgpu", collective_s=1e-3)
        # 10x the collective time under a 12.3x link tax must cost more
        assert measured.overhead > base.overhead
        # the override replaces (not adds to) the closed-form estimate
        same = predict(terms, "cgpu", collective_s=1e-4)
        assert abs(same.overhead - base.overhead) < 1e-12

    def test_link_tax_provenance_pinned(self):
        """Insight 12: 40/3 - 1 ≈ 12.3 (host-routed vs RDMA, §V-D4). The
        measured path prices the same ratio off observed collective time."""
        assert PROFILES["cgpu"].link_tax == pytest.approx(40 / 3 - 1, abs=0.1)

    def test_channel_stats_collective_fields(self):
        ch = ChannelStats()
        assert ch.collective_s_per_step == 0.0
        ch.collective_steps, ch.collective_bytes, ch.collective_s = 4, 400, 2.0
        assert ch.collective_s_per_step == 0.5
        ch.reset()
        assert (ch.collective_steps, ch.collective_bytes, ch.collective_s) \
            == (0, 0, 0.0)


@pytest.mark.slow
class TestEightDeviceParity:
    def test_sharded_outputs_byte_identical_with_preemption(self, subproc):
        """Acceptance: seeded generate() under ShardedPlan (slot AND paged)
        is byte-identical to single-device, including across sealed
        preemption/restore, on a real 8-device mesh — and the mesh engine
        measures nonzero collective traffic."""
        out = subproc("""
import jax, numpy as np
from repro.configs import smoke_config
from repro.models import build_model
from repro.core import TrustDomain
from repro.runtime import (Engine, GenerationRequest, SamplingParams,
                           ShardedKVBackend)

cfg = smoke_config("deepseek-7b")
m = build_model(cfg)
params = m.init_params(jax.random.key(0))
rng = np.random.default_rng(3)
prompts = [rng.integers(1, cfg.vocab_size, size=int(l)).astype(np.int32)
           for l in rng.integers(8, 40, size=10)]

def scenario(mesh, kv):
    td = TrustDomain("tdx")
    eng = Engine(m, params, max_slots=8, max_len=64,
                 prefill_buckets=(8, 16, 32), trust_domain=td,
                 kv_backend=kv, page_size=8, mesh=mesh)
    low = [eng.submit(GenerationRequest(
               prompt=p, max_new_tokens=10, priority=0,
               params=SamplingParams(temperature=0.8, top_k=16, seed=i,
                                     repetition_penalty=1.2)))
           for i, p in enumerate(prompts)]
    for _ in range(3):
        eng.step()
    high = [eng.submit(GenerationRequest(
                prompt=prompts[i][:8], max_new_tokens=6, priority=5,
                params=SamplingParams(temperature=0.8, top_k=16,
                                      seed=100 + i)))
            for i in range(8)]
    eng.run(max_steps=100_000)
    assert all(r.finished for r in low + high)
    return ([r.output for r in low + high],
            sum(r.n_preemptions for r in low), eng, td)

for kv in ("slot", "paged"):
    single, p1, _, _ = scenario(None, kv)
    mesh, p2, eng, td = scenario("dp=8", kv)
    assert single == mesh, f"{kv}: sharded outputs diverged"
    assert p1 > 0 and p2 > 0, f"{kv}: no preemption exercised ({p1}, {p2})"
    assert isinstance(eng.kv, ShardedKVBackend)
    ch = td.channel.stats
    assert ch.collective_steps > 0 and ch.collective_bytes > 0, \\
        f"{kv}: no collective traffic measured"
    assert ch.collective_s > 0
    print(kv, "OK", ch.collective_bytes // ch.collective_steps, "B/step")
print("OK")
""", devices=8)
        assert "OK" in out
        assert "slot OK" in out and "paged OK" in out
