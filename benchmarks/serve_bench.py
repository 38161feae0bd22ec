"""Serving benchmark: v1-style static prefill vs v2 bucketed batched prefill,
the v3 frame-coalescing sweep (Insight-10 fixed-cost amortization), and the
v4 slot-dense vs paged KV-backend sweep under a long-context mix with forced
preemption (sealed bytes ∝ tokens used, not capacity reserved).

Measures the paper's two user-perceived serving metrics (§III-C) —
throughput (tokens/s) and next-token latency — plus time-to-first-token and
the boundary-crossing counts that drive the cgpu fixed-cost model
(Insight 10), for two engine configurations over the same mixed-length
workload:

  v1-style : one static prefill bucket, one request per prefill call
             (the seed engine's shape; long prompts now chunk instead of
             silently truncating, so outputs are comparable)
  v2       : power-of-two prefill buckets, same-bucket requests batched
             into one jitted prefill call

The coalescing sweep then serves the same workload with FramePolicy
coalesce ∈ {1, 4, 16}: decoded output must be unchanged while boundary
crossings per token fall as 1/N — the amortization curve behind the paper's
observation that cGPU overhead is fixed-cost-per-crossing dominated. The
modeled column prices each point with the cgpu profile's
``fixed_boundary_s``.

The KV-backend sweep (``--kv-backend both``, the default) serves a
long-context seeded-sampling mix on the slot-dense and the paged backend,
forcing sealed-KV preemptions with a late high-priority wave. It asserts
byte-identical outputs between the backends and strictly fewer sealed
bytes per preemption for paged — the Insight-10 claim that what crosses
the boundary (pages actually holding tokens vs a whole max_len slot) is
the lever.

The prefix-sharing sweep serves a shared-prefix workload (one long common
head + distinct same-length tails) with ``prefix_sharing`` off and on,
both under on-demand allocation, forcing preemption with a high-priority
wave. It asserts byte-identical outputs, strictly fewer physical pages
written, and strictly lower sealed bytes with sharing on — the tentpole
claim that a shared prefix is stored once and sealed at most once.

The two-phase sweep serves a burst of long prompts arriving just ahead of
short ones — the TTFT operating point §III-C's latency numbers care about —
three ways: the v5 baseline (batched admission), step-level continuous
batching (``continuous_batching=True``: chunked prefill interleaves into
decode steps under a per-step token budget, short requests backfill budget
a long head chunk cannot use), and disaggregated prefill
(``prefill_plan="dedicated"``: prefill runs on its own ComputePlan and the
finished KV rows cross the plan boundary as a sealed handoff priced in
``ChannelStats``). It asserts byte-identical outputs across all three
modes, a strictly lower TTFT p99 for continuous batching, and nonzero
sealed handoff bytes for the two-plan mode, then writes every mode's
serving metrics to ``BENCH_serve.json``.

The page-store sweep serves a recurring-prompt mix (a RAG-style shared head
with distinct tails) for two epochs on one engine carrying a persistent
sealed-page store: the cold epoch publishes full pages at release, the warm
epoch restores them content-addressed (MAC-verified) — asserting a nonzero
warm hit rate, strictly fewer pages written, byte-identical tokens, and the
``overheads``-priced restore-vs-recompute breakeven.

The mesh sweep (``--mesh dp=2`` or ``dp=2,tp=2``; relaunches itself with
forced host devices when needed) serves the same seeded workload on a
single device and on a mesh-spanning engine, asserts byte-identical
outputs on dp-only meshes, and reports the *measured* collective path:
``ChannelStats.collective_bytes``/``collective_s`` (HLO-parsed bytes +
all-gather probe on the real mesh) against the closed-form bytes/ICI_BW
estimate, priced through ``overheads.predict`` both ways — the
measured-vs-modeled link_tax delta for the paper's §V-D4 Insight 12.

    PYTHONPATH=src:. python benchmarks/serve_bench.py [--requests 12] [--tee tdx]
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import build_bench_model
from repro.core import TrustDomain
from repro.core.overheads import PROFILES, measured_link_tax
from repro.launch.cache import use_compile_cache
from repro.runtime import (Engine, FramePolicy, GenerationRequest,
                           SamplingParams, parse_mesh, stats_from_requests)


def make_workload(n: int, vocab: int, seed: int = 7):
    """Mixed prompt lengths spanning the bucket range (8..100 tokens)."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(8, 100, size=n)
    return [rng.integers(1, vocab, size=int(l)).astype(np.int32)
            for l in lengths]


def reqs_for(prompts, max_new_tokens: int, coalesce: int = 1):
    return [GenerationRequest(prompt=p, max_new_tokens=max_new_tokens,
                              frame=FramePolicy(coalesce=coalesce))
            for p in prompts]


def run_config(label: str, model, params, prompts, *, max_new_tokens: int,
               tee: str, buckets, batch_prefill: bool, max_slots: int,
               coalesce: int = 1):
    td = TrustDomain(tee)
    eng = Engine(model, params, max_slots=max_slots, max_len=256,
                 trust_domain=td, prefill_buckets=buckets,
                 batch_prefill=batch_prefill)
    # warmup wave: pays every (rows, bucket) prefill compilation once, so the
    # measured wave reports steady-state serving numbers.
    for r in reqs_for(prompts, max_new_tokens, coalesce):
        eng.submit(r)
    eng.run(max_steps=100_000)
    td.channel.stats.reset()

    t0 = time.monotonic()
    reqs = [eng.submit(r) for r in reqs_for(prompts, max_new_tokens, coalesce)]
    eng.run(max_steps=100_000)
    wall = time.monotonic() - t0
    assert all(r.finished for r in reqs)
    stats = stats_from_requests(reqs)
    frames = td.channel.stats.messages_out if td.confidential else 0
    print(f"{label:8s} {stats.total_tokens:6d} tok  {wall:6.2f}s  "
          f"{stats.throughput_tps:8.1f} tok/s  "
          f"TTFT mean {stats.mean_ttft_s * 1e3:7.1f}ms p99 {stats.p99_ttft_s * 1e3:7.1f}ms  "
          f"step mean {stats.mean_latency_s * 1e3:6.1f}ms  "
          f"egress frames {frames}")
    return stats, reqs, td.channel.stats


def coalesce_sweep(model, params, prompts, *, max_new_tokens: int, tee: str,
                   max_slots: int, windows=(1, 4, 16)):
    """Serve the identical workload at each coalesce window; verify output
    invariance and monotonically decreasing crossings/token, and price each
    point with the cgpu fixed per-crossing cost (Insight 10)."""
    print(f"\nframe-coalescing sweep (coalesce ∈ {list(windows)}, tee={tee}):")
    fixed_s = PROFILES["cgpu"].fixed_boundary_s
    outputs, curve, expected = [], [], []
    for w in windows:
        _, reqs, ch = run_config(f"N={w}", model, params, prompts,
                                 max_new_tokens=max_new_tokens, tee=tee,
                                 buckets=(16, 32, 64, 128), batch_prefill=True,
                                 max_slots=max_slots, coalesce=w)
        outputs.append([r.output for r in reqs])
        want = sum(-(-len(r.output) // w) for r in reqs)   # sum of ceil(t/w)
        assert ch.messages_out == want, \
            f"coalesce={w}: {ch.messages_out} frames, expected {want}"
        expected.append(want)
        cpt = ch.crossings_per_token if ch.tokens_out else 0.0
        curve.append(cpt)
        print(f"         -> {ch.messages_out} frames / {ch.tokens_out} tokens"
              f" = {cpt:.3f} crossings/token | modeled cgpu fixed cost "
              f"{cpt * fixed_s * 1e6:.1f} us/token")
    assert all(o == outputs[0] for o in outputs[1:]), \
        "coalescing changed decoded output"
    # strictly fewer crossings whenever a wider window can actually pack
    # more tokens per frame; ties are only legal when even the expected
    # frame counts tie (every request shorter than both windows).
    for (a, b), (ea, eb) in zip(zip(curve, curve[1:]),
                                zip(expected, expected[1:])):
        assert b < a or (b == a and eb == ea), \
            f"crossings/token must fall monotonically with coalesce, got {curve}"
    print("coalescing sweep OK: identical tokens, "
          f"crossings/token {' >= '.join(f'{c:.3f}' for c in curve)}")


def kv_backend_sweep(model, params, vocab, *, tee: str, max_slots: int,
                     requests: int, page_size: int, backends=("slot", "paged")):
    """Slot-dense vs paged under a long-context mix with forced preemption.

    Identical seeded workload per backend: a low-priority wave fills every
    slot, then a high-priority wave arrives and preempts (sealed-KV
    eviction) before the victims restore and finish. Asserts byte-identical
    outputs across backends and strictly fewer sealed bytes per preemption
    for paged (it moves ceil(tokens/page_size) pages, not max_len)."""
    max_len = 256
    rng = np.random.default_rng(11)
    lens = rng.integers(24, 200, size=requests)
    prompts = [rng.integers(1, vocab, size=int(l)).astype(np.int32)
               for l in lens]
    print(f"\nKV-backend sweep ({' vs '.join(backends)}, tee={tee}, "
          f"page_size={page_size}): {requests} low-prio + "
          f"{max_slots} high-prio requests, prompt lens "
          f"{lens.min()}-{lens.max()}, max_len={max_len}")

    results = {}
    for backend in backends:
        td = TrustDomain(tee)
        eng = Engine(model, params, max_slots=max_slots, max_len=max_len,
                     trust_domain=td, prefill_buckets=(32, 64, 128),
                     kv_backend=backend, page_size=page_size)
        # warmup wave: pay every (rows, bucket) compile before timing
        for p in prompts[:max_slots]:
            eng.submit(GenerationRequest(prompt=p, max_new_tokens=4))
        eng.run(max_steps=100_000)
        td.channel.stats.reset()

        t0 = time.monotonic()
        low = [eng.submit(GenerationRequest(
                   prompt=p, max_new_tokens=24, priority=0,
                   params=SamplingParams(temperature=0.8, top_k=32, seed=i)))
               for i, p in enumerate(prompts)]
        for _ in range(4):          # let the low wave claim slots + decode
            eng.step()
        high = [eng.submit(GenerationRequest(
                    prompt=prompts[i % len(prompts)][:48],
                    max_new_tokens=12, priority=5,
                    params=SamplingParams(temperature=0.8, top_k=32,
                                          seed=1000 + i)))
                for i in range(max_slots)]
        eng.run(max_steps=200_000)
        wall = time.monotonic() - t0
        assert all(r.finished for r in low + high)
        stats = stats_from_requests(low + high)
        ch = td.channel.stats
        per_seal = ch.seal_bytes_per_event
        print(f"  {backend:5s} {stats.total_tokens:6d} tok  {wall:6.2f}s  "
              f"{stats.throughput_tps:8.1f} tok/s  "
              f"TTFT mean {stats.mean_ttft_s * 1e3:7.1f}ms  "
              f"preempt {stats.preemptions:2d}  "
              f"sealed {ch.seal_bytes:8d}B ({per_seal:9.0f} B/seal)  "
              f"crossings {ch.messages_in + ch.messages_out}")
        results[backend] = dict(
            outputs=[r.output for r in low + high],
            preemptions=stats.preemptions, per_seal=per_seal, stats=stats)

    if len(backends) == 2:
        a, b = (results[k] for k in backends)
        assert a["outputs"] == b["outputs"], \
            "KV backends must produce byte-identical outputs"
        assert a["preemptions"] > 0 and b["preemptions"] > 0, \
            "the sweep must actually exercise sealed preemption"
        assert results["paged"]["per_seal"] < results["slot"]["per_seal"], \
            (f"paged must seal strictly fewer bytes per preemption "
             f"(paged {results['paged']['per_seal']:.0f} vs "
             f"slot {results['slot']['per_seal']:.0f})")
        ratio = results["slot"]["per_seal"] / results["paged"]["per_seal"]
        print(f"KV sweep OK: identical tokens under preemption; paged seals "
              f"{ratio:.1f}x fewer bytes per eviction")


def prefix_sharing_sweep(model, params, vocab, *, tee: str, max_slots: int,
                         requests: int, page_size: int):
    """Shared-prefix workload (one long common head — a RAG context / system
    prompt — plus distinct same-length tails) served with prefix sharing
    off and on, both under on-demand allocation so sharing is the only
    delta. Asserts byte-identical outputs, strictly fewer physical pages
    written, and strictly lower sealed bytes with sharing — the shared head
    is stored once, and a victim's shared pages seal by reference (parked
    at most once at last-reference drop) instead of as per-victim
    ciphertext."""
    max_len, bucket, head_len = 256, 128, 96
    rng = np.random.default_rng(17)
    head = rng.integers(1, vocab, size=head_len).astype(np.int32)
    prompts = [np.concatenate([head, rng.integers(
                   1, vocab, size=bucket - head_len).astype(np.int32)])
               for _ in range(requests)]
    print(f"\nprefix-sharing sweep (tee={tee}, page_size={page_size}): "
          f"{requests} requests sharing a {head_len}-token head of "
          f"{bucket}-token prompts, + {max_slots} high-prio preemptors")

    results = {}
    for mode in ("off", "on"):
        td = TrustDomain(tee)
        eng = Engine(model, params, max_slots=max_slots, max_len=max_len,
                     trust_domain=td, prefill_buckets=(bucket,),
                     kv_backend="paged", page_size=page_size,
                     kv_alloc="ondemand", prefix_sharing=(mode == "on"))
        # warmup wave: pay the compile cost outside the measured window
        for p in prompts[:max_slots]:
            eng.submit(GenerationRequest(prompt=p, max_new_tokens=4))
        eng.run(max_steps=100_000)
        td.channel.stats.reset()
        pages0 = eng.kv.pages_written

        t0 = time.monotonic()
        low = [eng.submit(GenerationRequest(
                   prompt=p, max_new_tokens=16, priority=0,
                   params=SamplingParams(temperature=0.8, top_k=32, seed=i)))
               for i, p in enumerate(prompts)]
        for _ in range(4):
            eng.step()
        high = [eng.submit(GenerationRequest(
                    prompt=prompts[i % len(prompts)], max_new_tokens=8,
                    priority=5,
                    params=SamplingParams(temperature=0.8, top_k=32,
                                          seed=1000 + i)))
                for i in range(max_slots)]
        eng.run(max_steps=200_000)
        wall = time.monotonic() - t0
        assert all(r.finished for r in low + high)
        stats = stats_from_requests(low + high)
        ch = td.channel.stats
        pages = eng.kv.pages_written - pages0
        print(f"  sharing={mode:3s} {stats.total_tokens:6d} tok  {wall:6.2f}s "
              f" {stats.throughput_tps:8.1f} tok/s  preempt "
              f"{stats.preemptions:2d}  pages written {pages:4d}  shared "
              f"maps {eng.kv.shared_page_maps:3d}  CoW {eng.kv.cow_copies:2d}"
              f"  sealed {ch.seal_bytes:8d}B")
        results[mode] = dict(outputs=[r.output for r in low + high],
                             pages=pages, sealed=ch.seal_bytes,
                             shared=eng.kv.shared_page_maps,
                             preemptions=stats.preemptions)

    a, b = results["off"], results["on"]
    assert a["outputs"] == b["outputs"], \
        "prefix sharing must not change decoded output"
    assert a["preemptions"] > 0 and b["preemptions"] > 0, \
        "the sweep must actually exercise sealed preemption"
    assert b["shared"] > 0, "no page was ever shared — sweep is broken"
    assert b["pages"] < a["pages"], \
        (f"sharing must write strictly fewer physical pages "
         f"({b['pages']} vs {a['pages']})")
    assert b["sealed"] < a["sealed"], \
        (f"sharing must seal strictly fewer bytes "
         f"({b['sealed']} vs {a['sealed']})")
    print(f"prefix-sharing sweep OK: identical tokens; "
          f"{a['pages']}→{b['pages']} pages written, "
          f"{a['sealed']}→{b['sealed']} sealed bytes "
          f"({a['sealed'] / max(b['sealed'], 1):.2f}x)")


def two_phase_sweep(model, params, vocab, *, tee: str, json_out: str):
    """Long-prompt burst served by the baseline engine, step-level
    continuous batching, and the disaggregated two-plan engine.

    The workload is the TTFT-hostile shape: a burst of long prompts (each a
    full largest-bucket prefill, decoding for a long time) lands in the
    middle of a stream of short requests. The baseline admits in strict
    queue order, so the longs grab every freed slot and the trailing shorts
    wait out the longs' entire decode — the TTFT tail is a short request
    stuck behind the burst. Continuous batching charges live decode rows
    against the per-step token budget, so while short traffic keeps the
    engine busy the long head chunk does not fit and trailing shorts
    backfill past it; the longs run once the short stream drains. The
    asserted win is a strictly lower TTFT p99. The two-plan mode routes
    prefill through a dedicated ComputePlan and hands finished KV rows to
    the decode plan as a seal/restore pair — the sweep asserts that handoff
    traffic lands in ``ChannelStats`` (nonzero sealed bytes across the plan
    boundary). Outputs must be byte-identical across all three modes
    (scheduling moves tokens in time, never changes them). Per-mode serving
    metrics go to ``json_out``."""
    max_slots, max_len, bucket = 4, 192, 128
    # one long chunk + a couple of decode rows: with >= 3 live rows the
    # long head is budget-blocked and shorts backfill past it
    step_tokens = 130
    rng = np.random.default_rng(31)
    longs = [rng.integers(1, vocab, size=bucket).astype(np.int32)
             for _ in range(2)]
    shorts = [rng.integers(1, vocab, size=16).astype(np.int32)
              for _ in range(16)]
    print(f"\ntwo-phase sweep (tee={tee}): {len(longs)} long "
          f"({bucket}-token) prompts bursting into a stream of "
          f"{len(shorts)} short (16-token) ones, slots={max_slots}, "
          f"step budget {step_tokens}")

    def short_req(i):
        # staggered decode lengths so the live-row count never collapses to
        # zero in one step (which would let the long burst flood in early)
        return GenerationRequest(
            prompt=shorts[i], max_new_tokens=6 + (i % 8), priority=0,
            params=SamplingParams(temperature=0.8, top_k=32, seed=100 + i))

    def workload():
        reqs = [short_req(i) for i in range(4)]
        reqs += [GenerationRequest(
                    prompt=p, max_new_tokens=32, priority=0,
                    params=SamplingParams(temperature=0.8, top_k=32, seed=i))
                 for i, p in enumerate(longs)]
        reqs += [short_req(i) for i in range(4, len(shorts))]
        return reqs

    modes = {
        "baseline": {},
        "continuous": dict(continuous_batching=True,
                           step_tokens=step_tokens),
        "two-plan": dict(prefill_plan="dedicated"),
    }
    results, report = {}, {}
    for label, extra in modes.items():
        td = TrustDomain(tee)
        eng = Engine(model, params, max_slots=max_slots, max_len=max_len,
                     trust_domain=td, prefill_buckets=(16, bucket),
                     kv_backend="paged", page_size=16, **extra)
        # warmup wave: pays every (rows, bucket) prefill compile — and, in
        # two-plan mode, the dedicated prefill plan's compile — outside the
        # measured window.
        for r in workload():
            eng.submit(r)
        eng.run(max_steps=100_000)
        td.channel.stats.reset()
        pages0 = getattr(eng.kv, "pages_written", 0)

        t0 = time.monotonic()
        reqs = [eng.submit(r) for r in workload()]
        eng.run(max_steps=200_000)
        wall = time.monotonic() - t0
        assert all(r.finished for r in reqs)
        stats = stats_from_requests(reqs)
        ch = td.channel.stats
        print(f"  {label:10s} {stats.total_tokens:5d} tok  {wall:6.2f}s  "
              f"{stats.throughput_tps:8.1f} tok/s  "
              f"TTFT p50 {stats.p50_ttft_s * 1e3:7.1f}ms "
              f"p99 {stats.p99_ttft_s * 1e3:7.1f}ms  "
              f"handoffs {stats.handoffs:2d} ({stats.handoff_bytes}B)  "
              f"backfills {stats.backfilled_requests:2d}")
        results[label] = dict(outputs=[r.output for r in reqs], stats=stats,
                              ch=ch)
        report[label] = dict(
            tokens_per_s=round(stats.throughput_tps, 1),
            ttft_p50_ms=round(stats.p50_ttft_s * 1e3, 2),
            ttft_p99_ms=round(stats.p99_ttft_s * 1e3, 2),
            sealed_bytes_per_request=ch.seal_bytes // max(len(reqs), 1),
            pages_written=int(getattr(eng.kv, "pages_written", 0) - pages0),
            crossings_per_token=round(
                ch.crossings_per_token if ch.tokens_out else 0.0, 3),
            handoffs=stats.handoffs, handoff_bytes=stats.handoff_bytes,
            backfilled_requests=stats.backfilled_requests)

    base, cb, tp2 = (results[k] for k in modes)
    assert base["outputs"] == cb["outputs"] == tp2["outputs"], \
        "scheduling mode changed decoded output"
    assert cb["stats"].p99_ttft_s < base["stats"].p99_ttft_s, \
        (f"continuous batching must cut TTFT p99 at the burst operating "
         f"point ({cb['stats'].p99_ttft_s * 1e3:.1f}ms vs "
         f"{base['stats'].p99_ttft_s * 1e3:.1f}ms)")
    assert cb["stats"].backfilled_requests > 0, \
        "the burst must actually exercise backfill admission"
    assert tp2["stats"].handoffs > 0 and tp2["stats"].handoff_bytes > 0, \
        "two-plan mode moved no sealed KV across the plan boundary"
    assert tp2["ch"].seal_bytes >= tp2["stats"].handoff_bytes, \
        "handoff bytes must be priced in ChannelStats sealed traffic"
    Path(json_out).write_text(json.dumps(report, indent=2) + "\n")
    print(f"two-phase sweep OK: identical tokens; TTFT p99 "
          f"{base['stats'].p99_ttft_s * 1e3:.1f}ms -> "
          f"{cb['stats'].p99_ttft_s * 1e3:.1f}ms under continuous batching "
          f"({cb['stats'].backfilled_requests} backfills); two-plan handoff "
          f"{tp2['stats'].handoff_bytes}B sealed across the boundary; "
          f"metrics -> {json_out}")


def handoff_batch_sweep(model, params, vocab, *, tee: str):
    """Grouped sealed handoffs on the dedicated prefill plan: the same
    seeded workload served with ``handoff_batch`` ∈ {1, 2, 4} must decode
    byte-identically while sealed plan-boundary crossings per token fall
    monotonically — N finished prefill rows ride one seal/restore pair
    instead of N, the same fixed-cost-per-crossing amortization lever as
    frame coalescing (Insight 10), applied to the KV handoff direction."""
    max_slots, bucket = 4, 16
    rng = np.random.default_rng(43)
    prompts = [rng.integers(1, vocab, size=bucket).astype(np.int32)
               for _ in range(8)]

    def workload():
        return [GenerationRequest(
                    prompt=p, max_new_tokens=8,
                    params=SamplingParams(temperature=0.8, top_k=32, seed=i))
                for i, p in enumerate(prompts)]

    print(f"\nhandoff-batch sweep (tee={tee}, prefill_plan=dedicated, "
          f"batch ∈ [1, 2, 4]): {len(prompts)} requests, slots={max_slots}")
    outputs, curve = [], []
    for batch in (1, 2, 4):
        td = TrustDomain(tee)
        eng = Engine(model, params, max_slots=max_slots, max_len=64,
                     trust_domain=td, prefill_buckets=(bucket,),
                     prefill_plan="dedicated", handoff_batch=batch)
        for r in workload():      # warmup: both plans' compiles
            eng.submit(r)
        eng.run(max_steps=100_000)
        td.channel.stats.reset()
        crossings0 = eng.handoff_crossings

        reqs = [eng.submit(r) for r in workload()]
        eng.run(max_steps=100_000)
        assert all(r.finished for r in reqs)
        stats = stats_from_requests(reqs)
        crossings = eng.handoff_crossings - crossings0
        cpt = crossings / max(stats.total_tokens, 1)
        outputs.append([r.output for r in reqs])
        curve.append(cpt)
        print(f"  batch={batch}  {stats.handoffs:2d} handoffs over "
              f"{crossings:2d} sealed crossings / {stats.total_tokens} tokens"
              f" = {cpt:.4f} crossings/token  ({stats.handoff_bytes}B)")
        assert stats.handoffs == len(reqs), \
            "every request must cross the plan boundary exactly once"
    assert all(o == outputs[0] for o in outputs[1:]), \
        "handoff batching changed decoded output"
    for a, b in zip(curve, curve[1:]):
        assert b <= a, \
            f"crossings/token must fall monotonically with batch, got {curve}"
    assert curve[-1] < curve[0], \
        f"batching must strictly cut sealed crossings, got {curve}"
    print(f"handoff-batch sweep OK: identical tokens, crossings/token "
          f"{' >= '.join(f'{c:.4f}' for c in curve)}")


def long_context_sweep(model, params, vocab, *, tee: str, json_out: str,
                       contexts=(512, 2048, 8192), steps: int = 8,
                       page_size: int = 32):
    """Gather vs kernel paged decode across context lengths.

    One long prompt per context point, decoded ``steps`` tokens under each
    decode mode on an otherwise idle engine — the per-step cost isolates
    the decode path itself: the gather mode rematerializes the full dense
    [L, slots, max_len, ...] view per step (O(capacity)), the kernel mode
    streams only the valid pages through the Pallas table-walk
    (O(context)), so the gap must grow with context. Decoded tokens must
    be identical — the kernel is numerically close, and at these operating
    points the sampled token stream may not diverge. Rows merge under the
    ``long-context`` key of ``json_out``."""
    print(f"\nlong-context sweep (tee={tee}): gather vs kernel paged "
          f"decode, contexts {list(contexts)}, {steps} decode steps")
    report = {}
    for ctx in contexts:
        prompt_len = ctx - 1            # bucket == ctx, one token of room
        rng = np.random.default_rng(ctx)
        prompt = rng.integers(1, vocab, size=prompt_len).astype(np.int32)
        rows, outputs = {}, {}
        for mode in ("gather", "kernel"):
            td = TrustDomain(tee)
            eng = Engine(model, params, max_slots=1,
                         max_len=ctx + 2 * page_size,
                         trust_domain=td, prefill_buckets=(ctx,),
                         kv_backend="paged", page_size=page_size,
                         kv_decode=mode)
            req = eng.submit(GenerationRequest(
                prompt=prompt, max_new_tokens=steps,
                params=SamplingParams(temperature=0.8, top_k=32, seed=17)))
            eng.step()                  # prefill + first sampled token
            eng.step()                  # decode warmup (compile)
            times = []
            while not req.finished:
                t0 = time.monotonic()
                eng.step()
                times.append(time.monotonic() - t0)
            assert req.finish_reason == "stop" or req.finished
            outputs[mode] = list(req.output)
            times.sort()
            p50 = times[len(times) // 2]
            p99 = times[min(len(times) - 1, int(len(times) * 0.99))]
            rows[mode] = dict(
                decode_step_p50_ms=round(p50 * 1e3, 3),
                decode_step_p99_ms=round(p99 * 1e3, 3),
                tokens_per_s=round(len(times) / max(sum(times), 1e-9), 1))
            print(f"  ctx={ctx:5d} {mode:7s} step p50 "
                  f"{rows[mode]['decode_step_p50_ms']:8.2f}ms  p99 "
                  f"{rows[mode]['decode_step_p99_ms']:8.2f}ms  "
                  f"{rows[mode]['tokens_per_s']:8.1f} tok/s")
        assert outputs["gather"] == outputs["kernel"], \
            f"kernel decode changed tokens at ctx={ctx}"
        rows["speedup_p50"] = round(
            rows["gather"]["decode_step_p50_ms"]
            / max(rows["kernel"]["decode_step_p50_ms"], 1e-9), 3)
        report[str(ctx)] = rows
        print(f"  ctx={ctx:5d} identical tokens; kernel speedup "
              f"{rows['speedup_p50']}x (p50)")
    path = Path(json_out)
    data = json.loads(path.read_text()) if path.exists() else {}
    data["long-context"] = report
    path.write_text(json.dumps(data, indent=2) + "\n")
    print(f"long-context sweep rows -> {json_out}")
    return report


def fleet_sweep(model, params, vocab, *, tee: str, requests: int,
                json_out: str):
    """Multi-worker fleet vs one worker vs a forced mid-serve worker kill,
    all over the same seeded two-tenant workload. Outputs must be
    byte-identical across all three (placement and even enclave loss move
    *where* a request decodes, never *what* it decodes — the request's
    sealed KV and seeded sampling state travel), and the kill run must
    price its migration (sealed moves, ciphertext bytes) in FleetStats.
    Rows merge under the ``fleet`` key of ``json_out``."""
    from repro.fleet import EngineWorker, Gateway, Orchestrator

    rng = np.random.default_rng(41)
    prompts = [rng.integers(1, vocab, size=int(l)).astype(np.int32)
               for l in rng.integers(8, 60, size=requests)]

    def workload():
        # fresh objects per run: routing consumes the plaintext prompt
        # (the envelope round-trip replaces it)
        return [GenerationRequest(
                    prompt=p.copy(), max_new_tokens=12,
                    params=SamplingParams(temperature=0.8, top_k=32, seed=i),
                    tenant=f"t{i % 2}")
                for i, p in enumerate(prompts)]

    def serve(n_workers, kill_at=None):
        kw = dict(max_slots=2, max_len=128, prefill_buckets=(16, 32, 64))
        workers = [EngineWorker(f"w{i}", model, params, tee=tee,
                                engine_kw=kw) for i in range(n_workers)]
        gateway = Gateway(config_repr="bench")
        gateway.register_tenant("t0")
        gateway.register_tenant("t1")
        orch = Orchestrator(gateway, workers)
        t0 = time.monotonic()
        handles = [orch.submit(g) for g in workload()]
        step_i = 0
        occ_samples = []       # per-step busy slots / live capacity
        while not orch.idle and step_i < 100_000:
            if step_i == kill_at and len(orch.ready_workers()) > 1:
                victim = max(orch.ready_workers(), key=lambda w: w.load())
                orch.kill(victim.name)
            orch.step()
            live = orch.ready_workers()
            busy = sum(int(np.sum(w.engine._active_mask)) for w in live)
            cap = sum(w.engine.max_slots for w in live)
            if cap:
                occ_samples.append(busy / cap)
            step_i += 1
        wall = time.monotonic() - t0
        assert all(h.finished for h in handles)
        occupancy = float(np.mean(occ_samples)) if occ_samples else 0.0
        return handles, stats_from_requests(handles), orch, wall, occupancy

    print(f"\nfleet sweep (tee={tee}): {requests} requests over 2 tenants, "
          f"2 slots/worker")
    report, outputs = {}, {}
    for label, n, kill in (("workers=1", 1, None), ("workers=2", 2, None),
                           ("workers=2+kill", 2, 4)):
        handles, stats, orch, wall, occupancy = serve(n, kill)
        outputs[label] = [h.output for h in handles]
        fs = orch.stats
        print(f"  {label:15s} {stats.total_tokens:5d} tok  {wall:6.2f}s  "
              f"{stats.throughput_tps:8.1f} tok/s  "
              f"occupancy {occupancy * 100:5.1f}%  "
              f"TTFT p50 {stats.p50_ttft_s * 1e3:7.1f}ms "
              f"p99 {stats.p99_ttft_s * 1e3:7.1f}ms  "
              f"migrations {fs.migrations} ({fs.migrated_bytes}B, "
              f"{fs.kills} kills)")
        report[label] = dict(
            workers=n, tokens_per_s=round(stats.throughput_tps, 1),
            slot_occupancy=round(occupancy, 3),
            ttft_p50_ms=round(stats.p50_ttft_s * 1e3, 2),
            ttft_p99_ms=round(stats.p99_ttft_s * 1e3, 2),
            migrations=fs.migrations, migrated_bytes=fs.migrated_bytes,
            kills=fs.kills)
    # bench note (the workers=2 tokens/s regression vs workers=1): two
    # in-process workers step serially on one host, so wall time per fleet
    # step roughly doubles while per-engine batch occupancy *drops* — the
    # same request count spreads over twice the slots, so each engine
    # decodes with fewer rows per step. The occupancy column quantifies it;
    # real deployments step workers in parallel, where the regression
    # inverts. See the per-worker numbers in the JSON rows.
    report["bench-note"] = (
        "workers=2 throughput trails workers=1 on this single-host bench: "
        "in-process workers step serially, and per-engine occupancy falls "
        f"from {report['workers=1']['slot_occupancy']:.0%} to "
        f"{report['workers=2']['slot_occupancy']:.0%} as the same workload "
        "spreads across twice the slots. Parallel-stepping deployments "
        "recover the difference.")
    print(f"  note: {report['bench-note']}")
    assert outputs["workers=1"] == outputs["workers=2"] \
        == outputs["workers=2+kill"], \
        "fleet placement / worker kill changed decoded output"
    kill_row = report["workers=2+kill"]
    assert kill_row["kills"] == 1, "the kill run must actually kill a worker"
    assert kill_row["migrations"] > 0 and kill_row["migrated_bytes"] > 0, \
        "a mid-serve kill must move sealed KV to the survivor"
    path = Path(json_out)
    data = json.loads(path.read_text()) if path.exists() else {}
    data["fleet"] = report
    path.write_text(json.dumps(data, indent=2) + "\n")
    print(f"fleet sweep OK: identical tokens across 1 worker, 2 workers and "
          f"a mid-serve kill; {kill_row['migrations']} sealed moves / "
          f"{kill_row['migrated_bytes']}B migrated; rows -> {json_out}")


def page_store_sweep(model, params, vocab, *, tee: str, json_out: str):
    """Cold-start RAG workload through the persistent sealed-page store: a
    recurring-prompt mix (one long shared head — the RAG context — plus
    distinct tails) served twice on one engine. The cold epoch prefills and
    publishes every full page at release; the warm epoch finds them
    content-addressed in the store and restores MAC-verified ciphertext
    instead of writing fresh pages. Asserts a nonzero warm hit rate,
    strictly fewer pages written warm, byte-identical decoded tokens, and
    prices the restore-vs-recompute breakeven through the overhead model.
    Rows merge under the ``page-store`` key of ``json_out``."""
    from repro.core.overheads import store_restore_savings
    from repro.runtime.pagestore import SealedPageStore

    max_slots, max_len, bucket, head_len, page_size = 2, 256, 128, 96, 16
    rng = np.random.default_rng(29)
    head = rng.integers(1, vocab, size=head_len).astype(np.int32)
    prompts = [np.concatenate([head, rng.integers(
                   1, vocab, size=bucket - head_len).astype(np.int32)])
               for _ in range(4)]
    store = SealedPageStore(budget_pages=64, policy="cost", profile=tee)
    td = TrustDomain(tee)
    eng = Engine(model, params, max_slots=max_slots, max_len=max_len,
                 trust_domain=td, prefill_buckets=(bucket,),
                 kv_backend="paged", page_size=page_size,
                 prefix_sharing=True, page_store=store)
    print(f"\npage-store sweep (tee={tee}, policy={store.policy}, "
          f"budget={store.budget_pages} pages): {len(prompts)} recurring "
          f"{bucket}-token prompts sharing a {head_len}-token head, "
          f"2 epochs")

    def wave(seed0):
        return [eng.submit(GenerationRequest(
                    prompt=p, max_new_tokens=16,
                    params=SamplingParams(temperature=0.8, top_k=32,
                                          seed=seed0 + i)))
                for i, p in enumerate(prompts)]

    # warmup on DISJOINT prompts, twice: the first pass pays the prefill /
    # decode compiles, the second pays the store-hit restore path's shapes —
    # without seeding the store with the measured wave's content.
    warm_prompts = [rng.integers(1, vocab, size=bucket).astype(np.int32)
                    for _ in range(2)]
    for _ in range(2):
        for i, p in enumerate(warm_prompts):
            eng.submit(GenerationRequest(prompt=p, max_new_tokens=4))
        eng.run(max_steps=100_000)
    td.channel.stats.reset()

    rows, outputs = [], []
    for epoch in ("cold", "warm"):
        pages0 = eng.kv.pages_written
        hits0 = eng.kv.store_hits
        t0 = time.monotonic()
        reqs = wave(500)
        eng.run(max_steps=200_000)
        wall = time.monotonic() - t0
        assert all(r.finished for r in reqs)
        stats = stats_from_requests(reqs)
        pages = eng.kv.pages_written - pages0
        hits = eng.kv.store_hits - hits0
        outputs.append([r.output for r in reqs])
        rows.append(dict(
            epoch=epoch, tokens=stats.total_tokens,
            wall_s=round(wall, 3),
            tokens_per_s=round(stats.throughput_tps, 1),
            pages_written=pages, store_hits=hits,
            hit_rate=round(hits / max(hits + pages, 1), 3)))
        print(f"  {epoch:4s} {stats.total_tokens:5d} tok  {wall:6.2f}s  "
              f"{stats.throughput_tps:8.1f} tok/s  pages written {pages:3d}"
              f"  store hits {hits:3d}  hit rate {rows[-1]['hit_rate']:.0%}")

    cold, warm = rows
    assert outputs[0] == outputs[1], \
        "the store epoch changed decoded output"
    assert warm["store_hits"] > 0, \
        "the warm epoch never hit the store — the tier is dead"
    assert warm["pages_written"] < cold["pages_written"], \
        (f"warm epoch must write strictly fewer pages "
         f"({warm['pages_written']} vs {cold['pages_written']})")
    assert warm["tokens_per_s"] >= 0.85 * cold["tokens_per_s"], \
        (f"warm epoch slowed serving down "
         f"({warm['tokens_per_s']} vs {cold['tokens_per_s']} tok/s)")
    _, _, line = store_restore_savings(
        eng.kv.store_restored_pages, eng.kv.store_restored_bytes,
        eng.kv.store_restored_pages * page_size, tee)
    print(f"  {line}")
    report = dict(
        epochs=rows, policy=store.policy, budget_pages=store.budget_pages,
        publishes=store.publishes, republish_noops=store.republish_noops,
        evictions=store.evictions, resident_pages=store.resident_pages,
        restored_bytes=eng.kv.store_restored_bytes, breakeven=line)
    path = Path(json_out)
    data = json.loads(path.read_text()) if path.exists() else {}
    data["page-store"] = report
    path.write_text(json.dumps(data, indent=2) + "\n")
    print(f"page-store sweep OK: identical tokens; "
          f"{cold['pages_written']}→{warm['pages_written']} pages written, "
          f"warm hit rate {warm['hit_rate']:.0%}; rows -> {json_out}")


def mesh_sweep(model, params, vocab, *, mesh: str, tee: str, max_slots: int,
               requests: int):
    """Single-device vs mesh-spanning engine over one seeded workload:
    byte-identical outputs (dp meshes), then the measured-vs-modeled
    link_tax comparison from the mesh engine's collective counters."""
    dp, tp = parse_mesh(mesh)
    slots = max(max_slots, dp)           # divisible batch => sharded cache
    slots += (-slots) % dp
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, vocab, size=int(l)).astype(np.int32)
               for l in rng.integers(8, 60, size=requests)]
    print(f"\nmesh sweep (single vs {mesh}, tee={tee}, slots={slots}):")

    results = {}
    for label, spec in (("single", None), (mesh, mesh)):
        td = TrustDomain(tee if tee != "none" else "cgpu")
        eng = Engine(model, params, max_slots=slots, max_len=128,
                     trust_domain=td, prefill_buckets=(16, 32, 64),
                     mesh=spec)
        t0 = time.monotonic()
        reqs = [eng.submit(GenerationRequest(
                    prompt=p, max_new_tokens=12,
                    params=SamplingParams(temperature=0.8, top_k=16, seed=i)))
                for i, p in enumerate(prompts)]
        eng.run(max_steps=100_000)
        wall = time.monotonic() - t0
        assert all(r.finished for r in reqs)
        stats = stats_from_requests(reqs)
        print(f"  {label:8s} {stats.total_tokens:6d} tok  {wall:6.2f}s  "
              f"{stats.throughput_tps:8.1f} tok/s")
        results[label] = dict(outputs=[r.output for r in reqs], td=td,
                              plan=eng.plan, stats=stats)

    if tp == 1:
        assert results["single"]["outputs"] == results[mesh]["outputs"], \
            "dp mesh must produce byte-identical outputs"
        print("  outputs byte-identical across the mesh")
    else:
        print("  (tp > 1: outputs numerically equivalent, not bitwise — "
              "TP all-reduce ordering)")

    ch = results[mesh]["td"].channel.stats
    profile = tee if tee != "none" else "cgpu"
    _, _, line = measured_link_tax(
        ch, profile, results[mesh]["stats"].mean_latency_s or 1e-3)
    print(f"  link-tax ({profile}, {PROFILES[profile].link_tax}x): {line}")
    assert ch.collective_steps > 0, "mesh engine recorded no decode steps"
    if dp * tp > 1:
        assert ch.collective_bytes > 0, \
            "a multi-device mesh must move collective bytes"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--tee", default="tdx",
                    choices=["none", "vm", "sgx", "tdx", "cgpu", "tpu_cc"])
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--skip-sweep", action="store_true",
                    help="only run the v1/v2 comparison")
    ap.add_argument("--kv-backend", default="both",
                    choices=["both", "slot", "paged", "none"],
                    help="KV-backend sweep selection ('both' compares and "
                         "asserts; 'none' skips)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged-backend page size for the KV sweep")
    ap.add_argument("--prefix-sharing", default="both",
                    choices=["both", "none"],
                    help="shared-prefix workload sweep: sharing off vs on "
                         "under on-demand allocation ('none' skips)")
    ap.add_argument("--two-phase", default="both",
                    choices=["both", "none"],
                    help="long-prompt-burst sweep: baseline vs step-level "
                         "continuous batching vs disaggregated two-plan "
                         "serving, with BENCH_serve.json emission "
                         "('none' skips)")
    ap.add_argument("--handoff-sweep", default="both",
                    choices=["both", "none"],
                    help="grouped sealed prefill->decode handoffs: "
                         "handoff_batch 1 vs 2 vs 4 on the dedicated plan "
                         "('none' skips)")
    ap.add_argument("--long-context", default="both",
                    choices=["both", "none"],
                    help="gather vs kernel paged-decode sweep over context "
                         "lengths 512/2k/8k, rows merged into the JSON "
                         "report ('none' skips)")
    ap.add_argument("--fleet", default="both", choices=["both", "none"],
                    help="fleet sweep: 1 worker vs 2 vs 2+mid-serve kill, "
                         "rows merged into the JSON report ('none' skips)")
    ap.add_argument("--page-store", default="both", choices=["both", "none"],
                    help="persistent sealed-page store sweep: cold vs warm "
                         "epoch of a recurring-prompt mix, rows merged "
                         "into the JSON report ('none' skips)")
    ap.add_argument("--json-out", default="BENCH_serve.json",
                    help="where the two-phase sweep writes its per-mode "
                         "serving metrics")
    ap.add_argument("--mesh", default=None, metavar="dp=N[,tp=M]",
                    help="also run the mesh sweep: single-device vs "
                         "mesh-spanning engine with measured-vs-modeled "
                         "link-tax comparison")
    args = ap.parse_args()

    if args.mesh is not None:
        from repro.launch.mesh import ensure_host_devices
        dp, tp = parse_mesh(args.mesh)
        ensure_host_devices(dp * tp)
    use_compile_cache()

    cfg, model, params = build_bench_model(d_model=args.d_model,
                                           num_layers=args.layers)
    prompts = make_workload(args.requests, cfg.vocab_size)
    print(f"workload: {args.requests} requests, prompt lens "
          f"{min(map(len, prompts))}-{max(map(len, prompts))}, "
          f"{args.max_new_tokens} new tokens each, tee={args.tee}\n")

    common = dict(max_new_tokens=args.max_new_tokens, tee=args.tee,
                  max_slots=args.max_slots)
    run_config("v1-style", model, params, prompts,
               buckets=(64,), batch_prefill=False, **common)
    run_config("v2", model, params, prompts,
               buckets=(16, 32, 64, 128), batch_prefill=True, **common)
    if not args.skip_sweep:
        sweep_tee = args.tee if args.tee != "none" else "cgpu"
        coalesce_sweep(model, params, prompts, tee=sweep_tee, **{
            k: v for k, v in common.items() if k != "tee"})
    if args.kv_backend != "none":
        backends = (("slot", "paged") if args.kv_backend == "both"
                    else (args.kv_backend,))
        kv_backend_sweep(model, params, cfg.vocab_size,
                         tee=args.tee if args.tee != "none" else "cgpu",
                         max_slots=args.max_slots, requests=args.requests,
                         page_size=args.page_size, backends=backends)
    if args.prefix_sharing != "none":
        prefix_sharing_sweep(model, params, cfg.vocab_size,
                             tee=args.tee if args.tee != "none" else "cgpu",
                             max_slots=args.max_slots,
                             requests=args.requests,
                             page_size=args.page_size)
    if args.two_phase != "none":
        two_phase_sweep(model, params, cfg.vocab_size,
                        tee=args.tee if args.tee != "none" else "cgpu",
                        json_out=args.json_out)
    if args.handoff_sweep != "none":
        handoff_batch_sweep(model, params, cfg.vocab_size,
                            tee=args.tee if args.tee != "none" else "cgpu")
    if args.long_context != "none":
        long_context_sweep(model, params, cfg.vocab_size,
                           tee=args.tee if args.tee != "none" else "cgpu",
                           json_out=args.json_out)
    if args.fleet != "none":
        fleet_sweep(model, params, cfg.vocab_size,
                    tee=args.tee if args.tee != "none" else "cgpu",
                    requests=min(args.requests, 8), json_out=args.json_out)
    if args.page_store != "none":
        page_store_sweep(model, params, cfg.vocab_size,
                         tee=args.tee if args.tee != "none" else "cgpu",
                         json_out=args.json_out)
    if args.mesh is not None:
        mesh_sweep(model, params, cfg.vocab_size, mesh=args.mesh,
                   tee=args.tee, max_slots=args.max_slots,
                   requests=args.requests)


if __name__ == "__main__":
    main()
