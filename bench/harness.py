"""One run of one benchmark cell, driven by data.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
harness reads ``bench/configs/<config>.json`` (through the entry's
``file``), the model family that file names, ``bench/families/<family>/``
(the program's configuration and weights, the plain reference's layers,
the counts of operations and bytes), ``bench/traffic/<traffic>.json`` and,
in a traced run, ``bench/metrics/<metric>.py`` for every per-layer metric
the cell reports. A new family, model, mix or metric is new files; nothing
here names one.

A run: weights from the seed on the device; in a confidential mode the
trust domain seals them, the plaintext is dropped, ``load_sealed`` brings
them back and the quote is verified; the engine (paged KV, kernel decode)
is built and every shape the mix can produce is warmed; then the window.
The window drives ``Engine.step`` and ``Engine.submit`` as a client
would. Tokens are timed when ``on_token`` fires, after egress has left
the trust domain; latencies count from when a request was due. After the
window the program's state is freed and the plain reference checks the
greedy requests' tokens (``bench/reference.py`` with the family's layers).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import re
import resource
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import loadgen, reference, trace_reduce

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
# a traced run profiles the window's last seconds (and, in an open loop,
# the drain after it); the profiler's cost grows with what it records
TRACE_SECONDS = 15.0
# what a family gives: the program's configuration and weights; the plain
# reference's kind of each layer, weights of one layer, embedding and final
# norm, and its float32 layer forward, embedding lookup, final norm and
# output head; the counts of operations and bytes the per-layer readers use
FAMILY_PARTS = ("model_config", "program_params",
                "layer_kind", "layer", "embedding", "final_norm",
                "layer_forward", "embed", "norm", "logits",
                "decode_step_bytes", "paged_attention_cost",
                "paged_attention_layers", "token_flops", "prefill_flops")


class NoDevice(RuntimeError):
    """The chips the cell asks for are not there."""


def clock() -> float:
    return time.perf_counter()


# ---------------------------------------------------------------------------
# the manifest and the files it names
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    @functools.cached_property
    def family(self):
        """The model family the configuration names, loaded from the
        checkout."""
        return load_family(self.root, self.config["family"])


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json")
                     .read_text())

    def here(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in manifest["end_to_end"] if here(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if m["moves"] in names and here(m)]
    return Cell(workload, w["chips"], w["config"], config, w["traffic"], mix,
                e2e, per_layer, root)


def load_metric(root: Path, name: str):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(root: Path, name: str):
    """``bench/families/<name>/`` as a package of its own (its modules
    import each other relatively), loaded afresh: two checkouts in one
    process may each hold a family of that name."""
    path = root / "bench" / "families" / name
    mod_name = "bench_family_" + re.sub(r"\W", "_", name)
    for k in [k for k in sys.modules
              if k == mod_name or k.startswith(mod_name + ".")]:
        del sys.modules[k]
    spec = importlib.util.spec_from_file_location(
        mod_name, path / "__init__.py", submodule_search_locations=[str(path)])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    missing = [p for p in FAMILY_PARTS if not callable(getattr(mod, p, None))]
    if missing:
        raise AttributeError(f"family {name!r} ({path}) lacks {missing}")
    return mod


# ---------------------------------------------------------------------------
# host-side records
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts programs compiled or fetched from the persistent cache, and
    jaxprs traced, through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.compiles += 1
        elif event == TRACE_EVENT:
            self.traces += 1


class GcPauses:
    """Python's garbage collections, each with its generation and its start
    and end on the host clock."""

    def __init__(self):
        self.log: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = clock()
        else:
            self.log.append((info["generation"], self._t, clock()))

    def close(self):
        gc.callbacks.remove(self._on)


class Spans:
    """Host spans the benchmark opens around calls into the program, kept
    in memory (perf_counter) and, when tracing, in the profiler's trace."""

    def __init__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self.log: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = clock()
        with self._annotation(name):
            yield
        self.log.append((name, t0, clock()))

    def wrap(self, name: str, fn: Callable) -> Callable:
        def wrapped(*a, **kw):
            with self(name):
                return fn(*a, **kw)
        return wrapped


@dataclasses.dataclass
class Rec:
    """One request as the client saw it."""
    spec: loadgen.Spec
    bucket: int
    due: float = 0.0
    submit: float = 0.0
    first: Optional[float] = None
    first_step: int = -1
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    req: Any = None
    error: str = ""

    @property
    def finished(self) -> bool:
        return self.error != "" or (self.req is not None and self.req.finished)


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    tokens: int
    first_tokens: int
    ctx: List[int]
    used_pages: int
    reserved_pages: int


@dataclasses.dataclass
class RunData:
    """What a per-layer metric reader gets."""
    cell: Cell
    seconds: float
    t0: float
    t1: float
    slots: int
    requests: List[Rec]
    steps: List[StepRec]
    spans: List[tuple]
    peaks: dict
    trace: Optional[dict] = None
    trace_window: Optional[tuple] = None     # trace clock, ns
    traced: Optional[tuple] = None           # the same span, host clock
    busy_union: Optional[list] = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def family(self):
        return self.cell.family

    def window_steps(self) -> List[StepRec]:
        return [s for s in self.steps if s.t0 >= self.t0 and s.t1 <= self.t1]

    def traced_steps(self) -> List[StepRec]:
        """Steps inside the traced part of the window."""
        a, b = self.traced
        return [s for s in self.steps if s.t0 >= a and s.t1 <= b]

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t1


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def use_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says); every program is kept,
    however quickly it compiled, so a second run compiles nothing."""
    import os
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_devices(cell: Cell, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoDevice(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                       f"JAX found {len(devs)} {devs[0].platform} device(s)")
    peaks = json.loads((cell.root / "bench" / "peaks.json").read_text())
    kind = devs[0].device_kind
    if kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks["devices"][kind]


def model_config(cell: Cell):
    return dataclasses.replace(cell.family.model_config(cell.config),
                               name=cell.config_name)


@dataclasses.dataclass
class Served:
    engine: Any
    td: Any
    spans: Spans
    model_cfg: Any
    phases: Dict[str, float]


def build(cell: Cell, seed: int) -> Served:
    """Weights, trust domain and engine, as the configuration states."""
    import jax
    from repro.core import TrustDomain
    from repro.models.model import Model
    from repro.runtime.engine import Engine
    mc = model_config(cell)
    model = Model(mc)
    make = cell.family.program_params
    wseed = int(np.random.default_rng([seed, 0]).integers(2 ** 31 - 1))
    shapes = jax.eval_shape(lambda: make(wseed, cell.config))
    want = model.abstract_params()
    if jax.tree.structure(shapes) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(shapes), jax.tree.leaves(want))):
        raise ValueError(f"family {cell.config['family']!r} does not make the "
                         f"program's parameter tree for {cell.config_name}")
    phases, t = {}, clock()
    params = jax.block_until_ready(make(wseed, cell.config))
    phases["weights"] = clock() - t
    td = TrustDomain(cell.config["tee"])
    if td.confidential:
        t = clock()
        sealed = td.seal_params(params)
        del params
        phases["seal"] = clock() - t
        t = clock()
        params = jax.block_until_ready(
            td.load_sealed(sealed, model.abstract_params()))
        del sealed
        phases["load_sealed"] = clock() - t
        t = clock()
        verifier = td.make_verifier(mc.name)
        verifier.verify(td.quote(verifier.challenge(), mc.name))
        phases["attest"] = clock() - t
    t = clock()
    e = cell.config["engine"]
    engine = Engine(model, params, trust_domain=td, kv_backend="paged",
                    kv_decode="kernel", max_slots=e["max_slots"],
                    max_len=e["max_len"], page_size=e["page_size"],
                    num_pages=e["num_pages"],
                    prefill_buckets=tuple(e["prefill_buckets"]),
                    kv_alloc=e["kv_alloc"])
    del params
    phases["engine"] = clock() - t
    spans = Spans()
    td.ingress = spans.wrap("tee.ingress", td.ingress)
    td.egress_tokens = spans.wrap("tee.egress", td.egress_tokens)
    return Served(engine, td, spans, mc, phases)


def bucket_for(buckets, n: int) -> int:
    return next((b for b in sorted(buckets) if n <= b), max(buckets))


def sampling_for(greedy: bool, seed: int, mix: dict):
    from repro.runtime.api import SamplingParams
    if greedy:
        return SamplingParams()
    s = mix["sampling"]
    return SamplingParams(temperature=s["temperature"], top_p=s["top_p"],
                          seed=seed)


def warm_up(served: Served, cell: Cell, traffic: loadgen.Traffic,
            seconds: float, seed: int) -> None:
    """Run every shape the mix can produce once: each prefill bucket its
    prompts reach, at every power-of-two row count up to the slots, with
    the mix's sampler; the all-greedy and sampled decode steps; and, in a
    confidential mode, ingress of every prompt length the mix draws."""
    from repro.runtime.api import GenerationRequest
    from repro.runtime.kvcache import next_pow2
    eng = served.engine
    rng = np.random.default_rng([seed, 2])
    lengths = traffic.lengths(seconds)
    buckets = sorted({bucket_for(eng.prefill_buckets, n) for n in lengths})
    sampled = cell.mix.get("greedy_every", 1) != 1
    vocab = served.model_cfg.vocab_size

    def run(reqs):
        for g in reqs:
            eng.submit(g)
        while not eng.idle:
            eng.step()

    rows = [1 << i for i in range(next_pow2(eng.max_slots).bit_length())]
    for b in buckets:
        for r in rows:
            if r > eng.max_slots:
                continue
            run([GenerationRequest(
                prompt=rng.integers(1, vocab, b).astype(np.int32),
                max_new_tokens=2,
                params=sampling_for(not sampled,
                                    int(rng.integers(2 ** 31 - 1)), cell.mix))
                for _ in range(r)])
    # the decode step with greedy rows only
    run([GenerationRequest(prompt=rng.integers(1, vocab, buckets[0])
                           .astype(np.int32), max_new_tokens=3)])
    if served.td.confidential:
        for n in sorted(lengths):
            served.td.ingress(np.ones(n, np.int32))


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Tally:
    """Tokens the client has received, and the step they came in."""
    emitted: int = 0
    first_emitted: int = 0
    step_index: int = 0


class Driver:
    """Submits requests, steps the engine and keeps the client's records."""

    def __init__(self, served: Served, cell: Cell):
        self.s = served
        self.cell = cell
        self.recs: List[Rec] = []
        self.steps: List[StepRec] = []
        self.tally = Tally()

    def submit(self, spec: loadgen.Spec, due: float) -> Rec:
        from repro.runtime.api import GenerationRequest
        rec = Rec(spec, bucket_for(self.s.engine.prefill_buckets,
                                   len(spec.prompt)), due=due)

        def on_token(req, tok, rec=rec, tally=self.tally):
            t = clock()
            if rec.first is None:
                rec.first = t
                rec.first_step = tally.step_index
                tally.first_emitted += 1
            rec.times.append(t)
            rec.tokens.append(tok)
            tally.emitted += 1

        g = GenerationRequest(prompt=spec.prompt,
                              max_new_tokens=spec.max_new_tokens,
                              params=sampling_for(spec.greedy,
                                                  spec.sample_seed,
                                                  self.cell.mix),
                              on_token=on_token)
        rec.submit = clock()
        try:
            rec.req = self.s.engine.submit(g)
        except ValueError as err:
            rec.error = str(err)
        self.recs.append(rec)
        return rec

    def step(self) -> None:
        eng, tally = self.s.engine, self.tally
        n0, f0 = tally.emitted, tally.first_emitted
        with self.s.spans("engine.step"):
            t0 = clock()
            eng.step()
            t1 = clock()
        kv = eng.kv
        active = list(eng.slots.active)
        self.steps.append(StepRec(
            t0, t1, tally.emitted - n0, tally.first_emitted - f0,
            [int(kv.pos[s]) for s in active],
            sum(-(-int(kv.pos[s]) // kv.page_size) for s in active),
            kv.num_pages - kv.free_page_reserve))
        tally.step_index += 1

    def wait_until(self, t: float) -> None:
        with self.s.spans("gen.wait"):
            while clock() < t:
                time.sleep(min(0.002, max(0.0, t - clock())))


def drive_open(d: Driver, traffic: loadgen.Traffic, seconds: float,
               on_window: Callable[[str], None]):
    """Open loop: arrivals on their schedule from ``lead_s`` before the
    window; every request due inside the window is followed to its end."""
    specs = traffic.schedule(seconds)
    t0 = clock() + traffic.mix["lead_s"]
    t1 = t0 + seconds
    tt = t1 - min(seconds, TRACE_SECONDS)
    i, opened, traced, closed = 0, False, False, False
    window_recs: List[Rec] = []
    while True:
        now = clock()
        if not opened and now >= t0:
            on_window("open")
            opened = True
        if not traced and now >= tt:
            on_window("trace")
            traced = True
        if opened and not closed and now >= t1:
            on_window("close")
            closed = True
        while i < len(specs) and t0 + specs[i].due <= now:
            rec = d.submit(specs[i], t0 + specs[i].due)
            if specs[i].in_window:
                window_recs.append(rec)
            i += 1
        if i == len(specs) and closed and all(r.finished for r in window_recs):
            break
        if not d.s.engine.idle:
            d.step()
            continue
        wake = ([t0 + specs[i].due] if i < len(specs) else []) \
            + ([t0] if not opened else []) + ([tt] if not traced else []) \
            + ([t1] if not closed else [])
        if not wake:
            break
        d.wait_until(min(wake))
    return t0, t1


def drive_closed(d: Driver, traffic: loadgen.Traffic, seconds: float,
                 on_window: Callable[[str], None]):
    """Closed loop: a backlog ``queue_depth`` deeper than the slots is kept
    queued all through; the window opens ``lead_s`` after the start."""
    backlog = traffic.backlog()
    depth = d.s.engine.max_slots + traffic.mix["queue_depth"]
    t0 = clock() + traffic.mix["lead_s"]
    t1 = t0 + seconds
    tt = t1 - min(seconds, TRACE_SECONDS)
    opened = traced = False
    while True:
        now = clock()
        if not opened and now >= t0:
            on_window("open")
            opened = True
        if not traced and now >= tt:
            on_window("trace")
            traced = True
        if now >= t1:
            break
        waiting = sum(1 for r in d.recs if r.first is None and not r.error)
        running = d.s.engine.slots.num_active
        for _ in range(depth - waiting - running):
            d.submit(next(backlog), clock())
        d.step()
    on_window("close")
    return t0, t1


# ---------------------------------------------------------------------------
# end-to-end metrics and the check
# ---------------------------------------------------------------------------

def pct(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values), q)) if len(values) else None


def end_to_end(run: RunData, setup_s: float) -> Dict[str, float]:
    out = {"setup_s": setup_s}
    in_win = [r for r in run.requests if r.spec.in_window and r.first is not None
              and run.in_window(r.due)]
    if run.cell.mix["kind"] == "open":
        out["ttft_p75_ms"] = pct([(r.first - r.due) * 1e3 for r in in_win], 75)
        gaps = [(b - a) * 1e3 for r in run.requests
                for a, b in zip(r.times, r.times[1:]) if run.in_window(b)]
        out["itl_p50_ms"] = pct(gaps, 50)
        out["itl_p95_ms"] = pct(gaps, 95)
    toks = sum(1 for r in run.requests for t in r.times if run.in_window(t))
    out["tokens_per_s"] = toks / run.seconds
    return out


def check(cell: Cell, seed: int, recs: List[Rec], control: bool = False) -> dict:
    """Egress: every finished request's tokens as the client decrypted them
    equal the tokens the engine sampled. Logits: on a sample of finished
    greedy requests drawn from the seed, the longest among them, the
    reference's gap for every served token. With ``control`` the float8
    control takes the program's place: at the same positions, the
    reference's gap for the token the control puts first."""
    done = [r for r in recs if r.req is not None and r.req.finished]
    egress_bad = sum(1 for r in done if r.tokens != list(r.req.output))
    greedy = [r for r in done if r.spec.greedy and r.req.output]
    n = cell.mix["check_requests"]
    rng = np.random.default_rng([seed, 3])
    pick = []
    if greedy:
        longest = max(range(len(greedy)), key=lambda i: len(greedy[i].req.output))
        rest = [i for i in range(len(greedy)) if i != longest]
        pick = [longest] + sorted(rng.permutation(rest)[:n - 1].tolist())
    seqs, starts = [], []
    for i in pick:
        r = greedy[i]
        prompt = np.asarray(r.spec.prompt)[:r.bucket]
        padded = np.concatenate([np.zeros(r.bucket - len(prompt), np.int32),
                                 prompt, np.asarray(r.req.output[:-1], np.int32)])
        seqs.append(padded)
        starts.append(r.bucket)
    # the served token at position p is the one fed at p + 1: append the last
    seqs = [np.concatenate([s, [greedy[i].req.output[-1]]]).astype(np.int32)
            for s, i in zip(seqs, pick)]
    wseed = int(np.random.default_rng([seed, 0]).integers(2 ** 31 - 1))
    res = reference.gaps(cell.family, wseed, cell.config, seqs, starts,
                         control=control) if seqs else []
    gaps = {k: [g for r in res for g in r[k]] for k in ("served", "control")}
    return {"egress_mismatch": egress_bad, "checked_requests": len(seqs),
            "checked_tokens": len(gaps["served"]),
            **gap_numbers(gaps["control" if control else "served"]),
            "gaps": gaps}


# the numbers the reference's gaps give; a configuration's ``checks`` names
# the ones it compares, each with its limit
GAP_NUMBERS = ("logit_gap", "logit_gap_mean")


def gap_numbers(gaps: List[float]) -> dict:
    """The widest gap, and the mean over every checked token."""
    return {"logit_gap": max(gaps) if gaps else None,
            "logit_gap_mean": float(np.mean(gaps)) if gaps else None}


def peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def per_layer(data: RunData) -> Dict[str, dict]:
    """Each of the cell's per-layer metrics from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in data.cell.per_layer:
        value = load_metric(data.cell.root, m["name"]).read(data)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: Path = ROOT, require_tpu: bool = True,
        t_start: Optional[float] = None, log=print,
        control: bool = False, tee: Optional[str] = None) -> dict:
    """One run of one cell. Returns the result object; earlier lines go to
    ``log``, and the compared numbers with their limits are the result's
    ``checks``. With ``control`` the float8 control stands in the
    program's place in the comparison that decides ``correct``, and every
    gap is logged beside the program's numbers. ``tee`` runs the cell's
    configuration in another trust-domain mode."""
    t_start = clock() if t_start is None else t_start
    cell = load_cell(workload, root)
    if tee is not None:
        cell.config = dict(cell.config, tee=tee)
    import jax
    use_cache(root)
    peaks = check_devices(cell, require_tpu)
    counter = CompileCounter()
    pauses = GcPauses()
    served = build(cell, seed)
    traffic = loadgen.Traffic(cell.mix, seed, served.model_cfg.vocab_size)
    t = clock()
    c0 = counter.compiles
    warm_up(served, cell, traffic, seconds, seed)
    served.phases["warm_up"] = clock() - t
    log("setup_phases_s " + json.dumps(served.phases))
    log(f"setup_programs {c0} before warm-up, {counter.compiles - c0} in it")
    served.spans.log.clear()
    d = Driver(served, cell)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    marks, window_span = {}, []

    def on_window(what):
        marks[what] = (counter.compiles, counter.traces, clock())
        if what == "trace" and trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            window_span.append(jax.profiler.TraceAnnotation("bench.window"))
            window_span[0].__enter__()
        elif what == "close" and window_span:
            window_span[0].__exit__(None, None, None)

    drive = drive_open if cell.mix["kind"] == "open" else drive_closed
    t0, t1 = drive(d, traffic, seconds, on_window)
    if trace:
        t = clock()
        jax.profiler.stop_trace()
        log(f"trace_stop_s {clock() - t} trace_start_s "
            f"{marks['trace'][2] - marks['open'][2]} into the window")
    log(f"compiles_in_window {marks['close'][0] - marks['open'][0]}")
    log(f"traces_in_window {marks['close'][1] - marks['open'][1]}")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes()}
    data = RunData(cell, seconds, t0, t1, served.engine.max_slots, d.recs,
                   d.steps, list(served.spans.log), peaks)
    late = [(r.submit - r.due) * 1e3 for r in d.recs if data.in_window(r.due)]
    log(f"generator_late_ms p50 {pct(late, 50)} p99 {pct(late, 99)} "
        f"max {max(late) if late else None}")
    log(f"requests_in_window {len(late)} steps_in_window "
        f"{len(data.window_steps())}")
    # where a window stalls: inside a step, or in a garbage collection
    pauses.close()
    gcs = [(g, b - a) for g, a, b in pauses.log if data.in_window(a)]
    longest = max(data.window_steps(), key=lambda s: s.t1 - s.t0, default=None)
    if longest is not None:
        log(f"longest_step_ms {(longest.t1 - longest.t0) * 1e3} at "
            f"{longest.t0 - t0} s into the window")
    log(f"gc_in_window {len(gcs)} collections, {sum(x for _, x in gcs)} s, "
        f"longest {max((x for _, x in gcs), default=0.0)} s, "
        f"{sum(1 for g, _ in gcs if g == 2)} of generation 2")

    # the program's state goes before the reference runs
    del d, served
    gc.collect()

    breakdown = None
    if trace:
        t = clock()
        rec = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace_load_s {clock() - t}")
        log(f"trace_devices {rec['devices']} ops {len(rec['device_ops'])} "
            f"modules {sorted({n.split('(')[0] for n, _, _ in rec['device_modules']})[:20]}")
        w = trace_reduce.window(rec)
        data.trace, data.trace_window = rec, w
        data.traced = (marks["trace"][2], marks["close"][2])
        data.busy_union = trace_reduce.busy(rec, w)
        device["busy_s"] = (trace_reduce.total(data.busy_union) * 1e-9
                            / max(1, rec["devices"]))
        device["window_s"] = (w[1] - w[0]) * 1e-9
        gaps = trace_reduce.idle_by_span(rec, w, data.busy_union)
        breakdown = {"device_ops": trace_reduce.top_ops(rec, w),
                     "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                                         key=lambda kv: -kv[1])[:10]}
        metrics = per_layer(data)
    else:
        e2e = end_to_end(data, t0 - t_start)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}

    t = clock()
    chk = check(cell, seed, data.requests, control=control)
    log(f"check_s {clock() - t} requests {chk['checked_requests']} "
        f"host_maxrss_kb {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}")
    gaps = chk.pop("gaps")
    log("program " + json.dumps(gap_numbers(gaps["served"])))
    if control:
        log("gaps " + json.dumps(gaps))
    limits = cell.config["checks"]
    checks = {k: {"value": chk[k], "limit": limits[k]}
              for k in GAP_NUMBERS if k in limits}
    checks.update({
        "egress_mismatch": {"value": chk["egress_mismatch"], "limit": 0},
        "checked_tokens": {"value": chk["checked_tokens"],
                           "limit": limits["min_checked_tokens"]},
    })
    log("checks " + json.dumps(checks))
    correct = (all(chk[k] is not None and chk[k] <= limits[k]
                   for k in GAP_NUMBERS if k in limits)
               and chk["egress_mismatch"] == 0
               and chk["checked_tokens"] >= limits["min_checked_tokens"])
    counted = [r for r in data.requests if data.in_window(r.due)]
    failed = sum(1 for r in counted if r.error or (
        r.req.finished and r.req.finish_reason not in ("length", "stop")))
    result = {"correct": bool(correct), "attempted": len(counted),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
