"""One traced run of a benchmark cell with the program's own spans on.

    python3 bench/trace_program.py --workload <name> --seed <n> --seconds <s>

The run is ``bench/run.py --trace 1``'s: the same set-up, warm-up, traffic
and profiled last seconds of the window. In addition ``repro.obs`` is
enabled while the profiler runs, its counters and the channel's are read at
the trace mark and at the window's close, and the log reports the split the
program's spans give: idle device time by innermost program span, jaxpr
traces by span, and the numbers of ``bench/program_spans.py`` beside the
cell's per-layer metrics from the same trace. No reference check: this run
measures and decides nothing. The last line of standard output is a JSON
object of every number.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def run(workload: str, seed: int, seconds: float, root: Path = ROOT,
        require_tpu: bool = True, log=print) -> dict:
    import jax
    from bench import harness, loadgen, program_spans as ps, trace_reduce
    from repro import obs
    cell = harness.load_cell(workload, root)
    harness.use_cache(root)
    peaks = harness.check_devices(cell, require_tpu)
    served = harness.build(cell, seed)
    traffic = loadgen.Traffic(cell.mix, seed, served.model_cfg.vocab_size)
    harness.warm_up(served, cell, traffic, seconds, seed)
    served.spans.log.clear()
    d = harness.Driver(served, cell)
    stats = served.td.channel.stats
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    marks, snaps, window_span = {}, {}, []

    def snapshot():
        return {**obs.counters(), **dataclasses.asdict(stats)}

    def on_window(what):
        marks[what] = harness.clock()
        if what == "trace":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            obs.enable()
            snaps["trace"] = snapshot()
            window_span.append(jax.profiler.TraceAnnotation("bench.window"))
            window_span[0].__enter__()
        elif what == "close" and window_span:
            snaps["close"] = snapshot()
            window_span[0].__exit__(None, None, None)

    drive = harness.drive_open if cell.mix["kind"] == "open" \
        else harness.drive_closed
    t0, t1 = drive(d, traffic, seconds, on_window)
    obs.disable()
    jax.profiler.stop_trace()
    data = harness.RunData(cell, seconds, t0, t1, served.engine.max_slots,
                           d.recs, d.steps, list(served.spans.log), peaks)
    del d, served
    rec = trace_reduce.load(trace_dir)
    rec.update(ps.load(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    w = trace_reduce.window(rec)
    data.trace, data.trace_window = rec, w
    data.traced = (marks["trace"], marks["close"])
    data.busy_union = busy = trace_reduce.busy(rec, w)
    delta = ps.counter_delta(snaps["trace"], snaps["close"])
    a, b = data.traced
    tokens = sum(1 for r in data.requests for t in r.times if a <= t < b)
    log(f"steps_in_window {len(data.window_steps())} traced_steps "
        f"{len(data.traced_steps())} traced_tokens {tokens}")
    log("idle_by_span " + json.dumps(trace_reduce.idle_by_span(rec, w, busy)))
    log("idle_by_program_span " + json.dumps(
        ps.idle_by_program_span(rec, w, busy)))
    log("traces_by_span " + json.dumps(ps.traces_by_span(delta)))
    log("counters " + json.dumps(delta))
    program = {
        "egress_mac_ms_per_token": ps.egress_mac_ms_per_token(rec, w, tokens),
        "egress_kernel_ms_per_token":
            ps.egress_kernel_ms_per_token(rec, w, tokens),
        "egress_cipher_bytes_per_token":
            ps.egress_cipher_bytes_per_token(delta),
        "tee_ms_per_token": ps.tee_ms_per_token(rec, w, tokens),
        "step_prep_ms": ps.step_prep_ms(rec, w, busy),
        "emit_idle_ms": ps.emit_idle_ms(rec, w, busy),
        "idle_in_tee_share": ps.idle_in_tee_share(rec, w, busy),
        "traces_per_kstep": ps.traces_per_kstep(delta),
    }
    accepted = {k: v["value"] for k, v in harness.per_layer(data).items()}
    return {"program": program, "per_layer": accepted,
            "steps_in_window": len(data.window_steps())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    result = run(args.workload, args.seed, args.seconds,
                 log=lambda s: print(s, flush=True))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
