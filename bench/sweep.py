"""Find an open-loop mix's knee: one process, one set-up, several rates.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> --rates 1.5,2,2.5,3

For each rate the cell's mix runs at that rate (its lead, then the window,
then every request due in the window followed to its end), and one line
reports what was offered and completed, the backlog left queued when the
window closed, and the TTFT percentiles. The knee is the highest rate whose
backlog does not grow through the window.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from bench import harness, loadgen
    cell = harness.load_cell(args.workload, ROOT)
    harness.use_cache(ROOT)
    harness.check_devices(cell, True)
    served = harness.build(cell, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.mix, rate_per_s=rate)
        traffic = loadgen.Traffic(mix, args.seed, served.model_cfg.vocab_size)
        harness.warm_up(served, cell, traffic, args.seconds, args.seed)
        d = harness.Driver(served, cell)
        d.cell = harness.dataclasses.replace(cell, mix=mix)
        queued = {}

        def on_window(what, d=d, queued=queued):
            queued[what] = sum(1 for r in d.recs if r.first is None)

        t0, t1 = harness.drive_open(d, traffic, args.seconds, on_window)
        win = [r for r in d.recs if r.spec.in_window]
        ttft = [(r.first - r.due) * 1e3 for r in win if r.first is not None]
        toks = sum(1 for r in d.recs for t in r.times if t0 <= t < t1)
        done = sum(1 for r in d.recs
                   if r.req is not None and r.req.finished
                   and t0 <= r.req.t_done < t1)
        print(json.dumps({
            "rate_per_s": rate, "arrivals": len(win),
            "completed_per_s": done / args.seconds,
            "tokens_per_s": toks / args.seconds,
            "queued_at_open": queued.get("open"),
            "queued_at_close": queued.get("close"),
            "ttft_p50_ms": harness.pct(ttft, 50),
            "ttft_p75_ms": harness.pct(ttft, 75),
            "ttft_p90_ms": harness.pct(ttft, 90),
            "ttft_max_ms": float(np.max(ttft)) if ttft else None}), flush=True)
        while not served.engine.idle:
            served.engine.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
