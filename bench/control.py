"""Run a cell with the float8 control in the program's place in the check.

    python3 bench/control.py --workload <name> --seeds <n>[,<n>...] --seconds <s> [--tee none] [--control 0]

Runs the cell as ``bench/run.py`` does, once per seed in one process; the
comparison that decides ``correct`` then reads, at the served requests'
positions, the reference's gap for the token that the reference with
every matrix product's operands rounded to float8 (the precision below the
configuration's bfloat16, ``bench/reference.py``) puts first, against the
same limits. A sound limit makes this ``correct: false``. The same
reference pass reads the program's numbers too, so one run gives both
readings a limit is set from. ``--tee none`` runs the configuration with
the trust domain off: the unsealed weights are bit-identical, so the
numbers are the same and set-up is shorter. ``--control 0`` reads the
program's numbers alone, which saves the control's forward. Prints one
JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tee", default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    for seed in [int(s) for s in args.seeds.split(",")]:
        lines = []

        def log(s, lines=lines):
            lines.append(s)
            print(s, flush=True)

        result = harness.run(args.workload, seed, args.seconds, False,
                             root=ROOT, t_start=time.perf_counter(),
                             control=bool(args.control), tee=args.tee,
                             log=log)
        program = next(json.loads(s.split(" ", 1)[1]) for s in lines
                       if s.startswith("program "))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "tee": args.tee, "control": bool(args.control),
                          "correct": result["correct"],
                          "checks": result["checks"], "program": program}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
