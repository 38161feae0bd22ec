"""From when a request was due to the start of the engine step that admitted it (75th percentile)."""

from bench import readers


def read(run):
    waits = [max(0.0, run.steps[r.first_step].t0 - r.due) * 1e3
             for r in run.requests
             if r.spec.in_window and run.in_window(r.due) and r.first_step >= 0]
    return readers.pct(waits, 75)
