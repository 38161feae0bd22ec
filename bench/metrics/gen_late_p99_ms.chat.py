"""How late the generator submitted a request after it was due (99th percentile over the window's requests)."""

from bench import readers


def read(run):
    late = [(r.submit - r.due) * 1e3 for r in run.requests
            if r.spec.in_window and run.in_window(r.due)]
    return readers.pct(late, 99)
