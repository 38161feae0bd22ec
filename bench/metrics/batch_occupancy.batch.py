"""Rows that decoded per window step, over the slots."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 100.0 * sum(s.tokens - s.first_tokens for s in steps) / (
        len(steps) * run.slots)
