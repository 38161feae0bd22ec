"""Model operations of every prompt and output token in the window, over the window times the bf16 peak."""

from bench import readers


def read(run):
    return readers.step_mfu(run)
