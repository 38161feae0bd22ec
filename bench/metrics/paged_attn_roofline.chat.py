"""The paged-attention kernel's least time from its live tokens' operations and bytes, over its device time."""

from bench import readers


def read(run):
    return readers.paged_attn_roofline(run)
