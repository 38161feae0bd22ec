"""Bytes the decode steps must read, over what the chip's bandwidth moves in the decode program's device time."""

from bench import readers


def read(run):
    return readers.decode_hbm_share(run)
