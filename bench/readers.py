"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``.

Each reader takes the run's records (``harness.RunData``) and returns a
number, or None when the run holds nothing for it to read. Operations and
bytes come from the counts of the model family the configuration names
(``run.family``).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from bench import trace_reduce


def pct(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values), q)) if len(values) else None


def decode_steps(run):
    """Steps of the traced window in which rows decoded (the live rows'
    contexts), to set beside the trace's device times."""
    return [s for s in run.traced_steps()
            if s.ctx and s.tokens > s.first_tokens]


def device_time(run, items: str, match) -> Optional[float]:
    """Seconds of device events (``device_ops`` or ``device_modules``)
    whose name satisfies ``match``, per chip, in the traced window."""
    if run.trace is None:
        return None
    t = trace_reduce.time_by_name(run.trace[items], run.trace_window, match)
    return t * 1e-9 / max(1, run.trace["devices"]) if t > 0 else None


def is_decode_module(name: str) -> bool:
    return "decode_kernel" in name


def is_prefill_module(name: str) -> bool:
    return "prefill" in name


def is_paged_attention(name: str) -> bool:
    return name.startswith("paged_attention")


def decode_hbm_share(run) -> Optional[float]:
    """Bytes the decode steps must read at the least, over the bytes the
    chip could read in the decode program's device time."""
    t = device_time(run, "device_modules", is_decode_module)
    steps = decode_steps(run)
    if t is None or not steps:
        return None
    need = sum(run.family.decode_step_bytes(run.config, s.ctx)
               for s in steps)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / t


def paged_attn_roofline(run) -> Optional[float]:
    """The paged-attention kernel's least time, from the live tokens'
    operations and bytes, over its device time."""
    t = device_time(run, "device_ops", is_paged_attention)
    steps = decode_steps(run)
    if t is None or not steps:
        return None
    layers = run.family.paged_attention_layers(run.config)
    least = 0.0
    for s in steps:
        flops, nbytes = run.family.paged_attention_cost(run.config, s.ctx)
        least += layers * max(flops / run.peaks["bf16_flops_per_s"],
                              nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t


def step_mfu(run) -> Optional[float]:
    """Model operations for every prompt and output token the window
    processed, over the window's seconds times the bf16 peak."""
    steps = run.window_steps()
    if not steps:
        return None
    flops = sum(run.family.token_flops(run.config, c, True) for s in steps
                if s.ctx and s.tokens > s.first_tokens for c in s.ctx)
    first = {i for i, s in enumerate(run.steps)
             if s.t0 >= run.t0 and s.t1 <= run.t1}
    flops += sum(run.family.prefill_flops(run.config, len(r.spec.prompt))
                 for r in run.requests if r.first_step in first)
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops_per_s"])


def device_idle_share(run) -> Optional[float]:
    if run.trace is None or not run.trace["devices"]:
        return None
    w = run.trace_window
    busy = trace_reduce.total(run.busy_union) / run.trace["devices"]
    return 100.0 * (1.0 - busy / (w[1] - w[0]))
