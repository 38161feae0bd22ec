"""Reduction of a profiler trace to the numbers the per-layer metrics read.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain record: the device operations (``XLA Ops`` lines of every
``/device:`` plane), the device's program runs (``XLA Modules``), and the
host spans the benchmark opened with ``TraceAnnotation`` (names listed in
``SPANS``), all as ``[name, start_ns, end_ns]`` on the trace's one clock.
Everything else works on that record, so a test can hand it a small one.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z_]+:\d+$")
SPANS = ("bench.window", "engine.step", "tee.ingress", "tee.egress",
         "gen.wait")
Interval = Tuple[float, float]


def load(trace_dir: str) -> dict:
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    rec = {"device_ops": [], "device_modules": [], "host_spans": [],
           "devices": 0}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = 0
            for line in plane.lines:
                key = {"XLA Ops": "device_ops",
                       "XLA Modules": "device_modules"}.get(line.name)
                if key:
                    events = [[short_name(e.name), e.start_ns, e.end_ns]
                              for e in line.events]
                    rec[key] += events
                    ops += len(events) if key == "device_ops" else 0
            rec["devices"] += ops > 0
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                rec["host_spans"] += [[e.name, e.start_ns, e.end_ns]
                                      for e in line.events
                                      if e.name in SPANS]
    return rec


def short_name(name: str) -> str:
    """An HLO instruction's name without its text: ``%fusion.3 = bf16[..]
    fusion(..)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def window(rec: dict) -> Interval:
    spans = [(s, e) for n, s, e in rec["host_spans"] if n == "bench.window"]
    if len(spans) != 1:
        raise ValueError(f"expected one bench.window span, found {len(spans)}")
    return spans[0]


def clip(items: Sequence, w: Interval) -> List[Interval]:
    return [(max(s, w[0]), min(e, w[1])) for _, s, e in items
            if e > w[0] and s < w[1]]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def busy(rec: dict, w: Interval) -> List[Interval]:
    """Union of device-op intervals inside the window (one chip's plane or
    the overlay of several: callers divide by ``rec['devices']``)."""
    return union(clip(rec["device_ops"], w))


def idle(busy_union: List[Interval], w: Interval) -> List[Interval]:
    out, t = [], w[0]
    for s, e in busy_union:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w[1]:
        out.append((t, w[1]))
    return out


def overlap(union_list: List[Interval], s: float, e: float,
            starts: Optional[List[float]] = None) -> float:
    """Length of ``[s, e)`` covered by a sorted disjoint interval list
    (``starts``: its start points, when the caller already has them)."""
    if starts is None:
        starts = [a for a, _ in union_list]
    i = max(0, bisect.bisect_right(starts, s) - 1)
    got = 0.0
    while i < len(union_list) and union_list[i][0] < e:
        a, b = union_list[i]
        got += max(0.0, min(b, e) - max(a, s))
        i += 1
    return got


def time_by_name(items: Sequence, w: Interval, match) -> float:
    """Summed duration, clipped to the window, of events whose name
    satisfies ``match``."""
    return sum(min(e, w[1]) - max(s, w[0]) for n, s, e in items
               if match(n) and e > w[0] and s < w[1])


def top_ops(rec: dict, w: Interval, n: int = 10) -> List[list]:
    acc: Dict[str, float] = collections.defaultdict(float)
    for name, s, e in rec["device_ops"]:
        if e > w[0] and s < w[1]:
            acc[name] += min(e, w[1]) - max(s, w[0])
    return [[k, v * 1e-9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_span(rec: dict, w: Interval,
                 busy_union: Optional[List[Interval]] = None) -> Dict[str, float]:
    """Idle device time split by what the host was doing: each stretch of
    idle time goes to the innermost benchmark span open over it (spans are
    opened on one thread, so they nest), or to ``other``."""
    if busy_union is None:
        busy_union = busy(rec, w)
    gaps = idle(busy_union, w)
    pts = []
    for name, s, e in rec["host_spans"]:
        if name == "bench.window" or e <= w[0] or s >= w[1]:
            continue
        pts.append((max(s, w[0]), 1, name))
        pts.append((min(e, w[1]), 0, name))
    pts.sort(key=lambda p: (p[0], p[1]))
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[str] = []
    starts = [a for a, _ in gaps]
    t = w[0]
    for at, is_start, name in pts + [(w[1], 0, None)]:
        if at > t:
            label = stack[-1] if stack else "other"
            out[label] += overlap(gaps, t, at, starts)
            t = at
        if name is None:
            break
        if is_start:
            stack.append(name)
        elif name in stack:
            stack.remove(name)
    return {k: v * 1e-9 for k, v in out.items() if v > 0}


def span_self_times(rec: dict, w: Interval, name: str,
                    busy_union: Optional[List[Interval]] = None) -> List[float]:
    """For each ``name`` span inside the window: its length minus the
    device-busy time inside it, in seconds."""
    if busy_union is None:
        busy_union = busy(rec, w)
    starts = [a for a, _ in busy_union]
    return [((e - s) - overlap(busy_union, s, e, starts)) * 1e-9
            for n, s, e in rec["host_spans"]
            if n == name and s >= w[0] and e <= w[1]]
