"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is reduced from a flat list of events ``(plane, line, name,
start_ns, duration_ns)``, all on the profiler's one clock, so the
reduction can be checked on a small recorded list (``tests/``).

* Device busy time is the union of the intervals of the operations on
  the device planes' ``XLA Ops`` lines, clipped to the window: the span
  named ``WINDOW`` that the harness records on the host around the
  measured rounds. Averaged over the device planes.
* An operation's time is its self time: its interval less those of the
  operations nested in it on the same line (a ``while`` loop's event
  spans its body's operations). It is named by its HLO instruction name,
  the part of the event name before `` = ``.
* An idle gap is an interval of the window in which no operation runs on
  the first device. It is named by the harness's host span that covers
  most of it (``plan``, ``launch``, ``sample``, ``readback``,
  ``lifecycle``, ``collect``), or ``other``.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
HOST_SPANS = ("plan", "launch", "sample", "readback", "lifecycle",
              "collect")

Event = Tuple[str, str, str, float, float]


def load_events(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [(plane.name, line.name, ev.name, float(ev.start_ns),
             float(ev.duration_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Sorted, merged ``[start, end]`` intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def self_times(intervals) -> List[Tuple[str, float]]:
    """``(name, self time)`` of each ``(start, end, name)`` interval of
    one line, where an interval that lies inside another is its child."""
    out: List[List] = []
    stack: List[Tuple[float, int]] = []          # (end, index in out)
    for s, e, name in sorted(intervals, key=lambda i: (i[0], -i[1])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(e, stack[-1][0]) - s
        out.append([name, e - s])
        stack.append((e, len(out) - 1))
    return [(n, t) for n, t in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(events: List[Event], top: int = 10) -> Dict:
    """{"window_s", "busy_s", "idle_share", "device_ops", "idle_gaps",
    "n_devices"}; ``device_ops`` and ``idle_gaps`` are the ``top``
    largest ``[name, seconds]``."""
    windows = [(s, s + d) for plane, _, name, s, d in events
               if name == WINDOW and not plane.startswith("/device:")]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    ops: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    for plane, line, name, s, d in events:
        if plane.startswith("/device:") and line == OPS_LINE:
            for cs, ce in _clip([(s, s + d)], lo, hi):
                ops[plane].append((cs, ce, name.split(" = ")[0]))
    if not ops:
        raise ValueError("no device operation in the window")
    op_time: Dict[str, float] = defaultdict(float)
    for iv in ops.values():
        for name, t in self_times(iv):
            op_time[name] += t
    busy = {p: sum(e - s for s, e in union((s, e) for s, e, _ in iv))
            for p, iv in ops.items()}
    busy_ns = sum(busy.values()) / len(busy)

    spans = [(name, s, s + d) for plane, _, name, s, d in events
             if name in HOST_SPANS and not plane.startswith("/device:")]
    gaps = []
    prev = lo
    for s, e in union((s, e) for s, e, _ in ops[min(ops)]) + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = []
    for gs, ge in gaps:
        cover: Dict[str, float] = defaultdict(float)
        for name, s, e in spans:
            cover[name] += max(0.0, min(e, ge) - max(s, gs))
        best = max(cover, key=cover.get) if cover else None
        named.append([best if best and cover[best] > 0 else "other",
                      (ge - gs) * 1e-9])
    named.sort(key=lambda g: -g[1])
    device_ops = sorted(([n, t * 1e-9] for n, t in op_time.items()),
                        key=lambda o: -o[1])
    window_ns = hi - lo
    return {"window_s": window_ns * 1e-9, "busy_s": busy_ns * 1e-9,
            "idle_share": 1.0 - busy_ns / window_ns,
            "device_ops": device_ops[:top], "idle_gaps": named[:top],
            "n_devices": len(ops)}
