#!/usr/bin/env python3
"""Where a cell's round time goes, by the program's own phases and spans.

    python3 bench/phases.py --workload c10x100_fedsgd --seed 7 --seconds 10

The round program names its phases with ``jax.named_scope``
(``fedcd.train``, ``fedcd.aggregate``, ``fedcd.scatter``,
``fedcd.eval_val``, ``fedcd.eval_test``; ``fedcd.eval`` in a standalone
eval program), which reaches each HLO instruction's ``op_name``. The
control plane records host spans named ``fedcd.*`` and per-round
counters (``repro.spans``); while the recorder is on each span is also
an annotation in the profiler's trace, on the device's clock.

This script runs a cell's set-up and warm-up rounds and a window of
``--seconds`` under the profiler, as ``run.py --trace 1`` does but
without the harness's own spans and correctness comparison, with the
recorder on. It reads each program's ``op_name`` metadata from its
compiled HLO, by instruction name (the trace's device operations carry
none), and prints one JSON line: the window's device self time by
phase scope (``device_by_scope``, plus ``unscoped``), its device idle
time by the innermost program span covering it (``idle_by_span``, else
``other``), the mean lag from ``fedcd.launch.dispatch`` to the device's
next operation, the mean host milliseconds per round of each program
span, and the counters. The reductions are checked on the CPU in
``tests/``.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.trace import (OPS_LINE, WINDOW, Event, _clip,  # noqa: E402
                         self_times, union)

SCOPE_PREFIX = "fedcd."
MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
OTHER = "other"
DISPATCH = "fedcd.launch.dispatch"


def op_scope(op_name: str) -> str:
    """The innermost ``fedcd.*`` component of an HLO ``op_name`` path
    (``jit(round_step)/fedcd.train/while/body/...``), else
    ``unscoped``."""
    inner = [p for p in op_name.split("/") if p.startswith(SCOPE_PREFIX)]
    return inner[-1] if inner else UNSCOPED


OP_NAME = re.compile(r'op_name="([^"]*)"')
INSTR = re.compile(r"^\s+(ROOT )?%([\w.\-]+) = ")
CALLS = re.compile(r"calls=%([\w.\-]+)")


def hlo_op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """The module name of a compiled program's HLO text
    (``compile().as_text()``) and each instruction's ``op_name``. A
    fusion with none takes its fused computation's root's, else that of
    the first of its instructions in a ``fedcd.*`` scope; an instruction
    the compiler made (a copy, a buffer) has none."""
    module = re.search(r"^HloModule ([^\s,]+)", hlo_text, re.M).group(1)
    names: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    root: Dict[str, str] = {}
    members: Dict[str, List[str]] = defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        m = INSTR.match(line)
        if m is None or comp is None:
            continue
        name = m.group(2)
        op = OP_NAME.search(line)
        names[name] = op.group(1) if op else ""
        members[comp].append(name)
        if m.group(1):
            root[comp] = name
        c = CALLS.search(line)
        if c:
            calls[name] = c.group(1)
    for name, comp in calls.items():
        if names[name]:
            continue
        inner = [names.get(root.get(comp), "")] + [
            names[i] for i in members[comp]
            if op_scope(names[i]) != UNSCOPED]
        names[name] = next((o for o in inner if o), "")
    return module, names


def name_ops(events: List[Event],
             op_names: Dict[str, Dict[str, str]]) -> List[Event]:
    """``events`` (``trace.load_events``) with each device operation
    named by the phase scope (``op_scope``) of its ``op_name``, looked
    up by its HLO instruction name in ``op_names[module]`` of the
    program (``XLA Modules`` event on its plane) it ran in."""
    programs: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    for plane, line, name, s, d in events:
        if line == MODULES_LINE:
            programs[plane].append((s, s + d, name.split("(")[0]))
    starts = {}
    for plane, progs in programs.items():
        progs.sort()
        starts[plane] = [p[0] for p in progs]
    out: List[Event] = []
    for plane, line, name, s, d in events:
        if plane.startswith("/device:") and line == OPS_LINE:
            progs = programs.get(plane, [])
            i = bisect.bisect_right(starts.get(plane, []), s) - 1
            module = progs[i][2] if i >= 0 and s < progs[i][1] else None
            instr = name.split(" = ")[0].lstrip("%")
            name = op_scope(op_names.get(module, {}).get(instr, ""))
        out.append((plane, line, name, s, d))
    return out


def _window(events: List[Event]):
    """The window's bounds, its device operations by plane
    ``(start, end, name)`` and the ``fedcd.*`` host spans
    ``(name, start, end)``."""
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
                ops[plane].append((cs, ce, name))
    if not ops:
        raise ValueError("no device operation in the window")
    spans = [(name, s, s + d) for plane, _, name, s, d in events
             if name.startswith(SCOPE_PREFIX)
             and not plane.startswith("/device:")]
    return lo, hi, ops, spans


def device_by_scope(events: List[Event]) -> Dict[str, float]:
    """Seconds of device self time in the window by phase scope, where
    each device operation is named by its scope (``name_ops``);
    averaged over the device planes like ``trace.reduce``'s busy time,
    so the values add up to it."""
    _, _, ops, _ = _window(events)
    by: Dict[str, float] = defaultdict(float)
    for iv in ops.values():
        for scope, t in self_times(iv):
            by[scope] += t
    return {k: v * 1e-9 / len(ops) for k, v in sorted(by.items())}


def idle_by_span(events: List[Event]) -> Dict[str, float]:
    """Seconds of the window in which no operation runs on the first
    device, each instant put down to the innermost ``fedcd.*`` host span
    covering it (of those covering it, the one that started last, and
    of those the one that ends first), else ``other``."""
    lo, hi, ops, spans = _window(events)
    gaps = []
    prev = lo
    for s, e in union((s, e) for s, e, _ in ops[min(ops)]) + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    bounds = sorted({b for _, s, e in spans for b in (s, e) if lo < b < hi}
                    | {b for g in gaps for b in g})
    starts = sorted(spans, key=lambda sp: sp[1])
    by: Dict[str, float] = defaultdict(float)
    gi = si = 0
    live: List[Tuple[str, float, float]] = []
    for a, b in zip(bounds, bounds[1:]):
        while gi < len(gaps) and gaps[gi][1] <= a:
            gi += 1
        if gi == len(gaps):
            break
        if b <= gaps[gi][0]:
            continue                      # [a, b] lies before the gap
        while si < len(starts) and starts[si][1] <= a:
            live.append(starts[si])
            si += 1
        live = [sp for sp in live if sp[2] > a]
        name = (max(live, key=lambda sp: (sp[1], -sp[2]))[0] if live
                else OTHER)
        by[name] += b - a
    return {k: v * 1e-9 for k, v in sorted(by.items())}


def dispatch_lags(events: List[Event]) -> List[float]:
    """Seconds from the start of each ``fedcd.launch.dispatch`` span in
    the window to the first operation that starts after it on the first
    device: the start of the program that dispatch enqueued, where the
    device was idle when it came."""
    lo, hi, ops, spans = _window(events)
    starts = sorted(s for s, _, _ in ops[min(ops)])
    lags = []
    for name, s, _ in spans:
        if name != DISPATCH or not lo <= s < hi:
            continue
        i = bisect.bisect_left(starts, s)
        if i < len(starts):
            lags.append((starts[i] - s) * 1e-9)
    return lags


def reduce_phases(events: List[Event]) -> Dict:
    """{"device_by_scope", "idle_by_span", "dispatch_lag_s"} of one
    trace whose device operations are named by scope."""
    return {"device_by_scope": device_by_scope(events),
            "idle_by_span": idle_by_span(events),
            "dispatch_lag_s": dispatch_lags(events)}


# -- the program's own records ---------------------------------------------

def span_ms(recorded, n_rounds: int) -> Dict[str, float]:
    """Mean host milliseconds per round of each program span."""
    by: Dict[str, float] = defaultdict(float)
    for s in recorded.spans:
        by[s.name] += (s.end_ns - s.start_ns) * 1e-6
    return {k: v / n_rounds for k, v in sorted(by.items())}


def longest_rounds(recorded, k: int = 3) -> List[Dict]:
    """The ``k`` rounds whose root span ``fedcd.round`` ran longest, each
    with the seconds of its program spans by name and its counters."""
    by_round: Dict[int, Dict[str, float]] = {}
    for s in recorded.spans:
        names = by_round.setdefault(s.round, {})
        names[s.name] = names.get(s.name, 0.0) + (
            s.end_ns - s.start_ns) * 1e-9
    out = [{"round": r, **names, "counts": recorded.counts.get(r, {})}
           for r, names in by_round.items() if "fedcd.round" in names]
    return sorted(out, key=lambda d: -d["fedcd.round"])[:k]


def counter_runs(counts: Dict) -> List[list]:
    """Each round's counters as ``[first, last, counters]`` runs of
    consecutive rounds that counted the same."""
    runs: List[list] = []
    for r in sorted(c for c in counts if c is not None):
        if runs and runs[-1][1] == r - 1 and runs[-1][2] == counts[r]:
            runs[-1][1] = r
        else:
            runs.append([r, r, counts[r]])
    return runs


# -- a traced window with the recorder on -----------------------------------

PROGRAMS = ("_round", "_train", "_apply", "_finish", "_eval", "_pair_eval")


def _record_programs(executor, seen: Dict) -> None:
    """Record in ``seen`` each jitted round program the executor calls,
    once per argument signature, to read its compiled HLO after the
    window."""
    import jax

    def struct(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                    sharding=getattr(a, "sharding", None))

    for attr in PROGRAMS:
        program = getattr(executor, attr)

        def recorded(*args, _program=program):
            structs = jax.tree.map(struct, args)
            key = (id(_program), str(structs))
            if key not in seen:
                seen[key] = (_program, structs)
            return _program(*args)

        setattr(executor, attr, recorded)


def run_phases(spec: Dict, seed: int, seconds: float, keep: str = None,
               log=lambda *a: print(*a, file=sys.stderr, flush=True)
               ) -> Dict:
    """One traced window of the cell with the recorder on, reduced.
    ``keep``: a directory to copy the trace and the programs' HLO to."""
    import jax

    from bench import data, run, trace
    from bench.runners.fedcd_fused import bank_tree, server_config
    from repro import spans
    from repro.core.fedcd import FedCDServer
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.cnn import cnn_accuracy, cnn_loss

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the compile cache ignores op_name metadata by default: a program
    # cached before the scopes existed would run without them
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    run.check_chips(spec["cell"]["chips"])
    config, traffic = spec["config"], spec["traffic"]
    cfg, _ = server_config(config, traffic, seed)
    srv = FedCDServer(cfg, data.make_params(seed, config["model"]),
                      cnn_loss, cnn_accuracy,
                      data.make_data(seed, config["partition"]),
                      batch_size=config["batch_size"], spec="fused")
    programs: Dict = {}
    _record_programs(srv.executor, programs)
    t = 0
    for _ in range(traffic["compare_rounds"] + traffic["warm_rounds"]):
        t += 1
        srv.run_round(t)
    jax.block_until_ready(bank_tree(srv))
    programs.clear()

    trace_dir = tempfile.mkdtemp(prefix="bench_phases_")
    try:
        spans.enable()
        spans.drain()
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(WINDOW):
            w0 = time.perf_counter()
            n = 0
            while True:
                n += 1
                srv.run_round(t + n)
                if time.perf_counter() - w0 >= seconds:
                    break
            jax.block_until_ready(bank_tree(srv))
            w1 = time.perf_counter()
        jax.profiler.stop_trace()
        recorded = spans.drain()
        spans.disable()
        t0 = time.perf_counter()
        texts = [program.lower(*structs).compile().as_text()
                 for program, structs in programs.values()]
        op_names = dict(hlo_op_names(text) for text in texts)
        del srv
        events = trace.load_events(trace_dir)
        reduced = trace.reduce(events)
        phases = reduce_phases(name_ops(events, op_names))
        log(f"phases: reduced in {time.perf_counter() - t0:.1f} s")
        if keep:
            os.makedirs(keep, exist_ok=True)
            for path in glob.glob(os.path.join(trace_dir, "**", "*.pb"),
                                  recursive=True):
                shutil.copy(path, keep)
            for i, text in enumerate(texts):
                Path(keep, f"program_{i}.hlo.txt").write_text(text)
    finally:
        spans.disable()
        shutil.rmtree(trace_dir, ignore_errors=True)
    lags = phases["dispatch_lag_s"]
    return {
        "workload": spec["cell"]["name"], "seed": seed, "rounds": n,
        "round_s": (w1 - w0) / n, "window_s": reduced["window_s"],
        "busy_s": reduced["busy_s"], "idle_share": reduced["idle_share"],
        "device_by_scope": phases["device_by_scope"],
        "idle_by_span": phases["idle_by_span"],
        "dispatch_lag_ms": (1e3 * sum(lags) / len(lags)) if lags else None,
        "span_ms": span_ms(recorded, n),
        "counts": counter_runs(recorded.counts),
        "longest_rounds": longest_rounds(recorded),
        "programs": sorted(op_names),
        "device": jax.devices()[0].device_kind}


def main(argv=None) -> int:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", metavar="DIR",
                    help="also copy the trace and the programs' HLO here")
    args = ap.parse_args(argv)
    try:
        out = run_phases(run.load_cell(args.workload), args.seed,
                         args.seconds, keep=args.keep)
    except run.NoChip as e:
        print(f"phases.py: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
