#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 bench/run.py --workload c10x100_fedsgd --seed 7 --seconds 10 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``: the system's sizes and settings) and a
traffic mix (``bench/traffic/<traffic>.json``: the schedule, how many
rounds are compared and warmed). The configuration's ``"runner"`` key
names the runner, ``bench/runners/<runner>.py``, that builds the system
through its entry point (``fedcd_fused``, FedCD mode A, where the key is
absent; the runner's contract is in ``runners/__init__.py``). The run:

1. the runner makes the data and the weights on the device from
   ``--seed``, builds the system with ``Probe`` attached and runs its
   first rounds, recording what the compared rounds produced;
2. runs the traffic's warm-up rounds;
3. runs rounds for ``--seconds`` (the measured window), counting
   compiles;
4. has the runner free the system, and compares the recorded rounds
   with the runner's plain reference (``judge``); a compared round
   whose program (``session.programs``) the window did not run counts
   against ``correct``.

The set-up clock, the warm-up and the window, the compile count, the
profiler trace and its reduction, the memory peak, the verdict against
``bench/limits/<cell>.json``, the metrics and the result line are all
here, the same for every runner. With ``--trace 1`` the window runs
under the JAX profiler and the line carries the per-layer metrics; with
``--trace 0`` the end-to-end ones. Each metric is read by
``bench/metrics/<name>.py``. The last line of standard output is one
JSON object; a run on a machine whose JAX finds no TPU, or fewer chips
than the cell asks for, or whose configuration names no runner, exits 1
and prints none.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from bench import correct, trace  # noqa: E402
# ``bench/tests/test_correct.py`` reads mode A's ``run.server_config``
from bench.runners.fedcd_fused import server_config  # noqa: E402,F401

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


DEFAULT_RUNNER = "fedcd_fused"


class NoChip(RuntimeError):
    pass


class BadCell(ValueError):
    pass


# -- the cell, found by name ----------------------------------------------

def load_cell(name: str) -> Dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(m):
        return name in m.get("workloads", [name])

    spec = {
        "cell": cell,
        "config_file": cfg["file"],
        "config": json.loads((ROOT / cfg["file"]).read_text()),
        "traffic": json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads(
            (BENCH / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }
    runner_of(spec)
    return spec


# -- the runner, found by the configuration's "runner" key --------------

def runner_of(spec: Dict):
    """``bench.runners.<runner>`` of the cell's configuration."""
    name = spec["config"].get("runner", DEFAULT_RUNNER)
    module = f"bench.runners.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise BadCell(
            f'{spec.get("config_file", "the configuration")}: "runner": '
            f"{name!r} names no module bench/runners/{name}.py") from None


# -- the harness's own spans around the system's calls ---------------------

class Probe:
    """Host spans named ``plan``, ``launch`` and ``readback`` around the
    three calls that every FedCD entry point makes each round
    (``planner.build``, ``executor.launch``, ``executor.readback``), a
    record of each launched round (``{"round": plan.round,
    **describe(plan)}``), and a count of compiles while ``counting``. A
    runner attaches it to the system it builds and may span calls of its
    own with ``wrap``."""

    def __init__(self, system, annotate: bool, describe):
        self.system = system
        self.annotate = annotate
        self.spans: List[tuple] = []
        self.rounds: List[Dict] = []
        self.compiles = 0
        self.compile_s = 0.0
        self.counting = False
        self.plan = None
        self._round = 0
        ex = system.executor
        self.wrap(system.planner, "build", "plan")
        launch = ex.launch

        def on_launch(plan):
            self.plan = plan
            self.rounds.append({"round": plan.round, **describe(plan)})
            with self.span("launch"):
                return launch(plan)

        ex.launch = on_launch
        self.wrap(ex, "readback", "readback")
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        self.system = None

    def _on_event(self, event, duration, **_):
        if self.counting and event in COMPILE_EVENTS:
            self.compiles += 1
            self.compile_s += duration

    @contextlib.contextmanager
    def span(self, name: str):
        ann = (jax.profiler.TraceAnnotation(name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.append((name, self._round, t0, time.perf_counter()))

    def spanned(self, fn, name):
        def wrapped(*a, **k):
            with self.span(name):
                return fn(*a, **k)
        return wrapped

    def wrap(self, obj, attr, name):
        setattr(obj, attr, self.spanned(getattr(obj, attr), name))

    def run_round(self, t: int):
        self._round = t
        return self.system.run_round(t)

    def reset(self) -> None:
        self.spans, self.rounds = [], []


class Setup(dict):
    """Seconds of each named part of set-up, for the log."""

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self[name] = time.perf_counter() - t0


# -- one run ---------------------------------------------------------------

def check_chips(n: int) -> None:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform "
                     f"{devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")


def memory_peak() -> int:
    stats = [d.memory_stats() for d in jax.local_devices()]
    return max((s or {}).get("peak_bytes_in_use", 0) for s in stats)


def begin(spec: Dict, seed: int, setup: Setup, fault=None,
          annotate: bool = False):
    """The cell's runner's ``begin``, with ``Probe`` to attach: makes
    the data and the weights from ``seed``, builds the system and runs
    the compared rounds. Returns the runner's session; ``setup`` gets
    the times of its parts."""
    return runner_of(spec).begin(
        spec, seed, setup,
        lambda system, describe: Probe(system, annotate, describe), fault)


def judge(spec: Dict, seed: int, session) -> Dict[str, float]:
    """The cell's runner's ``judge``: the numbers that ``limits`` names
    but ``programs``, read after ``session.release()``."""
    return runner_of(spec).judge(spec, seed, session)


def stray_programs(programs: Dict[int, tuple], compared: List[int],
                   window: List[int]) -> float:
    """Compared rounds whose program (``programs``: round -> what picks
    the compiled program) no window round ran."""
    timed = {programs[t] for t in window}
    return float(sum(programs[t] not in timed for t in compared))


def run_cell(spec: Dict, seed: int, seconds: float, traced: bool,
             require_chip: bool = True, fault=None,
             log=lambda *a: print(*a, file=sys.stderr, flush=True)
             ) -> Dict:
    """One run of one cell; returns the result line as a dict.

    ``require_chip=False`` and ``fault`` exist for the tests in
    ``tests/``."""
    from repro.launch.compile_cache import enable_compile_cache

    traffic = spec["traffic"]
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if require_chip:
        check_chips(spec["cell"]["chips"])
    device = jax.devices()[0]
    setup = Setup()
    session = begin(spec, seed, setup, fault, annotate=traced)
    probe = session.probe
    n_compare = traffic["compare_rounds"]

    # warm-up: the traffic's rounds after the compared ones, so that the
    # window meets no program that a later round compiles first (the
    # traffic keeps the shapes fixed; the window counts compiles)
    probe.counting = True
    with setup.phase("warm_s"):
        for t in range(n_compare + 1,
                       n_compare + 1 + traffic["warm_rounds"]):
            session.run_round(t)
        jax.block_until_ready(session.state())
    setup["warm_compiles"], probe.compiles = probe.compiles, 0
    probe.reset()
    gc.collect()
    t = n_compare + traffic["warm_rounds"]
    setup_s = time.perf_counter() - T_START

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        jax.profiler.start_trace(trace_dir)
    probe.counting = True
    with (jax.profiler.TraceAnnotation(trace.WINDOW) if traced
          else contextlib.nullcontext()):
        w0 = time.perf_counter()
        n = 0
        while True:
            session.run_round(t + 1 + n)
            n += 1
            if time.perf_counter() - w0 >= seconds:
                break
        jax.block_until_ready(session.state())
        w1 = time.perf_counter()
    probe.counting = False
    if traced:
        jax.profiler.stop_trace()
    window_compiles = probe.compiles
    mem_peak = memory_peak()
    rounds, spans = probe.rounds, probe.spans
    session.release()
    gc.collect()

    reduced = None
    if traced:
        t0 = time.perf_counter()
        try:
            reduced = trace.reduce(trace.load_events(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: reduced in {time.perf_counter() - t0:.1f} s")

    t0 = time.perf_counter()
    numbers = judge(spec, seed, session)
    numbers["programs"] = stray_programs(
        session.programs, list(range(1, n_compare + 1)),
        [r["round"] for r in rounds])
    limits = spec["limits"]
    ok = correct.verdict({k: numbers.get(k, float("nan")) for k in limits},
                         limits)
    ref_s = time.perf_counter() - t0

    for r in rounds:
        r["samples"], r["flops"] = session.work(r)
    peaks = json.loads((BENCH / "peaks.json").read_text())
    ctx = RunContext(setup_s=setup_s, window_s=w1 - w0, rounds=rounds,
                     spans=spans, memory_peak_bytes=mem_peak,
                     trace=reduced, device_kind=device.device_kind,
                     peaks=peaks)
    wanted = spec["per_layer"] if traced else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    log(f"set-up: {json.dumps(setup)}; setup_s {setup_s:.3f}")
    log(f"window: {n} rounds in {w1 - w0:.3f} s of {seconds} s asked; "
        f"compiles in window {window_compiles}; warm-up compile events "
        f"{setup['warm_compiles']}")
    log("rounds: " + round_runs(rounds))
    log("longest rounds: " + json.dumps(longest_rounds(spans)))
    log(f"reference: {ref_s:.1f} s; correct: {ok}")
    log("readings: " + json.dumps(
        {k: v for k, v in numbers.items() if k not in limits}))
    for k in limits:
        log(f"compared {k}: {numbers.get(k)!r} (limit {limits[k]!r})")

    devices = jax.devices()
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": mem_peak}
    out = {"correct": ok,
           "attempted": len(rounds), "failed": 0, "metrics": metrics,
           "device": dev, "compiles_in_window": window_compiles}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["compared"] = {k: {"value": numbers.get(k), "limit": limits[k]}
                       for k in limits}
    return out


def round_runs(rounds: List[Dict]) -> str:
    """The window's round records as runs of consecutive rounds that
    differ in nothing but their number: ``6-149 pairs=10 live=1 ...``."""
    runs: List[list] = []
    for r in rounds:
        rest = {k: v for k, v in r.items() if k != "round"}
        if runs and runs[-1][1] == r["round"] - 1 and runs[-1][2] == rest:
            runs[-1][1] = r["round"]
        else:
            runs.append([r["round"], r["round"], rest])
    return "; ".join(f"{a}-{b} " + " ".join(f"{k}={v}" for k, v in
                                            rest.items())
                     for a, b, rest in runs)


def longest_rounds(spans: List[tuple], k: int = 3) -> List[Dict]:
    """The ``k`` window rounds longest from their first host span's start
    to their last one's end, each with the seconds of its spans by name:
    where a round that stalls spent its time."""
    by_round: Dict[int, List[tuple]] = {}
    for name, r, t0, t1 in spans:
        by_round.setdefault(r, []).append((name, t0, t1))
    out = []
    for r, ss in by_round.items():
        names: Dict[str, float] = {}
        for name, t0, t1 in ss:
            names[name] = names.get(name, 0.0) + t1 - t0
        out.append({"round": r, "s": max(t1 for _, _, t1 in ss)
                    - min(t0 for _, t0, _ in ss), **names})
    return sorted(out, key=lambda d: -d["s"])[:k]


@dataclasses.dataclass
class RunContext:
    """What a metric reader may read: the window's rounds and host spans
    (``(name, round, start_s, end_s)``), the set-up time, the device's
    memory peak, the reduced trace (``--trace 1``) and the chip's
    peaks."""
    setup_s: float
    window_s: float
    rounds: List[Dict]
    spans: List[tuple]
    memory_peak_bytes: int
    trace: Optional[Dict]
    device_kind: str
    peaks: Dict

    def peak(self, key: str) -> float:
        if self.device_kind not in self.peaks:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           f"in bench/peaks.json")
        return self.peaks[self.device_kind][key]


def read_metric(name: str, ctx: RunContext):
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(load_cell(args.workload), args.seed, args.seconds,
                       bool(args.trace))
    except (NoChip, BadCell) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
