#!/usr/bin/env python3
"""Run one benchmark cell of FedCD mode A once, on the chip.

    python3 bench/run.py --workload c10x100_fedsgd --seed 7 --seconds 10 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``: the model, the FedCD server settings,
the partition) and a traffic mix (``bench/traffic/<traffic>.json``: the
milestone schedule, how many rounds are compared and warmed). The run:

1. makes the data and the initial weights on the device from ``--seed``;
2. builds ``FedCDServer`` with the default ``fused`` spec and runs its
   first rounds, recording what the compared rounds produced and the
   argument shapes of the round program each one ran;
3. runs the traffic's warm-up rounds;
4. runs rounds for ``--seconds`` (the measured window), counting
   compiles;
5. frees the server and compares the recorded rounds with the plain
   reference (``reference.py``, ``correct.py``); a compared round whose
   round program the window did not run counts against ``correct``.

With ``--trace 1`` the window runs under the JAX profiler and the line
carries the per-layer metrics; with ``--trace 0`` the end-to-end ones.
Each metric is read by ``bench/metrics/<name>.py``. The last line of
standard output is one JSON object; a run on a machine whose JAX finds
no TPU, or fewer chips than the cell asks for, exits 1 and prints none.
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
import numpy as np  # noqa: E402

from bench import correct, data, flops, reference, trace  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(RuntimeError):
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

    return {
        "cell": cell,
        "config": json.loads((ROOT / cfg["file"]).read_text()),
        "traffic": json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads(
            (BENCH / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


# -- the benchmark's own spans around the server's calls ------------------

class Probe:
    """Wraps the server's planner, executor and lifecycle calls in named
    host spans, records what each round planned and the argument shapes
    of the round program it ran, counts compiles, and keeps the accuracy
    matrix and the weights of the compared rounds."""

    LIFECYCLE = ("push_accuracies", "apply_deletions", "clone_at_milestone")

    def __init__(self, srv, capture_rounds, annotate: bool):
        import repro.core.fedcd as fedcd_mod

        self.srv = srv
        self.annotate = annotate
        self.capture_rounds = set(capture_rounds)
        self.captured: Dict[int, reference.RoundRecord] = {}
        self.spans: List[tuple] = []
        self.rounds: List[Dict] = []
        self.programs: Dict[int, tuple] = {}
        self.compiles = 0
        self.compile_s = 0.0
        self.counting = False
        self._round = 0
        self._plan = None
        ex = srv.executor
        self._wrap(srv.planner, "build", "plan")
        self._wrap(srv, "_draw_sample", "sample")
        self._wrap(ex, "collect", "collect")
        launch, readback, eval_rows = ex.launch, ex.readback, ex.eval_rows
        round_program = ex._round

        def on_launch(plan):
            self._plan = plan
            self.rounds.append({
                "round": plan.round, "pairs": len(plan.pair_model),
                "live": len(plan.live), "val_rows": len(plan.val_stale),
                "test_rows": len(plan.test_stale), "extra_test_rows": 0})
            with self.span("launch"):
                return launch(plan)

        def on_readback():
            with self.span("readback"):
                result = readback()
            t = self._plan.round
            if t in self.capture_rounds:
                live = list(self._plan.live)
                reg = self.srv.registry
                self.captured[t] = reference.RoundRecord(
                    live, np.asarray(result.accs)[:, live].copy(),
                    {m: jax.tree.map(lambda a: np.asarray(a, np.float32),
                                     reg.params[m]) for m in live})
            return result

        def on_round_program(*args):
            # (m_idx, d_idx, perms, w, agg_rows, live_idx, test_idx): the
            # shapes that pick the compiled round program
            self.programs[self._plan.round] = tuple(
                np.shape(a) for a in args[1:8])
            return round_program(*args)

        def on_eval_rows(rows, split):
            if split == "test":
                self.rounds[-1]["extra_test_rows"] += len(rows)
            return eval_rows(rows, split)

        ex.launch, ex.readback, ex.eval_rows = (on_launch, on_readback,
                                                on_eval_rows)
        ex._round = on_round_program
        self._fedcd = fedcd_mod
        self._saved = {n: getattr(fedcd_mod, n) for n in self.LIFECYCLE}
        for n, fn in self._saved.items():
            setattr(fedcd_mod, n, self._spanned(fn, "lifecycle"))
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def close(self) -> None:
        for n, fn in self._saved.items():
            setattr(self._fedcd, n, fn)
        jax.monitoring.unregister_event_duration_listener(self._on_event)

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

    def _spanned(self, fn, name):
        def wrapped(*a, **k):
            with self.span(name):
                return fn(*a, **k)
        return wrapped

    def _wrap(self, obj, attr, name):
        setattr(obj, attr, self._spanned(getattr(obj, attr), name))

    def run_round(self, t: int):
        self._round = t
        return self.srv.run_round(t)

    def reset(self) -> None:
        self.spans, self.rounds = [], []


def bank_tree(srv):
    return srv.registry.params.tree


# -- one run ---------------------------------------------------------------

def check_chips(n: int) -> None:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform "
                     f"{devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")


def server_config(config: Dict, traffic: Dict, seed: int):
    from repro.config import FedCDConfig

    fed = dict(config["fed"], milestones=tuple(traffic["milestones"]))
    return FedCDConfig(seed=seed, **fed), fed


def memory_peak() -> int:
    stats = [d.memory_stats() for d in jax.local_devices()]
    return max((s or {}).get("peak_bytes_in_use", 0) for s in stats)


def begin(spec: Dict, seed: int, setup: Dict, fault=None,
          annotate: bool = False):
    """Make the data and the weights from ``seed``, build the server
    with the default ``fused`` spec and run the compared rounds through
    it. Returns ``(inputs, params0, fed, srv, probe)``; ``setup`` gets
    the times. ``fault`` (a callable given the server, which breaks its
    timed path) exists for the tests."""
    from repro.core.fedcd import FedCDServer
    from repro.models.cnn import cnn_accuracy, cnn_loss

    config, traffic = spec["config"], spec["traffic"]
    t0 = time.perf_counter()
    inputs = data.make_data(seed, config["partition"])
    params0 = data.make_params(seed, config["model"])
    jax.block_until_ready((inputs, params0))
    setup["data_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    cfg, fed = server_config(config, traffic, seed)
    srv = FedCDServer(cfg, params0, cnn_loss, cnn_accuracy, inputs,
                      batch_size=config["batch_size"], spec="fused")
    if fault is not None:
        fault(srv)
    n_compare = traffic["compare_rounds"]
    probe = Probe(srv, range(1, n_compare + 1), annotate=annotate)
    setup["server_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    for t in range(1, n_compare + 1):
        probe.run_round(t)
    jax.block_until_ready(bank_tree(srv))
    setup["compared_s"] = time.perf_counter() - t0
    return inputs, params0, fed, srv, probe


def judge(spec: Dict, seed: int, captured: List, inputs: Dict,
          params0: Dict, fed: Dict, **reference_kw) -> Dict[str, float]:
    """The numbers of ``correct.compare``: the recorded rounds against
    the plain reference run from the same seed."""
    config = spec["config"]
    ref = reference.run_reference(
        fed, config["batch_size"], inputs, params0, seed, len(captured),
        precision=config["operand_precision"], **reference_kw)
    host0 = jax.tree.map(lambda a: np.asarray(a, np.float32), params0)
    return correct.compare(captured, ref, host0)


def run_cell(spec: Dict, seed: int, seconds: float, traced: bool,
             require_chip: bool = True, fault=None,
             log=lambda *a: print(*a, file=sys.stderr, flush=True)
             ) -> Dict:
    """One run of one cell; returns the result line as a dict.

    ``require_chip=False`` and ``fault`` exist for the tests in
    ``tests/``."""
    from repro.launch.compile_cache import enable_compile_cache

    config, traffic = spec["config"], spec["traffic"]
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if require_chip:
        check_chips(spec["cell"]["chips"])
    device = jax.devices()[0]
    setup: Dict[str, float] = {}
    inputs, params0, fed, srv, probe = begin(spec, seed, setup, fault,
                                             annotate=traced)
    n_compare = traffic["compare_rounds"]

    # warm-up: the traffic's rounds after the compared ones, so that the
    # window meets no program that a later round compiles first (the
    # traffic keeps the shapes fixed; the window counts compiles)
    t0 = time.perf_counter()
    probe.counting = True
    for t in range(n_compare + 1, n_compare + 1 + traffic["warm_rounds"]):
        probe.run_round(t)
    jax.block_until_ready(bank_tree(srv))
    setup["warm_s"] = time.perf_counter() - t0
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
            probe.run_round(t + 1 + n)
            n += 1
            if time.perf_counter() - w0 >= seconds:
                break
        jax.block_until_ready(bank_tree(srv))
        w1 = time.perf_counter()
    probe.counting = False
    if traced:
        jax.profiler.stop_trace()
    window_compiles = probe.compiles
    mem_peak = memory_peak()
    probe.close()
    rounds, spans = probe.rounds, probe.spans
    captured = [probe.captured[r] for r in range(1, n_compare + 1)]
    timed = {probe.programs[r["round"]] for r in rounds}
    untimed = sum(probe.programs[r] not in timed
                  for r in range(1, n_compare + 1))
    del srv, probe
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
    numbers = judge(spec, seed, captured, inputs, params0, fed)
    numbers["programs"] = float(untimed)
    limits = spec["limits"]
    ok = correct.verdict({k: numbers.get(k, float("nan")) for k in limits},
                         limits)
    ref_s = time.perf_counter() - t0

    part = config["partition"]
    steps = fed["local_epochs"] * (part["n_train"] // config["batch_size"])
    n_dev = fed["n_devices"]
    fwd = flops.model_forward_flops(config["model"])
    for r in rounds:
        r["samples"] = r["pairs"] * steps * config["batch_size"]
        r["flops"] = fwd * (3 * r["samples"] + n_dev * (
            r["val_rows"] * part["n_val"]
            + (r["test_rows"] + r["extra_test_rows"]) * part["n_test"]))
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
    log("rounds: " + " ".join(f"{r['round']}:B{r['pairs']}/L{r['val_rows']}"
                              for r in rounds))
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
    spec = load_cell(args.workload)
    try:
        out = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
