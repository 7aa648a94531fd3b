#!/usr/bin/env python3
"""Readings of sound runs, of the control and of planted faults, for
setting the limits in ``limits/<cell>.json``.

    python3 bench/control.py --workload c10x100_fedsgd --seeds 11 12 13 \
        --control-seeds 3

For each seed, at the cell's own size, in one process: the program's
compared rounds, driven through the same ``run_round`` and programs as
a benchmark run's set-up (``run.begin``), against the plain reference
(``program``). For the first ``--control-seeds`` seeds also the
reference put in the program's place: the control (``control_fp8``, its
convolutions' and dense layers' operands in float8) and each planted
fault of ``reference.run_reference`` (``half_batch``,
``altered_answer``, and from round 2 on ``unchanged_from_r2``,
``uniform_from_r2``); ``unchanged`` reads 1 on the update gaps by
construction and is not run. Prints one JSON line per seed and reading:
the numbers ``correct.compare`` gives, and the per-leaf gaps of model 0
after the first and the last compared round. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import correct, reference, run  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

IN_PLACE = {"control_fp8": dict(operands="fp8"),
            "half_batch": dict(fault="half_batch"),
            "altered_answer": dict(fault="altered_answer"),
            "unchanged_from_r2": dict(fault="unchanged_from_r2"),
            "uniform_from_r2": dict(fault="uniform_from_r2")}


def readings(spec, seed: int, in_place=tuple(IN_PLACE)):
    setup = run.Setup()
    session = run.begin(spec, seed, setup)
    session.release()
    gc.collect()
    t0 = time.perf_counter()
    numbers = run.judge(spec, seed, session)
    yield "program", dict(numbers, seconds=time.perf_counter() - t0,
                          **setup)
    inputs, params0, fed = session.inputs, session.params0, session.fed
    captured = session.compared
    host0 = jax.tree.map(lambda a: np.asarray(a, np.float32), params0)
    config = spec["config"]
    ref = reference.run_reference(
        fed, config["batch_size"], inputs, params0, seed, len(captured),
        precision=config["operand_precision"])
    for name in in_place:
        t0 = time.perf_counter()
        got = reference.run_reference(
            fed, config["batch_size"], inputs, params0, seed, len(captured),
            precision=config["operand_precision"], **IN_PLACE[name])
        out = correct.compare(got, ref, host0, window=fed["score_window"])
        out["seconds"] = time.perf_counter() - t0
        out["leaves_r1"] = correct.leaf_gaps(
            got[0].params[0], ref[0].params[0], host0)
        out["leaves_last"] = correct.leaf_gaps(
            got[-1].params[0], ref[-1].params[0], host0)
        yield name, out


def main(argv=None) -> int:
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the control and "
                         "the faults")
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    enable_compile_cache()
    run.check_chips(spec["cell"]["chips"])
    for i, seed in enumerate(args.seeds):
        which = tuple(IN_PLACE) if i < args.control_seeds else ()
        for name, numbers in readings(spec, seed, which):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": name, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
