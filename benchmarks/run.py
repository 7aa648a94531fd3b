"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Results cached under
experiments/paper/ (delete or pass --force to re-run).

  python -m benchmarks.run [--fast] [--force] [--model mlp|cnn]
                           [--only a,b] [--json-out BENCH_x.json]

``--json-out`` additionally writes every CSV row (plus run metadata) to
a JSON artifact, so CI can upload it and the perf trajectory can be
tracked against the committed baseline (benchmarks/BASELINE.json).
"""
from __future__ import annotations

import argparse
import json
import platform
import subprocess
import sys
import time


def _git_rev() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:
        return "unknown"


def parse_csv_line(line: str) -> dict:
    name, us, derived = line.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer rounds (CI-scale)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--model", default="mlp", choices=["mlp", "cnn"])
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="add the mesh-sharded engine bench at N shards "
                         "(needs XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N set before python starts)")
    ap.add_argument("--json-out", default=None,
                    help="write results to this JSON artifact path")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_checkpoint, bench_comm,
                            bench_hierarchical, bench_hypergeometric,
                            bench_kernels, bench_llm,
                            bench_model_dynamics, bench_quantization,
                            bench_serve, bench_wallclock)

    long_rounds = 16 if args.fast else 40
    short_rounds = 10 if args.fast else 25
    dyn_rounds = 12 if args.fast else 30

    benches = {
        "hierarchical": lambda: bench_hierarchical.run(long_rounds,
                                                       args.model,
                                                       args.force),
        "hypergeometric": lambda: bench_hypergeometric.run(long_rounds,
                                                           args.model,
                                                           args.force),
        "quantization": lambda: bench_quantization.run(short_rounds,
                                                       args.model,
                                                       args.force),
        "dynamics": lambda: bench_model_dynamics.run(dyn_rounds, args.model,
                                                     args.force),
        "engines": lambda: bench_model_dynamics.compare_engines(
            8 if args.fast else 20, args.model, quick=args.fast),
        "mesh": lambda: bench_model_dynamics.compare_mesh(
            8 if args.fast else 16, args.model,
            shards=args.mesh or 4, quick=args.fast),
        "pipeline": lambda: bench_model_dynamics.compare_pipeline(
            8 if args.fast else 16, args.model,
            shards=args.mesh or 4, quick=args.fast),
        "datamesh": lambda: bench_model_dynamics.compare_datamesh(
            8 if args.fast else 12, args.model, quick=args.fast),
        "sparse": lambda: bench_model_dynamics.measure_sparse_eval(
            8 if args.fast else 16, args.model, quick=args.fast),
        "semisync": lambda: bench_model_dynamics.compare_semisync(
            8 if args.fast else 16, args.model, quick=args.fast),
        "checkpoint": lambda: bench_checkpoint.run(
            8 if args.fast else 16, args.model, quick=args.fast),
        "llm": lambda: bench_llm.run(8 if args.fast else 12,
                                     quick=args.fast),
        "serve": lambda: bench_serve.run(quick=args.fast),
        "spec": lambda: bench_serve.run_spec(quick=args.fast),
        "wallclock": lambda: bench_wallclock.run(long_rounds, args.model,
                                                 args.force),
        "comm": lambda: bench_comm.run(short_rounds, args.model, args.force),
        "kernels": lambda: bench_kernels.run(args.force, quick=args.fast),
    }
    if args.only:
        keep = set(args.only.split(","))
        benches = {k: v for k, v in benches.items() if k in keep}
    elif args.mesh is None:
        # the mesh benches only join the default sweep when shards are
        # requested (they clamp to 1 shard on a single-device host)
        benches.pop("mesh")
        benches.pop("pipeline")
        benches.pop("datamesh")

    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    results = []
    for name, fn in benches.items():
        try:
            t1 = time.time()
            for line in fn():
                print(line, flush=True)
                results.append(dict(parse_csv_line(line), bench=name))
            print(f"# {name} done in {time.time() - t1:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
    print(f"# total {time.time() - t0:.1f}s failures={failures}")
    if args.json_out:
        payload = {
            "git": _git_rev(),
            "created_unix": time.time(),
            "platform": platform.platform(),
            "python": platform.python_version(),
            "args": {"fast": args.fast, "model": args.model,
                     "only": args.only},
            "failures": failures,
            "results": results,
        }
        with open(args.json_out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json_out} ({len(results)} rows)")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
