import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production meshes, record memory/cost/collective analysis for §Roofline.

MUST be run as its own process (device count is locked at first jax
init): ``PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b
--shape train_4k --mesh single`` or ``--all``. Results are cached as JSON
under --out (default experiments/dryrun)."""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import INPUT_SHAPES, ArchConfig, ShapeConfig
from repro.configs import (all_arch_names, decode_window, get_arch,
                           input_specs, shape_supported)
from repro.launch import steps as steps_mod
from repro.launch.mesh import dp_axes_of, dp_size, make_production_mesh
from repro.launch.sharding import (auto_shardings, batch_spec,
                                   param_shardings, replicated)
from repro.models import encdec as ed
from repro.models import transformer as tf
from repro.roofline.analysis import roofline_terms
from repro.roofline.hlo_analyzer import analyze as hlo_analyze


def abstract_params(cfg: ArchConfig):
    key = jax.random.PRNGKey(0)
    if cfg.family == "audio":
        return jax.eval_shape(lambda k: ed.init_encdec(cfg, k), key)
    return jax.eval_shape(lambda k: tf.init_lm(cfg, k), key)


def abstract_caches(cfg: ArchConfig, shape: ShapeConfig, window: int,
                    params_abs=None):
    B = shape.global_batch
    if cfg.family == "audio":
        frames = jax.ShapeDtypeStruct((B, cfg.encdec.source_len, cfg.d_model),
                                      jnp.dtype(cfg.compute_dtype))
        return jax.eval_shape(
            lambda p, f: ed.init_encdec_caches(cfg, p, f, shape.seq_len,
                                               window),
            params_abs, frames)
    return jax.eval_shape(
        lambda: tf.init_lm_caches(cfg, B, shape.seq_len, window))


def build_case(cfg: ArchConfig, shape: ShapeConfig, mesh,
               lr: float = 1e-2, remat="full", microbatches: int = 1,
               hints: bool = False, decode_2d: bool = False):
    """Returns (fn, args_abstract, in_shardings, donate) for jit+lower."""
    dp = dp_axes_of(mesh)
    n_clients = dp_size(mesh)
    params_abs = abstract_params(cfg)
    policy = "decode_2d" if (decode_2d and shape.kind == "decode") else "train"
    pshard = param_shardings(cfg, params_abs, mesh, policy=policy)
    specs = input_specs(cfg, shape, n_clients)
    window = decode_window(cfg, shape)

    if shape.kind == "train":
        step = steps_mod.make_train_step(cfg, mesh, dp, lr=lr, remat=remat,
                                         microbatches=microbatches,
                                         hints=hints)
        args = [params_abs, specs["tokens"], specs["labels"],
                specs["client_scores"]]
        shards = [pshard, batch_spec(mesh, shape.global_batch, 2),
                  batch_spec(mesh, shape.global_batch, 2),
                  replicated(mesh, 1)]
        if cfg.family == "audio":
            args.append(specs["frames"])
            shards.append(batch_spec(mesh, shape.global_batch, 3))
        def fn(params, tokens, labels, scores, frames=None):
            return step(params, tokens, labels, scores, frames)
        return fn, args, shards, (0,)

    if shape.kind == "prefill":
        if cfg.family == "audio":
            # no incremental encdec prefill: full-sequence forward
            def fn(params, tokens, frames):
                logits, _ = ed.encdec_forward(cfg, params, frames, tokens)
                return logits[:, -1, :]
            args = [params_abs, specs["tokens"], specs["frames"]]
            shards = [pshard, batch_spec(mesh, shape.global_batch, 2),
                      batch_spec(mesh, shape.global_batch, 3)]
            return fn, args, shards, ()
        step = steps_mod.make_prefill_step(cfg, window, mesh, dp, hints=hints)
        caches_abs = abstract_caches(cfg, shape, window, params_abs)
        cshard = auto_shardings(caches_abs, mesh)
        # one chunk of the chunked prefill; a ring window caps chunk size
        S = min(shape.seq_len, window) if window else shape.seq_len
        tok_abs = jax.ShapeDtypeStruct((shape.global_batch, S), jnp.int32)
        nv_abs = jax.ShapeDtypeStruct((), jnp.int32)
        args = [params_abs, caches_abs, tok_abs, nv_abs]
        shards = [pshard, cshard, batch_spec(mesh, shape.global_batch, 2),
                  replicated(mesh, 0)]
        def fn(params, caches, tokens, n_valid):
            return step(params, caches, tokens, n_valid)
        return fn, args, shards, (1,)

    # decode
    step = steps_mod.make_serve_step(cfg, window, mesh, dp, hints=hints)
    caches_abs = abstract_caches(cfg, shape, window, params_abs)
    cshard = auto_shardings(caches_abs, mesh)
    args = [params_abs, caches_abs, specs["tokens"]]
    shards = [pshard, cshard, batch_spec(mesh, shape.global_batch, 2)]
    def fn(params, caches, tokens):
        return step(params, caches, tokens)
    return fn, args, shards, (1,)


def run_case(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             force: bool = False, remat="full",
             microbatches: int = 1, tag: str = "",
             hints: bool = False,
             decode_2d: bool = False) -> Optional[Dict[str, Any]]:
    cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    if not shape_supported(cfg, shape):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "skipped": "unsupported long-context"}
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}_{shape_name}_{mesh_kind}{tag}.json".replace("/", "-")
    path = os.path.join(out_dir, fname)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size
    t0 = time.time()
    try:
        fn, args, shards, donate = build_case(cfg, shape, mesh,
                                              remat=remat,
                                              microbatches=microbatches,
                                              hints=hints,
                                              decode_2d=decode_2d)
        jitted = jax.jit(fn, in_shardings=tuple(shards),
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        # loop-aware accounting from the optimized HLO (cost_analysis
        # counts while bodies once — see roofline/hlo_analyzer.py)
        acc = hlo_analyze(compiled.as_text())
        coll = {"total_bytes": acc["collective_bytes"],
                "by_kind": acc["collective_by_kind"],
                "counts": acc["collective_counts"]}
        terms = roofline_terms(
            {"flops": acc["flops"], "bytes accessed": acc["memory_bytes"]},
            coll, chips, cfg, shape)
        result = {
            "arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "chips": chips, "ok": True,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
            },
            "cost_raw_xla": {k: cost.get(k) for k in ("flops",
                                                      "bytes accessed")},
            "roofline": terms,
            "params_total": cfg.param_counts()["total"],
            "params_active": cfg.param_counts()["active"],
            "remat": remat, "microbatches": microbatches,
            "hints": hints,
        }
    except Exception as e:  # noqa: BLE001 — report failures as data
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "ok": False, "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-2000:]}
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[*INPUT_SHAPES, None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape) on --mesh")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "dots"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--hints", action="store_true")
    ap.add_argument("--decode2d", action="store_true")
    args = ap.parse_args()

    cases = []
    if args.all:
        for arch in all_arch_names():
            for shape in INPUT_SHAPES:
                cases.append((arch, shape))
    else:
        assert args.arch and args.shape
        cases.append((args.arch, args.shape))

    n_ok = n_fail = 0
    for arch, shape in cases:
        tag = args.tag or ("_hints" if args.hints else "")
        r = run_case(arch, shape, args.mesh, args.out, force=args.force,
                     remat=(False if args.no_remat else args.remat_policy),
                     microbatches=args.microbatches, tag=tag,
                     hints=args.hints, decode_2d=args.decode2d)
        status = ("SKIP" if r.get("skipped")
                  else "OK" if r.get("ok") else "FAIL")
        n_ok += status == "OK"
        n_fail += status == "FAIL"
        extra = ""
        if r.get("ok"):
            t = r["roofline"]
            extra = (f" dom={t['dominant']} tc={t['t_compute_s']:.3f}s "
                     f"tm={t['t_memory_s']:.3f}s tx={t['t_collective_s']:.3f}s"
                     f" compile={r['compile_s']}s")
        elif not r.get("skipped"):
            extra = " " + r.get("error", "")[:120]
        print(f"[dryrun] {arch:24s} {shape:12s} {args.mesh:6s} {status}{extra}",
              flush=True)
    print(f"[dryrun] done ok={n_ok} fail={n_fail}")


if __name__ == "__main__":
    main()
