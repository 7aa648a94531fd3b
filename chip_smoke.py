#!/usr/bin/env python3
"""FedCD mode A on the TPU at the paper's width: a smoke run.

  python chip_smoke.py               # one chip: phases 1-3
  python chip_smoke.py --four-chips  # four chips: sharded@2x2 vs fused

Configuration: ``configs/fedcd_cifar.HIERARCHICAL`` as published (30
devices, 15 per round, 2 local epochs, ``max_models=16``, lr 0.08), the
paper's 10-layer CNN at its published widths with random weights from
``--seed``, batch 32, and the hierarchical partition (paper §3.2) at
CIFAR-10's 40k/10k/10k split over 30 devices: 1333/333/333 examples per
device, generated from ``--seed``. Everything runs through
``FedCDServer``, the entry point a user calls.

Phase 1  rounds 1-2 with ``fused`` and with the ``legacy`` oracle from
         the same seed; population, mean accuracies and score spread
         must agree within the tolerances below.
Phase 2  the same configuration with the milestones moved to rounds
         1-5, run until the registry holds its cap of 16 models and one
         round more, so the largest pair (B) and eval-row (L) buckets
         the planner produces run on the chip.
Phase 3  round 1 with ``fused+kernel`` against phase 1's ``fused`` round
         1; the compiled aggregation kernel against the jnp einsum on
         the paper regime's largest shapes; the compiled quantize kernel
         against ``kernels/quantize/ref.py`` on the CNN's largest leaf.

Every round of every engine must leave finite parameters and
accuracies in [0, 1]. ``--four-chips`` runs only ``sharded@2x2``
against ``fused`` over phase 2's rounds, compared while their
populations agree (``four_chips`` says why), and checks from
``addressable_shards`` that the bank rows and the device data really
spread over the four chips.

The script exits non-zero, without its last line, when JAX finds no
TPU or any check fails. Wall times are smoke numbers taken after
``block_until_ready`` with compiles included, not a benchmark. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.fedcd_cifar import HIERARCHICAL  # noqa: E402
from repro.core.aggregate import multi_weighted_average  # noqa: E402
from repro.core.fedcd import FedCDServer  # noqa: E402
from repro.data.partition import (hierarchical_devices,  # noqa: E402
                                  stack_devices)
from repro.federated.simulation import pad_live_rows  # noqa: E402
from repro.kernels.quantize import ops as qops  # noqa: E402
from repro.kernels.quantize import ref as qref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn  # noqa: E402

BATCH = 32
SPLIT = (1333, 333, 333)          # CIFAR-10 40k/10k/10k over 30 devices
PHASE2_MILESTONES = (1, 2, 3, 4, 5)
PHASE2_MAX_ROUNDS = 8

# Agreement between two engines on the chip. The engines compile
# different programs (per-model convs in ``legacy``, grouped per-pair
# convs in ``fused``, per-cell blocks and a psum in ``sharded``), so
# their parameters differ in the last bits after the first SGD step, and
# local SGD on this CNN is chaotic: a 1e-7 relative change of the
# initial weights grows to ~2% of a leaf's scale in 10 steps and ~38% in
# one round's 82 (on the CPU, at lr 0.08 and at 0.02). On a TPU v5e,
# fused and legacy parameters ended round 1 ~18% of a leaf's scale
# apart, at the default and at f32 matmul precision alike. The engines'
# parameters are pinned bit for bit on the CPU instead
# (tests/test_engine_equivalence.py, where both compile alike); here
# they are compared on the metrics a round reports.
#
# Accuracies are argmax counts. Early in training the logits are nearly
# tied, so such a difference can flip one device's predicted class for
# most of its split and move that device by up to its majority-label
# share (~0.65 under the hierarchical partition), e.g. 0.16 on one
# device on a TPU v5e. Per device there is thus no useful bound; over 30
# devices one such flip moves the mean by at most ~0.022, and
# ACC_MEAN_TOL admits two.
ACC_MEAN_TOL = 0.05
# score_std is the mean over devices of the spread of eq-3 scores; one
# device's flip moves it by ~0.002, so 0.02 admits several.
SCORE_STD_TOL = 0.02
# Eq-1 aggregation, Pallas kernel vs jnp einsum on the same inputs: both
# accumulate in f32 (Precision.HIGHEST) and differ only in order.
AGG_REL_TOL = 1e-5


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def make_problem(seed: int, split=SPLIT):
    devs = hierarchical_devices(seed=seed, devices_per_archetype=3,
                                n_train=split[0], n_val=split[1],
                                n_test=split[2])
    return stack_devices(devs), init_cnn(jax.random.PRNGKey(seed))


class Run:
    """One ``FedCDServer`` driven round by round, recording each
    round's metrics, smoke wall time and its (B, L) buckets: B the
    train slots the executor dispatched (0 where it batches no pairs),
    L the eval rows."""

    def __init__(self, label: str, cfg, spec: str, data, params):
        self.label = label
        self.srv = FedCDServer(cfg, params, cnn_loss, cnn_accuracy, data,
                               batch_size=BATCH, spec=spec)
        self.metrics = []
        self.buckets = []
        executor = self.srv.executor
        launch = executor.launch
        batch_args = getattr(executor, "_batch_args", None)
        slots = [0]

        def count_slots(*args):
            out = batch_args(*args)
            slots[0] = len(out[0])          # the train batch's m_idx
            return out

        def observe(plan):
            slots[0] = 0
            launch(plan)
            self.buckets.append(
                (slots[0],
                 len(pad_live_rows(plan.val_stale or plan.live[:1]))))

        if batch_args is not None:
            executor._batch_args = count_slots
        executor.launch = observe

    def bank(self):
        reg = self.srv.registry
        return reg.stacked if reg.stacked is not None else reg.params

    def round(self, t: int):
        t0 = time.perf_counter()
        m = self.srv.run_round(t)
        jax.block_until_ready(self.bank())
        wall = time.perf_counter() - t0
        b, L = self.buckets[-1]
        if not all(bool(jnp.isfinite(a).all())
                   for a in jax.tree.leaves(self.bank())):
            fail(f"{self.label} round {t}: non-finite parameters")
        for name, v in (("val_acc", m.val_acc), ("test_acc", m.test_acc)):
            if not (np.all(np.isfinite(v)) and v.min() >= 0
                    and v.max() <= 1):
                fail(f"{self.label} round {t}: {name} outside [0, 1]")
        print(f"[{self.label}] round {t}: live={m.live_models} "
              f"active={m.active_models} B={b} L={L} "
              f"val_acc={m.val_acc.mean():.4f} "
              f"test_acc={m.test_acc.mean():.4f} "
              f"score_std={m.score_std:.4f} "
              f"wall_s={wall:.3f} (smoke, compiles included)", flush=True)
        self.metrics.append(m)
        return m


def population(m):
    return m.live_models, m.active_models


def agree(label: str, a, b) -> list:
    """Two engines' metrics for the same round: the population exactly,
    mean accuracies and score spread within the tolerances above.
    Prints every difference and returns the failures."""
    bad = []
    pop_a, pop_b = population(a), population(b)
    print(f"[{label}] round {a.round} (live, active): {pop_a} vs {pop_b}")
    if pop_a != pop_b:
        bad.append(f"{label} round {a.round}: (live, active) {pop_a} vs "
                   f"{pop_b}")
    for name in ("val_acc", "test_acc"):
        x, y = getattr(a, name), getattr(b, name)
        dmean = abs(float(x.mean() - y.mean()))
        print(f"[{label}] round {a.round} {name}: |d mean| {dmean:.4f} "
              f"(tol {ACC_MEAN_TOL}), max |d| per device "
              f"{float(np.abs(x - y).max()):.4f}")
        if dmean > ACC_MEAN_TOL:
            bad.append(f"{label} round {a.round}: mean {name} differs by "
                       f"{dmean:.4f}")
    dstd = abs(a.score_std - b.score_std)
    print(f"[{label}] round {a.round} score_std: |d| {dstd:.4f} "
          f"(tol {SCORE_STD_TOL})")
    if dstd > SCORE_STD_TOL:
        bad.append(f"{label} round {a.round}: score_std differs by "
                   f"{dstd:.4f}")
    return bad


def settle(phase: str, bad: list) -> None:
    if bad:
        fail(f"{phase}: " + "; ".join(bad))


def peak_bytes() -> int:
    return max(d.memory_stats()["peak_bytes_in_use"]
               for d in jax.local_devices())


def phase1(data, params):
    """fused vs legacy, rounds 1-2. Returns fused's round-1 metrics,
    phase 3's reference."""
    print("== phase 1: fused vs legacy, rounds 1-2 ==", flush=True)
    fused = Run("fused", HIERARCHICAL, "fused", data, params)
    legacy = Run("legacy", HIERARCHICAL, "legacy", data, params)
    bad = []
    for t in (1, 2):
        bad += agree("fused~legacy", fused.round(t), legacy.round(t))
    settle("phase 1", bad)
    print(f"phase 1 passed; peak_bytes_in_use={peak_bytes()}", flush=True)
    return fused.metrics[0]


def phase2(data, params, spec="fused", rounds=None):
    """Milestones early until the registry holds its cap of models,
    then one round more (or exactly ``rounds`` rounds when given).

    ``max_models`` bounds every model ever created (ids are never
    reused, the paper's M), so the live count reaches the cap only when
    no model was deleted on the way there."""
    cfg = dataclasses.replace(HIERARCHICAL, milestones=PHASE2_MILESTONES)
    run = Run(spec, cfg, spec, data, params)
    full = None
    for t in range(1, (rounds or PHASE2_MAX_ROUNDS) + 1):
        run.round(t)
        if full is None and run.srv.registry.total_created == cfg.max_models:
            full = t
        if rounds is None and full is not None and t > full:
            break
    if full is None:
        fail(f"{spec}: the registry never reached {cfg.max_models} models "
             f"in {len(run.metrics)} rounds")
    live = max(m.live_models for m in run.metrics)
    print(f"[{spec}] registry full ({cfg.max_models} models created) after "
          f"round {full}; most live models {live}; largest "
          f"B={max(b for b, _ in run.buckets)} "
          f"L={max(L for _, L in run.buckets)}", flush=True)
    return run


def phase3(data, params, fused_first):
    print("== phase 3: fused+kernel vs fused, kernels vs refs ==",
          flush=True)
    kern = Run("fused+kernel", HIERARCHICAL, "fused+kernel", data, params)
    bad = agree("fused+kernel~fused", kern.round(1), fused_first)

    # the round's aggregation on the paper regime's largest shapes: 240
    # trained pairs into 16 models, every CNN leaf
    n_pairs, n_models = 240, HIERARCHICAL.max_models
    keys = iter(jax.random.split(jax.random.PRNGKey(1),
                                 len(jax.tree.leaves(params)) + 2))
    stacked = jax.tree.map(
        lambda a: jax.random.normal(next(keys), (n_pairs,) + a.shape),
        params)
    w = jax.random.uniform(next(keys), (n_models, n_pairs))
    w = w * (jax.random.uniform(next(keys), (1, n_pairs)) > 0.2)
    got, want = (jax.jit(functools.partial(multi_weighted_average,
                                           use_kernel=k))(stacked, w)
                 for k in (True, False))
    worst = max(float(jnp.abs(g - r).max() / jnp.abs(r).max())
                for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    print(f"[multi_weighted_agg kernel~jnp] {n_pairs} pairs -> {n_models} "
          f"models: worst leaf differs by {worst:.2e} of its scale "
          f"(tol {AGG_REL_TOL})")
    if not worst <= AGG_REL_TOL:
        bad.append(f"aggregation kernel differs by {worst:.2e}")
    settle("phase 3", bad)

    leaf = max(jax.tree.leaves(params), key=lambda a: a.size)
    qk, sk = qops.quantize(leaf)
    qr, sr = qref.quantize_ref(leaf)
    dq = np.abs(np.asarray(qk, np.int32) - np.asarray(qr, np.int32))
    # a ulp apart in the scale can move a value on a rounding boundary
    # by one step; anything more is a kernel fault
    if dq.max() > 1 or (dq != 0).mean() >= 1e-3:
        fail(f"quantize kernel: {int((dq != 0).sum())} codes differ, "
             f"max {int(dq.max())}")
    if not np.allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6, atol=0):
        fail("quantize kernel: scales differ from the ref")
    xk = qops.dequantize(qk, sk, leaf.shape, leaf.dtype)
    xr = qref.dequantize_ref(qr, sr, leaf.shape, leaf.dtype)
    step = float(np.asarray(sk).max())
    err = float(np.abs(np.asarray(xk) - np.asarray(xr)).max())
    if err > 1.01 * step:
        fail(f"dequantize kernel: error {err} > one step {step}")
    print(f"quantize kernel on leaf {leaf.shape}: {int((dq != 0).sum())} "
          f"codes one step off, dequantize max |d| {err:.3e} "
          f"(step {step:.3e})")
    print(f"phase 3 passed; peak_bytes_in_use={peak_bytes()}", flush=True)


def check_spread(run) -> None:
    """The sharded bank's rows and the data bank's rows must sit on all
    four devices in distinct blocks, not all on the first."""
    n = len(jax.devices())
    for name, arr in (("bank", jax.tree.leaves(run.srv.registry.stacked)[0]),
                      ("train data", run.srv.databank.splits["train"][0])):
        shards = arr.addressable_shards
        devices = {s.device for s in shards}
        blocks = {(s.index[0].start, s.index[0].stop) for s in shards}
        rows = {s.data.shape[0] for s in shards}
        print(f"[spread] {name}: {len(devices)} devices, row blocks "
              f"{sorted(blocks)}")
        if len(devices) != n or len(blocks) < 2 or rows != {
                arr.shape[0] // len(blocks)}:
            fail(f"{name} rows are not spread over the {n} devices")


def four_chips(data, params):
    print("== four chips: sharded@2x2 vs fused over phase 2's rounds ==",
          flush=True)
    fused = phase2(data, params, "fused")
    sharded = phase2(data, params, "sharded@2x2",
                     rounds=len(fused.metrics))
    check_spread(sharded)
    # Rounds 1-2 only clone, so their populations are fixed. Later, the
    # engines' accuracies, computed from chaotically diverging
    # parameters, feed eq-4 deletions, and once the populations part the
    # two runs are no longer the same experiment.
    bad = []
    for a, b in zip(sharded.metrics, fused.metrics):
        if a.round > 2 and population(a) != population(b):
            print(f"[sharded@2x2~fused] populations part at round "
                  f"{a.round}: {population(a)} vs {population(b)}; later "
                  f"rounds are not compared")
            break
        bad += agree("sharded@2x2~fused", a, b)
    settle("four chips", bad)
    print(f"four-chip phase passed; peak_bytes_in_use={peak_bytes()}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded@2x2 against fused on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    data, params = make_problem(args.seed)
    print(f"set-up: data + params in {time.perf_counter() - t0:.1f} s "
          f"({devices[0].device_kind} x{len(devices)})", flush=True)
    if args.four_chips:
        four_chips(data, params)
    else:
        fused_first = phase1(data, params)
        phase2(data, params)
        print(f"phase 2 passed; peak_bytes_in_use={peak_bytes()}",
              flush=True)
        phase3(data, params, fused_first)
    print(f"total {time.perf_counter() - t0:.1f} s; "
          f"peak_bytes_in_use={peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
