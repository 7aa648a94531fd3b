"""FedCD server orchestration — paper Algorithm 1, mode A (simulation).

One ``FedCDServer.run_round`` = one line-for-line pass of Algorithm 1:
sample K devices → each trains all its active models for E epochs →
score-weighted aggregation per model (eq 1) → evaluate on validation
data → update scores (eq 2-3) → deletions (eq 4 + late rule) → milestone
cloning. Metrics needed by every paper figure/table are recorded in
``self.metrics``.

The server is the CONTROL PLANE only (DESIGN.md §10): every round it
asks a :class:`~repro.core.plan.RoundPlanner` for a host-side
:class:`~repro.core.plan.RoundPlan` (sampled cohort, gathered work
pairs, stale eval rows, transport count, lifecycle intents) and hands
it to a :class:`~repro.federated.executors.RoundExecutor` — the
device-side data plane — as ``dispatch(plan) → RoundResult``. All
engines share identical RNG streams (DESIGN.md §7):

* ``engine="fused"`` (default): the device-resident data plane
  (DESIGN.md §2) — stacked param bank, one donated round dispatch,
  eval-row caching, test-row prediction, sampling prefetch.
* ``engine="fused"`` with ``mesh=``: the mesh-sharded fused data plane
  (DESIGN.md §9) — bank rows and work pairs bucket per owning shard.
* ``engine="batched"``: the PR 1 engine, kept as the fused engine's
  benchmark baseline.
* ``engine="legacy"``: the original per-model Python loop, kept as the
  equivalence oracle.

``pipeline=True`` (fused and sharded engines) additionally dispatches
round t+1's *training* speculatively — from the prefetched sample and
the pre-lifecycle population — while round t's eval matrices are still
in flight; the speculation is repaired (deletions) or invalidated and
retrained (clones) at the next launch (DESIGN.md §10).

``sparse_eval=crossover`` lets the planner score only holders' splits
when the active (model, device) matrix is sparse enough for the pair
form to beat the dense eval GEMM.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro import spans
from repro.checkpoint.state import (CheckpointManager, latest_checkpoint,
                                    restore_server_state,
                                    save_server_state)
from repro.checkpoint.io import CheckpointError
from repro.config import FedCDConfig
from repro.core import quantize as qz
from repro.core.lifecycle import apply_deletions, clone_at_milestone
from repro.core.plan import RoundPlanner
from repro.core.registry import ModelRegistry
from repro.core.spec import resolve_spec
from repro.core.scores import (init_scores, normalized_scores,
                               push_accuracies)
from repro.data.bank import DeviceDataBank
from repro.federated.executors import (BatchedExecutor, FusedExecutor,
                                       LegacyExecutor, Sharded2DExecutor,
                                       ShardedExecutor)
from repro.federated.simulation import draw_round_sample
from repro.launch.mesh import data_axis_size, model_axis_size
from repro.launch.sharding import bank_rows_per_shard, bank_shardings

# the three MESHLESS engines (tests/benches iterate this tuple);
# engine="sharded" additionally names the fused data plane dispatched
# over a launch mesh — it REQUIRES mesh=, and passing a mesh with
# engine="fused" selects it too (back-compat spelling)
ENGINES = ("fused", "batched", "legacy")

LIFECYCLE_STREAM = 0xFEDCD   # keys the clone-noise RNG off the sampling one


@dataclass
class RoundMetrics:
    round: int
    test_acc: np.ndarray            # (N,) best-model test accuracy per device
    val_acc: np.ndarray             # (N,)
    active_models: int              # total active (device, model) preferences
    live_models: int                # models alive on the server
    score_std: float                # mean over devices of σ(c_i) (Fig 9)
    comm_bytes: int                 # up+down transport this round (§3.6)
    wall_s: float                   # monotonic, round start to collect
    preferred: np.ndarray           # (N,) argmax-score model id (Fig 7)


class FedCDServer:
    def __init__(self, cfg: FedCDConfig, init_params: Any,
                 loss_fn: Callable, acc_fn: Callable,
                 data: Dict[str, Any], batch_size: int = 64,
                 spec: Any = None,
                 use_agg_kernel: Optional[bool] = None,
                 engine: Optional[str] = None,
                 mesh: Any = None, pipeline: Optional[bool] = None,
                 sparse_eval: Optional[float] = None,
                 scenario: Any = None,
                 migrate_threshold: Optional[float] = None,
                 straggler: Any = None):
        """data: stacked device splits from ``partition.stack_devices``:
        {"train": (xs (N,n,...), ys), "val": ..., "test": ...}. The
        fused-family engines wrap it into a device-resident
        :class:`~repro.data.bank.DeviceDataBank` (DESIGN.md §11).

        ``spec``: an :class:`~repro.core.spec.EngineSpec` (or preset
        string like ``"sharded@2x2+pipeline"``) — the one validated
        description of the engine: data plane, mesh shape, pipelining,
        sparse eval, churn scenario, row migration, aggregation kernel
        and the semi-synchronous straggler model (DESIGN.md §12). Every
        invalid combination fails here, at construction.

        The remaining engine kwargs (``engine=``, ``mesh=``,
        ``pipeline=``, ``sparse_eval=``, ``scenario=``,
        ``migrate_threshold=``, ``use_agg_kernel=``, ``straggler=``)
        are the pre-spec spellings, kept one release as a deprecation
        shim — they translate through ``EngineSpec.from_legacy`` and
        may not be combined with ``spec=``."""
        spec = resolve_spec(
            spec, dict(engine=engine, mesh=mesh, pipeline=pipeline,
                       sparse_eval=sparse_eval, scenario=scenario,
                       migrate_threshold=migrate_threshold,
                       use_agg_kernel=use_agg_kernel,
                       straggler=straggler), "FedCDServer")
        if spec.engine == "llm":
            raise ValueError(
                "engine='llm' is the mode-B LM plane — construct "
                "federated.llm.FedLLMTrainer with this spec instead")
        engine, mesh = spec.engine, spec.resolve_mesh()
        self.spec = spec
        self.cfg = cfg
        # Two host RNG streams (DESIGN.md §7): ``rng`` drives round
        # sampling (participation + perms) ONLY, so the fused engine can
        # draw round t+1's sample while step t is in flight without
        # reordering anything; ``life_rng`` drives clone-score noise.
        self.rng = np.random.default_rng(cfg.seed)
        self.life_rng = np.random.default_rng([cfg.seed, LIFECYCLE_STREAM])
        self.data = data
        self.batch_size = batch_size
        n_initial = data["train"][0].shape[0]
        assert n_initial == cfg.n_devices, (n_initial, cfg.n_devices)
        self.mesh = mesh
        self.engine = engine
        self.pipeline = spec.pipeline
        self.use_agg_kernel = spec.use_agg_kernel
        self.scenario = scenario = spec.scenario
        self.migrate_threshold = spec.migrate_threshold
        self._n_shards = model_axis_size(mesh) if mesh is not None else 0
        self._rows_per_shard = (bank_rows_per_shard(cfg.max_models, mesh)
                                if mesh is not None else 0)
        # device-id space (DESIGN.md §11): ids are control plane and
        # never reused, so the score state sizes to every id the
        # scenario can ever create; data ROWS are bank layout and are
        # reused. Static populations keep id space == row space == N.
        self.n_devices = n_initial + (scenario.total_joins
                                      if scenario is not None else 0)
        self.present = np.zeros(self.n_devices, bool)
        self.present[:n_initial] = True
        self._churn_rng = (scenario.make_rng()
                           if scenario is not None else None)
        self.databank = (DeviceDataBank(
            data, n_cap=(scenario.row_capacity(n_initial)
                         if scenario is not None else None),
            id_cap=self.n_devices,
            mesh=(mesh if mesh is not None and data_axis_size(mesh) > 1
                  else None))
            if engine == "fused" else None)
        # only the fused engine stores params device-resident: the
        # legacy/batched baselines keep PR 1's host dict storage so the
        # engine benchmark compares against them as shipped
        self.registry = ModelRegistry.create(
            init_params, cfg.max_models, stacked=(engine == "fused"),
            shardings=(bank_shardings(mesh, init_params)
                       if mesh is not None else None),
            n_shards=max(self._n_shards, 1))
        self.state = init_scores(self.n_devices, cfg.max_models,
                                 cfg.score_window)
        # ids beyond the initial population haven't joined yet
        self.state.active[n_initial:, :] = False
        self.planner = RoundPlanner(cfg, sparse_eval=spec.sparse_eval,
                                    straggler=spec.straggler,
                                    n_devices=self.n_devices)
        self.executor = self._make_executor(loss_fn, acc_fn)
        self.metrics: List[RoundMetrics] = []
        self._model_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(init_params))
        # compressed transport size depends only on leaf shapes, which all
        # models share — precompute so accounting never dereferences a
        # (possibly extinct) live model's params
        self._compressed_bytes = (
            qz.compressed_bytes(init_params, cfg.quantize_bits)
            if cfg.quantize_bits else self._model_bytes)
        self._prefetch: Tuple[int, Tuple[np.ndarray, np.ndarray]] = None
        # elastic checkpoint/resume + fault injection (DESIGN.md §13)
        self._faults = spec.faults
        self._ckpt = (CheckpointManager(spec.checkpoint_dir,
                                        spec.save_every,
                                        faults=spec.faults)
                      if spec.checkpoint_dir else None)
        if spec.resume_from:
            path = latest_checkpoint(spec.resume_from)
            if path is None:
                raise CheckpointError(
                    f"resume_from={spec.resume_from!r}: no valid "
                    "checkpoint found (torn/corrupt steps are skipped)")
            restore_server_state(self, path)

    def _make_executor(self, loss_fn: Callable, acc_fn: Callable):
        if self.engine == "fused":
            if self.mesh is not None:
                cls = (Sharded2DExecutor
                       if data_axis_size(self.mesh) > 1
                       else ShardedExecutor)
                return cls(
                    self.cfg, self.registry, self.databank, loss_fn,
                    acc_fn, self.mesh,
                    use_agg_kernel=self.use_agg_kernel,
                    pipeline=self.pipeline,
                    migrate_threshold=self.migrate_threshold)
            return FusedExecutor(
                self.cfg, self.registry, self.databank, loss_fn, acc_fn,
                use_agg_kernel=self.use_agg_kernel,
                pipeline=self.pipeline)
        cls = (BatchedExecutor if self.engine == "batched"
               else LegacyExecutor)
        return cls(self.cfg, self.registry, self.data, loss_fn, acc_fn,
                   self.batch_size, use_agg_kernel=self.use_agg_kernel)

    @property
    def pipeline_stats(self):
        """Speculation accounting (pipelined executors; None otherwise)."""
        return self.executor.stats

    @property
    def semisync_stats(self):
        """Semi-synchronous round accounting
        (:class:`~repro.core.plan.SemiSyncStats`; None when the spec has
        no straggler model)."""
        coord = self.planner.semisync
        return coord.stats if coord is not None else None

    # -- transport accounting (paper §3.6) --------------------------------
    def _transport_bytes(self, n_transfers: int) -> int:
        return n_transfers * self._compressed_bytes

    def _maybe_compress(self, params: Any) -> Any:
        return qz.roundtrip(params, self.cfg.quantize_bits)

    # -- round sampling ----------------------------------------------------
    def _draw_sample(self, present: Optional[np.ndarray] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """One round's participation mask + minibatch perms (shared by all
        models — every engine consumes the sampling stream identically).
        ``present`` overrides the current presence mask (the prefetch
        passes the NEXT round's post-churn population, which is
        computable because the schedule is scripted — DESIGN.md §11)."""
        return draw_round_sample(self.rng, self.n_devices,
                                 self.cfg.devices_per_round,
                                 self.data["train"][0].shape[1],
                                 self.batch_size, self.cfg.local_epochs,
                                 present=(self.present if present is None
                                          else present))

    def _round_sample(self, t: int) -> Tuple[np.ndarray, np.ndarray]:
        if self._prefetch is not None and self._prefetch[0] == t:
            sample = self._prefetch[1]
            self._prefetch = None
            return sample
        return self._draw_sample()

    # -- device churn (DESIGN.md §11) --------------------------------------
    def _present_after(self, t: int) -> np.ndarray:
        """The presence mask once round ``t``'s scheduled churn applies,
        WITHOUT applying it (joins claim sequential ids)."""
        mask = self.present.copy()
        if self.scenario is None or not self.scenario.has_events(t):
            return mask
        for e in self.scenario.leaves_at(t):
            mask[e.device] = False
        nid = self.databank.next_id
        for _ in self.scenario.joins_at(t):
            mask[nid] = True
            nid += 1
        return mask

    def _apply_churn(self, t: int) -> Tuple[List[int], List[int]]:
        """Resolve round ``t``'s device-lifecycle intents at round start
        (leaves → joins → drifts, the scenarios-module contract). A
        joining device activates every live model with an empty score
        window (raw score 1.0 — the paper's init); a leaving device's
        preferences clear and its data-bank slot frees for reuse; a
        drifting device's splits rewrite in place and its score window
        resets (its history scored the OLD distribution)."""
        if self.scenario is None or not self.scenario.has_events(t):
            return [], []
        joined: List[int] = []
        left: List[int] = []
        drifted: List[int] = []
        for e in self.scenario.leaves_at(t):
            d = e.device
            self.present[d] = False
            self.state.active[d, :] = False
            self.state.history[d] = np.nan
            self.databank.remove(d)
            left.append(d)
        for e in self.scenario.joins_at(t):
            dev = self.scenario.make_device(self._churn_rng, e.archetype)
            d = self.databank.add(dev)
            self.present[d] = True
            for m in self.registry.live_ids():
                self.state.active[d, m] = True
            joined.append(d)
        for e in self.scenario.drifts_at(t):
            self.databank.update(
                e.device,
                self.scenario.make_device(self._churn_rng, e.archetype))
            self.state.history[e.device] = np.nan
            drifted.append(e.device)
        self.executor.on_churn(joined, left, drifted)
        return joined, left

    # -- elastic checkpoint/resume (DESIGN.md §13) -------------------------
    def _fault(self, t: int, phase: str) -> None:
        """Fault-injection hook: raise SimulatedCrash when the spec's
        FaultSchedule scripts a crash at (round, phase)."""
        if self._faults is not None:
            self._faults.check(t, phase)

    def save(self, path: str) -> str:
        """Snapshot the complete logical round state (between rounds)."""
        return save_server_state(self, path)

    def restore(self, path: str) -> int:
        """Restore from a checkpoint directory (or a checkpoint root,
        resolving to its latest valid step). Returns the last completed
        round; ``run`` continues from the next one."""
        resolved = latest_checkpoint(path)
        if resolved is None:
            raise CheckpointError(f"no valid checkpoint under {path!r}")
        return restore_server_state(self, resolved)

    # -- Algorithm 1 -------------------------------------------------------
    def run_round(self, t: int) -> RoundMetrics:
        # wall_s is the root span: both read these two monotonic stamps
        # (the checkpoint, which saves wall_s, follows the round)
        t0 = time.perf_counter_ns()
        spans.round_scope(t)
        with spans.span("fedcd.round", start_ns=t0) as root:
            metrics = self._run_round(t)
            t1 = time.perf_counter_ns()
            root.end_ns = t1
        metrics.wall_s = (t1 - t0) * 1e-9
        self.metrics.append(metrics)
        self._fault(t, "post-readback")
        if self._ckpt is not None:
            with spans.span("fedcd.checkpoint"):
                self._ckpt.maybe_save(self, t)
        return metrics

    def _run_round(self, t: int) -> RoundMetrics:
        cfg = self.cfg
        with spans.span("fedcd.churn"):
            joined, left = self._apply_churn(t)
        with spans.span("fedcd.sample"):
            sample = self._round_sample(t)
        c = normalized_scores(self.state)

        churn_next = (self.scenario is not None
                      and self.scenario.has_events(t + 1))
        plan = self.planner.build(t, sample, c, self.state, self.registry,
                                  self.executor.plan_hints(),
                                  churn=(joined, left),
                                  churn_next=churn_next)
        self._fault(t, "post-plan")
        self.executor.launch(plan)
        # overlap: draw round t+1's participation + perms while the
        # dispatched work is still executing (ROADMAP: async sampling)
        with spans.span("fedcd.sample"):
            self._prefetch = (t + 1,
                              self._draw_sample(self._present_after(t + 1)))
        if self.pipeline:
            # cross-round speculation: enqueue round t+1's training from
            # the prefetched sample + pre-lifecycle state (DESIGN.md §10)
            spec = self.planner.build_speculative(
                t + 1, self._prefetch[1], self.state, self.registry)
            self.executor.speculate(spec)
        self._fault(t, "mid-dispatch")
        result = self.executor.readback()

        transfers = plan.transfers
        with spans.span("fedcd.lifecycle"):
            self.state = push_accuracies(self.state, result.accs)
            self.state, _ = apply_deletions(self.state, self.registry, t,
                                            cfg)
            if t in cfg.milestones:
                self.state, cloned = clone_at_milestone(
                    self.state, self.registry, t, cfg, self.life_rng,
                    clone_params_fn=self._maybe_compress)
                transfers += sum(int(self.state.active[:, m2].sum())
                                 for m2 in self.registry.live_ids())
                self.executor.on_clones(cloned)
                self.planner.on_clones(cloned)   # clones inherit fold mass

        with spans.span("fedcd.collect"):
            return self._collect(t, transfers)

    # -- metrics -----------------------------------------------------------
    def _collect(self, t: int, transfers: int) -> RoundMetrics:
        """The round's metrics; ``run_round`` sets ``wall_s``."""
        c = normalized_scores(self.state)
        preferred = np.argmax(np.where(self.state.active, c, -1.0), axis=1)
        test_acc, val_acc = self.executor.collect(preferred)
        stds = []
        for i in np.nonzero(self.present)[0]:
            ci = c[i, self.state.active[i]]
            stds.append(ci.std() if ci.size else 0.0)
        return RoundMetrics(
            round=t, test_acc=test_acc, val_acc=val_acc,
            active_models=int(self.state.active.sum()),
            live_models=len(self.registry.live_ids()),
            score_std=float(np.mean(stds)) if stds else 0.0,
            comm_bytes=self._transport_bytes(transfers),
            wall_s=0.0, preferred=preferred)

    def run(self, rounds: int, log_every: int = 0) -> List[RoundMetrics]:
        # a resumed server continues from the round after its checkpoint
        for t in range(len(self.metrics) + 1, rounds + 1):
            m = self.run_round(t)
            if log_every and t % log_every == 0:
                print(f"[fedcd] round {t:3d} live={m.live_models} "
                      f"active={m.active_models} "
                      f"test_acc={m.test_acc.mean():.3f} "
                      f"score_std={m.score_std:.3f}")
        return self.metrics
