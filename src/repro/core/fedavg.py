"""FedAvg baseline (McMahan et al. 2017) — the paper's comparison system.

Identical client loop and data plumbing as FedCDServer so the comparison
isolates the algorithm: one global model, uniform averaging over the
participating devices' updates.

The server shares FedCD's plan/executor split (DESIGN.md §10): each
round it builds a one-model :class:`~repro.core.plan.RoundPlan` (the
participating devices are the work pairs) and hands it to a FedAvg
executor. Engines mirror FedCDServer: ``"fused"`` (default) keeps the
global model device-resident and runs the round as one jitted donated
dispatch; with ``mesh=`` the work-PAIR axis shards over the mesh
(partial sums + one psum); ``"batched"`` / ``"legacy"`` are the PR 1 /
seed baselines. ``pipeline=True`` (fused/sharded) enqueues round t+1's
training before round t's eval matrices are read back — FedAvg has no
control-plane feedback, so the speculation is exact and never repaired.
All engines draw the same sampling stream as FedCDServer, so
FedCD-vs-FedAvg comparisons see identical per-round cohorts.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint.io import CheckpointError
from repro.checkpoint.state import (CheckpointManager, latest_checkpoint,
                                    restore_server_state,
                                    save_server_state)
from repro.config import FedCDConfig
from repro.core.plan import RoundPlan, SemiSyncCoordinator
from repro.core.spec import resolve_spec
from repro.federated.executors import (FedAvgFusedExecutor,
                                       FedAvgHostExecutor,
                                       FedAvgSharded2DExecutor,
                                       FedAvgShardedExecutor)
from repro.federated.simulation import draw_round_sample
from repro.launch.mesh import data_axis_size


@dataclass
class FedAvgRound:
    round: int
    test_acc: np.ndarray
    val_acc: np.ndarray
    comm_bytes: int
    wall_s: float


class FedAvgServer:
    def __init__(self, cfg: FedCDConfig, init_params: Any,
                 loss_fn: Callable, acc_fn: Callable,
                 data: Dict[str, Any], batch_size: int = 64,
                 spec: Any = None, engine: Optional[str] = None,
                 mesh: Any = None, pipeline: Optional[bool] = None,
                 straggler: Any = None):
        """``spec``: an :class:`~repro.core.spec.EngineSpec` (or preset
        string) — FedAvg supports the fused/batched/legacy planes,
        mesh sharding of the work-PAIR axis over ``model`` (one global
        model, so the parallel dimension is the participating devices;
        DESIGN.md §9), the 2-D mesh with the DEVICE axis sharded over
        ``data`` (a psum over both axes completes eq 1 — DESIGN.md
        §11), ``pipeline`` split-phase dispatch, and a semi-synchronous
        ``straggler`` model (DESIGN.md §12). FedCD-only capabilities
        (``scenario``, ``sparse_eval``, ``migrate_threshold``,
        ``use_agg_kernel``) are rejected here. The ``engine=``/
        ``mesh=``/``pipeline=``/``straggler=`` kwargs are the pre-spec
        spellings (one-release deprecation shim)."""
        spec = resolve_spec(
            spec, dict(engine=engine, mesh=mesh, pipeline=pipeline,
                       straggler=straggler), "FedAvgServer")
        if spec.engine == "llm":
            raise ValueError(
                "engine='llm' is the mode-B LM plane — construct "
                "federated.llm.FedLLMTrainer with this spec instead")
        for name, on in (("scenario churn", spec.scenario is not None),
                         ("sparse_eval", spec.sparse_eval is not None),
                         ("migrate_threshold",
                          spec.migrate_threshold is not None),
                         ("use_agg_kernel", spec.use_agg_kernel)):
            if on:
                raise ValueError(
                    f"FedAvgServer does not support {name} (FedCD only)")
        engine, mesh = spec.engine, spec.resolve_mesh()
        self.spec = spec
        self.cfg = cfg
        self.rng = np.random.default_rng(cfg.seed)
        self.data = data
        self.batch_size = batch_size
        self.n_devices = data["train"][0].shape[0]
        self.engine = engine
        self.mesh = mesh
        self.pipeline = spec.pipeline
        if engine == "fused":
            if mesh is not None and data_axis_size(mesh) > 1:
                self.executor = FedAvgSharded2DExecutor(
                    cfg, data, init_params, loss_fn, acc_fn, mesh,
                    pipeline=self.pipeline)
            elif mesh is not None:
                self.executor = FedAvgShardedExecutor(
                    cfg, data, init_params, loss_fn, acc_fn, mesh,
                    pipeline=self.pipeline)
            else:
                self.executor = FedAvgFusedExecutor(
                    cfg, data, init_params, loss_fn, acc_fn,
                    pipeline=self.pipeline)
        else:
            self.executor = FedAvgHostExecutor(
                cfg, data, init_params, loss_fn, acc_fn, batch_size,
                batched=(engine == "batched"))
        self.semisync = (SemiSyncCoordinator(spec.straggler,
                                             self.n_devices)
                         if spec.straggler is not None else None)
        self.metrics: List[FedAvgRound] = []
        self._model_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(init_params))
        self._prefetch = None
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

    @property
    def pipeline_stats(self):
        """Speculation accounting (pipelined executors; None otherwise)."""
        return self.executor.stats

    @property
    def semisync_stats(self):
        """Semi-synchronous round accounting
        (:class:`~repro.core.plan.SemiSyncStats`; None when the spec
        has no straggler model)."""
        return self.semisync.stats if self.semisync is not None else None

    @property
    def params(self) -> Any:
        """The global model (row 0 of the device bank in fused mode)."""
        return self.executor.get_params()

    @params.setter
    def params(self, value: Any) -> None:
        self.executor.set_params(value)

    def _plan(self, t: int, participating: np.ndarray,
              perms: np.ndarray) -> RoundPlan:
        """FedAvg's one-model work order: every participating device is
        a (model 0, device) pair with uniform weight."""
        d_ids = [int(d) for d in np.nonzero(participating)[0]]
        return RoundPlan(
            round=t, participating=participating, perms=perms,
            scores=np.ones((self.n_devices, 1), np.float32), live=[0],
            agg_models=[0], pair_model=[0] * len(d_ids),
            pair_device=d_ids, transfers=2 * len(d_ids),
            val_stale=[0], test_stale=[0])

    # -- elastic checkpoint/resume (DESIGN.md §13) -------------------------
    def _fault(self, t: int, phase: str) -> None:
        if self._faults is not None:
            self._faults.check(t, phase)

    def save(self, path: str) -> str:
        """Snapshot the complete logical round state (between rounds)."""
        return save_server_state(self, path)

    def restore(self, path: str) -> int:
        """Restore from a checkpoint directory (or root — resolves to
        its latest valid step); returns the last completed round."""
        resolved = latest_checkpoint(path)
        if resolved is None:
            raise CheckpointError(f"no valid checkpoint under {path!r}")
        return restore_server_state(self, resolved)

    def run_round(self, t: int) -> FedAvgRound:
        t0 = time.perf_counter_ns()
        cfg = self.cfg
        if self._prefetch is not None and self._prefetch[0] == t:
            participating, perms = self._prefetch[1]
            self._prefetch = None
        else:
            participating, perms = draw_round_sample(
                self.rng, self.n_devices, cfg.devices_per_round,
                self.data["train"][0].shape[1], self.batch_size,
                cfg.local_epochs)
        plan = self._plan(t, participating, perms)
        if self.semisync is not None:
            self.semisync.resolve(plan, live=[0])
        self._fault(t, "post-plan")
        self.executor.launch(plan)
        if self.pipeline:
            # FedAvg's next round depends on nothing this round computes:
            # prefetch the sample and enqueue its training immediately
            self._prefetch = (t + 1, draw_round_sample(
                self.rng, self.n_devices, cfg.devices_per_round,
                self.data["train"][0].shape[1], self.batch_size,
                cfg.local_epochs))
            self.executor.speculate(self._plan(t + 1, *self._prefetch[1]))
        self._fault(t, "mid-dispatch")
        result = self.executor.readback()
        m = FedAvgRound(
            round=t, test_acc=result.test_acc, val_acc=result.val_acc,
            comm_bytes=2 * int(participating.sum()) * self._model_bytes,
            wall_s=(time.perf_counter_ns() - t0) * 1e-9)
        self.metrics.append(m)
        self._fault(t, "post-readback")
        if self._ckpt is not None:
            self._ckpt.maybe_save(self, t)
        return m

    def run(self, rounds: int, log_every: int = 0) -> List[FedAvgRound]:
        # a resumed server continues from the round after its checkpoint
        for t in range(len(self.metrics) + 1, rounds + 1):
            m = self.run_round(t)
            if log_every and t % log_every == 0:
                print(f"[fedavg] round {t:3d} "
                      f"test_acc={m.test_acc.mean():.3f}")
        return self.metrics
