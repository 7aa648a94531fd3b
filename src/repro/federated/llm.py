"""FedCD at LM scale (mode B, DESIGN.md §3 + §14): the paper's control
plane (scores / clone / delete) driving compiled score-weighted train
steps, unified onto the plan/executor engine (DESIGN.md §10).

Each round:
  1. sample K participating clients (their scores weight the loss; 0 =
     not participating — eq 1's mask);
  2. the RoundPlanner gathers the live-model work order and the
     executor dispatches it — ``engine="llm"`` (default) trains and
     evals every live model in ONE stacked/vmapped donated dispatch
     over a per-layer-stacked ``StackedParamBank``; ``engine="legacy"``
     keeps the original per-model Python loop as the equivalence
     oracle (score-weighted loss == eq 1 aggregation per model);
  3. per-client token accuracy on a held-out stream -> eq 2-3 scores;
  4. deletions (eq 4 + late rule) and milestone cloning on the registry.

``"llm+pipeline"`` prefetches round t+1's host inputs (participation +
token batches) while round t's dispatch is in flight; the EngineSpec
checkpoint fields (``save_every``/``checkpoint_dir``/``resume_from``/
``faults``) give LM runs the same elastic cadence as FedCD/FedAvg.

Works on one CPU device (tests/examples) and on a production mesh (the
same step functions are what dryrun.py lowers at 256/512 chips; the
bank's model-row axis stays replicated OUTSIDE the tensor shardings —
``launch.sharding.lm_bank_shardings``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import CheckpointError
from repro.checkpoint.state import (CheckpointManager, latest_checkpoint,
                                    restore_server_state,
                                    save_server_state)
from repro.config import ArchConfig, FedCDConfig
from repro.core.lifecycle import apply_deletions, clone_at_milestone
from repro.core.plan import RoundPlanner
from repro.core.registry import ModelRegistry
from repro.core.scores import (init_scores, normalized_scores,
                               push_accuracies)
from repro.core.spec import EngineSpec
from repro.data.tokens import lm_batch
from repro.federated.executors import FedLLMExecutor, LLMLegacyExecutor
from repro.launch import steps as steps_mod
from repro.launch.sharding import lm_bank_shardings
from repro.models import transformer as tf

LLM_ENGINES = ("llm", "legacy")


@dataclass
class LLMRoundMetrics:
    round: int
    mean_loss: float                # NaN when no model trained
    client_acc: np.ndarray          # (N,) best-model token accuracy
    live_models: int
    active_models: int
    score_std: float
    wall_s: float
    trained_models: int = 0         # models with nonzero eq-1 mass


def make_acc_step(cfg: ArchConfig, n_clients: int, mesh=None,
                  dp_axes=("data",), batch_size: Optional[int] = None):
    """Per-client next-token top-1 accuracy (the LM analogue of the
    paper's validation accuracy).

    The per-client reduction reshapes the batch to
    ``(n_clients, B // n_clients)`` — rows are grouped by client, so a
    batch size that ``n_clients`` does not divide would silently mix
    clients' rows into the wrong accuracy slots. Pass ``batch_size`` to
    reject that at construction; the returned step re-checks the actual
    batch at trace time either way."""
    if batch_size is not None and batch_size % n_clients:
        raise ValueError(
            f"eval batch size {batch_size} is not divisible by "
            f"n_clients={n_clients}: per-client accuracy rows would "
            "silently mix clients (rows are grouped per client)")

    def step(params, tokens, labels):
        B = tokens.shape[0]
        if B % n_clients:
            raise ValueError(
                f"eval batch size {B} is not divisible by "
                f"n_clients={n_clients}: per-client accuracy rows would "
                "silently mix clients (rows are grouped per client)")
        logits, _, _ = tf.lm_forward(cfg, params, tokens, mesh=mesh,
                                     dp_axes=dp_axes)
        pred = jnp.argmax(logits, axis=-1)
        acc = (pred == labels).mean(axis=-1)          # (B,)
        return acc.reshape(n_clients, B // n_clients).mean(axis=-1)

    return step


class FedLLMTrainer:
    """Mode-B FedCD over a fleet of LM replicas (module docstring).

    ``spec``: an :class:`~repro.core.spec.EngineSpec` (or preset string)
    with ``engine`` in ``("llm", "legacy")``; ``"llm"`` (default) is
    the stacked plan/executor engine, ``"legacy"`` the per-model loop
    oracle. ``mesh``: an optional TENSOR-parallel launch mesh threaded
    into the step functions (orthogonal to the spec's model/data shard
    counts, which describe the mode-A bank planes and stay 1 here)."""

    def __init__(self, arch: ArchConfig, fed: FedCDConfig, n_clients: int,
                 per_client: int, seq: int, n_archetypes: int = 2,
                 mesh=None, seed: int = 0,
                 spec: "EngineSpec | str" = "llm",
                 draft_layers: int = 0):
        spec = EngineSpec.coerce(spec)
        if spec.engine not in LLM_ENGINES:
            raise ValueError(
                f"FedLLMTrainer supports engine='llm' (stacked) or "
                f"'legacy' (per-model loop oracle): got {spec.engine!r} "
                "— the mode-A engines live on FedCDServer")
        self.spec = spec
        self.arch, self.fed = arch, fed
        self.n_clients, self.per_client, self.seq = n_clients, per_client, seq
        self.n_archetypes = n_archetypes
        self.mesh = mesh
        self.pipeline = spec.pipeline
        self.rng = np.random.default_rng(seed)
        key = jax.random.PRNGKey(seed)
        init = tf.init_lm(arch, key)
        if spec.engine == "llm":
            shardings = (lm_bank_shardings(arch, init, mesh)
                         if mesh is not None else None)
            self.registry = ModelRegistry.create(
                init, fed.max_models, stacked=True, shardings=shardings)
        else:
            self.registry = ModelRegistry.create(init, fed.max_models)
        self.state = init_scores(n_clients, fed.max_models, fed.score_window)
        dp = ("data",) if mesh is None else tuple(
            a for a in ("pod", "data") if a in mesh.axis_names)
        train_fn = steps_mod.make_train_step(
            arch, mesh, dp, lr=fed.lr, remat=False)
        acc_fn = make_acc_step(arch, n_clients, mesh, dp,
                               batch_size=n_clients * per_client)
        cls = FedLLMExecutor if spec.engine == "llm" else LLMLegacyExecutor
        self.executor = cls(fed, self.registry, n_clients, train_fn, acc_fn)
        self.planner = RoundPlanner(fed, n_devices=n_clients)
        # mode B has no minibatch schedule — the plan's perms slot is a
        # fixed placeholder (each round is one step over one batch)
        self._perms = np.zeros((n_clients, 1, 1), np.int32)
        self._prefetch = None
        self.metrics: List[LLMRoundMetrics] = []
        # cluster-shared draft rows for speculative serving (DESIGN.md
        # §16): population state refreshed after every round's clone/
        # delete pass and snapshotted with the trainer checkpoint
        self.draft_layers = draft_layers
        if draft_layers:
            from repro.serve.draft import DraftBank
            self.draft = DraftBank(arch, draft_layers, fed.max_models)
            self.draft.refresh(self.registry,
                               params_of=self.executor.params_of)
        else:
            self.draft = None
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

    def _batch(self):
        return lm_batch(self.rng, self.n_clients, self.per_client, self.seq,
                        self.arch.vocab_size, self.n_archetypes)

    def _draw_inputs(self):
        """One round's host draws, in the historical stream order:
        participation choice -> train batch -> val batch (training
        consumes no host RNG, so drawing val up front preserves the
        legacy loop's stream walk exactly)."""
        participating = np.zeros(self.n_clients, bool)
        k = min(self.fed.devices_per_round, self.n_clients)
        participating[self.rng.choice(self.n_clients, k,
                                      replace=False)] = True
        tokens, labels = self._batch()
        vt, vl = self._batch()
        return participating, tokens, labels, vt, vl

    def _round_inputs(self, t: int):
        if self._prefetch is not None and self._prefetch[0] == t:
            inputs, self._prefetch = self._prefetch[1:], None
            return inputs
        self._prefetch = None
        return self._draw_inputs()

    def _fault(self, t: int, phase: str) -> None:
        """Fault-injection hook: raise SimulatedCrash when the spec's
        FaultSchedule scripts a crash at (round, phase)."""
        if self._faults is not None:
            self._faults.check(t, phase)

    def run_round(self, t: int) -> LLMRoundMetrics:
        t0 = time.perf_counter_ns()
        fed = self.fed
        participating, tokens, labels, vt, vl = self._round_inputs(t)
        c = normalized_scores(self.state)
        plan = self.planner.build(t, (participating, self._perms), c,
                                  self.state, self.registry,
                                  self.executor.plan_hints())
        self._fault(t, "post-plan")
        self.executor.set_batches(tokens, labels, vt, vl)
        self.executor.launch(plan)
        if self.pipeline and t not in fed.milestones:
            # prefetch round t+1's host inputs while the dispatch is in
            # flight. NOT across a milestone: clone-score noise draws
            # from this same stream AFTER the val draw, so prefetching
            # there would reorder the walk vs the synchronous trainer.
            self._prefetch = (t + 1,) + self._draw_inputs()
        self._fault(t, "mid-dispatch")
        accs = self.executor.readback().accs
        self.state = push_accuracies(self.state, accs)
        self.state, _ = apply_deletions(self.state, self.registry, t, fed)
        if t in fed.milestones:
            self.state, cloned = clone_at_milestone(
                self.state, self.registry, t, fed, self.rng,
                clone_params_fn=lambda p: jax.tree.map(jnp.copy, p))
            self.executor.on_clones(cloned)
        if self.draft is not None:
            # post-round draft "training": re-truncate from the freshly
            # aggregated rows, pre-warm clones, drop deleted clusters
            self.draft.refresh(self.registry,
                               params_of=self.executor.params_of)

        losses = self.executor.round_losses
        cn = normalized_scores(self.state)
        best = np.max(np.where(self.state.active, accs, 0.0), axis=1)
        # masked per-client score dispersion (population σ over each
        # client's active models), vectorized over the fleet
        act = self.state.active
        cnt = act.sum(axis=1)
        mu = np.where(act, cn, 0.0).sum(axis=1) / np.maximum(cnt, 1)
        var = (np.where(act, (cn - mu[:, None]) ** 2, 0.0).sum(axis=1)
               / np.maximum(cnt, 1))
        stds = np.sqrt(var)
        stds[cnt == 0] = 0.0
        m = LLMRoundMetrics(
            round=t,
            # NaN, not 0.0: a no-train round must not read as a
            # perfect-loss round
            mean_loss=float(np.mean(losses)) if losses else float("nan"),
            client_acc=best, live_models=len(self.registry.live_ids()),
            active_models=int(self.state.active.sum()),
            score_std=float(stds.mean()),
            wall_s=(time.perf_counter_ns() - t0) * 1e-9,
            trained_models=len(losses))
        self.metrics.append(m)
        self._fault(t, "post-readback")
        if self._ckpt is not None:
            self._ckpt.maybe_save(self, t)
        return m

    # -- elastic checkpoint/resume (DESIGN.md §13) -------------------------
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

    def run(self, rounds: int, log_every: int = 0):
        # a resumed trainer continues from the round after its checkpoint
        for t in range(len(self.metrics) + 1, rounds + 1):
            m = self.run_round(t)
            if log_every and t % log_every == 0:
                print(f"[fedcd-llm] round {t:3d} loss={m.mean_loss:.3f} "
                      f"live={m.live_models} acc={m.client_acc.mean():.3f}",
                      flush=True)
        return self.metrics
