"""Device-side round executors: the data-plane half of a federated round.

A :class:`RoundExecutor` turns one host-built :class:`~repro.core.plan.
RoundPlan` into device work — "dispatch(plan) -> RoundResult" — and owns
everything layout-specific: bank-row resolution (``row_of``), work-pair
bucketing, per-shard scheduling, eval-row caches, and the jitted
programs themselves. The four FedCD engines and the FedAvg baselines
each implement the same five-call contract (DESIGN.md §10):

    plan_hints()  -> what the executor can reuse bit-identically
    launch(plan)  -> dispatch the round's device work (non-blocking
                     for the device-resident engines)
    speculate(p)  -> optionally pre-dispatch round t+1's TRAINING from
                     a speculative plan (pipelined executors only)
    readback()    -> block on the eval matrices, return RoundResult
    collect(pref) -> the round's preferred-model test/val accuracies

**Pipelined execution** (``pipeline=True`` on the fused and sharded
executors): training is a pure read of the parameter bank
(``make_pair_train`` / ``make_sharded_train``), so round t+1's train
dispatch is enqueued — from the prefetched sample and the
pre-lifecycle population — while round t's eval matrices are still in
flight. The in-order device queue then never drains across the host's
readback + lifecycle + planning gap. At the next ``launch`` the
speculation is *repaired* (deletions only shrink the pair set: dead
pairs keep zero aggregation weight, dead rows drop out of the scatter)
or *invalidated and retrained* (clones wrote bank rows / added pairs —
detected via the bank ``version`` counter and a pair-subset check).
Aggregation weights, scatter rows, and eval schedules are never
speculative: they are resolved from the TRUE plan at launch, which is
why repair is exact (DESIGN.md §10).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.config import FedCDConfig
from repro.core import quantize as qz
from repro.core.aggregate import multi_weighted_average, weighted_average
from repro.core.plan import EvalHints, RoundPlan
from repro.core.registry import ModelRegistry
from repro.data.bank import DeviceDataBank
from repro.federated.simulation import (bucket_size, make_eval,
                                        make_fused_apply, make_fused_eval,
                                        make_fused_finish,
                                        make_fused_round, make_group_eval,
                                        make_group_train, make_llm_eval,
                                        make_llm_round, make_local_train,
                                        make_pair_eval, make_pair_train,
                                        make_sharded2d_apply,
                                        make_sharded2d_eval,
                                        make_sharded2d_fedavg_eval,
                                        make_sharded2d_fedavg_finish,
                                        make_sharded2d_fedavg_round,
                                        make_sharded2d_fedavg_train,
                                        make_sharded2d_finish,
                                        make_sharded2d_pair_eval,
                                        make_sharded2d_round,
                                        make_sharded2d_train,
                                        make_sharded_apply,
                                        make_sharded_eval,
                                        make_sharded_fedavg_finish,
                                        make_sharded_fedavg_round,
                                        make_sharded_fedavg_train,
                                        make_sharded_finish,
                                        make_sharded_pair_eval,
                                        make_sharded_round,
                                        make_sharded_train, pad_live_rows,
                                        pad_work_batch, shard_eval_pairs,
                                        shard_eval_pairs_2d, shard_pairs_2d,
                                        shard_rows, shard_work_batch)
from repro.launch.mesh import data_axis_size, model_axis_size
from repro.launch.sharding import bank_rows_per_shard


@dataclass
class RoundResult:
    """What the control plane needs back from one dispatched round."""
    accs: np.ndarray                     # (N, M_cap) val accuracies


class _Pending(NamedTuple):
    """One dispatched eval matrix: the device output ``readback`` waits
    on, and the reader that copies it into per-model host rows."""
    fut: Any
    read: Callable[[], Dict[int, np.ndarray]]


@dataclass
class PipelineStats:
    """Cross-round speculation accounting (reported by the benches)."""
    speculated: int = 0                  # train dispatches pre-launched
    hit: int = 0                         # consumed unchanged
    repaired: int = 0                    # consumed after deletions
    invalidated: int = 0                 # stale at launch (clone writes /
    #                                      pairs outside the batch)
    discarded: int = 0                   # never consumed (extinction /
    #                                      no-pair round — degenerate repair)
    skipped: int = 0                     # not speculated (milestone intent)

    def as_dict(self) -> Dict[str, int]:
        return {"speculated": self.speculated, "hit": self.hit,
                "repaired": self.repaired,
                "invalidated": self.invalidated,
                "discarded": self.discarded, "skipped": self.skipped}


@dataclass
class TrainMeta:
    """Which (model, device) pairs a dispatched train batch covers, in
    bucket-column order (the repair contract: aggregation weights are
    addressed by these columns, so a superset batch aggregates
    identically once dead pairs get zero weight). ``positions[k]`` is
    pair k's row in the trained batch's leading axis (identity for the
    unsharded engines, bucket-slot for the sharded ones) — the harvest
    path reads straggler pairs' trained rows through it."""
    pair_model: List[int]
    pair_device: List[int]
    b_pad: int
    pair_groups: Optional[List[List[int]]] = None    # sharded only
    positions: Optional[List[int]] = None


def _count_pairs(n_pairs: int, n_slots: int) -> None:
    """Count a bucketed work batch: its real pairs and its padding."""
    spans.count("pairs", n_pairs)
    spans.count("pairs_padded", n_slots - n_pairs)


def _to_host(fut: Any) -> np.ndarray:
    """Copy one eval output to the host, counting its bytes."""
    mat = np.asarray(fut)
    spans.count("d2h_bytes", mat.nbytes)
    return mat


def _group_positions(groups: List[List[int]], width: int,
                     n_pairs: int) -> List[int]:
    """Pair k's trained-batch row under a grouped bucketing: group g's
    j-th member sits at ``g * width + j`` (the shard/cell block
    layout of ``shard_work_batch`` / ``shard_pairs_2d``)."""
    pos = [0] * n_pairs
    for g, members in enumerate(groups):
        for j, k in enumerate(members):
            pos[k] = g * width + j
    return pos


def _harvest_rows(stale_updates: Dict[Tuple[int, int, int], Any],
                  plan: RoundPlan, trained: Any, meta: TrainMeta) -> None:
    """Pull straggler pairs' trained rows to the host (at readback, when
    the batch has materialized anyway) into the carry-over buffer keyed
    (dispatch round, model, device). Addressed by (model, device)
    through META's positions — on a repaired speculation the batch is a
    superset in its own column order, so plan indices must not be used
    directly."""
    pos_of = {(m, d): meta.positions[k]
              for k, (m, d) in enumerate(zip(meta.pair_model,
                                             meta.pair_device))}
    for k in plan.straggler_pairs:
        m, d = plan.pair_model[k], plan.pair_device[k]
        p = pos_of.get((m, d))
        if p is None:
            continue
        stale_updates[(plan.round, m, d)] = jax.tree.map(
            lambda a: np.asarray(a[p]), trained)


def _blend_stale(current: Any, mass: float,
                 updates: List[Tuple[float, Any]]) -> Any:
    """The eq-1 fold (DESIGN.md §12): blend staleness-discounted stale
    updates into a model's params as a mass-weighted average —
    ``(M·w + Σ c̃_j·v_j) / (M + Σ c̃_j)``. With M = 0 (a model that
    never aggregated) this degenerates to the plain eq-1 average of the
    late arrivals, exactly what the synchronous round would have
    computed were they the only contributions. Accumulates in float32
    and casts back per leaf, mirroring ``aggregate.weighted_average``."""
    total = mass + sum(w for w, _ in updates)
    weights = [w for w, _ in updates]

    def blend(cur, *stale):
        acc = np.asarray(cur, np.float32) * np.float32(mass)
        for w, s in zip(weights, stale):
            acc = acc + np.float32(w) * np.asarray(s, np.float32)
        return (acc / np.float32(total)).astype(np.asarray(cur).dtype)

    return jax.tree.map(blend, current, *[t for _, t in updates])


class RoundExecutor:
    """Shared scaffolding; engines override the dispatch internals."""

    pipeline = False
    stats: Optional[PipelineStats] = None

    def __init__(self, cfg: FedCDConfig, registry: ModelRegistry,
                 data: Any):
        self.cfg = cfg
        self.registry = registry
        self.data = data
        self.databank = data if isinstance(data, DeviceDataBank) else None
        self.n_devices = (self.databank.id_cap
                          if self.databank is not None
                          else data["train"][0].shape[0])
        self._result: Optional[RoundResult] = None

    # -- contract ---------------------------------------------------------
    def plan_hints(self) -> Optional[EvalHints]:
        return None                      # no bit-identical reuse

    def launch(self, plan: RoundPlan) -> None:
        raise NotImplementedError

    def speculate(self, plan: RoundPlan) -> None:
        pass                             # synchronous engines: no-op

    def readback(self) -> RoundResult:
        result, self._result = self._result, None
        return result

    def on_clones(self, cloned: List[Tuple[int, int]]) -> None:
        pass

    def on_churn(self, joined: List[int], left: List[int],
                 drifted: List[int]) -> None:
        pass                             # device-lifecycle hook

    def quiesce(self) -> None:
        """Snapshot barrier (DESIGN.md §13): drain-and-discard any
        in-flight speculation and release retired buffers so a
        checkpoint reads settled state. Safe because speculative batches
        are repairable — the next launch simply trains synchronously.
        No-op for the synchronous engines."""

    def collect(self, preferred: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError

    # -- shared helpers ---------------------------------------------------
    def _maybe_compress(self, params: Any) -> Any:
        return qz.roundtrip(params, self.cfg.quantize_bits)

    def _holder_weights(self, plan: RoundPlan, m: int) -> np.ndarray:
        """Per-device aggregation weight for model ``m``: c_m_i on its
        work-pair devices, 0 elsewhere — the plan-based form of
        ``aggregate.participation_weights`` (the pair list IS
        ``participating & active``, so masking reduces to a gather)."""
        w = np.zeros(self.n_devices, np.float32)
        d = np.asarray(plan.pair_device,
                       np.int64)[np.asarray(plan.pair_model) == m]
        w[d] = plan.scores[d, m]
        return w


class LegacyExecutor(RoundExecutor):
    """The original per-model Python loop: every model with work trains
    ALL N devices (non-holders zero-weighted away), one dispatch per
    model for training and for each eval. O(models x devices) work;
    kept as the equivalence oracle."""

    def __init__(self, cfg, registry, data, loss_fn, acc_fn,
                 batch_size: int, use_agg_kernel: bool = False):
        super().__init__(cfg, registry, data)
        self.local_train = make_local_train(loss_fn, cfg.lr, batch_size)
        self.evaluate = make_eval(acc_fn)
        self.use_agg_kernel = use_agg_kernel

    def launch(self, plan: RoundPlan) -> None:
        xs, ys = self.data["train"]
        for m in plan.agg_models:
            trained = self.local_train(self.registry.params[m], xs, ys,
                                       plan.perms)
            w = self._holder_weights(plan, m)
            new_params = weighted_average(trained, w,
                                          use_kernel=self.use_agg_kernel)
            self.registry.params[m] = self._maybe_compress(
                jax.tree.map(np.asarray, new_params))

        accs = np.zeros((self.n_devices, self.cfg.max_models))
        vx, vy = self.data["val"]
        for m in plan.live:
            accs[:, m] = np.asarray(self.evaluate(self.registry.params[m],
                                                  vx, vy))
        self._result = RoundResult(accs)

    def collect(self, preferred: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
        tx, ty = self.data["test"]
        vx, vy = self.data["val"]
        test_acc = np.zeros(self.n_devices)
        val_acc = np.zeros(self.n_devices)
        for m in np.unique(preferred):
            sel = preferred == m
            if m not in self.registry.params:
                continue
            test_acc[sel] = np.asarray(self.evaluate(
                self.registry.params[m], tx, ty))[sel]
            val_acc[sel] = np.asarray(self.evaluate(
                self.registry.params[m], vx, vy))[sel]
        return test_acc, val_acc


class BatchedExecutor(RoundExecutor):
    """PR 1's engine: one jitted vmapped train step over the gathered
    pairs + fused multi-model aggregation, but host hops around
    aggregation/quantization and dense (live, N) eval matrices
    re-dispatched in collect. Kept as the fused engine's benchmark
    baseline."""

    def __init__(self, cfg, registry, data, loss_fn, acc_fn,
                 batch_size: int, use_agg_kernel: bool = False):
        super().__init__(cfg, registry, data)
        self.group_train = make_group_train(loss_fn, cfg.lr, batch_size)
        self.group_eval = make_group_eval(acc_fn)
        self.use_agg_kernel = use_agg_kernel

    def _stack_params(self, model_ids: List[int], pad_to: int) -> Any:
        trees = [self.registry.params[m] for m in model_ids]
        trees += [trees[0]] * (pad_to - len(trees))
        return jax.tree.map(lambda *ls: jnp.stack(ls), *trees)

    def _eval_matrix(self, x: np.ndarray, y: np.ndarray
                     ) -> Tuple[np.ndarray, List[int]]:
        live = self.registry.live_ids()
        if not live:
            return np.zeros((0, self.n_devices)), live
        stacked = self._stack_params(live, bucket_size(len(live),
                                                       minimum=1))
        return np.asarray(self.group_eval(stacked, x, y)), live

    def launch(self, plan: RoundPlan) -> None:
        xs, ys = self.data["train"]
        agg_models = plan.agg_models
        if agg_models:
            b = len(plan.pair_model)
            m_pad = bucket_size(len(agg_models), minimum=1)
            slot = {m: j for j, m in enumerate(agg_models)}
            m_idx, d_idx, pperms = pad_work_batch(
                [slot[m] for m in plan.pair_model], plan.pair_device,
                [plan.perms[d] for d in plan.pair_device])
            stacked = self._stack_params(agg_models, m_pad)
            trained = self.group_train(stacked, m_idx, xs, ys, d_idx,
                                       pperms)
            w = np.zeros((m_pad, len(m_idx)), np.float32)
            w[m_idx[:b], np.arange(b)] = plan.scores[plan.pair_device,
                                                     plan.pair_model]
            agg = jax.tree.map(np.asarray, multi_weighted_average(
                trained, w, use_kernel=self.use_agg_kernel))
            for j, m in enumerate(agg_models):
                self.registry.params[m] = self._maybe_compress(
                    jax.tree.map(lambda a: a[j], agg))

        accs = np.zeros((self.n_devices, self.cfg.max_models))
        vx, vy = self.data["val"]
        mat, live = self._eval_matrix(vx, vy)
        for j, m in enumerate(live):
            accs[:, m] = mat[j]
        self._result = RoundResult(accs)

    def collect(self, preferred: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
        tx, ty = self.data["test"]
        vx, vy = self.data["val"]
        test_acc = np.zeros(self.n_devices)
        val_acc = np.zeros(self.n_devices)
        test_mat, live = self._eval_matrix(tx, ty)
        val_mat, _ = self._eval_matrix(vx, vy)
        slot = {m: j for j, m in enumerate(live)}
        for i in range(self.n_devices):
            j = slot.get(int(preferred[i]))
            if j is not None:
                test_acc[i] = test_mat[j, i]
                val_acc[i] = val_mat[j, i]
        return test_acc, val_acc


class FusedExecutor(RoundExecutor):
    """The device-resident data plane (DESIGN.md §2): params live in the
    registry's stacked bank and the synchronous dense round is ONE
    jitted donated dispatch. Owns the per-model eval-row caches and the
    test-row prediction. ``pipeline=True`` switches to the split-phase
    dispatch with cross-round speculation (module docstring)."""

    def __init__(self, cfg, registry, data, loss_fn, acc_fn,
                 use_agg_kernel: bool = False, pipeline: bool = False):
        super().__init__(cfg, registry, data)
        if self.databank is None:
            # adopt a bare stacked-splits dict as an identity-mapped
            # single-shard data bank (DESIGN.md §11)
            self.databank = self._make_bank(data)
            self.n_devices = self.databank.id_cap
        self.pipeline = pipeline
        self.use_agg_kernel = use_agg_kernel
        self._build_programs(loss_fn, acc_fn)
        # eval-row caches: a model's params change ONLY when it trains
        # or is born, so its (N,) accuracy rows are reused bit-
        # identically until then (DESIGN.md §2)
        self._val_cache: Dict[int, np.ndarray] = {}
        self._test_cache: Dict[int, np.ndarray] = {}
        self._pred_rows: List[int] = [0]
        self._needs_refresh = False
        self._pending: Optional[
            Tuple[RoundPlan, Dict[str, _Pending]]] = None
        self._spec: Optional[Tuple[RoundPlan, Any, TrainMeta,
                                   Tuple[int, int]]] = None
        self._spec_graveyard: List[Any] = []
        self._last_plan: Optional[RoundPlan] = None
        # semi-synchronous carry-over buffer (DESIGN.md §12): harvested
        # straggler trained rows keyed (dispatch round, model, device),
        # blended back in by ``_fold_stale`` when the planner says so
        self._stale_updates: Dict[Tuple[int, int, int], Any] = {}
        self._pending_harvest: Optional[
            Tuple[RoundPlan, Any, TrainMeta]] = None
        self.stats = PipelineStats() if pipeline else None
        # pipelined dispatch pads row schedules to a coarser floor so
        # the finish program's (A, L, R) shape key stops changing every
        # round — the split exists to decouple shape keys, and a stable
        # key turns per-round retraces into cache hits (DESIGN.md §10)
        self._row_floor = 4 if pipeline else 1

    def _make_bank(self, data: Dict[str, Any]) -> DeviceDataBank:
        return DeviceDataBank(data)

    @property
    def _dev(self):
        """The CURRENT device-resident splits. A property, not a cached
        reference: the bank replaces its split arrays on every churn
        row write, and a dispatch must read the post-churn data."""
        return self.databank.splits

    def _build_programs(self, loss_fn, acc_fn) -> None:
        cfg = self.cfg
        self._round = make_fused_round(loss_fn, acc_fn, cfg.lr,
                                       cfg.quantize_bits,
                                       self.use_agg_kernel)
        self._eval = make_fused_eval(acc_fn)
        self._pair_eval = make_pair_eval(acc_fn)
        self._train = make_pair_train(loss_fn, cfg.lr)
        self._apply = make_fused_apply(cfg.quantize_bits,
                                       self.use_agg_kernel)
        self._finish = make_fused_finish(acc_fn, cfg.quantize_bits,
                                         self.use_agg_kernel)

    # -- device id -> data row resolution (DESIGN.md §11) -----------------
    def _drows(self, device_ids: List[int]) -> List[int]:
        """Plans reference devices by ID; the bank's ``row_of`` resolves
        the data rows at dispatch (identity while there is no churn)."""
        row_of = self.databank.row_of
        return [row_of[d] for d in device_ids]

    def _to_id_row(self, vec: np.ndarray) -> np.ndarray:
        """Map one eval-matrix row (indexed by data-bank row) to the
        device-ID indexing the control plane uses. The full-fleet
        identity fast path keeps the no-churn layout bit-identical to
        PR 4; any absence forces the explicit map so DEPARTED devices'
        columns read 0 identically on every engine (their stale rows
        still compute values that must not leak into metrics)."""
        bank = self.databank
        if (bank.id_cap == bank.n_cap == bank.n_present
                and bank.identity_map()):
            return vec
        out = np.zeros(self.n_devices, vec.dtype)
        for d in bank.present_ids():
            out[d] = vec[bank.row_of[d]]
        return out

    # -- planning hints + lifecycle hooks ---------------------------------
    def plan_hints(self) -> EvalHints:
        return EvalHints(set(self._val_cache), set(self._test_cache),
                         list(self._pred_rows))

    def on_churn(self, joined: List[int], left: List[int],
                 drifted: List[int]) -> None:
        """Joins and drifts rewrite data-bank rows, so every cached
        per-model accuracy row holds stale columns — drop the caches
        and let the next plan mark the population stale (one full
        re-eval round, identical across engines). A pure leave changes
        no data: the departed device's cached entries are simply never
        read again (its active row is cleared)."""
        if joined or drifted:
            self._val_cache.clear()
            self._test_cache.clear()

    def on_clones(self, cloned: List[Tuple[int, int]]) -> None:
        if not cloned:
            return
        if self.cfg.quantize_bits:
            # clones are quantize roundtrips of their parents — cached
            # rows don't transfer; re-eval the population in collect
            self._needs_refresh = True
        else:
            # a clone's params are bit-identical to its parent's
            for parent, clone in cloned:
                if parent in self._val_cache:
                    self._val_cache[clone] = self._val_cache[parent]
                if parent in self._test_cache:
                    self._test_cache[clone] = self._test_cache[parent]

    # -- weight / batch builders ------------------------------------------
    def _apply_weights(self, meta: TrainMeta, plan: RoundPlan
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """(A_pad, B) weight matrix + padded agg row indices for the
        aggregate+scatter phase, addressed by META's pair columns (on a
        repaired speculation they are a superset of the plan's pairs —
        dead pairs score c=0 and models outside the plan's agg set get
        no weight row, so the superset aggregates identically)."""
        with spans.span("fedcd.launch.args"):
            agg_rows = pad_live_rows(plan.agg_models, self._row_floor)
            slot = {m: j for j, m in enumerate(plan.agg_models)}
            w = np.zeros((len(agg_rows), meta.b_pad), np.float32)
            for k, (m, d) in enumerate(zip(meta.pair_model,
                                           meta.pair_device)):
                j = slot.get(m)
                if j is not None:
                    w[j, k] = plan.scores[d, m]
            w[len(plan.agg_models):] = w[0]
            return w, agg_rows

    def _batch_args(self, pair_model: List[int],
                    pair_device: List[int], perms: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                               TrainMeta]:
        """ONE bucketing of the work pairs shared by the monolithic
        round and the split train phase, so the sync and pipelined
        programs can never see different batch schedules. ``d_idx``
        entries are data-bank ROWS (resolved here, at dispatch); perms
        stay device-ID-indexed — they are host control-plane state."""
        with spans.span("fedcd.launch.args"):
            m_idx, d_idx, pperms = pad_work_batch(
                pair_model, self._drows(pair_device),
                [perms[d] for d in pair_device])
            meta = TrainMeta(list(pair_model), list(pair_device),
                             len(m_idx),
                             positions=list(range(len(pair_model))))
        _count_pairs(len(pair_model), len(m_idx))
        return m_idx, d_idx, pperms, meta

    def _dispatch_train(self, tree: Any, pair_model: List[int],
                        pair_device: List[int], perms: np.ndarray
                        ) -> Tuple[Any, TrainMeta]:
        m_idx, d_idx, pperms, meta = self._batch_args(pair_model,
                                                      pair_device, perms)
        with spans.span("fedcd.launch.dispatch"):
            trained = self._train(tree, m_idx, *self._dev["train"], d_idx,
                                  pperms)
        return trained, meta

    def _dispatch_apply(self, trained: Any, meta: TrainMeta,
                        plan: RoundPlan) -> None:
        bank = self.registry.params
        w, agg_rows = self._apply_weights(meta, plan)
        with spans.span("fedcd.launch.dispatch"):
            bank.swap(self._apply(bank.tree, trained, w, agg_rows))

    # -- eval dispatch / readers ------------------------------------------
    def _val_reader_dense(self, fut: Any, models: List[int]) -> _Pending:
        def read() -> Dict[int, np.ndarray]:
            mat = _to_host(fut)[:len(models)]
            return {m: self._to_id_row(mat[j])
                    for j, m in enumerate(models)}
        return _Pending(fut, read)

    def _val_reader_sparse(self, fut: Any, plan: RoundPlan,
                           positions: List[int]) -> _Pending:
        """Merge sparse per-pair accuracies into full cached rows:
        pair k's value sits at ``positions[k]`` of the eval vector
        (identity for the single-device layout, shard-bucket slots for
        the sharded one); untouched entries keep their cached value and
        never-scored rows start at zero (only active entries are ever
        read — DESIGN.md §10)."""
        def read() -> Dict[int, np.ndarray]:
            vec = _to_host(fut)
            rows: Dict[int, np.ndarray] = {}
            for k, (m, d) in enumerate(zip(plan.val_pair_model,
                                           plan.val_pair_device)):
                if m not in rows:
                    rows[m] = self._val_cache.get(
                        m, np.zeros(self.n_devices)).copy()
                rows[m][d] = vec[positions[k]]
            return rows
        return _Pending(fut, read)

    def _dispatch_sparse_val(self, plan: RoundPlan) -> _Pending:
        with spans.span("fedcd.launch.args"):
            p = len(plan.val_pair_model)
            p_pad = bucket_size(p)
            m_idx = np.zeros(p_pad, np.int32)
            m_idx[:p] = plan.val_pair_model
            d_idx = np.zeros(p_pad, np.int32)
            d_idx[:p] = self._drows(plan.val_pair_device)
        with spans.span("fedcd.launch.dispatch"):
            fut = self._pair_eval(self.registry.params.tree, m_idx, d_idx,
                                  *self._dev["val"])
        return self._val_reader_sparse(fut, plan, list(range(p)))

    def _dispatch_dense(self, models: List[int], split: str) -> _Pending:
        rows = pad_live_rows(models, self._row_floor)
        with spans.span("fedcd.launch.dispatch"):
            fut = self._eval(self.registry.params.tree, rows,
                             *self._dev[split])
        return self._val_reader_dense(fut, models)

    def _dispatch_evals(self, plan: RoundPlan) -> Dict[str, _Pending]:
        pend: Dict[str, _Pending] = {}
        if plan.val_stale:
            pend["val"] = (self._dispatch_sparse_val(plan)
                           if plan.sparse_val
                           else self._dispatch_dense(plan.val_stale,
                                                     "val"))
        if plan.test_stale:
            pend["test"] = self._dispatch_dense(plan.test_stale, "test")
        return pend

    # -- semi-synchronous fold + harvest (DESIGN.md §12) -------------------
    def _fold_stale(self, plan: RoundPlan) -> None:
        """Blend the plan's matured straggler updates into their models'
        bank rows — a host-side row read/modify/write through the bank's
        item protocol, so it is engine-independent (the sharded banks
        re-pin the written row to its owning shard) and bumps the bank
        ``version``, which correctly invalidates any speculation built
        on pre-fold params. Runs at launch, BEFORE dispatch: this
        round's training and eval see post-fold params. The quantize
        roundtrip mirrors the aggregate→quantize→scatter order of the
        round programs."""
        for key in plan.fold_drops:
            self._stale_updates.pop(key, None)
        for m, (mass, entries) in plan.folds.items():
            updates = []
            for e in entries:
                tree = self._stale_updates.pop(
                    (e.dispatch_round, m, e.device), None)
                if tree is not None:
                    updates.append((e.weight, tree))
            if not updates or m not in self.registry.params:
                continue
            new = _blend_stale(self.registry.params[m], mass, updates)
            self.registry.params[m] = self._maybe_compress(new)

    # -- launch -----------------------------------------------------------
    def launch(self, plan: RoundPlan) -> None:
        with spans.span("fedcd.launch"):
            self._last_plan = plan
            self._fold_stale(plan)
            self._note_load(plan)
            spans.count("val_rows", len(plan.val_stale))
            spans.count("test_rows", len(plan.test_stale))
            if self.pipeline:
                self._launch_split(plan)
            else:
                self._launch_sync(plan)

    def _note_load(self, plan: RoundPlan) -> None:
        pass                             # sharded executor observes load

    def _launch_sync(self, plan: RoundPlan) -> None:
        bank = self.registry.params
        if plan.pair_model and not plan.sparse_val \
                and not plan.semisync_work():
            # the whole round as ONE donated dispatch (DESIGN.md §2)
            m_idx, d_idx, pperms, meta = self._batch_args(
                plan.pair_model, plan.pair_device, plan.perms)
            w, agg_rows = self._apply_weights(meta, plan)
            with spans.span("fedcd.launch.args"):
                val_rows = pad_live_rows(plan.val_stale or plan.live[:1])
                test_rows = pad_live_rows(plan.test_stale or plan.live[:1])
            with spans.span("fedcd.launch.dispatch"):
                new_stacked, val_mat, test_mat = self._round(
                    bank.tree, m_idx, d_idx, pperms, w, agg_rows, val_rows,
                    test_rows, *self._dev["train"], *self._dev["val"],
                    *self._dev["test"])
            bank.swap(new_stacked)
            pend: Dict[str, _Pending] = {}
            if plan.val_stale:
                pend["val"] = self._val_reader_dense(val_mat,
                                                     plan.val_stale)
            if plan.test_stale:
                pend["test"] = self._val_reader_dense(test_mat,
                                                      plan.test_stale)
        else:
            # sparse-val and semi-sync rounds use the split phases
            # (train + apply, then eval dispatches; semi-sync needs the
            # materialized train batch for the straggler harvest and
            # skips apply when no pair made the deadline); no-pair
            # rounds are eval-only
            if plan.pair_model and (plan.agg_models
                                    or plan.straggler_pairs):
                trained, meta = self._dispatch_train(
                    bank.tree, plan.pair_model, plan.pair_device,
                    plan.perms)
                if plan.agg_models:
                    self._dispatch_apply(trained, meta, plan)
                if plan.straggler_pairs:
                    self._pending_harvest = (plan, trained, meta)
            pend = self._dispatch_evals(plan)
        self._pending = (plan, pend)

    def _finish_round(self, trained: Any, meta: TrainMeta,
                      plan: RoundPlan) -> Dict[str, _Pending]:
        """Aggregate + scatter + stale-row eval as ONE dispatch (the
        ``make_*_finish`` program) — everything the monolithic round
        does after training, with the same program fusion."""
        bank = self.registry.params
        w, agg_rows = self._apply_weights(meta, plan)
        with spans.span("fedcd.launch.args"):
            val_rows = pad_live_rows(plan.val_stale or plan.live[:1],
                                     self._row_floor)
            test_rows = pad_live_rows(plan.test_stale or plan.live[:1],
                                      self._row_floor)
        with spans.span("fedcd.launch.dispatch"):
            new_stacked, val_mat, test_mat = self._finish(
                bank.tree, trained, w, agg_rows, val_rows, test_rows,
                *self._dev["val"], *self._dev["test"])
        bank.swap(new_stacked)
        pend: Dict[str, _Pending] = {}
        if plan.val_stale:
            pend["val"] = self._val_reader_dense(val_mat, plan.val_stale)
        if plan.test_stale:
            pend["test"] = self._val_reader_dense(test_mat,
                                                  plan.test_stale)
        return pend

    def _launch_split(self, plan: RoundPlan) -> None:
        bank = self.registry.params
        if plan.pair_model and (plan.agg_models or plan.straggler_pairs):
            spec = self._take_speculation(plan)
            if spec is None:
                trained, meta = self._dispatch_train(
                    bank.tree, plan.pair_model, plan.pair_device,
                    plan.perms)
            else:
                trained, meta = spec
            if plan.straggler_pairs:
                self._pending_harvest = (plan, trained, meta)
            if plan.sparse_val or not plan.agg_models:
                if plan.agg_models:
                    self._dispatch_apply(trained, meta, plan)
                pend = self._dispatch_evals(plan)
            else:
                pend = self._finish_round(trained, meta, plan)
        else:
            self._drop_speculation()
            pend = self._dispatch_evals(plan)
        self._pending = (plan, pend)

    # -- speculation ------------------------------------------------------
    def _discard_spec(self, invalidated: bool) -> None:
        """Abandon the pending speculation. Its in-flight ``trained``
        future is parked until the next readback — destructing it here
        would block on its pending execution (see StackedParamBank.
        swap). ``invalidated`` separates launch-time staleness (clones)
        from never-consumed batches (extinction / no-pair rounds, the
        degenerate repair) in the stats."""
        self._spec_graveyard.append(self._spec[1])
        self._spec = None
        if invalidated:
            self.stats.invalidated += 1
        else:
            self.stats.discarded += 1

    def _drop_speculation(self) -> None:
        if self._spec is not None:
            self._discard_spec(invalidated=False)

    def quiesce(self) -> None:
        """Snapshot barrier (DESIGN.md §13): discard the in-flight
        speculative batch (repairable, so drain-and-discard is safe —
        the resumed round trains synchronously and computes identical
        params) and free the graveyard + retired bank trees. May block
        on the speculation's pending execution; a snapshot blocks on
        the bank pull anyway."""
        self._drop_speculation()
        self._spec_graveyard.clear()
        self.registry.params.release_retired()

    def _take_speculation(self, plan: RoundPlan
                          ) -> Optional[Tuple[Any, TrainMeta]]:
        """Consume the pending speculative train batch if it still
        covers the true plan: deletions and device leaves only shrink
        the pair set, so a superset batch is repairable (dead pairs
        aggregate with zero weight); clones add pairs and rewrite
        PARAM bank rows, joins/drifts rewrite DATA bank rows — either
        version mismatch retrains from scratch."""
        if self._spec is None:
            return None
        spec_plan, trained, meta, versions = self._spec
        if (spec_plan.round != plan.round
                or (self.registry.params.version,
                    self.databank.version) != versions):
            self._discard_spec(invalidated=True)
            return None
        covered = set(zip(meta.pair_model, meta.pair_device))
        if any(p not in covered for p in plan.pairs()):
            self._discard_spec(invalidated=True)
            return None
        self._spec = None
        if len(plan.pair_model) == len(meta.pair_model):
            self.stats.hit += 1
        else:
            self.stats.repaired += 1
        return trained, meta

    def speculate(self, plan: RoundPlan) -> None:
        if not self.pipeline:
            return
        with spans.span("fedcd.speculate"):
            self._speculate(plan)

    def _speculate(self, plan: RoundPlan) -> None:
        self._drop_speculation()
        if self._last_plan is not None and (
                self._last_plan.clone_milestone
                or self._last_plan.churn_next
                or self._last_plan.fold_next):
            # pending lifecycle intent: milestone clones rewrite param
            # rows and add pairs; next-round device churn rewrites data
            # rows / changes the cohort; a next-round stale fold
            # rewrites param rows at launch — don't burn a dispatch
            self.stats.skipped += 1
            return
        if not plan.pair_model:
            return
        trained, meta = self._dispatch_train(
            self.registry.params.tree, plan.pair_model,
            plan.pair_device, plan.perms)
        self._spec = (plan, trained, meta,
                      (self.registry.params.version,
                       self.databank.version))
        self.stats.speculated += 1

    # -- readback + collect -----------------------------------------------
    def readback(self) -> RoundResult:
        with spans.span("fedcd.readback"):
            plan, pend = self._pending
            self._pending = None
            with spans.span("fedcd.readback.wait"):
                jax.block_until_ready([p.fut for p in pend.values()])
            with spans.span("fedcd.readback.copy"):
                accs = self._read_pending(plan, pend)
            # the step's consumers have completed: retired bank trees and
            # abandoned speculative batches can now destruct without
            # blocking the host (registry docstring)
            with spans.span("fedcd.readback.release"):
                self.registry.params.release_retired()
                self._spec_graveyard.clear()
        return RoundResult(accs)

    def _read_pending(self, plan: RoundPlan, pend: Dict[str, _Pending]
                      ) -> np.ndarray:
        """Copy the round's eval matrices into the row caches; the
        (N, M_cap) validation accuracies of the live models."""
        if self._pending_harvest is not None:
            hplan, trained, meta = self._pending_harvest
            self._pending_harvest = None
            _harvest_rows(self._stale_updates, hplan, trained, meta)
        if "val" in pend:
            self._val_cache.update(pend["val"].read())
        if "test" in pend:
            self._test_cache.update(pend["test"].read())
        # a changed model's (aggregated or stale-folded) old test row is
        # stale: drop it unless it was just re-evaluated
        for m in plan.changed_models():
            if m not in plan.test_stale:
                self._test_cache.pop(m, None)
        accs = np.zeros((self.n_devices, self.cfg.max_models))
        for m in plan.live:
            accs[:, m] = self._val_cache[m]
        return accs

    def eval_rows(self, rows: List[int], split: str) -> np.ndarray:
        """(len(rows), N) accuracy of the given models on one split —
        the standalone eval dispatch for collect's misprediction
        fallback and the quantized-cloning refresh."""
        with spans.span("fedcd.collect.eval"):
            spans.count("eval_rows_extra", len(rows))
            mat = np.asarray(self._eval(self.registry.params.tree,
                                        pad_live_rows(rows, self._row_floor),
                                        *self._dev[split]))
            return np.stack([self._to_id_row(r) for r in mat[:len(rows)]])

    def _refresh_caches(self) -> None:
        """Quantized cloning made every clone's params differ from its
        parent's: re-score the whole live population once."""
        live = self.registry.live_ids()
        if not live:
            self._val_cache, self._test_cache = {}, {}
            return
        val = self.eval_rows(live, "val")
        test = self.eval_rows(live, "test")
        self._val_cache = {m: val[j] for j, m in enumerate(live)}
        self._test_cache = {m: test[j] for j, m in enumerate(live)}

    def collect(self, preferred: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
        if self._needs_refresh:
            self._refresh_caches()
            self._needs_refresh = False
        entries = self.registry.entries
        wanted = [int(m) for m in preferred]
        # departed (or not-yet-joined) devices report no accuracy: their
        # cached columns may hold pre-leave values on one dispatch path
        # and zeros on another, so presence gates the read (DESIGN.md
        # §11)
        usable = [m if (m in entries and entries[m].alive
                        and m in self._val_cache
                        and i in self.databank) else None
                  for i, m in enumerate(wanted)]
        missing = sorted({m for m in usable
                          if m is not None and m not in self._test_cache})
        if missing:
            # test-row prediction missed (a preference shifted to a
            # model that didn't train): one small dense eval
            extra = self.eval_rows(missing, "test")
            for j, m in enumerate(missing):
                self._test_cache[m] = extra[j]
        test_acc = np.zeros(self.n_devices)
        val_acc = np.zeros(self.n_devices)
        for i, m in enumerate(usable):
            if m is not None:
                test_acc[i] = self._test_cache[m][i]
                val_acc[i] = self._val_cache[m][i]
        # predict next round's test rows: what devices prefer now
        self._pred_rows = sorted({m for m in usable if m is not None})
        return test_acc, val_acc


class ShardedExecutor(FusedExecutor):
    """The fused data plane over a 1-D ``model``-axis mesh (DESIGN.md
    §9): bank rows and work pairs bucket per owning shard, each mesh
    slice trains/aggregates/scatters only its resident rows, and only
    the small row-sharded eval matrices cross back to the host. Feeds
    the observed per-shard pair load into the bank's work-aware row
    placement every round."""

    def __init__(self, cfg, registry, data, loss_fn, acc_fn, mesh,
                 use_agg_kernel: bool = False, pipeline: bool = False,
                 migrate_threshold: Optional[float] = None):
        self.mesh = mesh
        self._n_shards = model_axis_size(mesh)
        self._rows_per_shard = bank_rows_per_shard(cfg.max_models, mesh)
        self.migrate_threshold = migrate_threshold
        self.migrations = 0
        super().__init__(cfg, registry, data, loss_fn, acc_fn,
                         use_agg_kernel, pipeline)

    def _make_bank(self, data):
        if data_axis_size(self.mesh) > 1:
            return DeviceDataBank(data, mesh=self.mesh)
        return DeviceDataBank(data)

    def _build_programs(self, loss_fn, acc_fn) -> None:
        cfg = self.cfg
        self._round = make_sharded_round(loss_fn, acc_fn, cfg.lr,
                                         self.mesh, cfg.quantize_bits,
                                         self.use_agg_kernel)
        self._eval = make_sharded_eval(acc_fn, self.mesh)
        self._pair_eval = make_sharded_pair_eval(acc_fn, self.mesh)
        self._train = make_sharded_train(loss_fn, cfg.lr, self.mesh)
        self._apply = make_sharded_apply(self.mesh, cfg.quantize_bits,
                                         self.use_agg_kernel)
        self._finish = make_sharded_finish(acc_fn, self.mesh,
                                           cfg.quantize_bits,
                                           self.use_agg_kernel)

    def _rows(self, models: List[int]) -> List[int]:
        row_of = self.registry.params.row_of
        return [row_of[m] for m in models]

    def _note_load(self, plan: RoundPlan) -> None:
        counts = np.zeros(self._n_shards)
        for r in self._rows(plan.pair_model):
            counts[r // self._rows_per_shard] += 1
        self.registry.params.note_pair_load(counts)
        if self.migrate_threshold is not None:
            # rebalance BEFORE this round's bucketing so the moved
            # row's pairs land on its new shard; the bank version bump
            # invalidates any speculation built on the old placement
            self.migrations += len(
                self.registry.params.rebalance(self.migrate_threshold))

    def _shard_row_slots(self, bank_rows: List[int]
                         ) -> Tuple[np.ndarray, Dict[int, int]]:
        with spans.span("fedcd.launch.args"):
            idx, groups, width = shard_rows(bank_rows,
                                            self._rows_per_shard,
                                            self._n_shards,
                                            minimum=self._row_floor)
            pos = {r: s * width + j
                   for s, g in enumerate(groups) for j, r in enumerate(g)}
            return idx, pos

    def _batch_args(self, pair_model: List[int],
                    pair_device: List[int], perms: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                               TrainMeta]:
        # per-shard bucket floor scales down with the shard count: the
        # global work splits S ways (DESIGN.md §9)
        with spans.span("fedcd.launch.args"):
            m_idx, d_idx, pperms, pair_groups, b_pad = shard_work_batch(
                self._rows(pair_model), self._drows(pair_device),
                [perms[d] for d in pair_device], self._rows_per_shard,
                self._n_shards, minimum=max(8 // self._n_shards, 2))
            meta = TrainMeta(list(pair_model), list(pair_device), b_pad,
                             pair_groups,
                             positions=_group_positions(pair_groups, b_pad,
                                                        len(pair_model)))
        _count_pairs(len(pair_model), len(m_idx))
        return m_idx, d_idx, pperms, meta

    def _dispatch_train(self, tree: Any, pair_model: List[int],
                        pair_device: List[int], perms: np.ndarray
                        ) -> Tuple[Any, TrainMeta]:
        m_idx, d_idx, pperms, meta = self._batch_args(pair_model,
                                                      pair_device, perms)
        with spans.span("fedcd.launch.dispatch"):
            trained = self._train(tree, m_idx, d_idx, pperms,
                                  *self._dev["train"])
        return trained, meta

    def _shard_agg_plan(self, agg_rows: List[int], meta: TrainMeta,
                        c: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Per-shard aggregation schedule (DESIGN.md §9): LOCAL agg row
        indices (S*A,), the (S*A, B) weight blocks, and the keep mask
        guarding the scatter. Pairs are addressed by META's bucket
        columns; a repaired speculation's dead pairs simply find no
        slot (their model left the agg set) or score c=0."""
        with spans.span("fedcd.launch.args"):
            S = self._n_shards
            row_of = self.registry.params.row_of
            agg_idx, agg_groups, a_pad = shard_rows(
                agg_rows, self._rows_per_shard, S, minimum=self._row_floor)
            keep = np.zeros(S * a_pad, bool)
            w = np.zeros((S * a_pad, meta.b_pad), np.float32)
            for s, group in enumerate(agg_groups):
                if not group:
                    continue
                base = s * a_pad
                keep[base:base + a_pad] = True
                slot = {r: j for j, r in enumerate(group)}
                for col, k in enumerate(meta.pair_groups[s]):
                    m, d = meta.pair_model[k], meta.pair_device[k]
                    j = slot.get(row_of[m])
                    if j is not None:
                        w[base + j, col] = c[d, m]
                w[base + len(group):base + a_pad] = w[base]
            return agg_idx, keep, w

    def _dispatch_apply(self, trained: Any, meta: TrainMeta,
                        plan: RoundPlan) -> None:
        bank = self.registry.params
        agg_idx, keep, w = self._shard_agg_plan(
            self._rows(plan.agg_models), meta, plan.scores)
        with spans.span("fedcd.launch.dispatch"):
            bank.swap(self._apply(bank.tree, trained, w, agg_idx, keep))

    def _finish_round(self, trained: Any, meta: TrainMeta,
                      plan: RoundPlan) -> Dict[str, _Pending]:
        bank = self.registry.params
        agg_idx, keep, w = self._shard_agg_plan(
            self._rows(plan.agg_models), meta, plan.scores)
        vidx, vpos = self._shard_row_slots(
            self._rows(plan.val_stale or plan.live[:1]))
        tidx, tpos = self._shard_row_slots(
            self._rows(plan.test_stale or plan.live[:1]))
        with spans.span("fedcd.launch.dispatch"):
            new_stacked, val_mat, test_mat = self._finish(
                bank.tree, trained, w, agg_idx, keep, vidx, tidx,
                *self._dev["val"], *self._dev["test"])
        bank.swap(new_stacked)
        pend: Dict[str, _Pending] = {}
        if plan.val_stale:
            pend["val"] = self._sharded_reader(val_mat, plan.val_stale,
                                               vpos)
        if plan.test_stale:
            pend["test"] = self._sharded_reader(test_mat,
                                                plan.test_stale, tpos)
        return pend

    def _sharded_reader(self, fut: Any, models: List[int],
                        pos: Dict[int, int]) -> _Pending:
        row_of = self.registry.params.row_of

        def read() -> Dict[int, np.ndarray]:
            mat = _to_host(fut)           # the eval all-gather boundary
            return {m: self._to_id_row(mat[pos[row_of[m]]])
                    for m in models}
        return _Pending(fut, read)

    def _dispatch_dense(self, models: List[int], split: str) -> _Pending:
        idx, pos = self._shard_row_slots(self._rows(models))
        with spans.span("fedcd.launch.dispatch"):
            fut = self._eval(self.registry.params.tree, idx,
                             *self._dev[split])
        return self._sharded_reader(fut, models, pos)

    def _dispatch_sparse_val(self, plan: RoundPlan) -> _Pending:
        with spans.span("fedcd.launch.args"):
            m_idx, d_idx, groups, width = shard_eval_pairs(
                self._rows(plan.val_pair_model),
                self._drows(plan.val_pair_device),
                self._rows_per_shard, self._n_shards,
                minimum=max(8 // self._n_shards, 2))
        with spans.span("fedcd.launch.dispatch"):
            fut = self._pair_eval(self.registry.params.tree, m_idx, d_idx,
                                  *self._dev["val"])
        positions = [0] * len(plan.val_pair_model)
        for s, g in enumerate(groups):
            for j, k in enumerate(g):
                positions[k] = s * width + j
        return self._val_reader_sparse(fut, plan, positions)

    def _launch_sync(self, plan: RoundPlan) -> None:
        bank = self.registry.params
        if plan.pair_model and not plan.sparse_val \
                and not plan.semisync_work():
            m_idx, d_idx, pperms, meta = self._batch_args(
                plan.pair_model, plan.pair_device, plan.perms)
            agg_idx, keep, w = self._shard_agg_plan(
                self._rows(plan.agg_models), meta, plan.scores)
            vidx, vpos = self._shard_row_slots(
                self._rows(plan.val_stale or plan.live[:1]))
            tidx, tpos = self._shard_row_slots(
                self._rows(plan.test_stale or plan.live[:1]))
            with spans.span("fedcd.launch.dispatch"):
                new_stacked, val_mat, test_mat = self._round(
                    bank.tree, m_idx, d_idx, pperms, w, agg_idx, keep,
                    vidx, tidx, *self._dev["train"], *self._dev["val"],
                    *self._dev["test"])
            bank.swap(new_stacked)
            pend: Dict[str, _Pending] = {}
            if plan.val_stale:
                pend["val"] = self._sharded_reader(val_mat,
                                                   plan.val_stale, vpos)
            if plan.test_stale:
                pend["test"] = self._sharded_reader(test_mat,
                                                    plan.test_stale,
                                                    tpos)
        else:
            if plan.pair_model and (plan.agg_models
                                    or plan.straggler_pairs):
                trained, meta = self._dispatch_train(
                    bank.tree, plan.pair_model, plan.pair_device,
                    plan.perms)
                if plan.agg_models:
                    self._dispatch_apply(trained, meta, plan)
                if plan.straggler_pairs:
                    self._pending_harvest = (plan, trained, meta)
            pend = self._dispatch_evals(plan)
        self._pending = (plan, pend)

    def eval_rows(self, rows: List[int], split: str) -> np.ndarray:
        with spans.span("fedcd.collect.eval"):
            spans.count("eval_rows_extra", len(rows))
            row_of = self.registry.params.row_of
            idx, pos = self._shard_row_slots(self._rows(rows))
            mat = np.asarray(self._eval(self.registry.params.tree, idx,
                                        *self._dev[split]))
            return np.stack([self._to_id_row(mat[pos[row_of[m]]])
                             for m in rows])


class Sharded2DExecutor(ShardedExecutor):
    """The data plane on the full 2-D ``(model × data)`` launch mesh
    (DESIGN.md §11): the param bank's rows shard over ``model`` exactly
    as in :class:`ShardedExecutor`, the device data bank's rows shard
    over ``data`` (splits are no longer replicated per model shard),
    and dispatch-time bucketing groups work pairs by owning MESH CELL —
    (model shard of the pair's bank row) × (data shard of the pair's
    data row). Per PR 4's plan/executor split this is dispatch-layer
    work only: the round/train/finish/apply programs share the 1-D
    engine's signatures, so launch, speculation, repair, readback, and
    the eval-row caches are all inherited unchanged. The one new
    collective is the ``data``-axis psum completing eq 1 (a model's
    holders may live on several data shards), which is why this
    executor's params match the 1-data-shard oracle to reduction order
    while its discrete state matches exactly."""

    def __init__(self, cfg, registry, data, loss_fn, acc_fn, mesh,
                 use_agg_kernel: bool = False, pipeline: bool = False,
                 migrate_threshold: Optional[float] = None):
        if use_agg_kernel:
            raise ValueError(
                "use_agg_kernel is unsupported with a sharded data axis "
                "(eq 1 completes with a psum over partial sums)")
        self._n_dshards = data_axis_size(mesh)
        super().__init__(cfg, registry, data, loss_fn, acc_fn, mesh,
                         use_agg_kernel=False, pipeline=pipeline,
                         migrate_threshold=migrate_threshold)

    def _build_programs(self, loss_fn, acc_fn) -> None:
        cfg = self.cfg
        self._round = make_sharded2d_round(loss_fn, acc_fn, cfg.lr,
                                           self.mesh, cfg.quantize_bits)
        self._eval = make_sharded2d_eval(acc_fn, self.mesh)
        self._pair_eval = make_sharded2d_pair_eval(acc_fn, self.mesh)
        self._train = make_sharded2d_train(loss_fn, cfg.lr, self.mesh)
        self._apply = make_sharded2d_apply(self.mesh, cfg.quantize_bits)
        self._finish = make_sharded2d_finish(acc_fn, self.mesh,
                                             cfg.quantize_bits)

    @property
    def _n_cells(self) -> int:
        return self._n_shards * self._n_dshards

    def _note_load(self, plan: RoundPlan) -> None:
        # model-axis load -> param bank (inherited), plus the DATA-axis
        # twin: per-data-shard pair counts feed the data bank's
        # churn-aware row placement, so joining devices land away from
        # shards whose resident devices concentrate the round's pairs
        super()._note_load(plan)
        counts = np.zeros(self._n_dshards)
        for d in plan.pair_device:
            counts[self.databank.shard_of(d)] += 1
        self.databank.note_pair_load(counts)

    def _batch_args(self, pair_model: List[int],
                    pair_device: List[int], perms: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                               TrainMeta]:
        # the global work now splits Sm*Sd ways — scale the bucket
        # floor with the cell count, not the model-shard count
        with spans.span("fedcd.launch.args"):
            m_idx, d_idx, pperms, cell_groups, b_pad = shard_pairs_2d(
                self._rows(pair_model), self._drows(pair_device),
                [perms[d] for d in pair_device], self._rows_per_shard,
                self._n_shards, self.databank.rows_per_shard,
                self._n_dshards, minimum=max(8 // self._n_cells, 2))
            meta = TrainMeta(list(pair_model), list(pair_device), b_pad,
                             cell_groups,
                             positions=_group_positions(cell_groups, b_pad,
                                                        len(pair_model)))
        _count_pairs(len(pair_model), len(m_idx))
        return m_idx, d_idx, pperms, meta

    def _shard_agg_plan(self, agg_rows: List[int], meta: TrainMeta,
                        c: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The 2-D aggregation schedule: LOCAL agg row indices (Sm*A,),
        the (Sm*A, Sd*B) weight grid — cell (sm, sd) holds the (A, B)
        block pairing model shard sm's agg rows with cell (sm, sd)'s
        bucket columns — and the keep mask guarding the scatter. Padding
        agg rows repeat the shard's first row's FULL weight row (all
        data-shard blocks), so duplicate scatter indices still carry
        identical post-psum values; empty model shards keep-mask their
        rows to existing values exactly as in the 1-D plan."""
        with spans.span("fedcd.launch.args"):
            S_m, S_d = self._n_shards, self._n_dshards
            row_of = self.registry.params.row_of
            agg_idx, agg_groups, a_pad = shard_rows(
                agg_rows, self._rows_per_shard, S_m, minimum=self._row_floor)
            keep = np.zeros(S_m * a_pad, bool)
            w = np.zeros((S_m * a_pad, S_d * meta.b_pad), np.float32)
            for sm, group in enumerate(agg_groups):
                if not group:
                    continue
                base = sm * a_pad
                keep[base:base + a_pad] = True
                slot = {r: j for j, r in enumerate(group)}
                for sd in range(S_d):
                    cbase = sd * meta.b_pad
                    for col, k in enumerate(
                            meta.pair_groups[sm * S_d + sd]):
                        m, d = meta.pair_model[k], meta.pair_device[k]
                        j = slot.get(row_of[m])
                        if j is not None:
                            w[base + j, cbase + col] = c[d, m]
                w[base + len(group):base + a_pad] = w[base]
            return agg_idx, keep, w

    def _dispatch_sparse_val(self, plan: RoundPlan) -> _Pending:
        with spans.span("fedcd.launch.args"):
            m_idx, d_idx, groups, width = shard_eval_pairs_2d(
                self._rows(plan.val_pair_model),
                self._drows(plan.val_pair_device),
                self._rows_per_shard, self._n_shards,
                self.databank.rows_per_shard, self._n_dshards,
                minimum=max(8 // self._n_cells, 2))
        with spans.span("fedcd.launch.dispatch"):
            fut = self._pair_eval(self.registry.params.tree, m_idx, d_idx,
                                  *self._dev["val"])
        positions = [0] * len(plan.val_pair_model)
        for cell, g in enumerate(groups):
            for j, k in enumerate(g):
                positions[k] = cell * width + j
        return self._val_reader_sparse(fut, plan, positions)


# -- FedAvg executors -------------------------------------------------------

@dataclass
class FedAvgResult:
    val_acc: np.ndarray                  # (N,)
    test_acc: np.ndarray                 # (N,)


class FedAvgExecutorBase:
    """FedAvg's round has no control-plane feedback at all (one global
    model, uniform weights), so its executors share the FedCD contract
    but speculation is exact: the next round's train batch IS the next
    plan, never repaired or invalidated."""

    pipeline = False
    stats: Optional[PipelineStats] = None

    def __init__(self, cfg, data):
        self.cfg = cfg
        self.data = data
        self.n_devices = data["train"][0].shape[0]
        self._result: Optional[FedAvgResult] = None

    def get_params(self) -> Any:
        raise NotImplementedError

    def set_params(self, value: Any) -> None:
        raise NotImplementedError

    def launch(self, plan: RoundPlan) -> None:
        raise NotImplementedError

    def speculate(self, plan: RoundPlan) -> None:
        pass

    def quiesce(self) -> None:
        pass                             # snapshot barrier (DESIGN.md §13)

    def readback(self) -> FedAvgResult:
        result, self._result = self._result, None
        return result


class FedAvgHostExecutor(FedAvgExecutorBase):
    """The legacy / batched FedAvg paths: host-resident global model."""

    def __init__(self, cfg, data, init_params, loss_fn, acc_fn,
                 batch_size: int, batched: bool):
        super().__init__(cfg, data)
        self.params = init_params
        self.batched = batched
        if batched:
            self.group_train = make_group_train(loss_fn, cfg.lr,
                                                batch_size)
        else:
            self.local_train = make_local_train(loss_fn, cfg.lr,
                                                batch_size)
        self.evaluate = make_eval(acc_fn)

    def get_params(self) -> Any:
        return self.params

    def set_params(self, value: Any) -> None:
        self.params = value

    def launch(self, plan: RoundPlan) -> None:
        xs, ys = self.data["train"]
        if self.batched:
            d_ids = plan.pair_device
            b = len(d_ids)
            m_idx, d_idx, pp = pad_work_batch(
                [0] * b, list(d_ids), [plan.perms[d] for d in d_ids])
            stacked = jax.tree.map(lambda a: jnp.asarray(a)[None],
                                   self.params)
            trained = self.group_train(stacked, m_idx, xs, ys, d_idx, pp)
            w = np.zeros((1, len(m_idx)), np.float32)
            w[0, :b] = 1.0
            agg = multi_weighted_average(trained, w)
            self.params = jax.tree.map(lambda a: np.asarray(a[0]), agg)
        else:
            trained = self.local_train(self.params, xs, ys, plan.perms)
            w = plan.participating.astype(np.float32)
            self.params = jax.tree.map(np.asarray,
                                       weighted_average(trained, w))
        tx, ty = self.data["test"]
        vx, vy = self.data["val"]
        self._result = FedAvgResult(
            val_acc=np.asarray(self.evaluate(self.params, vx, vy)),
            test_acc=np.asarray(self.evaluate(self.params, tx, ty)))


class FedAvgFusedExecutor(FedAvgExecutorBase):
    """Device-resident FedAvg: the global model is row 0 of a (1, ...)
    bank and the synchronous round is one donated dispatch
    (``make_fused_round`` with one-row buckets). ``pipeline=True``
    splits train / apply / eval so the next round's training is
    enqueued before this round's eval matrices are read back."""

    def __init__(self, cfg, data, init_params, loss_fn, acc_fn,
                 pipeline: bool = False):
        super().__init__(cfg, data)
        self.pipeline = pipeline
        self._dev = {k: (jnp.asarray(x), jnp.asarray(y))
                     for k, (x, y) in data.items()}
        self._stacked = jax.tree.map(
            lambda a: jnp.asarray(a)[None], init_params)
        self._build_programs(loss_fn, acc_fn)
        self._pending: Optional[Tuple[Any, Any]] = None
        self._spec: Optional[Tuple[int, Any, TrainMeta]] = None
        self._retired: List[Any] = []     # see StackedParamBank.swap
        self.stats = PipelineStats() if pipeline else None
        # semi-sync state (DESIGN.md §12): buffered straggler updates
        # keyed (dispatch round, model, device) + the deferred harvest
        self._stale_updates: Dict[Tuple[int, int, int], Any] = {}
        self._pending_harvest: Optional[
            Tuple[RoundPlan, Any, TrainMeta]] = None
        self._last_plan: Optional[RoundPlan] = None

    def _swap(self, new_stacked: Any) -> None:
        self._retired.append(self._stacked)
        self._stacked = new_stacked

    def _build_programs(self, loss_fn, acc_fn) -> None:
        cfg = self.cfg
        self._round = make_fused_round(loss_fn, acc_fn, cfg.lr)
        self._train = make_pair_train(loss_fn, cfg.lr)
        self._finish = make_fused_finish(acc_fn)
        self._evalp = make_fused_eval(acc_fn)

    def get_params(self) -> Any:
        return jax.tree.map(lambda a: a[0], self._stacked)

    def set_params(self, value: Any) -> None:
        self._retired.append(self._stacked)
        self._stacked = jax.tree.map(lambda a: jnp.asarray(a)[None],
                                     value)
        self._park_spec()                # the bank was rewritten

    def quiesce(self) -> None:
        """Snapshot barrier (DESIGN.md §13): park any speculative round
        and free retired trees before the bank is serialized."""
        self._park_spec()
        self._retired.clear()

    def _park_spec(self) -> None:
        """Drop a pending speculation without destructing its
        in-flight buffers (see StackedParamBank.swap)."""
        if self._spec is not None:
            self._retired.append(self._spec[1])
            self._spec = None

    # -- semi-sync fold (DESIGN.md §12) -----------------------------------
    def _fold_stale(self, plan: RoundPlan) -> None:
        """Blend buffered straggler updates into the global model BEFORE
        this round's dispatch (FedAvg has one model, id 0)."""
        for key in plan.fold_drops:
            self._stale_updates.pop(key, None)
        for m, (mass, entries) in plan.folds.items():
            updates = []
            for e in entries:
                tree = self._stale_updates.pop(
                    (e.dispatch_round, m, e.device), None)
                if tree is not None:
                    updates.append((e.weight, tree))
            if updates:
                self.set_params(_blend_stale(self.get_params(), mass,
                                             updates))

    # -- split-phase pieces (overridden by the sharded variant) -----------
    def _dispatch_train(self, plan: RoundPlan) -> Tuple[Any, TrainMeta]:
        d_ids = plan.pair_device
        m_idx, d_idx, pp = pad_work_batch(
            [0] * len(d_ids), list(d_ids),
            [plan.perms[d] for d in d_ids])
        trained = self._train(self._stacked, m_idx, *self._dev["train"],
                              d_idx, pp)
        return trained, TrainMeta([0] * len(d_ids), list(d_ids),
                                  len(m_idx),
                                  positions=list(range(len(d_ids))))

    def _dispatch_finish(self, trained: Any, meta: TrainMeta,
                         plan: RoundPlan) -> Tuple[Any, Any]:
        # weights come from the TRUE plan (eq-1 for FedAvg: 1 per
        # on-time pair, 0 for weight-zeroed straggler/dropout pairs)
        w = np.zeros((1, meta.b_pad), np.float32)
        for d, p in zip(meta.pair_device, meta.positions):
            w[0, p] = plan.scores[d, 0]
        new_stacked, val_mat, test_mat = self._finish(
            self._stacked, trained, w, np.zeros(1, np.int32),
            np.zeros(1, np.int32), np.zeros(1, np.int32),
            *self._dev["val"], *self._dev["test"])
        self._swap(new_stacked)
        return val_mat, test_mat

    def _dispatch_eval_only(self) -> Tuple[Any, Any]:
        """A round whose every pair straggled or dropped: the global
        model keeps its (post-fold) params; only the eval rows run."""
        row = np.zeros(1, np.int32)
        return (self._evalp(self._stacked, row, *self._dev["val"]),
                self._evalp(self._stacked, row, *self._dev["test"]))

    def _launch_sync(self, plan: RoundPlan) -> None:
        d_ids = plan.pair_device
        b = len(d_ids)
        m_idx, d_idx, pp = pad_work_batch(
            [0] * b, list(d_ids), [plan.perms[d] for d in d_ids])
        w = np.zeros((1, len(m_idx)), np.float32)
        w[0, :b] = plan.scores[np.asarray(d_ids, np.int64), 0]
        new_stacked, val_mat, test_mat = self._round(
            self._stacked, m_idx, d_idx, pp, w, np.zeros(1, np.int32),
            np.zeros(1, np.int32), np.zeros(1, np.int32),
            *self._dev["train"], *self._dev["val"], *self._dev["test"])
        self._swap(new_stacked)
        self._pending = (val_mat, test_mat)

    def launch(self, plan: RoundPlan) -> None:
        self._last_plan = plan
        self._fold_stale(plan)           # parks any (pre-fold) spec
        if not self.pipeline and not plan.semisync_work():
            self._launch_sync(plan)
            return
        trained = meta = None
        if plan.agg_models or plan.straggler_pairs:
            if self._spec is not None and self._spec[0] == plan.round:
                _, trained, meta = self._spec
                self._spec = None
                self.stats.hit += 1
            else:
                self._park_spec()
                trained, meta = self._dispatch_train(plan)
        if plan.agg_models:
            self._pending = self._dispatch_finish(trained, meta, plan)
        else:
            self._pending = self._dispatch_eval_only()
        if plan.straggler_pairs and trained is not None:
            self._pending_harvest = (plan, trained, meta)

    def speculate(self, plan: RoundPlan) -> None:
        if not self.pipeline:
            return
        if self._last_plan is not None and self._last_plan.fold_next:
            # round t+1 starts by folding buffered updates into the
            # bank — training against pre-fold params would be wasted
            self.stats.skipped += 1
            return
        trained, meta = self._dispatch_train(plan)
        self._spec = (plan.round, trained, meta)
        self.stats.speculated += 1

    def readback(self) -> FedAvgResult:
        if self._pending_harvest is not None:
            hplan, trained, meta = self._pending_harvest
            self._pending_harvest = None
            _harvest_rows(self._stale_updates, hplan, trained, meta)
        val_mat, test_mat = self._pending
        self._pending = None
        result = FedAvgResult(val_acc=np.asarray(val_mat)[0],
                              test_acc=np.asarray(test_mat)[0])
        self._retired.clear()            # consumers completed; no block
        return result


class FedAvgShardedExecutor(FedAvgFusedExecutor):
    """FedAvg's fused round with the work-PAIR axis sharded over the
    mesh's ``model`` axis (one global model, replicated): participating
    devices deal round-robin over shards, each shard reduces a partial
    weighted sum, and one psum completes eq 1 (DESIGN.md §9)."""

    def __init__(self, cfg, data, init_params, loss_fn, acc_fn, mesh,
                 pipeline: bool = False):
        self.mesh = mesh
        self._n_shards = model_axis_size(mesh)
        super().__init__(cfg, data, init_params, loss_fn, acc_fn,
                         pipeline)

    def _build_programs(self, loss_fn, acc_fn) -> None:
        cfg = self.cfg
        self._round = make_sharded_fedavg_round(loss_fn, acc_fn, cfg.lr,
                                                self.mesh)
        self._train = make_sharded_fedavg_train(loss_fn, cfg.lr,
                                                self.mesh)
        self._finish = make_sharded_fedavg_finish(acc_fn, self.mesh)
        self._evalp = make_fused_eval(acc_fn)

    def _shard_batch(self, plan: RoundPlan
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray, int]:
        """Deal the participating devices round-robin over the mesh and
        pad each shard's block to one shared bucket (zero-weight
        padding pairs), mirroring the FedCD sharded work batch. Pair
        weights come from ``plan.scores`` (1 on time, 0 weight-zeroed)."""
        S = self._n_shards
        d_ids = np.asarray(plan.pair_device, np.int64)
        chunks = [d_ids[s::S] for s in range(S)]
        width = bucket_size(max(len(ch) for ch in chunks),
                            minimum=max(8 // S, 2))
        m_idx = np.zeros(S * width, np.int32)
        d_idx = np.zeros(S * width, np.int32)
        pp = np.zeros((S * width,) + plan.perms[0].shape, np.int32)
        w = np.zeros(S * width, np.float32)
        for s, ch in enumerate(chunks):
            base = s * width
            d_idx[base:base + len(ch)] = ch
            w[base:base + len(ch)] = plan.scores[ch, 0]
            for j, d in enumerate(ch):
                pp[base + j] = plan.perms[d]
        return m_idx, d_idx, pp, w, width

    def _positions(self, n_pairs: int, width: int) -> List[int]:
        """Pair k deals to shard ``k % S`` slot ``k // S``."""
        S = self._n_shards
        return [(k % S) * width + (k // S) for k in range(n_pairs)]

    def _launch_sync(self, plan: RoundPlan) -> None:
        m_idx, d_idx, pp, w, _ = self._shard_batch(plan)
        new_stacked, val_mat, test_mat = self._round(
            self._stacked, m_idx, d_idx, pp, w,
            *self._dev["train"], *self._dev["val"], *self._dev["test"])
        self._swap(new_stacked)
        self._pending = (val_mat, test_mat)

    def _dispatch_train(self, plan: RoundPlan) -> Tuple[Any, TrainMeta]:
        m_idx, d_idx, pp, _, width = self._shard_batch(plan)
        trained = self._train(self._stacked, m_idx, d_idx, pp,
                              *self._dev["train"])
        b = len(plan.pair_device)
        meta = TrainMeta([0] * b, list(plan.pair_device), width,
                         positions=self._positions(b, width))
        return trained, meta

    def _dispatch_finish(self, trained: Any, meta: TrainMeta,
                         plan: RoundPlan) -> Tuple[Any, Any]:
        w = np.zeros(self._n_shards * meta.b_pad, np.float32)
        for d, p in zip(meta.pair_device, meta.positions):
            w[p] = plan.scores[d, 0]
        new_stacked, val_mat, test_mat = self._finish(
            self._stacked, trained, w,
            *self._dev["val"], *self._dev["test"])
        self._swap(new_stacked)
        return val_mat, test_mat


class FedAvgSharded2DExecutor(FedAvgFusedExecutor):
    """FedAvg on the full 2-D (model × data) launch mesh (DESIGN.md
    §11): the device data's row axis shards over ``data``, each
    participating device's pair runs on a cell in its owning data slice
    (dealt round-robin over the ``model`` axis within the slice — one
    global model, so the model axis is pure extra work parallelism),
    and one psum over BOTH axes completes eq 1. This is the baseline's
    sharded data plane: device populations scale past one slice's
    memory exactly as FedCD's do."""

    def __init__(self, cfg, data, init_params, loss_fn, acc_fn, mesh,
                 pipeline: bool = False):
        self.mesh = mesh
        self._n_mshards = model_axis_size(mesh)
        self._n_dshards = data_axis_size(mesh)
        n = data["train"][0].shape[0]
        if n % self._n_dshards:
            raise ValueError(
                f"n_devices={n} must divide evenly over the data axis "
                f"({self._n_dshards} shards)")
        self._rows_per_dshard = n // self._n_dshards
        super().__init__(cfg, data, init_params, loss_fn, acc_fn,
                         pipeline)

    def _build_programs(self, loss_fn, acc_fn) -> None:
        cfg = self.cfg
        self._round = make_sharded2d_fedavg_round(loss_fn, acc_fn,
                                                  cfg.lr, self.mesh)
        self._train = make_sharded2d_fedavg_train(loss_fn, cfg.lr,
                                                  self.mesh)
        self._finish = make_sharded2d_fedavg_finish(acc_fn, self.mesh)
        self._eval2d = make_sharded2d_fedavg_eval(acc_fn, self.mesh)

    def _cell_batch(self, plan: RoundPlan
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                               np.ndarray, List[List[int]], int]:
        """Bucket pairs per owning mesh cell: device d lives in data
        shard ``d // rows_per_dshard``; within the slice pairs deal
        round-robin over the model axis. Cells are model-major
        (``cell = sm * Sd + sd``, the ``P(("model", "data"))`` block
        order); padding pairs carry zero weight."""
        Sm, Sd = self._n_mshards, self._n_dshards
        groups: List[List[int]] = [[] for _ in range(Sm * Sd)]
        dealt = [0] * Sd
        for k, d in enumerate(plan.pair_device):
            sd = d // self._rows_per_dshard
            groups[(dealt[sd] % Sm) * Sd + sd].append(k)
            dealt[sd] += 1
        width = bucket_size(max((len(g) for g in groups), default=0),
                            minimum=2)
        m_idx = np.zeros(Sm * Sd * width, np.int32)
        d_idx = np.zeros(Sm * Sd * width, np.int32)
        pp = np.zeros((Sm * Sd * width,) + plan.perms[0].shape, np.int32)
        w = np.zeros(Sm * Sd * width, np.float32)
        for c, g in enumerate(groups):
            base = c * width
            for j, k in enumerate(g):
                d = plan.pair_device[k]
                d_idx[base + j] = d % self._rows_per_dshard
                pp[base + j] = plan.perms[d]
                w[base + j] = plan.scores[d, 0]
        return m_idx, d_idx, pp, w, groups, width

    def _launch_sync(self, plan: RoundPlan) -> None:
        m_idx, d_idx, pp, w, _, _ = self._cell_batch(plan)
        new_stacked, val_mat, test_mat = self._round(
            self._stacked, m_idx, d_idx, pp, w,
            *self._dev["train"], *self._dev["val"], *self._dev["test"])
        self._swap(new_stacked)
        self._pending = (val_mat, test_mat)

    def _dispatch_train(self, plan: RoundPlan) -> Tuple[Any, TrainMeta]:
        m_idx, d_idx, pp, _, groups, width = self._cell_batch(plan)
        trained = self._train(self._stacked, m_idx, d_idx, pp,
                              *self._dev["train"])
        b = len(plan.pair_device)
        meta = TrainMeta([0] * b, list(plan.pair_device), width,
                         pair_groups=groups,
                         positions=_group_positions(groups, width, b))
        return trained, meta

    def _dispatch_finish(self, trained: Any, meta: TrainMeta,
                         plan: RoundPlan) -> Tuple[Any, Any]:
        w = np.zeros(self._n_mshards * self._n_dshards * meta.b_pad,
                     np.float32)
        for d, p in zip(meta.pair_device, meta.positions):
            w[p] = plan.scores[d, 0]
        new_stacked, val_mat, test_mat = self._finish(
            self._stacked, trained, w,
            *self._dev["val"], *self._dev["test"])
        self._swap(new_stacked)
        return val_mat, test_mat

    def _dispatch_eval_only(self) -> Tuple[Any, Any]:
        return (self._eval2d(self._stacked, *self._dev["val"]),
                self._eval2d(self._stacked, *self._dev["test"]))


# -- mode-B LM executors (DESIGN.md §14) ----------------------------------

class LLMExecutorBase(RoundExecutor):
    """Shared scaffolding for the mode-B LM executors driven by
    ``federated.llm.FedLLMTrainer``. Unlike the mode-A executors the
    round's token batches are drawn host-side by the trainer (the LM
    data plane has no DeviceDataBank), so the trainer hands them over
    via :meth:`set_batches` before ``launch``. Train/eval steps come in
    UNJITTED (``launch.steps.make_train_step`` / ``llm.make_acc_step``);
    each executor owns its compiled form."""

    def __init__(self, fed: FedCDConfig, registry: ModelRegistry,
                 n_clients: int):
        # deliberately NOT RoundExecutor.__init__ — there is no mode-A
        # data plane to adopt
        self.cfg = fed
        self.registry = registry
        self.data = None
        self.databank = None
        self.n_devices = n_clients
        self._result: Optional[RoundResult] = None
        self._batches = None
        self._pending: Optional[RoundPlan] = None
        self.round_losses: List[float] = []

    def set_batches(self, tokens: np.ndarray, labels: np.ndarray,
                    vt: np.ndarray, vl: np.ndarray) -> None:
        """Hand this round's (train, val) token batches to the executor
        (host arrays; uploaded once per round here)."""
        self._batches = (jnp.asarray(tokens), jnp.asarray(labels),
                         jnp.asarray(vt), jnp.asarray(vl))

    def _train_sets(self, plan: RoundPlan
                    ) -> Tuple[List[int], List[np.ndarray]]:
        """Models that actually train this round + their per-client
        weight rows: the plan's agg set minus models whose weight mass
        is zero (scores can underflow to 0 for every holder — the
        legacy loop's ``w.sum() <= 0`` skip, kept so both engines train
        the identical set)."""
        models, weights = [], []
        for m in plan.agg_models:
            w = self._holder_weights(plan, m)
            if w.sum() <= 0:
                continue
            models.append(m)
            weights.append(w)
        return models, weights

    def readback(self) -> RoundResult:
        self._pending = None
        result, self._result = self._result, None
        return result

    def params_of(self, m: int):
        """Read model ``m``'s current param tree — dict entry (legacy)
        or bank-row view (stacked; ``bank[m]`` getitem). The serving
        plane's :class:`~repro.serve.draft.DraftBank` refresh uses this
        so draft truncation always reads post-round weights."""
        return self.registry.params[m]

    def collect(self, preferred: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError(
            "the LM path has no test split / preferred-model collection")


class LLMLegacyExecutor(LLMExecutorBase):
    """The original per-model Python loop over dict-mode registry
    storage — the LM plane's equivalence oracle (every model trains and
    evals in its own dispatch)."""

    def __init__(self, fed, registry, n_clients, train_fn, acc_fn):
        super().__init__(fed, registry, n_clients)
        self._train = jax.jit(train_fn)
        self._acc = jax.jit(acc_fn)

    def launch(self, plan: RoundPlan) -> None:
        tokens, labels, vt, vl = self._batches
        losses = []
        for m, w in zip(*self._train_sets(plan)):
            params, met = self._train(self.registry.params[m], tokens,
                                      labels, jnp.asarray(w), None)
            self.registry.params[m] = params
            losses.append(float(met["loss"]))
        self.round_losses = losses
        accs = np.zeros((self.n_devices, self.cfg.max_models))
        for m in plan.live:
            accs[:, m] = np.asarray(
                self._acc(self.registry.params[m], vt, vl))
        self._result = RoundResult(accs)
        self._pending = plan


class FedLLMExecutor(LLMExecutorBase):
    """The stacked LM engine: params live in a per-layer-stacked
    ``StackedParamBank`` (model-row axis composed OUTSIDE the tensor
    shardings — ``launch.sharding.lm_bank_shardings``) and the round is
    ONE jitted donated dispatch: gather padded training rows, scan the
    score-weighted train step over them, scatter back, scan per-client
    eval over the padded live rows (``simulation.make_llm_round``).
    The model axis is a pure batch axis, so the trajectory matches the
    per-model loop exactly in discrete state (params to reduction
    order — the equivalence tier pins it)."""

    def __init__(self, fed, registry, n_clients, train_fn, acc_fn):
        super().__init__(fed, registry, n_clients)
        self._round = make_llm_round(train_fn, acc_fn)
        self._eval = make_llm_eval(acc_fn)
        # row schedules pad to a static bucket: a transformer round
        # step is expensive to trace, and every distinct (train rows,
        # live rows) pair is a fresh executable. Eval rows take a
        # coarse floor (4) so live-count drift between deletions stops
        # changing the shape key; train rows take the EXACT small
        # count (floor 1) — a padding lane costs a full extra train
        # step (e.g. 4/3 compute when 3 models train padded to 4),
        # and trained counts revisit the same few values, so the key
        # set stays ≤ max_models while bucket_size still coarsens
        # counts past 8 (DESIGN.md §10/§14).
        self._row_floor = 4
        self._train_floor = 1

    def launch(self, plan: RoundPlan) -> None:
        tokens, labels, vt, vl = self._batches
        bank = self.registry.params
        models, weights = self._train_sets(plan)
        eval_rows = pad_live_rows([bank.row_of[m] for m in plan.live],
                                  self._row_floor)
        if models:
            rows = pad_live_rows([bank.row_of[m] for m in models],
                                 self._train_floor)
            # padding lanes repeat row 0 WITH row 0's weights: duplicate
            # scatters write identical values, so padding is invisible
            w = np.zeros((len(rows), self.n_devices), np.float32)
            w[:len(models)] = np.stack(weights)
            w[len(models):] = w[0]
            new_tree, losses, mat = self._round(
                bank.tree, rows, w, tokens, labels, vt, vl, eval_rows)
            bank.swap(new_tree)
            self.round_losses = [float(x)
                                 for x in np.asarray(losses)[:len(models)]]
        else:
            mat = self._eval(bank.tree, eval_rows, vt, vl)
            self.round_losses = []
        mat = np.asarray(mat)
        accs = np.zeros((self.n_devices, self.cfg.max_models))
        for j, m in enumerate(plan.live):
            accs[:, m] = mat[j]
        self._result = RoundResult(accs)
        self._pending = plan

    def readback(self) -> RoundResult:
        # the launch matrices have materialized: the tree retired by
        # swap() (and any clone-write retirees from last round's
        # lifecycle) can destruct without blocking the host
        self.registry.params.release_retired()
        return super().readback()

    def quiesce(self) -> None:
        self.registry.params.release_retired()
