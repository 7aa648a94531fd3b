"""Mode-A federated simulation: vmapped per-device local training.

Every device trains a copy of a global model on its own data for E epochs
of minibatch SGD (the paper's client loop), all devices in one vmapped,
jitted call. Used by both FedCD and the FedAvg baseline.

Three generations of round data plane live here (DESIGN.md §2):

* ``make_local_train`` / ``make_eval`` — the legacy per-model loop's
  building blocks (every model trains all N devices).
* ``make_group_train`` / ``make_group_eval`` — the PR 1 batched engine:
  one jitted step over gathered (model, device) pairs, dense (M, N)
  eval matrices.
* ``make_fused_round`` / ``make_fused_eval`` — the fused device-resident
  engine: ONE jitted dispatch per round covering train, score-weighted
  multi-model aggregation, the on-device quantize roundtrip, and one
  val + one test (live, N) evaluation matrix, with the stacked
  parameter bank donated in and out.
* ``make_sharded_round`` / ``make_sharded_eval`` — the PR 3 mesh-sharded
  fused engine: the bank's ``max_models`` row axis is laid out over the
  launch mesh's ``model`` axis and the round runs as a ``shard_map``
  body per shard, each shard training/aggregating/scattering ONLY its
  resident rows from a per-shard work-pair bucket (``shard_work_batch``
  / ``shard_rows``). Only the small (rows, N) eval matrices cross the
  shard boundary back to the host control plane (DESIGN.md §9).
"""
from __future__ import annotations

import functools
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.aggregate import EQ1_PRECISION, multi_weighted_average


def make_local_train(loss_fn: Callable, lr: float, batch_size: int
                     ) -> Callable:
    """Returns jitted fn(params, xs (N,n,...), ys (N,n), perms (N,T,b))
    -> stacked trained params with leading device axis N.

    ``perms`` are per-device minibatch index matrices covering E epochs
    (T = E * steps_per_epoch), built host-side each round so data order
    is faithful to per-round shuffling.
    """

    def one_device(params, x, y, perm):
        def step(p, idx):
            g = jax.grad(loss_fn)(p, (x[idx], y[idx]))
            p = jax.tree.map(lambda a, b: a - lr * b, p, g)
            return p, None
        params, _ = jax.lax.scan(step, params, perm)
        return params

    return jax.jit(jax.vmap(one_device, in_axes=(None, 0, 0, 0)))


def make_eval(acc_fn: Callable) -> Callable:
    """Returns jitted fn(params, xs (N,n,...), ys (N,n)) -> (N,) accuracy."""
    return jax.jit(jax.vmap(acc_fn, in_axes=(None, 0, 0)))


def bucket_size(n: int, minimum: int = 8) -> int:
    """Static bucket for the batched engine's work buffers: round ``n``
    up to an eighth-octave step (multiples of 2^k/8 within each
    power-of-two octave). The jitted group step sees at most 8 distinct
    shapes per octave instead of retracing every round; padding waste
    ``(bucket - n) / bucket`` stays < 20% once ``n > 8 * minimum``
    (worst case just past a power of two, e.g. n=65 -> 80; smaller
    octaves clamp the step to ``minimum``, so n=9 pads to 16 at the
    default floor of 8 and to 10 at the train batch's floor of 2,
    ``train_bucket``). Property-tested in tests/test_property.py."""
    if n <= minimum:
        return minimum
    octave = 1 << (n - 1).bit_length()          # next power of two >= n
    step = max(octave // 8, minimum)
    return -(-n // step) * step


def train_bucket(n: int) -> int:
    """Slots of the one-chip train work batch for ``n`` pairs. A padding
    pair costs a full forward and backward pass, so the floor is 2 (the
    sharded engines' per-shard floor), not 8: 10 pairs train as 10
    slots. Above 64 pairs the eighth-octave step is >= 8 and the buckets
    are the default floor's."""
    return bucket_size(n, minimum=2)


def pad_work_batch(model_idx: "list[int]", device_idx: "list[int]",
                   perm_rows: "list[np.ndarray]"
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad gathered (model, device, perm) pair lists to one static
    bucket (``train_bucket``) for the group train step. Padding pairs
    point at model 0 / device 0 with all-zero perms; callers mask them
    out of aggregation with zero weight columns."""
    b = len(model_idx)
    b_pad = train_bucket(b)
    m_idx = np.zeros(b_pad, np.int32)
    m_idx[:b] = model_idx
    d_idx = np.zeros(b_pad, np.int32)
    d_idx[:b] = device_idx
    perms = np.zeros((b_pad,) + perm_rows[0].shape, np.int32)
    perms[:b] = np.stack(perm_rows)
    return m_idx, d_idx, perms


def pad_live_rows(live: "list[int]", minimum: int = 1) -> np.ndarray:
    """Pad the live-model row-index list to one static bucket (padding
    rows repeat the first live row; callers slice the first ``len(live)``
    matrix rows). The default ``minimum=1`` gives each live count its
    own executable (populations are small); the pipelined executors
    pass a coarser floor so the finish program's shape key stops
    changing every round (DESIGN.md §10)."""
    pad = bucket_size(len(live), minimum=minimum)
    idx = np.full(pad, live[0] if live else 0, np.int32)
    idx[:len(live)] = live
    return idx


def _pair_train(loss_fn: Callable, lr: float) -> Callable:
    """Unjitted single-(model, device)-pair local training: gathers the
    pair's model row out of the stacked params and runs E epochs of
    minibatch SGD with per-step data gathers (the (B, n, ...) gathered
    dataset is never materialized)."""

    def one_pair(stacked_params, m_idx, xs, ys, d_idx, perm):
        params = jax.tree.map(lambda a: a[m_idx], stacked_params)

        def step(p, idx):
            batch = (xs[d_idx, idx], ys[d_idx, idx])
            g = jax.grad(loss_fn)(p, batch)
            p = jax.tree.map(lambda a, b: a - lr * b, p, g)
            return p, None

        params, _ = jax.lax.scan(step, params, perm)
        return params

    return one_pair


# Most work pairs trained side by side in one step of the sequential map
# over a work batch. Peak round memory then follows this constant and the
# model, not the bucket B (which reaches 240 at the paper's regime).
PAIR_GROUP = 8


def pair_group_width(b: int) -> int:
    """Pairs per step of the map over a ``b``-slot work batch: the
    largest divisor of ``b`` not above ``PAIR_GROUP``, so the groups are
    equal and the program holds one train body (10 slots train as 2
    groups of 5, where a width of 8 would add a vmapped remainder of 2).
    Every multiple of 8 keeps 8; up to 8 slots train as one group."""
    for w in range(min(b, PAIR_GROUP), 1, -1):
        if b % w == 0:
            return w
    return 1


def _train_pairs(one_pair: Callable, stacked, m_idx, xs, ys, d_idx, perms):
    """Train every (model, device) pair of a work batch:
    ``pair_group_width`` pairs vmapped at a time, read from the batch's
    static shape, the groups in a sequential ``lax.map``. Each pair's
    computation is ``one_pair`` exactly as under a full ``vmap``; only
    how many run at once is bounded."""
    with jax.named_scope("fedcd.train"):
        return jax.lax.map(
            lambda a: one_pair(stacked, a[0], xs, ys, a[1], a[2]),
            (m_idx, d_idx, perms),
            batch_size=pair_group_width(m_idx.shape[0]))


def make_pair_train(loss_fn: Callable, lr: float) -> Callable:
    """The TRAIN phase alone: jitted fn(stacked_params, model_idx (B,),
    xs (N,n,...), ys (N,n), device_idx (B,), perms (B,T,b)) -> trained
    params with leading pair axis B.

    Pure read of the bank — no scatter, no aggregation — which is what
    lets the pipelined executors dispatch round t+1's training
    speculatively while round t's eval matrices are still in flight and
    simply discard the result on a mispeculation (DESIGN.md §10)."""
    return jax.jit(functools.partial(_train_pairs, _pair_train(loss_fn, lr)))


def make_group_train(loss_fn: Callable, lr: float, batch_size: int
                     ) -> Callable:
    """Batched multi-model local training over a gathered work batch.

    Returns jitted fn(stacked_params, model_idx (B,), xs (N,n,...),
    ys (N,n), device_idx (B,), perms (B,T,b)) -> trained params with
    leading pair axis B.

    ``stacked_params`` is a pytree with a leading model axis (M, ...);
    pair ``b`` trains model ``model_idx[b]`` on device ``device_idx[b]``'s
    data. Only ``(participating & holder)`` pairs are materialized by the
    caller (padding pairs are masked out at aggregation), so the engine
    does O(pairs) work instead of the legacy O(models · devices).
    """
    return make_pair_train(loss_fn, lr)


def make_group_eval(acc_fn: Callable) -> Callable:
    """Returns jitted fn(stacked_params (M, ...), xs (N,n,...), ys (N,n))
    -> (M, N) accuracy of every model on every device's split, in one
    fused call (the batched engine's evaluation matrix)."""
    per_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    return jax.jit(jax.vmap(per_model, in_axes=(0, None, None)))



def _aggregate_rows(trained, w, quantize_bits: int,
                    use_agg_kernel: bool):
    """Steps 2-3 of the round body: bucketed eq-1 aggregation over the
    (A, B) weight matrix + the in-jit quantize roundtrip. ONE shared
    implementation for the monolithic, apply, and finish builders (both
    layouts), so the aggregation/transport semantics the equivalence
    tiers pin can never diverge between the sync and pipelined
    programs."""
    with jax.named_scope("fedcd.aggregate"):
        agg = multi_weighted_average(trained, w, use_kernel=use_agg_kernel)
        if quantize_bits:
            from repro.core import quantize as qz
            agg = jax.vmap(lambda t: qz.roundtrip(t, quantize_bits))(agg)
        return agg


def _scatter_rows(stacked, agg, agg_rows, keep=None):
    """Step 4: the idempotent-padding scatter writeback; with ``keep``
    the keep-masked sharded variant (empty shards rewrite their rows'
    existing values, so padding can never zero a live row)."""
    def write(old, new):
        cur = old[agg_rows]
        k = keep.reshape((-1,) + (1,) * (cur.ndim - 1))
        return old.at[agg_rows].set(jnp.where(k, new.astype(old.dtype),
                                              cur))

    with jax.named_scope("fedcd.scatter"):
        if keep is None:
            return jax.tree.map(
                lambda old, new: old.at[agg_rows].set(new.astype(old.dtype)),
                stacked, agg)
        return jax.tree.map(write, stacked, agg)


def _eval_gathered(eval_model, stacked, idx, xs, ys, split=None):
    """Step 5: score each scheduled bank row on every device's split —
    the (rows, N) accuracy matrix. Rows run one after another in a
    ``lax.map``, so peak memory is one row's eval over all N splits
    whatever the number of rows. Named ``fedcd.eval_<split>`` in the
    program where the caller knows the split, else ``fedcd.eval``."""
    def one_row(i):
        return eval_model(jax.tree.map(lambda a: a[i], stacked), xs, ys)

    with jax.named_scope(f"fedcd.eval_{split}" if split else "fedcd.eval"):
        return jax.lax.map(one_row, idx)


def make_fused_round(loss_fn: Callable, acc_fn: Callable, lr: float,
                     quantize_bits: int = 0,
                     use_agg_kernel: bool = False) -> Callable:
    """The fused engine's whole round as ONE jitted dispatch.

    Returns fn(stacked (m_cap, ...) [donated], m_idx (B,), d_idx (B,),
    perms (B,T,b), w (A, B), agg_rows (A,), live_idx (L,),
    test_idx (R,), xs, ys, vx, vy, tx, ty) ->
    (new_stacked (m_cap, ...), val_mat (L, N), test_mat (R, N)).

    Semantics, in order (paper Algorithm 1 lines 5-12):
      1. train the gathered (participating & holder) pairs (O(pairs));
      2. score-weighted aggregation of the models that trained this
         round in one ``multi_weighted_average`` over the bucketed
         (A, B) weight matrix (row j weights the pairs of model
         ``agg_rows[j]``; padding rows repeat row 0, making their
         scatter idempotent);
      3. when transport quantization is on, the quantize→dequantize
         roundtrip runs on device (kernels/quantize ref numerics),
         vmapped over the A aggregated rows only, instead of the
         legacy host loop;
      4. the updated rows are scattered into the donated bank with one
         ``.at[agg_rows].set`` (no host roundtrip), so the bank is
         updated in place;
      5. the gathered live rows are evaluated on every device's val
         split (the full (live, N) matrix — every active pair's score
         history needs it), and the rows in ``test_idx`` on every
         device's test split. ``push_accuracies`` and ``_collect`` both
         read these, closing PR 1's double val-matrix dispatch; the
         test rows are the caller's *predicted* preferred models (last
         round's — sticky in steady state), so test work is O(preferred
         models · N) instead of PR 1's full O(live · N) matrix, of
         which only N entries were ever read. Mispredictions fall back
         to a small ``make_fused_eval`` dispatch in ``_collect``. The
         dense model-major matrix is deliberate: one weight-shared GEMM
         per model beats an active-pair gather formulation by ~8x
         measured FLOP efficiency on CPU (the weight row is reused
         across all N devices' examples).

    Retraces only when the (B, L, R) buckets change (``bucket_size``).
    """
    one_pair = _pair_train(loss_fn, lr)
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))   # one row, all N

    def round_step(stacked, m_idx, d_idx, perms, w, agg_rows,
                   live_idx, test_idx, xs, ys, vx, vy, tx, ty):
        trained = _train_pairs(one_pair, stacked, m_idx, xs, ys,
                               d_idx, perms)
        agg = _aggregate_rows(trained, w, quantize_bits, use_agg_kernel)
        new_stacked = _scatter_rows(stacked, agg, agg_rows)
        val = _eval_gathered(eval_model, new_stacked, live_idx, vx, vy,
                             "val")
        test = _eval_gathered(eval_model, new_stacked, test_idx, tx, ty,
                              "test")
        return new_stacked, val, test

    return jax.jit(round_step, donate_argnums=(0,))


def make_fused_apply(quantize_bits: int = 0,
                     use_agg_kernel: bool = False) -> Callable:
    """The AGGREGATE+WRITEBACK phase alone (pipelined split,
    DESIGN.md §10): fn(stacked [donated], trained (B, ...), w (A, B),
    agg_rows (A,)) -> new_stacked. Same aggregation, quantize
    roundtrip, and idempotent-padding scatter semantics as steps 2-4 of
    ``make_fused_round`` — the weights and scatter rows arrive AFTER
    training was dispatched, which is what lets the host resolve them
    from round t-1's readback while the train phase runs."""

    def apply_step(stacked, trained, w, agg_rows):
        agg = _aggregate_rows(trained, w, quantize_bits, use_agg_kernel)
        return _scatter_rows(stacked, agg, agg_rows)

    return jax.jit(apply_step, donate_argnums=(0,))


def make_fused_finish(acc_fn: Callable, quantize_bits: int = 0,
                      use_agg_kernel: bool = False) -> Callable:
    """Everything AFTER training as one dispatch (pipelined split,
    DESIGN.md §10): fn(stacked [donated], trained (B, ...), w (A, B),
    agg_rows (A,), live_idx (L,), test_idx (R,), vx, vy, tx, ty) ->
    (new_stacked, val (L, N), test (R, N)). Identical to steps 2-5 of
    ``make_fused_round`` — aggregation weights, scatter rows, and eval
    schedules arrive AFTER the train batch was dispatched, so the host
    resolves them from round t-1's readback while training runs."""
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))

    def finish_step(stacked, trained, w, agg_rows, live_idx, test_idx,
                    vx, vy, tx, ty):
        agg = _aggregate_rows(trained, w, quantize_bits, use_agg_kernel)
        new_stacked = _scatter_rows(stacked, agg, agg_rows)
        val = _eval_gathered(eval_model, new_stacked, live_idx, vx, vy,
                             "val")
        test = _eval_gathered(eval_model, new_stacked, test_idx, tx, ty,
                              "test")
        return new_stacked, val, test

    return jax.jit(finish_step, donate_argnums=(0,))


def make_pair_eval(acc_fn: Callable) -> Callable:
    """Holder-only (sparse) evaluation: fn(stacked, m_idx (P,),
    d_idx (P,), xs, ys) -> (P,) accuracy of model row ``m_idx[k]`` on
    device ``d_idx[k]``'s split. The sparse form does O(active pairs)
    eval work instead of the dense matrix's O(rows · N); the dense GEMM
    wins the weight reuse back above a density crossover, so the
    planner only selects this below ``sparse_eval`` (DESIGN.md §10)."""

    def one_pair(stacked, m, d, xs, ys):
        params = jax.tree.map(lambda a: a[m], stacked)
        return acc_fn(params, xs[d], ys[d])

    return jax.jit(jax.vmap(one_pair, in_axes=(None, 0, 0, None, None)))


def make_fused_eval(acc_fn: Callable) -> Callable:
    """Returns jitted fn(stacked (m_cap, ...), live_idx (L,), xs, ys)
    -> (L, N): the fused engine's standalone eval-matrix dispatch, for
    rounds with no training pairs and for the quantized-cloning refresh
    in ``_collect`` (clone rows differ from their parents' then)."""
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))

    def mat(stacked, live_idx, xs, ys):
        return _eval_gathered(eval_model, stacked, live_idx, xs, ys)

    return jax.jit(mat)


# -- mesh-sharded fused engine (DESIGN.md §9) -------------------------------

def shard_rows(rows: "list[int]", rows_per_shard: int, n_shards: int,
               minimum: int = 1) -> Tuple[np.ndarray, List[List[int]], int]:
    """Partition global bank-row ids by owning shard (row ``m`` lives on
    shard ``m // rows_per_shard``) and pad every shard's list to ONE
    shared bucket ``L = bucket_size(max per-shard count, minimum=1)``.

    Returns ``(idx, groups, L)``: ``idx`` is the (S*L,) int32 array of
    LOCAL row indices consumed by the shard_map body (shard s reads
    ``idx[s*L:(s+1)*L]``), ``groups[s]`` lists shard s's global ids in
    bucket order — the matrix row of global id ``groups[s][j]`` in a
    sharded (S*L, N) eval output is ``s*L + j``. Padding entries repeat
    the shard's first real local row (or local row 0 on an empty shard);
    callers discard their output rows. The per-shard partition is a
    disjoint cover of ``rows`` with the documented <20% padding-waste
    bound per shard once the densest shard holds > 8 rows
    (property-tested in tests/test_property.py)."""
    groups: List[List[int]] = [[] for _ in range(n_shards)]
    for r in rows:
        groups[r // rows_per_shard].append(r)
    width = bucket_size(max((len(g) for g in groups), default=0),
                        minimum=minimum)
    idx = np.zeros(n_shards * width, np.int32)
    for s, g in enumerate(groups):
        base = s * width
        fill = g[0] - s * rows_per_shard if g else 0
        idx[base:base + width] = fill
        idx[base:base + len(g)] = [r - s * rows_per_shard for r in g]
    return idx, groups, width


def shard_work_batch(pair_model: "list[int]", pair_device: "list[int]",
                     perm_rows: "list[np.ndarray]", rows_per_shard: int,
                     n_shards: int, minimum: int = 8
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                List[List[int]], int]:
    """Bucket the gathered (model, device) pairs per OWNING shard so each
    mesh slice trains only its resident rows: pair k goes to shard
    ``pair_model[k] // rows_per_shard`` and its model index is made
    shard-LOCAL. Every shard's pair list is padded to one shared bucket
    ``B`` (the sharded analogue of ``pad_work_batch``; padding pairs
    point at local row 0 / device 0 with all-zero perms and are masked
    out of aggregation by zero weight columns).

    Returns ``(m_idx (S*B,), d_idx (S*B,), perms (S*B, T, b),
    pair_groups, B)`` where ``pair_groups[s]`` lists the original pair
    positions assigned to shard s in bucket-column order (column ``j``
    of shard s's weight block is pair ``pair_groups[s][j]``)."""
    groups: List[List[int]] = [[] for _ in range(n_shards)]
    for k, m in enumerate(pair_model):
        groups[m // rows_per_shard].append(k)
    width = bucket_size(max(len(g) for g in groups), minimum)
    m_idx = np.zeros(n_shards * width, np.int32)
    d_idx = np.zeros(n_shards * width, np.int32)
    perms = np.zeros((n_shards * width,) + perm_rows[0].shape, np.int32)
    for s, g in enumerate(groups):
        base = s * width
        for j, k in enumerate(g):
            m_idx[base + j] = pair_model[k] - s * rows_per_shard
            d_idx[base + j] = pair_device[k]
            perms[base + j] = perm_rows[k]
    return m_idx, d_idx, perms, groups, width


def make_sharded_round(loss_fn: Callable, acc_fn: Callable, lr: float,
                       mesh: jax.sharding.Mesh, quantize_bits: int = 0,
                       use_agg_kernel: bool = False) -> Callable:
    """``make_fused_round`` sharded over the mesh's ``model`` axis.

    Returns fn(stacked (m_cap, ...) [donated, row-sharded], m_idx (S*B,),
    d_idx (S*B,), perms (S*B, T, b), w (S*A, B), agg_rows (S*A,),
    agg_keep (S*A,) bool, live_idx (S*L,), test_idx (S*R,), xs, ys, vx,
    vy, tx, ty) -> (new_stacked, val_mat (S*L, N), test_mat (S*R, N)).

    Each shard runs the full fused-round body on its OWN block: it
    gathers local model rows for its B pairs, trains them, aggregates
    its A rows from its (A, B) weight block, quantize-roundtrips, and
    scatters back into its local bank block — no collective touches the
    parameters at any point. ``agg_keep`` guards the scatter: a shard
    with no training work this round (or padding rows on an empty shard)
    writes its rows' EXISTING values back, so an empty shard dispatches
    cleanly and padding can never zero a live row. Non-empty shards'
    padding rows instead repeat the shard's first aggregation row AND
    its weight row (the single-device idempotent-duplicate trick), so
    duplicate scatter indices always carry identical values. The only
    cross-shard traffic in the step is the caller reading back the small
    row-sharded eval matrices for the host control plane (the
    all-gather boundary, DESIGN.md §9)."""
    one_pair = _pair_train(loss_fn, lr)
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    row = P("model")
    rep = P()

    def body(stacked, m_idx, d_idx, perms, w, agg_rows, agg_keep,
             live_idx, test_idx, xs, ys, vx, vy, tx, ty):
        trained = _train_pairs(one_pair, stacked, m_idx, xs, ys,
                               d_idx, perms)
        agg = _aggregate_rows(trained, w, quantize_bits, use_agg_kernel)
        new_stacked = _scatter_rows(stacked, agg, agg_rows, keep=agg_keep)
        val = _eval_gathered(eval_model, new_stacked, live_idx, vx, vy,
                             "val")
        test = _eval_gathered(eval_model, new_stacked, test_idx, tx, ty,
                              "test")
        return new_stacked, val, test

    step = shard_map(
        body, mesh=mesh,
        in_specs=(row, row, row, row, row, row, row, row, row,
                  rep, rep, rep, rep, rep, rep),
        out_specs=(row, row, row), check_vma=False)
    return jax.jit(step, donate_argnums=(0,))


def make_sharded_eval(acc_fn: Callable, mesh: jax.sharding.Mesh
                      ) -> Callable:
    """``make_fused_eval`` over a row-sharded bank: fn(stacked,
    idx (S*L,) LOCAL row indices from ``shard_rows``, xs, ys) ->
    (S*L, N) row-sharded accuracy matrix. Each shard evaluates only its
    resident rows (on the replicated eval splits); the caller's
    ``np.asarray`` readback is the all-gather boundary."""
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    row = P("model")
    rep = P()

    def mat(stacked, idx, xs, ys):
        return _eval_gathered(eval_model, stacked, idx, xs, ys)

    return jax.jit(shard_map(mat, mesh=mesh,
                             in_specs=(row, row, rep, rep),
                             out_specs=row, check_vma=False))


def _make_sharded_pair_train(loss_fn: Callable, lr: float,
                             mesh: jax.sharding.Mesh,
                             bank_spec: P) -> Callable:
    """Shared body of the sharded TRAIN phase: each shard trains its
    B-pair block against the bank laid out per ``bank_spec``
    (row-sharded for FedCD's per-model rows, replicated for FedAvg's
    single global model). Pure read of the bank, so the pipelined
    executors can dispatch it speculatively (DESIGN.md §10)."""
    one_pair = _pair_train(loss_fn, lr)
    row = P("model")
    rep = P()

    def body(stacked, m_idx, d_idx, perms, xs, ys):
        return _train_pairs(one_pair, stacked, m_idx, xs, ys, d_idx,
                            perms)

    return jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(bank_spec, row, row, row, rep, rep),
                             out_specs=row, check_vma=False))


def make_sharded_train(loss_fn: Callable, lr: float,
                       mesh: jax.sharding.Mesh) -> Callable:
    """``make_pair_train`` over the model mesh (shard-LOCAL ``m_idx``
    from ``shard_work_batch``): fn(stacked [row-sharded], m_idx (S*B,),
    d_idx (S*B,), perms (S*B, T, b), xs, ys) -> trained (S*B, ...)
    row-sharded."""
    return _make_sharded_pair_train(loss_fn, lr, mesh, P("model"))


def make_sharded_apply(mesh: jax.sharding.Mesh, quantize_bits: int = 0,
                       use_agg_kernel: bool = False) -> Callable:
    """``make_fused_apply`` over the model mesh: each shard aggregates
    its A rows from its (A, B) weight block of the trained pairs and
    scatters into its local bank block behind the keep mask (identical
    semantics to steps 2-4 of ``make_sharded_round``; empty shards
    rewrite existing values).

    fn(stacked [donated, row-sharded], trained (S*B, ...) row-sharded,
    w (S*A, B), agg_rows (S*A,) LOCAL, agg_keep (S*A,) bool) ->
    new_stacked."""
    row = P("model")

    def body(stacked, trained, w, agg_rows, agg_keep):
        agg = _aggregate_rows(trained, w, quantize_bits, use_agg_kernel)
        return _scatter_rows(stacked, agg, agg_rows, keep=agg_keep)

    step = shard_map(body, mesh=mesh,
                     in_specs=(row, row, row, row, row),
                     out_specs=row, check_vma=False)
    return jax.jit(step, donate_argnums=(0,))


def make_sharded_finish(acc_fn: Callable, mesh: jax.sharding.Mesh,
                        quantize_bits: int = 0,
                        use_agg_kernel: bool = False) -> Callable:
    """``make_fused_finish`` over the model mesh: each shard aggregates
    its (A, B) weight block, quantize-roundtrips, scatters behind the
    keep mask, and evaluates its resident stale rows — steps 2-5 of
    ``make_sharded_round`` as their own dispatch (pipelined split).

    fn(stacked [donated, row-sharded], trained (S*B, ...) row-sharded,
    w (S*A, B), agg_rows (S*A,) LOCAL, agg_keep (S*A,), live_idx (S*L,),
    test_idx (S*R,), vx, vy, tx, ty) -> (new_stacked, val (S*L, N),
    test (S*R, N))."""
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    row = P("model")
    rep = P()

    def body(stacked, trained, w, agg_rows, agg_keep, live_idx, test_idx,
             vx, vy, tx, ty):
        agg = _aggregate_rows(trained, w, quantize_bits, use_agg_kernel)
        new_stacked = _scatter_rows(stacked, agg, agg_rows, keep=agg_keep)
        val = _eval_gathered(eval_model, new_stacked, live_idx, vx, vy,
                             "val")
        test = _eval_gathered(eval_model, new_stacked, test_idx, tx, ty,
                              "test")
        return new_stacked, val, test

    step = shard_map(body, mesh=mesh,
                     in_specs=(row, row, row, row, row, row, row,
                               rep, rep, rep, rep),
                     out_specs=(row, row, row), check_vma=False)
    return jax.jit(step, donate_argnums=(0,))


def make_sharded_pair_eval(acc_fn: Callable, mesh: jax.sharding.Mesh
                           ) -> Callable:
    """``make_pair_eval`` over the model mesh: fn(stacked [row-sharded],
    m_idx (S*P,) LOCAL rows, d_idx (S*P,), xs, ys) -> (S*P,) row-sharded
    accuracies; pairs bucket per owning shard (``shard_eval_pairs``) and
    padding outputs are discarded by the caller."""
    row = P("model")
    rep = P()

    def one_pair(stacked, m, d, xs, ys):
        params = jax.tree.map(lambda a: a[m], stacked)
        return acc_fn(params, xs[d], ys[d])

    def body(stacked, m_idx, d_idx, xs, ys):
        return jax.vmap(one_pair, in_axes=(None, 0, 0, None, None))(
            stacked, m_idx, d_idx, xs, ys)

    return jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(row, row, row, rep, rep),
                             out_specs=row, check_vma=False))


def shard_eval_pairs(pair_rows: "list[int]", pair_device: "list[int]",
                     rows_per_shard: int, n_shards: int,
                     minimum: int = 8
                     ) -> Tuple[np.ndarray, np.ndarray,
                                List[List[int]], int]:
    """Bucket (bank row, device) eval pairs per OWNING shard (the eval
    analogue of ``shard_work_batch``): pair k goes to shard
    ``pair_rows[k] // rows_per_shard`` with a shard-LOCAL row index.
    Returns ``(m_idx (S*P,), d_idx (S*P,), groups, P)`` where
    ``groups[s]`` lists the original pair positions assigned to shard s
    in bucket order — the output slot of pair ``groups[s][j]`` in the
    (S*P,) accuracy vector is ``s*P + j``. Padding pairs point at local
    row 0 / device 0 and their outputs are discarded."""
    groups: List[List[int]] = [[] for _ in range(n_shards)]
    for k, r in enumerate(pair_rows):
        groups[r // rows_per_shard].append(k)
    width = bucket_size(max((len(g) for g in groups), default=0), minimum)
    m_idx = np.zeros(n_shards * width, np.int32)
    d_idx = np.zeros(n_shards * width, np.int32)
    for s, g in enumerate(groups):
        base = s * width
        for j, k in enumerate(g):
            m_idx[base + j] = pair_rows[k] - s * rows_per_shard
            d_idx[base + j] = pair_device[k]
    return m_idx, d_idx, groups, width


# -- 2-D (model × data) mesh engine (DESIGN.md §11) -------------------------

def shard_pairs_2d(pair_mrows: "list[int]", pair_drows: "list[int]",
                   perm_rows: "list[np.ndarray]", rows_per_mshard: int,
                   n_mshards: int, rows_per_dshard: int, n_dshards: int,
                   minimum: int = 2
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                              List[List[int]], int]:
    """Bucket gathered work pairs per owning MESH CELL: pair k (model
    bank row ``pair_mrows[k]``, data bank row ``pair_drows[k]``) can only
    run on the cell holding both blocks — model shard
    ``pair_mrows[k] // rows_per_mshard`` × data shard
    ``pair_drows[k] // rows_per_dshard``. Cells are indexed model-major
    (``cell = sm * n_dshards + sd``), matching the block order of a
    ``P(("model", "data"))``-sharded leading axis on the launch mesh.
    Every cell's pair list pads to ONE shared bucket ``B`` (padding
    pairs point at local row 0 / local data row 0 with all-zero perms
    and are masked out of aggregation by zero weight columns).

    Returns ``(m_idx (C*B,), d_idx (C*B,), perms (C*B, T, b),
    cell_groups, B)`` with ``C = n_mshards * n_dshards``; both index
    arrays are shard-LOCAL. ``cell_groups[c]`` lists the original pair
    positions assigned to cell c in bucket-column order. The partition
    is a disjoint cover of the pairs with the documented <20% per-cell
    padding-waste bound once the densest cell holds > 8 pairs
    (property-tested in tests/test_property.py); at one data shard it
    degenerates to ``shard_work_batch``'s per-model-shard bucketing."""
    n_cells = n_mshards * n_dshards
    groups: List[List[int]] = [[] for _ in range(n_cells)]
    for k, (mr, dr) in enumerate(zip(pair_mrows, pair_drows)):
        cell = (mr // rows_per_mshard) * n_dshards + dr // rows_per_dshard
        groups[cell].append(k)
    width = bucket_size(max((len(g) for g in groups), default=0), minimum)
    m_idx = np.zeros(n_cells * width, np.int32)
    d_idx = np.zeros(n_cells * width, np.int32)
    perms = np.zeros((n_cells * width,) + perm_rows[0].shape, np.int32)
    for c, g in enumerate(groups):
        base = c * width
        for j, k in enumerate(g):
            m_idx[base + j] = pair_mrows[k] % rows_per_mshard
            d_idx[base + j] = pair_drows[k] % rows_per_dshard
            perms[base + j] = perm_rows[k]
    return m_idx, d_idx, perms, groups, width


def shard_eval_pairs_2d(pair_mrows: "list[int]", pair_drows: "list[int]",
                        rows_per_mshard: int, n_mshards: int,
                        rows_per_dshard: int, n_dshards: int,
                        minimum: int = 2
                        ) -> Tuple[np.ndarray, np.ndarray,
                                   List[List[int]], int]:
    """``shard_eval_pairs`` per mesh CELL (sparse holder-only eval on
    the 2-D mesh): pair k goes to cell (model shard × data shard) with
    shard-LOCAL row indices; the output slot of pair ``cell_groups[c][j]``
    in the (C*P,) accuracy vector is ``c*P + j``."""
    n_cells = n_mshards * n_dshards
    groups: List[List[int]] = [[] for _ in range(n_cells)]
    for k, (mr, dr) in enumerate(zip(pair_mrows, pair_drows)):
        cell = (mr // rows_per_mshard) * n_dshards + dr // rows_per_dshard
        groups[cell].append(k)
    width = bucket_size(max((len(g) for g in groups), default=0), minimum)
    m_idx = np.zeros(n_cells * width, np.int32)
    d_idx = np.zeros(n_cells * width, np.int32)
    for c, g in enumerate(groups):
        base = c * width
        for j, k in enumerate(g):
            m_idx[base + j] = pair_mrows[k] % rows_per_mshard
            d_idx[base + j] = pair_drows[k] % rows_per_dshard
    return m_idx, d_idx, groups, width


def _aggregate_rows_psum(trained, w, quantize_bits: int, axis: str):
    """Steps 2-3 of the round body on the 2-D mesh: each cell reduces
    eq-1 PARTIAL weighted sums over its own pair block, one ``psum``
    over the ``data`` axis completes the average (a model's holders may
    live on several data shards), then the in-jit quantize roundtrip.
    Numerically this is ``multi_weighted_average``'s einsum with its B
    columns split across the data shards — identical at one data shard,
    reduction-order float drift otherwise (the 2-D equivalence tier
    pins discrete state exactly and params to reduction order)."""
    with jax.named_scope("fedcd.aggregate"):
        num = jax.tree.map(
            lambda t: jnp.einsum("b...,ab->a...", t.astype(jnp.float32), w,
                                 precision=EQ1_PRECISION),
            trained)
        num = jax.lax.psum(num, axis)
        den = jnp.maximum(jax.lax.psum(jnp.sum(w, axis=1), axis), 1e-12)
        agg = jax.tree.map(
            lambda n, t: (n / den.reshape((-1,) + (1,) * (n.ndim - 1))
                          ).astype(t.dtype), num, trained)
        if quantize_bits:
            from repro.core import quantize as qz
            agg = jax.vmap(lambda t: qz.roundtrip(t, quantize_bits))(agg)
        return agg


def make_sharded2d_round(loss_fn: Callable, acc_fn: Callable, lr: float,
                         mesh: jax.sharding.Mesh, quantize_bits: int = 0
                         ) -> Callable:
    """``make_sharded_round`` on the full 2-D ``(model × data)`` mesh.

    Returns fn(stacked [donated, model-row-sharded], m_idx (C*B,),
    d_idx (C*B,), perms (C*B, T, b), w (Sm*A, Sd*B), agg_rows (Sm*A,),
    agg_keep (Sm*A,), live_idx (Sm*L,), test_idx (Sm*R,), xs, ys, vx,
    vy, tx, ty [data-row-sharded]) -> (new_stacked,
    val (Sm*L, n_cap), test (Sm*R, n_cap)).

    Layout (DESIGN.md §11): the bank's row axis over ``model`` (each
    row replicated along ``data``), the data bank's row axis over
    ``data`` (each block replicated along ``model``), pair arrays over
    BOTH (one block per cell, model-major — ``shard_pairs_2d``), and
    the weight matrix over both independently (cell (sm, sd) holds the
    (A, B) block pairing its model rows with its pairs). Each cell
    trains its resident (model row × data row) pairs, the ``data``-axis
    psum completes eq 1 (``_aggregate_rows_psum``), and every data
    slice then performs the IDENTICAL keep-masked scatter into its
    (replicated-along-data) bank copy, so the bank stays consistent
    without any parameter collective beyond that one psum. Eval rows
    score only the LOCAL data block — the (Sm*L, n_cap) matrices are
    the only row+column-sharded arrays the host reads back, and their
    columns are data-bank ROWS (the executor resolves device ids
    through ``DeviceDataBank.row_of`` at readback)."""
    one_pair = _pair_train(loss_fn, lr)
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    row = P("model")
    drow = P("data")
    cell = P(("model", "data"))
    grid = P("model", "data")

    def body(stacked, m_idx, d_idx, perms, w, agg_rows, agg_keep,
             live_idx, test_idx, xs, ys, vx, vy, tx, ty):
        trained = _train_pairs(one_pair, stacked, m_idx, xs, ys,
                               d_idx, perms)
        agg = _aggregate_rows_psum(trained, w, quantize_bits, "data")
        new_stacked = _scatter_rows(stacked, agg, agg_rows, keep=agg_keep)
        val = _eval_gathered(eval_model, new_stacked, live_idx, vx, vy,
                             "val")
        test = _eval_gathered(eval_model, new_stacked, test_idx, tx, ty,
                              "test")
        return new_stacked, val, test

    step = shard_map(
        body, mesh=mesh,
        in_specs=(row, cell, cell, cell, grid, row, row, row, row,
                  drow, drow, drow, drow, drow, drow),
        out_specs=(row, grid, grid), check_vma=False)
    return jax.jit(step, donate_argnums=(0,))


def make_sharded2d_train(loss_fn: Callable, lr: float,
                         mesh: jax.sharding.Mesh) -> Callable:
    """The TRAIN phase of the 2-D round alone (pure bank+data read —
    speculable, DESIGN.md §10): fn(stacked [model-row-sharded],
    m_idx (C*B,), d_idx (C*B,), perms (C*B, T, b), xs, ys
    [data-row-sharded]) -> trained (C*B, ...) cell-sharded."""
    one_pair = _pair_train(loss_fn, lr)
    row = P("model")
    drow = P("data")
    cell = P(("model", "data"))

    def body(stacked, m_idx, d_idx, perms, xs, ys):
        return _train_pairs(one_pair, stacked, m_idx, xs, ys, d_idx,
                            perms)

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(row, cell, cell, cell, drow, drow),
        out_specs=cell, check_vma=False))


def make_sharded2d_apply(mesh: jax.sharding.Mesh, quantize_bits: int = 0
                         ) -> Callable:
    """Aggregate + writeback of the 2-D round alone: fn(stacked
    [donated], trained (C*B, ...) cell-sharded, w (Sm*A, Sd*B),
    agg_rows (Sm*A,) LOCAL, agg_keep (Sm*A,)) -> new_stacked."""
    row = P("model")
    cell = P(("model", "data"))
    grid = P("model", "data")

    def body(stacked, trained, w, agg_rows, agg_keep):
        agg = _aggregate_rows_psum(trained, w, quantize_bits, "data")
        return _scatter_rows(stacked, agg, agg_rows, keep=agg_keep)

    step = shard_map(body, mesh=mesh,
                     in_specs=(row, cell, grid, row, row),
                     out_specs=row, check_vma=False)
    return jax.jit(step, donate_argnums=(0,))


def make_sharded2d_finish(acc_fn: Callable, mesh: jax.sharding.Mesh,
                          quantize_bits: int = 0) -> Callable:
    """Steps 2-5 of the 2-D round as their own dispatch (pipelined
    split): fn(stacked [donated], trained (C*B, ...) cell-sharded,
    w (Sm*A, Sd*B), agg_rows (Sm*A,) LOCAL, agg_keep (Sm*A,),
    live_idx (Sm*L,), test_idx (Sm*R,), vx, vy, tx, ty) ->
    (new_stacked, val (Sm*L, n_cap), test (Sm*R, n_cap))."""
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    row = P("model")
    drow = P("data")
    cell = P(("model", "data"))
    grid = P("model", "data")

    def body(stacked, trained, w, agg_rows, agg_keep, live_idx, test_idx,
             vx, vy, tx, ty):
        agg = _aggregate_rows_psum(trained, w, quantize_bits, "data")
        new_stacked = _scatter_rows(stacked, agg, agg_rows, keep=agg_keep)
        val = _eval_gathered(eval_model, new_stacked, live_idx, vx, vy,
                             "val")
        test = _eval_gathered(eval_model, new_stacked, test_idx, tx, ty,
                              "test")
        return new_stacked, val, test

    step = shard_map(
        body, mesh=mesh,
        in_specs=(row, cell, grid, row, row, row, row,
                  drow, drow, drow, drow),
        out_specs=(row, grid, grid), check_vma=False)
    return jax.jit(step, donate_argnums=(0,))


def make_sharded2d_eval(acc_fn: Callable, mesh: jax.sharding.Mesh
                        ) -> Callable:
    """Standalone eval matrix on the 2-D mesh: fn(stacked, idx (Sm*L,)
    LOCAL model rows, xs, ys [data-row-sharded]) -> (Sm*L, n_cap)
    row+column-sharded accuracies (columns are data-bank rows)."""
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    row = P("model")
    drow = P("data")
    grid = P("model", "data")

    def mat(stacked, idx, xs, ys):
        return _eval_gathered(eval_model, stacked, idx, xs, ys)

    return jax.jit(shard_map(mat, mesh=mesh,
                             in_specs=(row, row, drow, drow),
                             out_specs=grid, check_vma=False))


def make_sharded2d_pair_eval(acc_fn: Callable, mesh: jax.sharding.Mesh
                             ) -> Callable:
    """Holder-only eval on the 2-D mesh: fn(stacked, m_idx (C*P,) LOCAL
    model rows, d_idx (C*P,) LOCAL data rows, xs, ys) -> (C*P,)
    cell-sharded accuracies (``shard_eval_pairs_2d`` slot order)."""
    row = P("model")
    drow = P("data")
    cell = P(("model", "data"))

    def one_pair(stacked, m, d, xs, ys):
        params = jax.tree.map(lambda a: a[m], stacked)
        return acc_fn(params, xs[d], ys[d])

    def body(stacked, m_idx, d_idx, xs, ys):
        return jax.vmap(one_pair, in_axes=(None, 0, 0, None, None))(
            stacked, m_idx, d_idx, xs, ys)

    return jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(row, cell, cell, drow, drow),
                             out_specs=cell, check_vma=False))


def make_sharded_fedavg_round(loss_fn: Callable, acc_fn: Callable,
                              lr: float, mesh: jax.sharding.Mesh
                              ) -> Callable:
    """FedAvg's fused round with the work-PAIR axis sharded over the
    mesh's ``model`` axis (one global model — there is no model axis to
    split, so the parallel dimension is the participating-device pairs).

    Returns fn(stacked (1, ...) [donated, replicated], m_idx (S*B,),
    d_idx (S*B,), perms (S*B, T, b), w (S*B,), xs, ys, vx, vy, tx, ty)
    -> (new_stacked (1, ...), val (1, N), test (1, N)).

    Each shard trains its B-pair block and reduces a partial weighted
    sum; one ``psum`` over ``model`` completes eq 1's average, leaving
    the updated model replicated on every shard (the FedAvg analogue of
    the FedCD engine's shard-local aggregation)."""
    one_pair = _pair_train(loss_fn, lr)
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    row = P("model")
    rep = P()

    def body(stacked, m_idx, d_idx, perms, w, xs, ys, vx, vy, tx, ty):
        trained = _train_pairs(one_pair, stacked, m_idx, xs, ys,
                               d_idx, perms)
        num = jax.tree.map(
            lambda t: jnp.einsum("b...,b->...", t.astype(jnp.float32), w,
                                 precision=EQ1_PRECISION),
            trained)
        num = jax.lax.psum(num, "model")
        den = jnp.maximum(jax.lax.psum(jnp.sum(w), "model"), 1e-12)
        new_stacked = jax.tree.map(
            lambda n, o: (n / den).astype(o.dtype)[None], num, stacked)
        model = jax.tree.map(lambda a: a[0], new_stacked)
        val = eval_model(model, vx, vy)[None]
        test = eval_model(model, tx, ty)[None]
        return new_stacked, val, test

    step = shard_map(
        body, mesh=mesh,
        in_specs=(rep, row, row, row, row, rep, rep, rep, rep, rep, rep),
        out_specs=(rep, rep, rep), check_vma=False)
    return jax.jit(step, donate_argnums=(0,))


def make_sharded_fedavg_train(loss_fn: Callable, lr: float,
                              mesh: jax.sharding.Mesh) -> Callable:
    """The TRAIN phase of ``make_sharded_fedavg_round`` alone: the
    replicated (1, ...) global model trains each shard's B-pair block
    (pipelined FedAvg split, DESIGN.md §10): fn(stacked (1, ...)
    replicated, m_idx (S*B,), d_idx (S*B,), perms (S*B, T, b), xs, ys)
    -> trained (S*B, ...) row-sharded."""
    return _make_sharded_pair_train(loss_fn, lr, mesh, P())


def make_sharded_fedavg_finish(acc_fn: Callable,
                               mesh: jax.sharding.Mesh) -> Callable:
    """Aggregate + evaluate phases of ``make_sharded_fedavg_round`` as
    their own dispatch (pipelined FedAvg split): fn(stacked (1, ...)
    [donated, replicated], trained (S*B, ...) row-sharded, w (S*B,),
    vx, vy, tx, ty) -> (new_stacked, val (1, N), test (1, N))."""
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    row = P("model")
    rep = P()

    def body(stacked, trained, w, vx, vy, tx, ty):
        num = jax.tree.map(
            lambda t: jnp.einsum("b...,b->...", t.astype(jnp.float32), w,
                                 precision=EQ1_PRECISION),
            trained)
        num = jax.lax.psum(num, "model")
        den = jnp.maximum(jax.lax.psum(jnp.sum(w), "model"), 1e-12)
        new_stacked = jax.tree.map(
            lambda n, o: (n / den).astype(o.dtype)[None], num, stacked)
        model = jax.tree.map(lambda a: a[0], new_stacked)
        val = eval_model(model, vx, vy)[None]
        test = eval_model(model, tx, ty)[None]
        return new_stacked, val, test

    step = shard_map(body, mesh=mesh,
                     in_specs=(rep, row, row, rep, rep, rep, rep),
                     out_specs=(rep, rep, rep), check_vma=False)
    return jax.jit(step, donate_argnums=(0,))


def _fedavg_psum_avg(stacked, trained, w, axes):
    """FedAvg's eq 1 on a mesh: partial weighted sums per shard, one
    ``psum`` over ``axes`` completes the average, leaving the global
    model replicated everywhere."""
    num = jax.tree.map(
        lambda t: jnp.einsum("b...,b->...", t.astype(jnp.float32), w,
                             precision=EQ1_PRECISION),
        trained)
    num = jax.lax.psum(num, axes)
    den = jnp.maximum(jax.lax.psum(jnp.sum(w), axes), 1e-12)
    return jax.tree.map(
        lambda n, o: (n / den).astype(o.dtype)[None], num, stacked)


def make_sharded2d_fedavg_round(loss_fn: Callable, acc_fn: Callable,
                                lr: float, mesh: jax.sharding.Mesh
                                ) -> Callable:
    """FedAvg on the full 2-D ``(model × data)`` launch mesh: the device
    data's row axis shards over ``data`` (each device's pair can only
    run in its owning data slice), pairs deal round-robin over the
    ``model`` axis WITHIN each slice (one global model — the model axis
    is pure extra work parallelism), and a psum over BOTH axes completes
    eq 1 (DESIGN.md §11's sharded data plane for the baseline).

    Returns fn(stacked (1, ...) [donated, replicated], m_idx (C*B,)
    zeros, d_idx (C*B,) LOCAL data rows, perms (C*B, T, b), w (C*B,),
    xs, ys, vx, vy, tx, ty [data-row-sharded]) -> (new_stacked,
    val (1, N), test (1, N) [column data-sharded]). Cells are
    model-major (``cell = sm * Sd + sd``, the block order of a
    ``P(("model", "data"))`` leading axis). Eval scores the updated
    global model against each data slice's LOCAL device block — the
    (1, N) matrices' columns are device rows, data-sharded."""
    one_pair = _pair_train(loss_fn, lr)
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    cell = P(("model", "data"))
    drow = P("data")
    rep = P()
    vcol = P(None, "data")

    def body(stacked, m_idx, d_idx, perms, w, xs, ys, vx, vy, tx, ty):
        trained = _train_pairs(one_pair, stacked, m_idx, xs, ys,
                               d_idx, perms)
        new_stacked = _fedavg_psum_avg(stacked, trained, w,
                                       ("model", "data"))
        model = jax.tree.map(lambda a: a[0], new_stacked)
        val = eval_model(model, vx, vy)[None]
        test = eval_model(model, tx, ty)[None]
        return new_stacked, val, test

    step = shard_map(
        body, mesh=mesh,
        in_specs=(rep, cell, cell, cell, cell,
                  drow, drow, drow, drow, drow, drow),
        out_specs=(rep, vcol, vcol), check_vma=False)
    return jax.jit(step, donate_argnums=(0,))


def make_sharded2d_fedavg_train(loss_fn: Callable, lr: float,
                                mesh: jax.sharding.Mesh) -> Callable:
    """The TRAIN phase of ``make_sharded2d_fedavg_round`` alone (pure
    read — speculable): fn(stacked (1, ...) replicated, m_idx (C*B,),
    d_idx (C*B,) LOCAL, perms (C*B, T, b), xs, ys [data-row-sharded])
    -> trained (C*B, ...) cell-sharded."""
    one_pair = _pair_train(loss_fn, lr)
    cell = P(("model", "data"))
    drow = P("data")
    rep = P()

    def body(stacked, m_idx, d_idx, perms, xs, ys):
        return _train_pairs(one_pair, stacked, m_idx, xs, ys, d_idx,
                            perms)

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(rep, cell, cell, cell, drow, drow),
        out_specs=cell, check_vma=False))


def make_sharded2d_fedavg_finish(acc_fn: Callable,
                                 mesh: jax.sharding.Mesh) -> Callable:
    """Aggregate + evaluate phases of ``make_sharded2d_fedavg_round``
    as their own dispatch: fn(stacked (1, ...) [donated, replicated],
    trained (C*B, ...) cell-sharded, w (C*B,), vx, vy, tx, ty) ->
    (new_stacked, val (1, N), test (1, N) [column data-sharded])."""
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    cell = P(("model", "data"))
    drow = P("data")
    rep = P()
    vcol = P(None, "data")

    def body(stacked, trained, w, vx, vy, tx, ty):
        new_stacked = _fedavg_psum_avg(stacked, trained, w,
                                       ("model", "data"))
        model = jax.tree.map(lambda a: a[0], new_stacked)
        val = eval_model(model, vx, vy)[None]
        test = eval_model(model, tx, ty)[None]
        return new_stacked, val, test

    step = shard_map(body, mesh=mesh,
                     in_specs=(rep, cell, cell, drow, drow, drow, drow),
                     out_specs=(rep, vcol, vcol), check_vma=False)
    return jax.jit(step, donate_argnums=(0,))


def make_sharded2d_fedavg_eval(acc_fn: Callable,
                               mesh: jax.sharding.Mesh) -> Callable:
    """Eval of the current global model alone (a semi-sync round whose
    every pair straggled or dropped): fn(stacked (1, ...) replicated,
    xs, ys [data-row-sharded]) -> (1, N) column data-sharded."""
    eval_model = jax.vmap(acc_fn, in_axes=(None, 0, 0))
    drow = P("data")
    rep = P()
    vcol = P(None, "data")

    def body(stacked, xs, ys):
        model = jax.tree.map(lambda a: a[0], stacked)
        return eval_model(model, xs, ys)[None]

    return jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(rep, drow, drow),
                             out_specs=vcol, check_vma=False))


def make_perms(rng: np.random.Generator, n_devices: int, n_examples: int,
               batch_size: int, epochs: int) -> np.ndarray:
    """(N, epochs*steps, batch) minibatch index matrices.

    Vectorized: one ``rng.permuted`` call draws all N*epochs independent
    row permutations at once instead of the former per-device/per-epoch
    ``rng.permutation`` Python loop (PR 2). NOTE this is an intentional
    host-RNG-stream change: seeded runs shuffle differently than PR 1
    (``permuted`` consumes the BitGenerator differently from sequential
    ``permutation`` calls). All round engines share this stream, so
    engine equivalence is unaffected; see DESIGN.md §7.
    """
    steps = max(n_examples // batch_size, 1)
    flat = np.broadcast_to(np.arange(n_examples, dtype=np.int32),
                           (n_devices * epochs, n_examples))
    perms = rng.permuted(flat, axis=1)
    perms = perms.reshape(n_devices, epochs, n_examples)
    return perms[:, :, :steps * batch_size].reshape(
        n_devices, epochs * steps, batch_size)


def draw_round_sample(rng: np.random.Generator, n_devices: int,
                      devices_per_round: int, n_examples: int,
                      batch_size: int, epochs: int,
                      present: "np.ndarray | None" = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """One round's participation mask + shared minibatch perms.

    The ONE place the sampling stream is consumed: FedCDServer and
    FedAvgServer both call exactly this with identically-seeded
    generators, so FedCD-vs-FedAvg comparisons train identical
    per-round cohorts and the stream walk stays engine-independent
    (DESIGN.md §7). ``present`` (churn scenarios): sample only present
    device ids, clamping the cohort to the population; the full-fleet
    fast path consumes the BitGenerator exactly as the presence-free
    form, so static-population runs keep their historical streams."""
    participating = np.zeros(n_devices, bool)
    if present is None or present.all():
        chosen = rng.choice(n_devices, devices_per_round, replace=False)
    else:
        ids = np.nonzero(present)[0]
        k = min(devices_per_round, len(ids))
        chosen = ids[rng.choice(len(ids), k, replace=False)]
    participating[chosen] = True
    perms = make_perms(rng, n_devices, n_examples, batch_size, epochs)
    return participating, perms


# -- mode-B LM round programs (DESIGN.md §14) -----------------------------

def make_llm_round(train_fn: Callable, acc_fn: Callable) -> Callable:
    """ONE jitted donated dispatch for a mode-B LM round over a
    per-layer-stacked bank: gather the padded training rows, scan the
    score-weighted train step over the model-row axis, scatter the
    trained rows back, then scan per-client eval over the padded live
    rows. Padding rows repeat the first entry with its weight row
    (``w[pad] = w[0]``), so duplicate scatters write identical values
    and the extra eval lanes are sliced off host-side.

    ``train_fn``/``acc_fn`` are the UNJITTED single-model steps from
    ``launch.steps.make_train_step`` / ``llm.make_acc_step``. The
    model-row axis is a pure batch axis (every contraction stays within
    one model), so batching it with ``vmap`` OR iterating it with
    ``lax.scan`` both compute exactly the per-model loop's values. We
    scan: vmapping per-lane params turns every matmul into a batched
    dot, which misses XLA:CPU's fast single-GEMM kernels (measured 1.3x
    SLOWER than the per-model loop at equal compute), while the scanned
    body keeps each lane on the single-GEMM path and still gets the
    one-dispatch wins — fused train+eval per lane and no host
    round-trips between models (measured 1.6x faster than the loop).
    """

    def round_step(bank, train_rows, w, tokens, labels, vt, vl, eval_rows):
        def train_body(_, pw):
            row_params, wm = pw
            p2, met = train_fn(row_params, tokens, labels, wm, None)
            return _, (p2, met["loss"])

        rows = jax.tree.map(lambda a: a[train_rows], bank)
        _, (new_rows, losses) = jax.lax.scan(train_body, None, (rows, w))
        bank = jax.tree.map(
            lambda a, r: a.at[train_rows].set(r.astype(a.dtype)),
            bank, new_rows)
        ev = jax.tree.map(lambda a: a[eval_rows], bank)
        _, accs = jax.lax.scan(                            # (L_pad, N)
            lambda _, p: (_, acc_fn(p, vt, vl)), None, ev)
        return bank, losses, accs

    return jax.jit(round_step, donate_argnums=(0,))


def make_llm_eval(acc_fn: Callable) -> Callable:
    """Eval-only LM dispatch (rounds where no model trains): scan the
    per-client accuracy step over the padded live rows (same
    single-GEMM rationale as ``make_llm_round``), bank read-only (not
    donated)."""

    def eval_step(bank, eval_rows, vt, vl):
        ev = jax.tree.map(lambda a: a[eval_rows], bank)
        _, accs = jax.lax.scan(                            # (L_pad, N)
            lambda _, p: (_, acc_fn(p, vt, vl)), None, ev)
        return accs

    return jax.jit(eval_step)
