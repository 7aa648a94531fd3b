"""Hypothesis property tests on the system's invariants."""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need the dev extra: pip install -e .[dev]")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.config import FedCDConfig
from repro.core.lifecycle import apply_deletions
from repro.core.registry import ModelRegistry
from repro.core.scores import (init_scores, normalized_scores,
                               push_accuracies)
from repro.kernels.quantize import ref as qref

import jax.numpy as jnp


accs_arrays = st.integers(2, 6).flatmap(
    lambda n: st.integers(2, 5).flatmap(
        lambda m: st.lists(
            st.lists(st.floats(0.01, 0.99), min_size=m, max_size=m),
            min_size=n, max_size=n)))


@given(accs_arrays)
@settings(max_examples=30, deadline=None)
def test_scores_always_normalized(acc_rows):
    a = np.array(acc_rows)
    n, m = a.shape
    s = init_scores(n, m, ell=2)
    s.active[:] = True
    s.alive[:] = True
    s = push_accuracies(s, a)
    c = normalized_scores(s)
    assert np.allclose(c.sum(axis=1), 1.0, atol=1e-9)
    assert (c >= 0).all() and (c <= 1).all()


@given(accs_arrays, st.integers(1, 40))
@settings(max_examples=30, deadline=None)
def test_deletion_never_leaves_device_modelless(acc_rows, round_):
    a = np.array(acc_rows)
    n, m = a.shape
    s = init_scores(n, m, ell=2)
    s.active[:] = True
    s.alive[:] = True
    s = push_accuracies(s, a)
    from repro.core.registry import ModelEntry
    reg = ModelRegistry(m_cap=m)
    for i in range(m):
        reg.entries[i] = ModelEntry(i, None, 0)
        reg.params[i] = {"w": np.zeros(1)}
    cfg = FedCDConfig(n_devices=n, max_models=m)
    s2, _ = apply_deletions(s, reg, round_, cfg)
    assert (s2.active.sum(axis=1) >= 1).all()
    # server holds exactly the models someone still uses
    for mid in range(m):
        held = s2.active[:, mid].any()
        assert reg.entries[mid].alive == bool(held)


@given(st.integers(1, 200), st.integers(1, 400),
       st.sampled_from([4, 8]))
@settings(max_examples=20, deadline=None)
def test_quantize_roundtrip_bounded(rows, cols, bits):
    rng = np.random.default_rng(rows * 1000 + cols)
    x = jnp.asarray(rng.normal(0, 2, (rows, cols)).astype(np.float32))
    q, s = qref.quantize_ref(x, bits=bits)
    xr = qref.dequantize_ref(q, s, x.shape, x.dtype)
    err = np.abs(np.asarray(xr) - np.asarray(x))
    # per-block: |err| <= scale/2 (+ tie rounding); scale = blockmax/qmax
    assert err.max() <= np.asarray(s).max() * 0.500001 + 1e-7
    assert np.abs(np.asarray(xr)).max() <= np.abs(np.asarray(x)).max() + 1e-6


@given(st.integers(0, 4096), st.sampled_from([1, 4, 8, 16]))
@settings(max_examples=200, deadline=None)
def test_bucket_size_sound_and_bounded(n, minimum):
    """bucket_size must (a) cover n, (b) be monotone in n, (c) be
    idempotent (a bucket is its own bucket, so re-bucketing a padded
    batch never regrows it), and (d) waste < 20% of the bucket once
    n > 8*minimum. NOTE: the seed documented a <14% bound, but the
    eighth-octave construction's true worst case is (step-1)/bucket ->
    20% just past a power of two (e.g. n=65 -> 80, 18.75% waste); this
    property test found the discrepancy and the docs now state the
    tight bound."""
    from repro.federated.simulation import bucket_size
    b = bucket_size(n, minimum)
    assert b >= max(n, minimum)
    assert bucket_size(b, minimum) == b
    assert bucket_size(n + 1, minimum) >= b
    if n > 8 * minimum:
        assert (b - n) / b < 0.2


@given(st.integers(1, 40), st.integers(2, 6), st.integers(2, 5))
@settings(max_examples=25, deadline=None)
def test_pad_work_batch_padding_is_masked(n_pairs, models, dim):
    """Padding pairs (all-zero weight columns) must not influence any
    model's aggregate: aggregating the padded batch with zero-extended
    weights equals aggregating the unpadded batch."""
    from repro.core.aggregate import multi_weighted_average
    from repro.federated.simulation import pad_work_batch
    rng = np.random.default_rng(n_pairs * 100 + models * 10 + dim)
    model_idx = rng.integers(0, models, n_pairs).tolist()
    device_idx = rng.integers(0, 4, n_pairs).tolist()
    perm_rows = [rng.integers(0, 8, (3, 2)).astype(np.int32)
                 for _ in range(n_pairs)]
    m_idx, d_idx, perms = pad_work_batch(model_idx, device_idx, perm_rows)
    b_pad = len(m_idx)
    assert b_pad >= n_pairs
    np.testing.assert_array_equal(m_idx[:n_pairs], model_idx)
    np.testing.assert_array_equal(d_idx[:n_pairs], device_idx)
    np.testing.assert_array_equal(perms[:n_pairs], np.stack(perm_rows))
    assert (perms[n_pairs:] == 0).all()

    updates = rng.normal(0, 1, (n_pairs, dim)).astype(np.float32)
    w = np.zeros((models, n_pairs), np.float32)
    w[model_idx, np.arange(n_pairs)] = rng.uniform(0.1, 1.0, n_pairs)
    padded_updates = np.zeros((b_pad, dim), np.float32)
    padded_updates[:n_pairs] = updates
    padded_updates[n_pairs:] = 99.0          # garbage that must be masked
    w_pad = np.zeros((models, b_pad), np.float32)
    w_pad[:, :n_pairs] = w
    ref = multi_weighted_average({"x": jnp.asarray(updates)}, w)["x"]
    out = multi_weighted_average({"x": jnp.asarray(padded_updates)},
                                 w_pad)["x"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_train_bucket_wastes_little_and_groups_evenly():
    """The one-chip train work batch for every pair count 1..256 wastes
    under 2 slots or under 20% of the bucket, and trains as equal groups
    whose width divides the bucket and is at most ``PAIR_GROUP`` (8 for
    every multiple of 8, the whole bucket up to 8 slots). Above 64 pairs
    the buckets are the floor of 8's, and over 1..256 at most 8 shapes
    are new."""
    from repro.federated.simulation import (PAIR_GROUP, bucket_size,
                                            pair_group_width, train_bucket)
    for n in range(1, 257):
        b = train_bucket(n)
        assert b >= n and (b - n < 2 or (b - n) / b < 0.2), (n, b)
        w = pair_group_width(b)
        assert b % w == 0 and w <= PAIR_GROUP, (n, b, w)
        if b % 8 == 0:
            assert w == 8, (n, b, w)
        if b <= PAIR_GROUP:
            assert w == b, (n, b, w)
        if n > 64:
            assert b == bucket_size(n), n
    new = ({train_bucket(n) for n in range(1, 257)}
           - {bucket_size(n) for n in range(1, 257)})
    assert len(new) <= 8, sorted(new)


def _pair_problem(n_pairs, models=3, n_dev=6, n_ex=16, dim=12):
    """A stacked bank of small MLPs, data, and ``n_pairs`` (model,
    device, perm) pairs that give every model at least one pair."""
    import jax
    from repro.models.mlp import init_mlp_classifier
    rng = np.random.default_rng(n_pairs)
    keys = jax.random.split(jax.random.PRNGKey(n_pairs), models)
    stacked = jax.tree.map(
        lambda *a: jnp.stack(a),
        *[init_mlp_classifier(k, in_dim=dim, hidden=16) for k in keys])
    xs = jnp.asarray(rng.normal(size=(n_dev, n_ex, dim)), jnp.float32)
    ys = jnp.asarray(rng.integers(0, 10, (n_dev, n_ex)), jnp.int32)
    model_idx = rng.permutation([k % models for k in range(n_pairs)])
    device_idx = rng.integers(0, n_dev, n_pairs)
    perm_rows = [rng.permutation(n_ex).reshape(2, n_ex // 2)
                 .astype(np.int32) for _ in range(n_pairs)]
    return (stacked, xs, ys, model_idx.tolist(), device_idx.tolist(),
            perm_rows)


@pytest.mark.parametrize("n_pairs", [10, 13])
def test_train_bucket_trains_like_the_floor_of_8(n_pairs):
    """Training ``n_pairs`` pairs padded to the floor of 8 (16 slots,
    two groups of 8) and to ``train_bucket`` (10 slots as two groups of
    5; 14 as two of 7) gives the same real trained rows and the same
    eq-1 aggregate."""
    import jax
    from repro.core.aggregate import multi_weighted_average
    from repro.federated.simulation import (make_pair_train,
                                            pad_work_batch, train_bucket)
    from repro.models.mlp import mlp_loss
    stacked, xs, ys, model_idx, device_idx, perm_rows = _pair_problem(
        n_pairs)
    models = len(jax.tree.leaves(stacked)[0])
    scores = np.random.default_rng(1).uniform(0.1, 1.0, n_pairs)
    train = make_pair_train(mlp_loss, 0.1)
    now = pad_work_batch(model_idx, device_idx, perm_rows)
    assert len(now[0]) == train_bucket(n_pairs) < 16
    # the floor of 8's batch: the same pairs, zero padding out to 16
    floor8 = [np.concatenate([a, np.zeros((16 - len(a),) + a.shape[1:],
                                          a.dtype)]) for a in now]
    out = []
    for m_idx, d_idx, perms in (floor8, now):
        trained = train(stacked, m_idx, xs, ys, d_idx, perms)
        w = np.zeros((models, len(m_idx)), np.float32)
        w[model_idx, np.arange(n_pairs)] = scores
        out.append((jax.tree.map(lambda a: a[:n_pairs], trained),
                     multi_weighted_average(trained, w)))
    for a, b in zip(jax.tree.leaves(out[0]), jax.tree.leaves(out[1])):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("b", [8, 16, 64])
def test_train_program_of_multiples_of_8_is_unchanged(b):
    """A work batch whose slots are a multiple of 8 traces the same
    train program as the fixed groups of 8 did."""
    import jax
    from repro.federated.simulation import _pair_train, _train_pairs
    from repro.models.mlp import mlp_loss
    stacked, xs, ys, _, _, _ = _pair_problem(1)
    one_pair = _pair_train(mlp_loss, 0.1)

    def groups_of_8(stacked, m_idx, xs, ys, d_idx, perms):
        with jax.named_scope("fedcd.train"):
            return jax.lax.map(
                lambda a: one_pair(stacked, a[0], xs, ys, a[1], a[2]),
                (m_idx, d_idx, perms), batch_size=8)

    i32 = np.int32
    args = (stacked, np.zeros(b, i32), xs, ys, np.zeros(b, i32),
            np.zeros((b, 2, 8), i32))
    now = jax.make_jaxpr(
        lambda *a: _train_pairs(one_pair, *a))(*args)
    assert str(now) == str(jax.make_jaxpr(groups_of_8)(*args))


@given(st.lists(st.integers(0, 15), min_size=1, max_size=16, unique=True))
@settings(max_examples=30, deadline=None)
def test_pad_live_rows_prefix_preserved(live):
    from repro.federated.simulation import bucket_size, pad_live_rows
    idx = pad_live_rows(live)
    assert len(idx) == bucket_size(len(live), minimum=1)
    np.testing.assert_array_equal(idx[:len(live)], live)
    # padding rows repeat a real live row (they are computed, discarded)
    assert set(idx[len(live):].tolist()) <= set(live) | {live[0]}


@given(st.integers(1, 8), st.integers(1, 8),
       st.data())
@settings(max_examples=50, deadline=None)
def test_shard_row_assignment_is_disjoint_cover(n_shards, rows_per_shard,
                                                data):
    """The sharded engine's row partition (DESIGN.md §9): for arbitrary
    (max_models = n_shards * rows_per_shard, live mask), ``shard_rows``
    must (a) partition the live rows into a DISJOINT COVER with every
    row on its owning shard, (b) respect the documented <20% per-shard
    padding-waste bound once the densest shard holds more than 8 rows,
    and (c) round-trip through the local-index scatter/gather: local
    index + shard offset reconstructs exactly the input rows, each at a
    unique matrix slot."""
    from repro.federated.simulation import shard_rows
    m_cap = n_shards * rows_per_shard
    live = data.draw(st.lists(st.integers(0, m_cap - 1), unique=True,
                              max_size=m_cap))
    idx, groups, width = shard_rows(live, rows_per_shard, n_shards)

    # (a) disjoint cover on the owning shards
    flat = [m for g in groups for m in g]
    assert sorted(flat) == sorted(live)
    assert len(set(flat)) == len(flat)
    for s, g in enumerate(groups):
        for m in g:
            assert m // rows_per_shard == s

    # (b) one shared bucket, <20% padding waste per shard past the
    # bucket_size threshold (minimum=1 -> n > 8)
    from repro.federated.simulation import bucket_size
    densest = max((len(g) for g in groups), default=0)
    assert width == bucket_size(densest, minimum=1)
    if densest > 8:
        assert (width - densest) / width < 0.2

    # (c) scatter/gather roundtrip: every live row's matrix slot holds
    # its own local index, and padding slots stay inside the shard
    assert len(idx) == n_shards * width
    assert (idx >= 0).all() and (idx < rows_per_shard).all()
    for s, g in enumerate(groups):
        for j, m in enumerate(g):
            assert idx[s * width + j] + s * rows_per_shard == m


@given(st.integers(1, 6), st.integers(1, 6), st.data())
@settings(max_examples=40, deadline=None)
def test_shard_work_batch_partitions_pairs(n_shards, rows_per_shard, data):
    """Work pairs land on the shard owning their MODEL row, with
    shard-local model indices and their perm rows carried along
    unchanged; padding slots are zeroed (masked out by zero weight
    columns downstream)."""
    from repro.federated.simulation import shard_work_batch
    m_cap = n_shards * rows_per_shard
    n_pairs = data.draw(st.integers(1, 24))
    rng = np.random.default_rng(n_pairs * 7 + m_cap)
    pair_model = rng.integers(0, m_cap, n_pairs).tolist()
    pair_device = rng.integers(0, 5, n_pairs).tolist()
    perm_rows = [rng.integers(0, 8, (3, 2)).astype(np.int32)
                 for _ in range(n_pairs)]
    m_idx, d_idx, perms, pair_groups, width = shard_work_batch(
        pair_model, pair_device, perm_rows, rows_per_shard, n_shards)

    flat = [k for g in pair_groups for k in g]
    assert sorted(flat) == list(range(n_pairs))     # disjoint cover
    assert len(m_idx) == len(d_idx) == len(perms) == n_shards * width
    assert (m_idx >= 0).all() and (m_idx < rows_per_shard).all()
    for s, g in enumerate(pair_groups):
        assert len(g) <= width
        for j, k in enumerate(g):
            slot = s * width + j
            assert m_idx[slot] + s * rows_per_shard == pair_model[k]
            assert d_idx[slot] == pair_device[k]
            np.testing.assert_array_equal(perms[slot], perm_rows[k])
        # padding slots are zeroed
        assert (m_idx[s * width + len(g):(s + 1) * width] == 0).all()
        assert (perms[s * width + len(g):(s + 1) * width] == 0).all()


@given(st.lists(st.floats(0.0, 1.0), min_size=2, max_size=8))
@settings(max_examples=30, deadline=None)
def test_weighted_average_permutation_invariant(ws):
    from repro.core.aggregate import weighted_average
    n = len(ws)
    w = np.array(ws) + 1e-3
    u = np.random.default_rng(n).normal(0, 1, (n, 5)).astype(np.float32)
    out = weighted_average({"x": jnp.asarray(u)}, jnp.asarray(w))["x"]
    perm = np.random.default_rng(1).permutation(n)
    out_p = weighted_average({"x": jnp.asarray(u[perm])},
                             jnp.asarray(w[perm]))["x"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_p), atol=1e-5)


@given(st.integers(1, 4), st.integers(1, 4), st.integers(1, 4),
       st.integers(1, 4), st.data())
@settings(max_examples=40, deadline=None)
def test_shard_pairs_2d_partitions_by_cell(n_mshards, rows_per_mshard,
                                           n_dshards, rows_per_dshard,
                                           data):
    """The 2-D mesh's dispatch bucketing (DESIGN.md §11): every work
    pair lands on the ONE mesh cell owning both its model bank row and
    its data bank row (disjoint cover), with shard-LOCAL indices whose
    scatter/gather roundtrip reconstructs the global rows exactly, one
    shared bucket with the <20% per-cell padding-waste bound past the
    bucket_size threshold, and zeroed padding slots (masked out of
    aggregation by zero weight columns)."""
    from repro.federated.simulation import bucket_size, shard_pairs_2d
    m_cap = n_mshards * rows_per_mshard
    n_cap = n_dshards * rows_per_dshard
    n_pairs = data.draw(st.integers(1, 24))
    rng = np.random.default_rng(n_pairs * 13 + m_cap * 5 + n_cap)
    pair_mrows = rng.integers(0, m_cap, n_pairs).tolist()
    pair_drows = rng.integers(0, n_cap, n_pairs).tolist()
    perm_rows = [rng.integers(0, 8, (3, 2)).astype(np.int32)
                 for _ in range(n_pairs)]
    m_idx, d_idx, perms, groups, width = shard_pairs_2d(
        pair_mrows, pair_drows, perm_rows, rows_per_mshard, n_mshards,
        rows_per_dshard, n_dshards, minimum=2)

    n_cells = n_mshards * n_dshards
    flat = [k for g in groups for k in g]
    assert sorted(flat) == list(range(n_pairs))     # disjoint cover
    assert len(groups) == n_cells
    assert len(m_idx) == len(d_idx) == len(perms) == n_cells * width
    assert (m_idx >= 0).all() and (m_idx < rows_per_mshard).all()
    assert (d_idx >= 0).all() and (d_idx < rows_per_dshard).all()
    densest = max(len(g) for g in groups)
    assert width == bucket_size(densest, minimum=2)
    if densest > 16:                                # 8 * minimum
        assert (width - densest) / width < 0.2
    for c, g in enumerate(groups):
        sm, sd = divmod(c, n_dshards)               # model-major cells
        assert len(g) <= width
        for j, k in enumerate(g):
            slot = c * width + j
            # the cell owns BOTH rows, and the local-index roundtrip
            # reconstructs the globals
            assert m_idx[slot] + sm * rows_per_mshard == pair_mrows[k]
            assert d_idx[slot] + sd * rows_per_dshard == pair_drows[k]
            np.testing.assert_array_equal(perms[slot], perm_rows[k])
        assert (m_idx[c * width + len(g):(c + 1) * width] == 0).all()
        assert (d_idx[c * width + len(g):(c + 1) * width] == 0).all()
        assert (perms[c * width + len(g):(c + 1) * width] == 0).all()
