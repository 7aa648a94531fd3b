"""Score-weighted federated aggregation (paper eq 1).

``w_m = Σ_i w_m_i · c_m_i / Σ_i c_m_i`` over the devices contributing to
model m. (The paper's printed denominator Σ_m c_m_i is a typo — it equals
1 after eq 3 and would make w_m a *sum*, not an average; the literal form
is available behind ``literal_eq1=True`` for completeness. See DESIGN.md.)

Two backends:
  * pytree path (default): jnp einsum over a stacked (N, ...) update tree;
  * Pallas path: fused weighted accumulation over flattened updates
    (kernels/weighted_agg) — the server hot-spot for CNN-scale mode-A
    aggregation; validated against this module in tests.

Both are traceable and compose under jit/vmap: the fused round engine
calls ``multi_weighted_average`` inside its single round dispatch with a
bucketed (A, B) weight matrix over the models that trained this round,
then scatters the aggregated rows into its stacked parameter bank
(DESIGN.md §2).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# Eq 1 is a weighted average and stays f32, as on the CPU. The TPU's
# default matmul precision rounds f32 operands to bf16, which would
# round every aggregated parameter to 8 mantissa bits each round.
EQ1_PRECISION = jax.lax.Precision.HIGHEST


def weighted_average(stacked_updates: Any, weights: jax.Array,
                     literal_eq1: bool = False,
                     use_kernel: bool = False) -> Any:
    """stacked_updates: pytree with leading device axis N; weights (N,).

    Devices with weight 0 contribute nothing (deleted/non-participating).
    """
    w = jnp.asarray(weights, jnp.float32)
    denom = jnp.float32(1.0) if literal_eq1 else jnp.maximum(jnp.sum(w), 1e-12)

    if use_kernel:
        from repro.kernels.weighted_agg import ops as wa_ops
        leaves, treedef = jax.tree_util.tree_flatten(stacked_updates)
        outs = [wa_ops.weighted_agg(leaf, w, denom) for leaf in leaves]
        return jax.tree_util.tree_unflatten(treedef, outs)

    def avg(leaf: jax.Array) -> jax.Array:
        wf = w.reshape((-1,) + (1,) * (leaf.ndim - 1))
        acc = jnp.sum(leaf.astype(jnp.float32) * wf, axis=0)
        return (acc / denom).astype(leaf.dtype)

    return jax.tree.map(avg, stacked_updates)


def multi_weighted_average(stacked_updates: Any, weights: jax.Array,
                           literal_eq1: bool = False,
                           use_kernel: bool = False) -> Any:
    """Aggregate every live model from one shared work batch (eq 1, fused).

    stacked_updates: pytree with leading pair axis B (trained
    ``(model, device)`` pairs from the batched engine); weights (M, B)
    with row m carrying c_m_i for pairs that belong to model m and 0
    elsewhere (padding pairs are all-zero columns). Returns a pytree with
    leading model axis M.
    """
    w = jnp.asarray(weights, jnp.float32)
    row_sums = jnp.sum(w, axis=1)
    denoms = (jnp.ones_like(row_sums) if literal_eq1
              else jnp.maximum(row_sums, 1e-12))

    if use_kernel:
        from repro.kernels.weighted_agg import ops as wa_ops
        leaves, treedef = jax.tree_util.tree_flatten(stacked_updates)
        outs = [wa_ops.multi_weighted_agg(leaf, w, denoms) for leaf in leaves]
        return jax.tree_util.tree_unflatten(treedef, outs)

    def avg(leaf: jax.Array) -> jax.Array:
        acc = jnp.einsum("b...,mb->m...", leaf.astype(jnp.float32), w,
                         precision=EQ1_PRECISION)
        df = denoms.reshape((-1,) + (1,) * (acc.ndim - 1))
        return (acc / df).astype(leaf.dtype)

    return jax.tree.map(avg, stacked_updates)


def participation_weights(scores_c: np.ndarray, model_id: int,
                          participating: np.ndarray,
                          active: np.ndarray) -> np.ndarray:
    """Per-device weight for aggregating model ``model_id`` this round:
    c_m_i for participating devices that hold m, else 0."""
    w = scores_c[:, model_id].copy()
    w[~participating] = 0.0
    w[~active[:, model_id]] = 0.0
    return w
