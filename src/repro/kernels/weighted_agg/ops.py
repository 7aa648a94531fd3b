"""Public wrappers for the weighted-aggregation kernels (pytree leaves).

The kernel body runs in interpret mode on the CPU and compiled on the
TPU (``repro.kernels.interpret_mode``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.weighted_agg import kernel as K


def weighted_agg(stacked_leaf: jax.Array, weights: jax.Array,
                 denom: jax.Array) -> jax.Array:
    """stacked_leaf (N, ...) -> weighted average with original trailing shape."""
    N = stacked_leaf.shape[0]
    tail = stacked_leaf.shape[1:]
    flat = stacked_leaf.reshape(N, -1).astype(jnp.float32)
    D = flat.shape[1]
    pad = (-D) % K.TILE_D
    flat = jnp.pad(flat, ((0, 0), (0, pad)))
    out = K.weighted_agg_2d(flat, weights, jnp.asarray(denom),
                            interpret=interpret_mode())
    return out[:D].reshape(tail).astype(stacked_leaf.dtype)


def multi_weighted_agg(stacked_leaf: jax.Array, weights: jax.Array,
                       denoms: jax.Array) -> jax.Array:
    """stacked_leaf (B, ...), weights (M, B), denoms (M,) -> (M, ...)
    per-model weighted averages of one shared work batch."""
    B = stacked_leaf.shape[0]
    M = weights.shape[0]
    tail = stacked_leaf.shape[1:]
    flat = stacked_leaf.reshape(B, -1).astype(jnp.float32)
    D = flat.shape[1]
    pad = (-D) % K.TILE_D
    flat = jnp.pad(flat, ((0, 0), (0, pad)))
    out = K.multi_weighted_agg_2d(flat, weights, jnp.asarray(denoms),
                                  interpret=interpret_mode())
    return out[:, :D].reshape((M,) + tail).astype(stacked_leaf.dtype)


def dequant_agg(q: jax.Array, scales: jax.Array, weights: jax.Array,
                denom: jax.Array, block: int = 128) -> jax.Array:
    """Aggregate compressed payloads directly. q (N, D), D % block == 0,
    scales (N, D // block)."""
    N, D = q.shape
    nb = D // block
    pad = (-nb) % K.TILE_BLOCKS
    qb = jnp.pad(q.reshape(N, nb, block), ((0, 0), (0, pad), (0, 0)))
    sb = jnp.pad(scales.reshape(N, nb, 1), ((0, 0), (0, pad), (0, 0)))
    out = K.dequant_agg_2d(qb, sb, weights, jnp.asarray(denom),
                           interpret=interpret_mode())
    return out[:nb].reshape(D)
