"""Pure-jnp oracle for score-weighted aggregation (paper eq 1).

updates (N, D) f32, weights (N,) f32, denom scalar ->
    out (D,) = sum_i w_i * u_i / denom
Fused variant with int8 inputs: dequantize per 128-block then accumulate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def weighted_agg_ref(updates: jax.Array, weights: jax.Array,
                     denom: jax.Array) -> jax.Array:
    acc = jnp.einsum("nd,n->d", updates.astype(jnp.float32),
                     weights.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return acc / denom


def multi_weighted_agg_ref(updates: jax.Array, weights: jax.Array,
                           denoms: jax.Array) -> jax.Array:
    """Multi-model aggregation over one shared work batch.

    updates (B, D) f32 — trained pair payloads; weights (M, B) f32 with
    row m holding pair weights for model m (0 where the pair belongs to a
    different model or is padding); denoms (M,) -> out (M, D).
    """
    acc = jnp.einsum("bd,mb->md", updates.astype(jnp.float32),
                     weights.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return acc / denoms[:, None]


def dequant_agg_ref(q: jax.Array, scales: jax.Array, weights: jax.Array,
                    denom: jax.Array, block: int = 128) -> jax.Array:
    """q (N, D) int8, scales (N, D//block) f32 -> (D,) f32."""
    N, D = q.shape
    nb = D // block
    x = q.astype(jnp.float32).reshape(N, nb, block) * scales[..., None]
    return weighted_agg_ref(x.reshape(N, D), weights, denom)
