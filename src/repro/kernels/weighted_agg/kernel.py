"""Pallas TPU kernel: fused score-weighted aggregation (paper eq 1).

``out[d] = Σ_i w_i · u_i[d] / denom`` over N client payloads without
materializing the weighted copies — the N-way multiply-accumulate happens
in VMEM registers.

Tiling: grid over the payload dim D in tiles of TILE_D (=2048 lanes);
each program streams all N client rows for its tile (N ≤ a few hundred
in FL rounds, so the (N, TILE_D) f32 tile = N·8 KiB sits in VMEM).
Weights and denominators are small whole-array VMEM blocks, read as
vectors (the TPU compiler loads only from VMEM/SMEM). A second fused
variant consumes int8 payloads + per-block scales, dequantizing on the
fly — aggregation of *compressed* client uploads, the beyond-paper
optimization described in DESIGN.md §6. It sees the payload as
``(N, n_blocks, block)`` so each block's scale is a ``(.., 1)`` column
broadcast along lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_D = 2048
TILE_BLOCKS = 32        # quantization blocks per dequant-agg program


def _weighted_agg_kernel(u_ref, w_ref, d_ref, o_ref):
    u = u_ref[...].astype(jnp.float32)          # (N, TILE_D)
    w = w_ref[...].astype(jnp.float32)          # (N, 1)
    o_ref[...] = (jnp.sum(u * w, axis=0, keepdims=True) / d_ref[...]
                  ).astype(o_ref.dtype)


def _multi_weighted_agg_kernel(u_ref, w_ref, d_ref, o_ref):
    u = u_ref[...].astype(jnp.float32)          # (B, TILE_D)
    w = w_ref[...].astype(jnp.float32)          # (M, B) — every model
    acc = jax.lax.dot_general(
        w, u, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)     # (M, TILE_D) on the MXU
    o_ref[...] = (acc / d_ref[...]).astype(o_ref.dtype)


def _dequant_agg_kernel(q_ref, s_ref, w_ref, d_ref, o_ref):
    x = q_ref[...].astype(jnp.float32) * s_ref[...]   # (N, tb, block)
    acc = jnp.sum(x * w_ref[...], axis=0)             # w (N, 1, 1)
    o_ref[...] = (acc / d_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def weighted_agg_2d(updates: jax.Array, weights: jax.Array,
                    denom: jax.Array, *, interpret: bool) -> jax.Array:
    """updates (N, D) with D % TILE_D == 0; weights (N,); denom scalar."""
    N, D = updates.shape
    w2 = weights.reshape(N, 1).astype(jnp.float32)
    d2 = jnp.reshape(denom.astype(jnp.float32), (1, 1))
    return pl.pallas_call(
        _weighted_agg_kernel,
        grid=(D // TILE_D,),
        in_specs=[
            pl.BlockSpec((N, TILE_D), lambda j: (0, j)),
            pl.BlockSpec((N, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TILE_D), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(updates, w2, d2)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def multi_weighted_agg_2d(updates: jax.Array, weights: jax.Array,
                          denoms: jax.Array, *, interpret: bool
                          ) -> jax.Array:
    """updates (B, D) with D % TILE_D == 0; weights (M, B); denoms (M,).

    Grid over payload tiles: each program streams the full work batch
    for its tile and multiplies it by the whole (M, B) weight matrix —
    all M aggregates come out of one fused call."""
    B, D = updates.shape
    M = weights.shape[0]
    w2 = weights.astype(jnp.float32)
    d2 = denoms.astype(jnp.float32).reshape(M, 1)
    return pl.pallas_call(
        _multi_weighted_agg_kernel,
        grid=(D // TILE_D,),
        in_specs=[
            pl.BlockSpec((B, TILE_D), lambda j: (0, j)),
            pl.BlockSpec((M, B), lambda j: (0, 0)),
            pl.BlockSpec((M, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((M, TILE_D), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((M, D), jnp.float32),
        interpret=interpret,
    )(updates, w2, d2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_agg_2d(q: jax.Array, scales: jax.Array, weights: jax.Array,
                   denom: jax.Array, *, interpret: bool) -> jax.Array:
    """q (N, n_blocks, block) int8 with n_blocks % TILE_BLOCKS == 0,
    scales (N, n_blocks, 1) f32 -> (n_blocks, block) f32 aggregate."""
    N, nb, block = q.shape
    w3 = weights.reshape(N, 1, 1).astype(jnp.float32)
    d2 = jnp.reshape(denom.astype(jnp.float32), (1, 1))
    return pl.pallas_call(
        _dequant_agg_kernel,
        grid=(nb // TILE_BLOCKS,),
        in_specs=[
            pl.BlockSpec((N, TILE_BLOCKS, block), lambda j: (0, j, 0)),
            pl.BlockSpec((N, TILE_BLOCKS, 1), lambda j: (0, j, 0)),
            pl.BlockSpec((N, 1, 1), lambda j: (0, 0, 0)),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_BLOCKS, block), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        interpret=interpret,
    )(q, scales, w3, d2)
