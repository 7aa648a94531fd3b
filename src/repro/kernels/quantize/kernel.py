"""Pallas TPU kernel: blockwise symmetric int8 quantize / dequantize.

Layout: the payload arrives as one quantization block per row, an
``(n_blocks, block)`` array (``ops`` reshapes any leaf into it). A row's
scale is then a per-row max along lanes, and the scales form an
``(n_blocks, 1)`` column whose block ``(TILE_ROWS, 1)`` spans the full
last dimension — the form the TPU compiler accepts for a lane dimension
that is not a multiple of 128.

Grid: ``(n_blocks / TILE_ROWS,)``; each program owns ``TILE_ROWS`` whole
blocks, so no reduction crosses a program. A (512, 128) f32 tile is
256 KiB, well inside the v5e's scoped VMEM with the int8 output tile
and the scale column. ``TILE_ROWS`` is a multiple of 32, the sublane
tiling of int8 arrays.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_ROWS = 512


def _quantize_kernel(x_ref, q_ref, s_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)                    # (tile, block)
    s = jnp.max(jnp.abs(x), axis=1, keepdims=True) / qmax  # (tile, 1)
    s = jnp.maximum(s, 1e-12)
    q_ref[...] = jnp.clip(jnp.round(x / s), -qmax, qmax).astype(jnp.int8)
    s_ref[...] = s


def _dequantize_kernel(q_ref, s_ref, x_ref):
    x = q_ref[...].astype(jnp.float32) * s_ref[...]
    x_ref[...] = x.astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_2d(x: jax.Array, bits: int = 8,
                *, interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """x (n_blocks, block), one quantization block per row, with
    ``n_blocks % TILE_ROWS == 0`` (callers pad).

    Returns (q (n_blocks, block) int8, scales (n_blocks, 1) f32)."""
    n, block = x.shape
    qmax = float((1 << (bits - 1)) - 1)
    return pl.pallas_call(
        functools.partial(_quantize_kernel, qmax=qmax),
        grid=(n // TILE_ROWS,),
        in_specs=[pl.BlockSpec((TILE_ROWS, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((TILE_ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, block), jnp.int8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def dequantize_2d(q: jax.Array, scales: jax.Array, dtype=jnp.float32,
                  *, interpret: bool) -> jax.Array:
    """q (n_blocks, block) int8, scales (n_blocks, 1) -> (n_blocks, block)."""
    n, block = q.shape
    return pl.pallas_call(
        _dequantize_kernel,
        grid=(n // TILE_ROWS,),
        in_specs=[
            pl.BlockSpec((TILE_ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, block), dtype),
        interpret=interpret,
    )(q, scales)
