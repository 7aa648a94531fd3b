"""Public jitted wrappers: arbitrary-shape pytree leaves -> kernel tiles.

Reshapes a leaf to 2D, pads its columns to ``block``, lays it out one
quantization block per row for the kernel, and crops on the way back.
The kernel body runs in interpret mode on the CPU and compiled on the
TPU (``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.quantize import kernel as K
from repro.kernels.quantize.ref import to_2d


def _pad_rows(x: jax.Array) -> jax.Array:
    return jnp.pad(x, ((0, (-x.shape[0]) % K.TILE_ROWS), (0, 0)))


def quantize(x: jax.Array, bits: int = 8, block: int = 128
             ) -> Tuple[jax.Array, jax.Array]:
    """Returns (q int8 (R, D_pad), scales f32 (R, D_pad // block)) where
    R is the collapsed leading dim — same contract as ref.quantize_ref."""
    x2, _ = to_2d(x)
    R, D = x2.shape
    d_pad = D + (-D) % block
    nb = d_pad // block
    xb = jnp.pad(x2.astype(jnp.float32), ((0, 0), (0, d_pad - D)))
    q, s = K.quantize_2d(_pad_rows(xb.reshape(R * nb, block)), bits=bits,
                         interpret=interpret_mode())
    return q[:R * nb].reshape(R, d_pad), s[:R * nb].reshape(R, nb)


def dequantize(q: jax.Array, scales: jax.Array, shape, dtype,
               block: int = 128) -> jax.Array:
    R, Dp = q.shape
    nb = Dp // block
    x = K.dequantize_2d(_pad_rows(q.reshape(R * nb, block)),
                        _pad_rows(scales.reshape(R * nb, 1)),
                        dtype=jnp.float32, interpret=interpret_mode())
    x = x[:R * nb].reshape(R, Dp)
    d_last = shape[-1] if len(shape) else 1
    x = x[:, :d_last] if len(shape) else x[0, :1]
    return x.reshape(shape).astype(dtype)
