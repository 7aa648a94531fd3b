"""The paper's 10-layer CNN for CIFAR-shaped inputs (section 3.1).

8 conv layers (3x3, channels 32-32-64-64-128-128-256-256, maxpool every
2) + 2 dense layers — ten weight layers total, matching the reference
implementation's scale. Pure ``jax.lax.conv_general_dilated``.

Weights start from the reference implementation's framework defaults
(Keras: Glorot-uniform kernels, zero biases). A He-normal start made
plain SGD at the paper config's lr 0.08 diverge to NaN within 16 steps
on the synthetic CIFAR-shaped inputs (std ~2).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import Params

CHANNELS = (32, 32, 64, 64, 128, 128, 256, 256)
DENSE = 256


def glorot_uniform(key: jax.Array, shape, fan_in: int, fan_out: int,
                   dtype) -> jax.Array:
    lim = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim
                              ).astype(dtype)


def init_cnn(key: jax.Array, n_classes: int = 10, in_ch: int = 3,
             image: int = 32, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, len(CHANNELS) + 2)
    p: Params = {"conv": [], "conv_b": []}
    c_in = in_ch
    for i, c_out in enumerate(CHANNELS):
        p["conv"].append(glorot_uniform(ks[i], (3, 3, c_in, c_out),
                                        9 * c_in, 9 * c_out, dtype))
        p["conv_b"].append(jnp.zeros((c_out,), dtype))
        c_in = c_out
    spatial = image // (2 ** (len(CHANNELS) // 2))
    flat = spatial * spatial * CHANNELS[-1]
    p["fc1"] = glorot_uniform(ks[-2], (flat, DENSE), flat, DENSE, dtype)
    p["fc1_b"] = jnp.zeros((DENSE,), dtype)
    p["fc2"] = glorot_uniform(ks[-1], (DENSE, n_classes), DENSE, n_classes,
                              dtype)
    p["fc2_b"] = jnp.zeros((n_classes,), dtype)
    return p


def apply_cnn(params: Params, x: jax.Array) -> jax.Array:
    """x (B, H, W, C) -> logits (B, n_classes)."""
    for i in range(len(CHANNELS)):
        x = jax.lax.conv_general_dilated(
            x, params["conv"][i], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + params["conv_b"][i])
        if i % 2 == 1:
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"] + params["fc1_b"])
    return x @ params["fc2"] + params["fc2_b"]


def cnn_loss(params: Params, batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
    images, labels = batch
    logits = apply_cnn(params, images)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def cnn_accuracy(params: Params, images: jax.Array,
                 labels: jax.Array) -> jax.Array:
    return jnp.mean(jnp.argmax(apply_cnn(params, images), -1) == labels)
