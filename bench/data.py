"""Inputs and initial weights, made on the device from ``--seed``.

The data follow the hierarchical partition of FedCD §3.2 over the
synthetic CIFAR-10-shaped classes of the program's
``data/synthetic.py`` and ``data/partition.py:hierarchical_devices``:
two meta-archetypes (labels 0-4 and 5-9) of five archetypes each; a
device of archetype ``a`` with bias ``b ~ U(0.6, 0.7)`` draws label ``a``
with probability ``b`` and each other label of its meta-archetype with
``(1 - b) / 4``; an image is its class template times a gain
``U(0.7, 1.3)`` plus Gaussian noise of std 2. The class templates are
the program's (fixed smooth patterns, seeded by constants). The
distribution is the program's; the draws are made with ``jax.random``
in one jitted call, so making a few hundred thousand images costs a
fraction of a second instead of the host's ten.

The initial weights are the paper CNN's (``models/cnn.py``): Glorot-
uniform kernels and zero biases, also in one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

N_CLASSES = 10
IMAGE = 32


def root_key(seed: int) -> jax.Array:
    """A key for any whole ``seed`` up to 64 bits."""
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _class_template(label: int) -> np.ndarray:
    base_rng = np.random.default_rng(999)
    r = np.random.default_rng(1234 + label)
    yy, xx = np.meshgrid(np.linspace(0, 1, IMAGE), np.linspace(0, 1, IMAGE),
                         indexing="ij")

    def field(rr, n, lo, hi):
        img = np.zeros((IMAGE, IMAGE, 3), np.float32)
        for c in range(3):
            for _ in range(n):
                fy, fx = rr.uniform(lo, hi, 2)
                ph = rr.uniform(0, 2 * np.pi)
                amp = rr.uniform(0.4, 1.0)
                img[..., c] += amp * np.sin(2 * np.pi * (fy * yy + fx * xx)
                                            + ph)
        return img

    img = 0.75 * field(base_rng, 3, 1, 4) + 0.45 * field(r, 3, 2, 8)
    return img / np.abs(img).max()


def class_templates() -> np.ndarray:
    return np.stack([_class_template(k) for k in range(N_CLASSES)])


def hierarchical_probs(archetype: jax.Array, bias: jax.Array) -> jax.Array:
    """(N,) archetypes and biases -> (N, 10) label distributions."""
    labels = jnp.arange(N_CLASSES)
    same_meta = (labels[None, :] // 5) == (archetype[:, None] // 5)
    p = jnp.where(same_meta, ((1.0 - bias) / 4.0)[:, None], 0.0)
    p = jnp.where(labels[None, :] == archetype[:, None], bias[:, None], p)
    return p / p.sum(axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n_devices", "sizes", "noise",
                                             "bias_range"))
def _make_splits(key, templates, n_devices, sizes, noise, bias_range):
    per_arch = n_devices // N_CLASSES
    archetype = jnp.repeat(jnp.arange(N_CLASSES), per_arch)
    k_bias, k_split = jax.random.split(key)
    bias = jax.random.uniform(k_bias, (n_devices,), jnp.float32,
                              bias_range[0], bias_range[1])
    logp = jnp.log(hierarchical_probs(archetype, bias))
    out = []
    for k, n in zip(jax.random.split(k_split, len(sizes)), sizes):
        k_lab, k_gain, k_noise = jax.random.split(k, 3)
        labels = jax.random.categorical(k_lab, logp[:, None, :],
                                        shape=(n_devices, n))
        gain = jax.random.uniform(k_gain, (n_devices, n, 1, 1, 1),
                                  jnp.float32, 0.7, 1.3)
        jitter = noise * jax.random.normal(
            k_noise, (n_devices, n, IMAGE, IMAGE, 3), jnp.float32)
        out.append((templates[labels] * gain + jitter,
                    labels.astype(jnp.int32)))
    return tuple(out)


def make_data(seed: int, partition: dict) -> dict:
    """{"train" | "val" | "test": (xs (N, n, 32, 32, 3), ys (N, n))} as
    device arrays, drawn from ``seed``."""
    if partition["kind"] != "hierarchical":
        raise ValueError(f"unknown partition {partition['kind']!r}")
    n_devices = partition["devices_per_archetype"] * N_CLASSES
    sizes = (partition["n_train"], partition["n_val"], partition["n_test"])
    splits = _make_splits(jax.random.fold_in(root_key(seed), 1),
                          jnp.asarray(class_templates()), n_devices, sizes,
                          float(partition["noise"]),
                          tuple(partition["bias_range"]))
    return dict(zip(("train", "val", "test"), splits))


def _glorot(key, shape, fan_in, fan_out):
    lim = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


@functools.partial(jax.jit, static_argnames=("channels", "dense",
                                             "n_classes", "image", "in_ch"))
def _init_cnn(key, channels, dense, n_classes, image, in_ch):
    ks = jax.random.split(key, len(channels) + 2)
    p = {"conv": [], "conv_b": []}
    c_in = in_ch
    for i, c_out in enumerate(channels):
        p["conv"].append(_glorot(ks[i], (3, 3, c_in, c_out), 9 * c_in,
                                 9 * c_out))
        p["conv_b"].append(jnp.zeros((c_out,), jnp.float32))
        c_in = c_out
    flat = (image // 2 ** (len(channels) // 2)) ** 2 * channels[-1]
    p["fc1"] = _glorot(ks[-2], (flat, dense), flat, dense)
    p["fc1_b"] = jnp.zeros((dense,), jnp.float32)
    p["fc2"] = _glorot(ks[-1], (dense, n_classes), dense, n_classes)
    p["fc2_b"] = jnp.zeros((n_classes,), jnp.float32)
    return p


def make_params(seed: int, model: dict) -> dict:
    """The CNN's initial weights (float32) at the configuration's
    widths, drawn from ``seed``."""
    return _init_cnn(jax.random.fold_in(root_key(seed), 2),
                     tuple(model["channels"]), model["dense"],
                     model["n_classes"], model["image"], model["in_channels"])
