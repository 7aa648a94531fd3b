"""The plain reference of the first FedCD rounds, independent of ``src/``.

It follows FedCD's Algorithm 1 (mode A) from the same inputs as the
program: the initial weights and data that ``data.py`` makes from the
seed, and the server's sampling stream, which it draws itself from the
configuration's seed exactly as the server does (``draw_sample``). Each
round: sample K devices; every (participating, holder) pair trains its
model for E epochs of minibatch SGD on the device's split; each model is
the eq-1 average of its pairs weighted by the eq-3 scores; every live
model is scored on every device's validation split; the eq-2 windows
shift; eq-4 deletions run; at a milestone every live model is cloned,
the clone's scores seeded to ``1 - c_parent`` plus noise.

The CNN is written out in ``jax.numpy``, with float32 weights, updates
and activations, its convolutions and dense layers at the operand
precision the configuration states (``operand_precision``: "default" is
bfloat16 operands with float32 accumulation on the TPU, float32 on the
CPU). ``operands="fp8"`` gives the control, the step below: every
convolution's and dense layer's two operands rounded to float8 (e4m3,
scaled per tensor by its largest magnitude) in the forward pass, the
gradient passed straight through.
``fault`` plants one of the faults the comparison has to catch:
``unchanged`` (the round leaves the weights as they were),
``half_batch`` (every other pair of a model left out of eq 1, the mean
taken over the rest), ``altered_answer`` (the accuracy matrix computed
against the next label); from round 2 on, after a sound round 1,
``unchanged_from_r2`` (as ``unchanged``) and ``uniform_from_r2`` (eq 3
ignored: every pair of a model weighted alike).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

LIFECYCLE_STREAM = 0xFEDCD
PRECISIONS = {"default": jax.lax.Precision.DEFAULT,
              "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}
FP8_MAX = 448.0                      # largest finite float8_e4m3fn


def fp8(x):
    """``x`` rounded to float8 e4m3 under a per-tensor scale that maps
    its largest magnitude to the format's largest; straight-through
    gradient."""
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


OPERANDS = {"same": lambda x: x, "fp8": fp8}


def forward(params, x, precision, operands="same"):
    o = OPERANDS[operands]
    for i, (w, b) in enumerate(zip(params["conv"], params["conv_b"])):
        x = jax.lax.conv_general_dilated(
            o(x), o(w), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        x = jax.nn.relu(x + b)
        if i % 2 == 1:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(o(x), o(params["fc1"]), precision=precision)
                    + params["fc1_b"])
    return (jnp.dot(o(x), o(params["fc2"]), precision=precision)
            + params["fc2_b"])


def loss(params, x, y, precision, operands="same"):
    logits = forward(params, x, precision, operands).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


@functools.partial(jax.jit, static_argnames=("precision", "operands"))
def train_pair(params, xs, ys, perm, lr, precision, operands):
    """E epochs of minibatch SGD of one model on one device's split."""
    def step(p, idx):
        g = jax.grad(loss)(p, xs[idx], ys[idx], precision, operands)
        return jax.tree.map(lambda a, b: a - lr * b, p, g), None
    return jax.lax.scan(step, params, perm)[0]


@functools.partial(jax.jit, static_argnames=("precision", "operands"))
def accuracy_row(params, xs, ys, precision, operands):
    """(N,) accuracy of one model on every device's split."""
    def one(x, y):
        pred = jnp.argmax(forward(params, x, precision, operands), axis=-1)
        return jnp.mean((pred == y).astype(jnp.float32))
    return jax.lax.map(lambda a: one(*a), (xs, ys))


def draw_sample(rng: np.random.Generator, n_devices: int, per_round: int,
                n_examples: int, batch: int, epochs: int):
    """One round's cohort and minibatch schedule, consuming the
    sampling stream as the server does: a cohort without replacement,
    then one row permutation per (device, epoch), truncated to whole
    batches."""
    participating = np.zeros(n_devices, bool)
    participating[rng.choice(n_devices, per_round, replace=False)] = True
    steps = max(n_examples // batch, 1)
    flat = np.broadcast_to(np.arange(n_examples, dtype=np.int32),
                           (n_devices * epochs, n_examples))
    perms = rng.permuted(flat, axis=1).reshape(n_devices, epochs,
                                               n_examples)
    perms = perms[:, :, :steps * batch].reshape(n_devices, epochs * steps,
                                                batch)
    return participating, perms


def normalized_scores(history: np.ndarray, active: np.ndarray):
    """eq 2 (window mean, 1 where empty) then eq 3 over active models."""
    filled = ~np.isnan(history)
    count = filled.sum(axis=2)
    total = np.where(filled, history, 0.0).sum(axis=2)
    s = np.where(count > 0, total / np.maximum(count, 1), 1.0)
    s = np.where(active, s, 0.0)
    denom = s.sum(axis=1, keepdims=True)
    return np.where(denom > 0, s / np.maximum(denom, 1e-12), 0.0)


def eq4_deletions(c: np.ndarray, active: np.ndarray) -> np.ndarray:
    """Delete m where max(c) - c_m >= sigma(c) on devices with three or
    more models, keeping each device's top two."""
    mask = np.zeros_like(active)
    for i in np.nonzero(active.sum(axis=1) >= 3)[0]:
        ci = c[i, active[i]]
        cand = active[i] & ((ci.max() - c[i]) >= ci.std()) & (c[i] < ci.max())
        cand[np.argsort(-np.where(active[i], c[i], -np.inf))[:2]] = False
        mask[i] = cand
    return mask


@dataclass
class RoundRecord:
    live: List[int]
    accs: np.ndarray                              # (N, len(live))
    params: Dict[int, dict] = field(default_factory=dict)   # after eq 1
    cohort: Optional[np.ndarray] = None           # the round's devices


def run_reference(fed: dict, batch: int, data: dict, params0: dict,
                  seed: int, rounds: int, precision: str = "default",
                  operands: str = "same",
                  fault: Optional[str] = None) -> List[RoundRecord]:
    """The first ``rounds`` rounds of FedCD from ``params0``.

    ``fed``: the configuration's FedCD settings (``n_devices``,
    ``devices_per_round``, ``local_epochs``, ``lr``, ``max_models``,
    ``score_window``, ``score_noise``, ``milestones``); ``precision``:
    the configuration's ``operand_precision``. Returns, per round, the
    live models, their validation accuracy matrix and their weights
    after aggregation, as host arrays."""
    prec = PRECISIONS[precision]
    n = fed["n_devices"]
    m_cap, ell = fed["max_models"], fed["score_window"]
    xs, ys = data["train"]
    vx, vy = data["val"]
    lr = jnp.asarray(fed["lr"], jnp.float32)
    rng = np.random.default_rng(seed)
    life_rng = np.random.default_rng([seed, LIFECYCLE_STREAM])
    bank = {0: params0}
    created = 1
    history = np.full((n, m_cap, ell), np.nan)
    active = np.zeros((n, m_cap), bool)
    active[:, 0] = True
    out: List[RoundRecord] = []
    for t in range(1, rounds + 1):
        now = fault
        if fault is not None and fault.endswith("_from_r2"):
            now = fault[:-len("_from_r2")] if t >= 2 else None
        part, perms = draw_sample(rng, n, fed["devices_per_round"],
                                  xs.shape[1], batch, fed["local_epochs"])
        c = normalized_scores(history, active)
        live = sorted(bank)
        for m in live:
            devs = np.nonzero(active[:, m] & part)[0]
            if not len(devs):
                continue
            w = c[devs, m].astype(np.float32)
            if now == "half_batch":
                w[1::2] = 0.0
            if now == "uniform":
                w[:] = 1.0
            trained = [train_pair(bank[m], xs[d], ys[d], perms[d], lr,
                                  prec, operands) for d in devs]
            denom = np.float32(max(float(w.sum()), 1e-12))
            if now != "unchanged":
                bank[m] = jax.tree.map(
                    lambda *leaves: sum(
                        np.float32(wk) * leaf for wk, leaf in zip(w, leaves)
                    ) / denom, *trained)
        label_shift = 1 if now == "altered_answer" else 0
        accs = np.zeros((n, m_cap))
        for m in live:
            accs[:, m] = np.asarray(accuracy_row(
                bank[m], vx, (vy + label_shift) % 10, prec, operands))
        out.append(RoundRecord(
            live, accs[:, live],
            {m: jax.tree.map(lambda a: np.asarray(a, np.float32), bank[m])
             for m in live}, np.flatnonzero(part)))
        rolled = np.roll(history, -1, axis=2)
        rolled[:, :, -1] = accs
        history = np.where(active[:, :, None], rolled, history)
        active &= ~eq4_deletions(normalized_scores(history, active), active)
        history = np.where(active[:, :, None], history, np.nan)
        for m in live:
            if not active[:, m].any():
                del bank[m]
        if t in fed["milestones"]:
            for parent in sorted(bank):
                if created >= m_cap:
                    break
                clone, created = created, created + 1
                c = normalized_scores(history, active)
                val = 1.0 - c[:, parent]
                if fed["score_noise"]:
                    val = np.clip(val + life_rng.normal(
                        0, fed["score_noise"], val.shape), 0.0, 1.0)
                holders = active[:, parent]
                history[:, clone, :] = np.where(holders[:, None],
                                                val[:, None], np.nan)
                active[:, clone] = holders
                bank[clone] = bank[parent]
    return out
