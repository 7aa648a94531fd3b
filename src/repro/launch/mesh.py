"""Production meshes (brief: 16x16 single pod, 2x16x16 multi-pod).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (device count is locked at first jax init —
dryrun.py sets XLA_FLAGS before any import).
"""
from __future__ import annotations

from typing import Tuple

import jax


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]
               ) -> jax.sharding.Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (cpu) devices exist — for tests."""
    return _make_mesh((data, model), ("data", "model"))


def make_launch_mesh(model: int = 1, data: int = 1) -> jax.sharding.Mesh:
    """The federated engines' 2-D ``(model × data)`` launch mesh
    (DESIGN.md §9/§11). The stacked parameter bank's leading
    ``max_models`` row axis lays out over ``model``; the device data
    bank's leading row axis lays out over ``data``; the gathered
    work-pair axis buckets over BOTH (one block per mesh cell,
    model-major). ``model * data`` must not exceed
    ``jax.device_count()`` (use ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` for simulated CPU devices)."""
    return _make_mesh((model, data), ("model", "data"))


def make_model_mesh(n_shards: int) -> jax.sharding.Mesh:
    """1-D model sharding: ``make_launch_mesh`` with a singleton data
    axis — the PR 3 sharded engine's launch mesh (DESIGN.md §9), kept
    as the 1-data-shard equivalence oracle for the 2-D data plane."""
    return make_launch_mesh(model=n_shards, data=1)


def model_axis_size(mesh: jax.sharding.Mesh) -> int:
    return mesh.shape.get("model", 1)


def data_axis_size(mesh: jax.sharding.Mesh) -> int:
    return mesh.shape.get("data", 1)


def dp_axes_of(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for a in dp_axes_of(mesh):
        n *= mesh.shape[a]
    return n
