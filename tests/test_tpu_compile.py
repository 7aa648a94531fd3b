"""Compiles for a described TPU v5e chip, with no chip attached.

Interpret mode (tests/test_kernels.py) cannot show what the TPU compiler
refuses: block shapes off the (8, 128) tiling, loads from the wrong
memory space, or a round program that outgrows the chip's HBM. These
tests compile each Pallas kernel with ``interpret=False`` at the paper
CNN's real leaf widths, the fused round at the paper's regime (16 eval
rows, a 240-pair bucket, 333 val/test examples per device) and at
McMahan et al.'s FedSGD round (a 10-pair bucket), for one chip of a
described ``v5e:2x2`` topology.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports every
test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.federated.simulation import (make_fused_round, pair_group_width,
                                        train_bucket)
from repro.kernels.quantize import kernel as QK
from repro.kernels.weighted_agg import kernel as WK
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn

HBM_BYTES = 16 * 2**30
N_DEVICES, N_TRAIN, N_EVAL, BATCH, EPOCHS = 30, 1333, 333, 32, 2
M_CAP, B_PAIRS = 16, 240          # 15 participants x 16 models


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile can be written to the persistent cache
    # but not read back without a chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def cnn_shapes():
    return jax.eval_shape(init_cnn, jax.random.PRNGKey(0))


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _largest_leaf(cnn_shapes) -> int:
    return max(a.size for a in jax.tree.leaves(cnn_shapes))


KERNELS = {
    # name: (kernel, argument shapes from the largest leaf's size D)
    "quantize_2d": (
        lambda x: QK.quantize_2d(x, interpret=False),
        lambda D: [((D // 128, 128), jnp.float32)]),
    "dequantize_2d": (
        lambda q, s: QK.dequantize_2d(q, s, interpret=False),
        lambda D: [((D // 128, 128), jnp.int8),
                   ((D // 128, 1), jnp.float32)]),
    "weighted_agg_2d": (
        lambda u, w, d: WK.weighted_agg_2d(u, w, d, interpret=False),
        lambda D: [((15, D), jnp.float32), ((15,), jnp.float32),
                   ((), jnp.float32)]),
    "multi_weighted_agg_2d": (
        lambda u, w, d: WK.multi_weighted_agg_2d(u, w, d, interpret=False),
        lambda D: [((B_PAIRS, D), jnp.float32),
                   ((M_CAP, B_PAIRS), jnp.float32),
                   ((M_CAP,), jnp.float32)]),
    "dequant_agg_2d": (
        lambda q, s, w, d: WK.dequant_agg_2d(q, s, w, d, interpret=False),
        lambda D: [((15, D // 128, 128), jnp.int8),
                   ((15, D // 128, 1), jnp.float32),
                   ((15,), jnp.float32), ((), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, cnn_shapes):
    fn, arg_shapes = KERNELS[name]
    D = _largest_leaf(cnn_shapes)
    assert D % WK.TILE_D == 0 and D % (128 * QK.TILE_ROWS) == 0
    args = [_sds(one_chip, s, dt) for s, dt in arg_shapes(D)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _round_bytes(one_chip, cnn_shapes, rows: int, pairs: int = B_PAIRS,
                 agg_rows: int = M_CAP, devices: int = N_DEVICES,
                 n_train: int = N_TRAIN, n_eval: int = N_EVAL,
                 batch: int = BATCH, epochs: int = EPOCHS) -> int:
    """temp + argument bytes of the fused round compiled for one v5e
    with ``rows`` eval rows (val and test) and a ``pairs``-slot train
    bucket; the paper's regime unless told otherwise."""
    f32, i32 = jnp.float32, jnp.int32
    steps = epochs * (n_train // batch)
    bank = jax.tree.map(lambda a: _sds(one_chip, (M_CAP,) + a.shape,
                                       a.dtype), cnn_shapes)
    image = (32, 32, 3)
    args = (bank, _sds(one_chip, (pairs,), i32),
            _sds(one_chip, (pairs,), i32),
            _sds(one_chip, (pairs, steps, batch), i32),
            _sds(one_chip, (agg_rows, pairs), f32),
            _sds(one_chip, (agg_rows,), i32),
            _sds(one_chip, (rows,), i32), _sds(one_chip, (rows,), i32),
            _sds(one_chip, (devices, n_train) + image, f32),
            _sds(one_chip, (devices, n_train), i32),
            _sds(one_chip, (devices, n_eval) + image, f32),
            _sds(one_chip, (devices, n_eval), i32),
            _sds(one_chip, (devices, n_eval) + image, f32),
            _sds(one_chip, (devices, n_eval), i32))
    step = make_fused_round(cnn_loss, cnn_accuracy, 0.08)
    mem = step.lower(*args).compile().memory_analysis()
    return mem.temp_size_in_bytes + mem.argument_size_in_bytes


def test_fused_round_fits_one_v5e_bounded_by_shape(one_chip, cnn_shapes):
    """The paper-regime round fits one chip with margin, and its peak
    does not grow with the number of eval rows."""
    full = _round_bytes(one_chip, cnn_shapes, rows=M_CAP)
    one = _round_bytes(one_chip, cnn_shapes, rows=1)
    assert full < 0.75 * HBM_BYTES, full
    assert abs(full - one) <= 0.10 * one, (full, one)


def test_fedsgd_round_at_its_ten_slot_bucket_fits_the_sixteen_slot_peak(
        one_chip, cnn_shapes):
    """McMahan et al.'s CIFAR-10 FedSGD round (100 devices of 500
    examples, 10 pairs of one 500-example step, one model, one val and
    one test row over 100 examples a device) compiles at its train
    bucket of 10 slots, two groups of 5, and needs no more memory than
    at the 16 slots the floor of 8 gave it."""
    cell = dict(rows=1, agg_rows=1, devices=100, n_train=500, n_eval=100,
                batch=500, epochs=1)
    assert train_bucket(10) == 10 and pair_group_width(10) == 5
    ten = _round_bytes(one_chip, cnn_shapes, pairs=10, **cell)
    sixteen = _round_bytes(one_chip, cnn_shapes, pairs=16, **cell)
    assert ten <= sixteen, (ten, sixteen)
