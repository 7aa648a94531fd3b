"""The comparison that decides ``correct``, on the CPU at a size a test
run can hold: the CNN's eight convolutions and two dense layers at 4-16
channels, 10 devices of 64 examples, 4 a round; the cell's own limits.

* the control, the reference with its operands in float8, put in the
  program's place, comes out not correct, where the reference itself
  reads nothing;
* a whole run of the harness (its look for a chip skipped) comes out
  not correct when the timed round is broken underneath: the bank
  returned unchanged, half of each model's pairs left out of eq 1 (the
  mean taken over the rest), the accuracy matrix scored against the
  wrong labels; and correct when nothing is broken.
"""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, data, reference, run

SEED = 3_000_000_019


def small(cell: str):
    spec = copy.deepcopy(run.load_cell(cell))
    whole = spec["config"]["batch_size"] == spec["config"]["partition"][
        "n_train"]
    spec["config"]["partition"].update(n_train=64, n_val=32, n_test=32,
                                       devices_per_archetype=1)
    if whole:                          # B=infinity stays the whole split
        spec["config"]["batch_size"] = 64
    spec["config"]["fed"].update(n_devices=10, devices_per_round=4)
    spec["config"]["model"].update(channels=[4, 4, 8, 8, 8, 8, 16, 16],
                                   dense=16)
    return spec


CELLS = ["c10x100_fedsgd"]


def _readings(spec, **in_place):
    config, traffic = spec["config"], spec["traffic"]
    _, fed = run.server_config(config, traffic, SEED)
    inputs = data.make_data(SEED, config["partition"])
    params0 = data.make_params(SEED, config["model"])
    args = (fed, config["batch_size"], inputs, params0, SEED,
            traffic["compare_rounds"])
    ref = reference.run_reference(*args)
    got = reference.run_reference(*args, **in_place)
    host0 = jax.tree.map(lambda a: np.asarray(a, np.float32), params0)
    # the reference in the program's place runs no round program
    return dict(correct.compare(got, ref, host0), programs=0.0)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    spec = small(cell)
    same = _readings(spec)
    assert correct.verdict(same, spec["limits"]), same
    control = _readings(spec, operands="fp8")
    assert not correct.verdict(control, spec["limits"]), control


# faults planted in the timed path: the executor's round program, called
# with (stacked, m_idx, d_idx, perms, w, agg_rows, live_idx, test_idx,
# xs, ys, vx, vy, tx, ty)

def _wrap_round(srv, change):
    real = srv.executor._round

    def broken(*args):
        return change(real, list(args))

    srv.executor._round = broken


def unchanged(srv):
    def change(real, args):
        kept = jax.tree.map(jnp.copy, args[0])
        _, val, test = real(*args)
        return kept, val, test
    _wrap_round(srv, change)


def half_batch(srv):
    def change(real, args):
        w = np.array(args[4])
        for row in w:
            row[np.flatnonzero(row)[1::2]] = 0.0
        args[4] = jnp.asarray(w)
        return real(*args)
    _wrap_round(srv, change)


def altered_answer(srv):
    def change(real, args):
        args[11] = (args[11] + 1) % 10
        return real(*args)
    _wrap_round(srv, change)


@pytest.mark.parametrize("fault", [unchanged, half_batch, altered_answer],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", CELLS)
def test_broken_round_makes_the_run_not_correct(cell, fault):
    out = run.run_cell(small(cell), SEED, 1.0, False, require_chip=False,
                       fault=fault, log=lambda *a: None)
    assert out["correct"] is False, out["compared"]


def test_sound_run_is_correct():
    out = run.run_cell(small("c10x100_fedsgd"), SEED, 1.0, False,
                       require_chip=False, log=lambda *a: None)
    assert out["correct"] is True, out["compared"]
    assert out["compiles_in_window"] == 0
