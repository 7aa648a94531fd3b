"""What the comparison holds after round 1, on the CPU at the size of
``test_correct.py``: a timed round broken from round 2 on (the bank
returned unchanged, or eq 3 ignored: every pair of a model weighted
alike) makes a whole run not correct; and ``change_r<T>`` is read after the
last round before the two sides part on eq 3, where one side weighs a
device of the cohort 0 and the other does not. Run with
``python -m pytest -q bench/tests``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, reference, run
from bench.tests.test_correct import SEED, small


def _from_round_2(change):
    """A fault that leaves the first call of the round program sound and
    breaks every later one."""
    def plant(srv):
        real, calls = srv.executor._round, []

        def broken(*args):
            calls.append(None)
            if len(calls) == 1:
                return real(*args)
            return change(real, list(args))

        srv.executor._round = broken
    plant.__name__ = change.__name__ + "_from_r2"
    return plant


def unchanged(real, args):
    kept = jax.tree.map(jnp.copy, args[0])
    _, val, test = real(*args)
    return kept, val, test


def uniform(real, args):
    """eq 3 ignored: every real pair of a model weighted alike (padding
    pairs have all-zero perms and keep weight 0)."""
    m_idx, perms, rows = (np.asarray(args[i]) for i in (1, 3, 5))
    real_pair = perms.reshape(len(perms), -1).any(axis=1)
    args[4] = jnp.asarray((m_idx[None, :] == rows[:, None])
                          & real_pair[None, :], jnp.float32)
    return real(*args)


@pytest.mark.parametrize("fault", [_from_round_2(unchanged),
                                   _from_round_2(uniform)],
                         ids=lambda f: f.__name__)
def test_round_broken_from_round_2_makes_the_run_not_correct(fault):
    out = run.run_cell(small("c10x100_fedsgd"), SEED, 1.0, False,
                       require_chip=False, fault=fault, log=lambda *a: None)
    assert out["compared"]["update_r1"]["value"] < 1e-3, out["compared"]
    assert out["correct"] is False, out["compared"]


def _rounds(accs_r1, steps, cohorts):
    """Three rounds of one model on three devices: round 1's accuracies
    ``accs_r1``, 0.5 everywhere after; the weights one leaf, moved by
    ``steps[t]`` in round ``t``."""
    out, w = [], np.zeros(4, np.float32)
    for t, cohort in enumerate(cohorts):
        w = w + np.float32(steps[t])
        accs = np.asarray(accs_r1 if t == 0 else [0.5] * 3)[:, None]
        out.append(reference.RoundRecord([0], accs, {0: {"w": w.copy()}},
                                         np.asarray(cohort)))
    return out


@pytest.mark.parametrize("cohorts, read_at", [
    ([[0, 1], [1, 2], [0, 2]], 1),     # device 1 parts the sides in round 2
    ([[0, 1], [0, 2], [0, 2]], 3),     # and sits out of rounds 2 and 3
])
def test_change_is_read_before_the_sides_part_on_eq_3(cohorts, read_at):
    base = {"w": np.zeros(4, np.float32)}
    prog = _rounds([0.3, 0.0, 0.2], [1.0, 1.0, 1.0], cohorts)
    ref = _rounds([0.3, 0.01, 0.2], [1.0, 2.0, 2.0], cohorts)
    assert correct.agreed_rounds(prog, ref, window=3) == read_at
    out = correct.compare(prog, ref, base, window=3)
    assert out["change_round"] == read_at
    # after round 1 the sides agree; after round 3 they are 3 against 5
    assert out["change_r3"] == pytest.approx(0.0 if read_at == 1 else 0.4)
