"""The comparison that decides ``correct``.

The first rounds that the benchmarked server runs (through the same
``run_round`` and, as ``run.py`` checks, the same compiled round
program that the window drives) against the plain reference run from
the same seed. The benchmarked traffic keeps the local work of a round
to one gradient step a pair, so the program and the reference stay
close through the compared rounds; over tens of local steps, local SGD
on this CNN is chaotic and the two part by as much as a change of
precision moves them (``PERF.md``).

* ``population``: models in one live set of a compared round and not
  in the other, summed over the rounds.
* ``programs`` (set by ``run.py``): compared rounds whose round program
  (its argument shapes) the window did not run.
* ``update_r1``: model 0's change after round 1, the first aggregated
  update, per leaf: the gap between the program's and the reference's
  norms of the leaf's change from the initial weights, against the
  larger of the reference's norm of that leaf and of its median leaf;
  the worst leaf.
* ``change_r<T>``: the same after the last compared round, over every
  model live in it.
* ``acc_gap``: the largest gap between an entry of the program's
  validation accuracy matrix (a live model on a device's split) and the
  reference's, over the compared rounds.

Leaves whose reference change is under a thousandth of the median
leaf's are left out of the gaps.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

FLOOR = 1e-3


def _leaf_norms(tree: dict, base: dict) -> List[float]:
    import jax
    return [float(np.linalg.norm(np.asarray(a, np.float64)
                                 - np.asarray(b, np.float64)))
            for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(base))]


def leaf_gaps(prog: dict, ref: dict, base: dict) -> List[float]:
    """Per leaf: |n_prog - n_ref| / max(n_ref, median n_ref), where n is
    the norm of the leaf's change from ``base``; NaN for a leaf whose
    reference change is under a thousandth of the median's."""
    n_p, n_r = _leaf_norms(prog, base), _leaf_norms(ref, base)
    med = float(np.median(n_r))
    return [float("nan") if b < FLOOR * med else abs(a - b) / max(b, med)
            for a, b in zip(n_p, n_r)]


def _acc_gap(p, r) -> float:
    both = sorted(set(p.live) & set(r.live))
    if not both:
        return float("inf")
    cols = [(p.live.index(m), r.live.index(m)) for m in both]
    return float(max(np.max(np.abs(p.accs[:, i] - r.accs[:, j]))
                     for i, j in cols))


def compare(prog: List, ref: List, params0: dict) -> Dict[str, float]:
    """``prog`` and ``ref``: per compared round, objects with ``live``,
    ``accs`` (N, len(live)) and ``params`` {model: weights}."""
    out: Dict[str, float] = {}
    out["population"] = float(sum(
        len(set(p.live) ^ set(r.live)) for p, r in zip(prog, ref)))
    out["acc_gap"] = max(_acc_gap(p, r) for p, r in zip(prog, ref))
    g1 = leaf_gaps(prog[0].params[0], ref[0].params[0], params0)
    out["update_r1"] = float(np.nanmax(g1))
    out["update_r1_median"] = float(np.nanmedian(g1))
    last = len(prog)
    both = sorted(set(prog[-1].params) & set(ref[-1].params))
    gl = [leaf_gaps(prog[-1].params[m], ref[-1].params[m], params0)
          for m in both] or [[float("inf")]]
    out[f"change_r{last}"] = float(max(np.nanmax(g) for g in gl))
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number within its limit; a limit with no number fails."""
    return all(np.isfinite(numbers.get(k, float("nan")))
               and numbers[k] <= limits[k] for k in limits)
