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
* ``update_r1_median``: the same, the median leaf. The worst leaf
  swings from seed to seed with the noise of its one leaf; the median
  leaf is steady, and moves with a lower precision on every seed
  (``PERF.md``).
* ``change_r<T>``, ``T`` the last compared round: the same as
  ``update_r1`` after round ``T``, over every model live in it; but
  after an earlier round where the two sides part on eq 3. A device
  whose eq-2 window mean accuracy is 0 takes eq-3 weight 0; one whose
  accuracy is 0 on one side and 0.01 on the other (one example of
  100, from rounding) takes weight 0 on one side and its full weight
  on the other, and the rounds whose cohort holds it average other
  pairs on the two sides. So the change is read after the last round
  ``T'`` before the first round whose cohort holds a device that one
  side weighs 0 and the other does not (``change_round``: ``T'``).
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


def _zero_weight(rounds: List, u: int, window) -> Dict[int, np.ndarray]:
    """Per model, the devices whose eq-2 window mean before round ``u``
    (over the records of rounds ``u - window`` to ``u - 1``, or every
    earlier round) is 0, so that eq 3 weighs them 0 in round ``u``."""
    lo = 1 if window is None else max(1, u - window)
    zero: Dict[int, np.ndarray] = {}
    for rec in rounds[lo - 1:u - 1]:
        for j, m in enumerate(rec.live):
            z = np.asarray(rec.accs[:, j]) == 0
            zero[m] = zero[m] & z if m in zero else z
    return zero


def agreed_rounds(prog: List, ref: List, window=None) -> int:
    """The last compared round before the first round ``u`` whose cohort
    (the reference's ``cohort``, every device where it has none) holds a
    device that eq 3 weighs 0 on one side only; all of them where there
    is none."""
    for u in range(2, len(prog) + 1):
        zp = _zero_weight(prog, u, window)
        zr = _zero_weight(ref, u, window)
        cohort = getattr(ref[u - 1], "cohort", None)
        for m in set(zp) & set(zr):
            a, b = zp[m], zr[m]
            if cohort is not None:
                a, b = a[cohort], b[cohort]
            if np.any(a != b):
                return u - 1
    return len(prog)


def compare(prog: List, ref: List, params0: dict,
            window=None) -> Dict[str, float]:
    """``prog`` and ``ref``: per compared round, objects with ``live``,
    ``accs`` (N, len(live)) and ``params`` {model: weights}; ``ref``'s
    may hold the round's ``cohort``. ``window``: the eq-2 score window
    (every earlier round where ``None``)."""
    out: Dict[str, float] = {}
    out["population"] = float(sum(
        len(set(p.live) ^ set(r.live)) for p, r in zip(prog, ref)))
    out["acc_gap"] = max(_acc_gap(p, r) for p, r in zip(prog, ref))
    g1 = leaf_gaps(prog[0].params[0], ref[0].params[0], params0)
    out["update_r1"] = float(np.nanmax(g1))
    out["update_r1_median"] = float(np.nanmedian(g1))
    last = agreed_rounds(prog, ref, window)
    p, r = prog[last - 1], ref[last - 1]
    both = sorted(set(p.params) & set(r.params))
    gl = [leaf_gaps(p.params[m], r.params[m], params0)
          for m in both] or [[float("inf")]]
    out[f"change_r{len(prog)}"] = float(max(np.nanmax(g) for g in gl))
    out["change_round"] = float(last)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number within its limit; a limit with no number fails."""
    return all(np.isfinite(numbers.get(k, float("nan")))
               and numbers[k] <= limits[k] for k in limits)
