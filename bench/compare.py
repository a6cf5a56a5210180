"""The comparison that decides ``correct``.

Every number is a gap of norms taken by the worst leaf: for each leaf, the
distance between the program's norm and the reference's, over the larger of
that leaf's reference norm and the median leaf's (some gradients are all but
zero). Gradients are read per worker.
"""

from __future__ import annotations

import numpy as np

#: leaves whose first reference gradient is under this share of the median
#: leaf's move by round-off alone and are left out of the parameter change
NOUGHT = 1e-3


def gap(prog: np.ndarray, want: np.ndarray, keep=None) -> float:
    """Worst-leaf gap of norms; arrays are (leaves,) or (leaves, workers)."""
    prog = np.asarray(prog, np.float64)
    want = np.asarray(want, np.float64)
    if keep is not None:
        prog, want = prog[keep], want[keep]
    denom = np.maximum(want, np.median(want, axis=0))
    denom = np.where(denom > 0, denom, 1.0)
    return float(np.max(np.abs(prog - want) / denom))


def numbers(prog, want) -> dict:
    """The cell's compared numbers: the program's readings (or the control's)
    against the reference's."""
    moved = want.first_grad >= NOUGHT * np.median(want.first_grad)
    rounds = range(len(want.grad))
    bits = max(abs(float(prog.bits[k]) - float(want.bits[k])) / float(want.bits[k])
               for k in rounds)
    return {
        "grad_gap": max(gap(prog.grad[k], want.grad[k]) for k in rounds),
        "estimator_gap": max(gap(prog.estimator[k], want.estimator[k])
                             for k in rounds),
        "change_gap": gap(prog.change, want.change, keep=moved),
        "bits_gap": bits,
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: [value, limit]}): each number at or under its limit;
    a number that is not finite fails."""
    out = {k: [values[k], limits[k]] for k in limits}
    ok = all(np.isfinite(v) and v <= lim for v, lim in out.values())
    return ok, out
