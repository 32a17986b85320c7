"""The comparison that decides ``correct``.

Both sides give the same readings of the first three rounds (see
``bench/reference.py``): each round's loss, the first gradient's norm
per parameter leaf, and per leaf the norm of each state variable's
change from after round 1 to after round 3.  Three numbers follow:

* ``loss_gap``   the worst round's |loss - loss_ref| / |loss_ref|;
* ``grad_gap``   the worst leaf's |norm - norm_ref| / max(norm_ref,
                 median leaf's norm_ref);
* ``change_gap`` the same over the change norms of W, z and phi, each
                 state variable against its own median leaf.

Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of both leaf numbers
(the rule is on the reference's gradient, never on a leaf's name).  A
number that is not finite fails, and so does a missing leaf.
"""
from __future__ import annotations

import math
from statistics import median
from typing import Dict, Tuple

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
GROUPS = ("W", "z", "phi")
NOUGHT = 1e-3


def _worst(prog: Dict[str, float], ref: Dict[str, float], keys
           ) -> Tuple[float, str]:
    keys = list(keys)
    med = median([ref[k] for k in keys])
    worst, at = 0.0, ""
    for k in keys:
        p = prog.get(k, float("nan"))
        gap = abs(p - ref[k]) / max(ref[k], med, 1e-30)
        if not gap <= worst:         # NaN wins, so it fails
            worst, at = gap, k
    return worst, at


def gaps(prog: Dict, ref: Dict) -> Dict:
    """The compared numbers, with where each was worst."""
    per_round = [abs(p - r) / abs(r) if r else float("inf")
                 for p, r in zip(prog["loss"], ref["loss"])]
    loss = max(per_round) if all(map(math.isfinite, per_round)) \
        and per_round and len(prog["loss"]) == len(ref["loss"]) \
        else float("nan")
    med = median(ref["grad"].values())
    moving = [k for k, v in ref["grad"].items() if v >= NOUGHT * med]
    grad, grad_at = _worst(prog["grad"], ref["grad"], moving)
    change, change_at = 0.0, ""
    for g in GROUPS:
        c, at = _worst(prog["change"], ref["change"],
                       [g + k for k in moving])
        if not c <= change:
            change, change_at = c, at
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change,
            "worst_leaf": {"grad_gap": grad_at, "change_gap": change_at},
            "left_out": sorted(set(ref["grad"]) - set(moving))}


def judge(numbers: Dict, limits: Dict) -> bool:
    return all(math.isfinite(numbers[n]) and numbers[n] <= limits[n]
               for n in NUMBERS)


def lines(numbers: Dict, limits: Dict):
    """One plain line per compared number, its reading beside its limit."""
    return [f"check {n} {numbers[n]!r} limit {limits[n]!r}" for n in NUMBERS]
