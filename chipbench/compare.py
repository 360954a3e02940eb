"""The comparison that decides ``correct``: the program's first steps
against the reference's, by these numbers.

- ``loss_gap``: the largest relative gap of a step's loss, over the
  steps compared;
- ``gnorm_gap``: the relative gap of the first step's global gradient
  norm before clipping (the program's from its guarded step's health
  bundle);
- ``grad_gap``: the first step's gradient as the optimizer took it (the
  program's, worked out from its first moment after one step), by the
  worst leaf: the gap between the two norms, over the larger of the
  reference's norm of that leaf and of the median leaf. Clipping to a
  global norm of 1 makes both sides read 1 whenever the raw norm is
  above it, so a cell compares it only where its limits file gives it a
  limit;
- ``grad_diff``: the same gradient by the worst leaf, the norm of the
  difference of the two sides over the same denominator: it sees a
  gradient that points elsewhere with the right norm;
- ``change_gap``: the gap of the norms of the parameters' change over
  the steps compared, measured as ``grad_gap`` is;
- ``change_diff``: the norm of the difference of the two changes,
  measured as ``grad_diff`` is: it sees an update that goes the wrong
  way (a reversed update reads 2, a state left unchanged 1).

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of the leaf measures: they move under Adam by
round-off alone. Each number has its limit in ``limits/<cell>.json``.
"""
from __future__ import annotations

import json
import math
import os
from typing import Callable, Dict, List, Sequence

import numpy as np

NUMBERS = ("loss_gap", "gnorm_gap", "grad_gap", "grad_diff", "change_gap",
           "change_diff")


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def _worst_leaf(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                keep: Sequence[str],
                gap: Callable[[np.ndarray, np.ndarray], float]) -> float:
    """max over ``keep`` of gap(prog leaf, ref leaf) over the larger of
    the reference's norm of that leaf and of the median leaf."""
    if not keep:
        return float("nan")
    rn = {k: _norm(ref[k]) for k in keep}
    med = float(np.median(list(rn.values())))
    worst = 0.0
    for k in keep:
        if k not in prog or not np.all(np.isfinite(prog[k])):
            return float("inf")
        worst = max(worst, gap(prog[k], ref[k]) / max(rn[k], med, 1e-30))
    return worst


def leaf_gap(prog, ref, keep) -> float:
    """The gap between the two sides' norms, by the worst leaf."""
    return _worst_leaf(prog, ref, keep, lambda p, r: abs(_norm(p) - _norm(r)))


def leaf_diff(prog, ref, keep) -> float:
    """The norm of the two sides' difference, by the worst leaf."""
    return _worst_leaf(prog, ref, keep,
                       lambda p, r: _norm(np.asarray(p, np.float64)
                                          - np.asarray(r, np.float64)))


def kept_leaves(ref_grad: Dict[str, np.ndarray]) -> List[str]:
    norms = {k: _norm(v) for k, v in ref_grad.items()}
    med = float(np.median(list(norms.values())))
    return sorted(k for k, n in norms.items() if n >= 1e-3 * med)


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog``/``ref``: {"losses": [...], "gnorm": float,
    "grad": {leaf: array}, "change": {leaf: array}}."""
    lp, lr = prog["losses"], ref["losses"]
    if len(lp) != len(lr) or not all(math.isfinite(x) for x in lp):
        loss_gap = float("inf")
    else:
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(lp, lr))
    keep = kept_leaves(ref["grad"])
    gp, gr = prog.get("gnorm", float("nan")), ref["gnorm"]
    return {"loss_gap": loss_gap,
            "gnorm_gap": abs(gp - gr) / gr if math.isfinite(gp)
            else float("inf"),
            "grad_gap": leaf_gap(prog["grad"], ref["grad"], keep),
            "grad_diff": leaf_diff(prog["grad"], ref["grad"], keep),
            "change_gap": leaf_gap(prog["change"], ref["change"], keep),
            "change_diff": leaf_diff(prog["change"], ref["change"], keep)}


def load_limits(root: str, cell: str) -> Dict[str, float]:
    with open(os.path.join(root, "chipbench", "limits", cell + ".json"),
              encoding="utf-8") as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def judge(got: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, value, limit)]): correct where every number is
    finite and within its limit."""
    rows = [(k, got[k], limits[k]) for k in NUMBERS if k in limits]
    ok = bool(rows) and all(math.isfinite(v) and v <= lim
                            for _, v, lim in rows)
    return ok, rows
