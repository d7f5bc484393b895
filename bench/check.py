"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, number by number, each against its limit.

Numbers (a workload's ``limits/<workload>.json`` names the ones compared):

- ``loss_r<i>``: |program - reference| of round i's loss (the mean over
  the round's clients of their last local step's loss);
- ``loss_r0_calls``: the mean, over the checked calls (the first call
  and the window's calls after it that the mix's ``check_calls``
  counts), of |program - reference| of round 0's loss, each call from
  the initial weights with its own seed;
- ``acc_r0``: |program - reference| of the first evaluated accuracy;
- ``dw_<when>_worst`` / ``dw_<when>_median``: per leaf, the gap between
  the program's and the reference's norm of the weights' change (after
  the first call, ``call``, or after round t, ``r<t>``), over the larger
  of that leaf's reference norm and the median leaf's; the worst leaf,
  or the median of the leaves.  Leaves whose first reference gradient
  is under a thousandth of the median leaf's are left out (their change
  is round-off).
"""
from __future__ import annotations

import math

import numpy as np


def leaf_norms(tree_a, tree_b):
    import jax
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64)
                                          - np.asarray(b, np.float64)))
                     for a, b in zip(jax.tree_util.tree_leaves(tree_a),
                                     jax.tree_util.tree_leaves(tree_b))])


def counted_leaves(grad_norms: np.ndarray) -> np.ndarray:
    return grad_norms >= 1e-3 * np.median(grad_norms)


def change_gaps(w0, w_prog, w_ref, counted):
    """``(worst, median)`` leaf gap of the change's norm, and the gaps."""
    prog = leaf_norms(w_prog, w0)
    ref = leaf_norms(w_ref, w0)
    scale = np.maximum(ref, np.median(ref[counted]))
    gaps = np.abs(prog - ref) / scale
    gaps = gaps[counted]
    return float(gaps.max()), float(np.median(gaps)), gaps


def numbers(prog: dict, ref: dict, counted) -> dict:
    """Every comparison number both sides carry (see the module doc)."""
    out = {}
    for r in range(min(3, len(prog["loss"]))):
        out[f"loss_r{r}"] = abs(float(prog["loss"][r]) - float(ref["loss"][r]))
    if "calls" in prog:
        gaps = [abs(float(prog["loss"][0]) - float(ref["loss"][0]))] + [
            abs(p - r) for (_, p), r in zip(prog["calls"],
                                            ref["calls_loss0"])]
        out["loss_r0_calls"] = float(np.mean(gaps))
    if prog.get("acc"):
        r0 = min(prog["acc"])
        out["acc_r0"] = abs(prog["acc"][r0] - ref["acc"][r0])
    for t, w_prog in prog["w"].items():
        worst, median, _ = change_gaps(prog["w0"], w_prog, ref["w"][t],
                                       counted)
        out[f"dw_{t}_worst"] = worst
        out[f"dw_{t}_median"] = median
    return out


def judge(nums: dict, limits: dict, finite: bool = True):
    """``(correct, checks)``: every limited number under its limit (a
    NaN or a missing number fails), and the run's outputs finite."""
    checks, ok = {}, bool(finite) and bool(limits)
    for name, limit in limits.items():
        v = nums.get(name, math.nan)
        checks[name] = {"value": v, "limit": limit}
        if not (v <= limit):
            ok = False
    return ok, checks
