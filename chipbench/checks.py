"""Whether the timed path trained correctly: program against reference.

Set-up drives the timed step through its first ``STEPS`` steps on rows
that all differ.  Those readings are compared with the plain reference's
on the same weights and rows:

  loss_gap_<k>     |loss_k - ref_k| / |ref_k|, for each step k
  grad_norm_gap    worst leaf of | |g| - |g_ref| | / max(|g_ref|, median
                   leaf's |g_ref|), g the first step's gradient as the
                   optimizer got it (worked out from its state)
  change_norm_gap  the same for the parameters' change after the steps
  grad_diff_gap    worst leaf of |g - g_ref| / max(|g_ref|, median leaf's
                   |g_ref|): the norms above average elementwise rounding
                   away, so a control computed in a lower precision can
                   read like the program on them; the difference sees it

Leaves whose reference gradient is under ``NOUGHT`` of the median leaf's
move by round-off alone and are left out of the leaf numbers.  Each number
has its limit in the cell's file; a limit of ``null`` prints the number
and compares nothing.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

STEPS = 3
NOUGHT = 1e-3


def counted_leaves(ref_grad_norms: List[float]) -> np.ndarray:
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= NOUGHT * np.median(g)


def worst_leaf_gap(prog: List[float], ref: List[float], keep: np.ndarray,
                   scale: List[float] = None) -> float:
    """Worst counted leaf of |prog - ref| over max(scale, median scale),
    the scale being ``ref`` unless given."""
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if p.shape != r.shape:
        return math.inf
    s = r if scale is None else np.asarray(scale, np.float64)
    floor = np.median(s[keep]) if keep.any() else 0.0
    gap = np.abs(p - r) / np.maximum(np.maximum(s, floor), 1e-30)
    gap = np.where(np.isfinite(p), gap, math.inf)
    return float(np.max(gap[keep])) if keep.any() else math.inf


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers compared, by name."""
    out = {}
    for k, (p, r) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss_gap_{k}"] = (abs(p - r) / abs(r)) if math.isfinite(p) \
            else math.inf
    keep = counted_leaves(ref["grad_norms"])
    out["grad_norm_gap"] = worst_leaf_gap(prog["grad_norms"],
                                          ref["grad_norms"], keep)
    out["change_norm_gap"] = worst_leaf_gap(prog["change_norms"],
                                            ref["change_norms"], keep)
    out["grad_diff_gap"] = worst_leaf_gap(
        ref["grad_diff_norms"], [0.0] * len(keep), keep,
        scale=ref["grad_norms"])
    return out


def judge(values: Dict[str, float],
          limits: Dict[str, float]) -> Tuple[bool, Dict[str, dict]]:
    """``correct`` and, per number, its value and limit.  A number whose
    limit is ``None`` is shown and not compared; a number with no entry
    in ``limits`` at all fails, so that a cell cannot skip a check by
    leaving it out."""
    checks, ok = {}, True
    for name, v in values.items():
        if name not in limits:
            checks[name] = {"value": v, "limit": None, "ok": False}
            ok = False
            continue
        lim = limits[name]
        passed = True if lim is None else bool(v <= lim)
        checks[name] = {"value": v, "limit": lim, "ok": passed}
        ok = ok and passed
    return ok, checks


def lines(checks: Dict[str, dict]) -> List[str]:
    return [f"check {name} value={c['value']!r} limit={c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAIL'}" for name, c in checks.items()]
