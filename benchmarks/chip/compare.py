"""The numbers that decide ``correct``: the program's first steps against the
plain reference's, each number beside its limit.

* ``loss_gap``: the relative gap of the first step's loss (the forward pass
  on the seeded weights).
* ``grad_gap``: over leaves, the largest gap between the norms of the first
  synced, clipped gradient as the optimizer holds it (its first moment
  after one step over ``1 - b1``), each measured against the reference's
  norm of that leaf or of the median leaf, whichever is larger.
* ``update_gap``: the largest such gap, over leaves, of the parameters'
  change after all the checked steps, over the leaves whose first
  reference gradient is at least a thousandth of the median leaf's (a leaf
  the loss hardly depends on moves under AdamW by round-off alone).

A cell's ``limits/<cell>.json`` gives a limit for every number, and under
``median_leaf`` the numbers it reads at the median leaf instead of the
largest, where the largest swings from seed to seed for a cause that lies
in the number itself (PERF.md section 4 gives the cause for each cell that
does so).
"""
from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_gap", "update_gap")
KEEP_SHARE = 1e-3


def _leaf_gaps(prog: dict, ref: dict, names) -> dict:
    names = list(names)
    med = statistics.median(ref[k] for k in names)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in names}


def _over_leaves(gaps: dict, median: bool) -> tuple:
    if median:
        return statistics.median(gaps.values()), "median leaf"
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def numbers(prog: dict, ref: dict, median_leaf=()) -> dict:
    """{number: (value, what it was read on)}; NaN where a reading is not
    finite, which fails every limit."""
    a, b = prog["losses"][0], ref["losses"][0]
    out = {"loss_gap": (abs(a - b) / abs(b), "step 1")}
    grad = _leaf_gaps(prog["grad"], ref["grad"], ref["grad"])
    out["grad_gap"] = _over_leaves(grad, "grad_gap" in median_leaf)
    med = statistics.median(ref["raw_grad"].values())
    kept = [k for k, g in ref["raw_grad"].items() if g >= KEEP_SHARE * med]
    upd = _leaf_gaps(prog["update"], ref["update"], kept)
    out["update_gap"] = _over_leaves(upd, "update_gap" in median_leaf)
    return {k: (v if math.isfinite(v) else math.nan, w)
            for k, (v, w) in out.items()}


def judge(nums: dict, limits: dict) -> tuple:
    """(correct, {number: {"value", "limit"}}); a number is within its limit
    when it is finite and no larger. ``limits`` has to name every number."""
    missing = [k for k in NUMBERS if k not in limits]
    if missing:
        raise KeyError(f"the limits name no {', '.join(missing)}")
    checks, ok = {}, True
    for k in NUMBERS:
        v = nums[k][0]
        checks[k] = {"value": v, "limit": limits[k]}
        ok = ok and math.isfinite(v) and v <= limits[k]
    return ok, checks
