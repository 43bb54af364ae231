"""The comparison that decides ``correct`` for a training cell.

Three numbers, each the gap between the program's reading and the plain
reference's over the first steps of the very object the window then drives:

* ``loss_gap``: the largest relative gap of a step's loss, over the steps
  the cell's limits file names (all checked steps where it names none);
* ``grad_gap``: the worst leaf's gap between the norms of the first
  gradient, as AdamW received it, over the reference's norm of that leaf or
  of the median leaf, whichever is larger;
* ``change_gap``: the same for the norms of the parameters' change over the
  checked steps, among the leaves whose reference gradient is at least a
  thousandth of the median leaf's (a key's bias has no gradient under
  softmax; AdamW moves it by round-off alone).

A cell that runs every stage of a deployment has the three numbers of each
stage, named ``s<k>.loss_gap`` and so on.  Each cell's limits are in
``bench/limits/<cell>.json``, with the readings they were set from.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, Optional, Tuple

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
MOVED = 1e-3   # a leaf counts for the change when its gradient is above this
               # share of the median leaf's


def _median(values) -> float:
    return float(np.median(np.asarray(list(values), np.float64)))


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
               names) -> Tuple[float, str]:
    names = sorted(names)
    med = _median(ref[n] for n in names)
    worst, where = 0.0, ""
    for n in names:
        p = prog.get(n, math.nan)
        gap = abs(p - ref[n]) / max(ref[n], med)
        if not gap <= worst:          # NaN counts as the worst
            worst, where = gap, n
            if math.isnan(gap):
                break
    return worst, where


def numbers(prog: dict, ref: dict, loss_steps=None
            ) -> Dict[str, Tuple[float, str]]:
    """{number: (value, where)} for the program's and the reference's raw
    readings (each: ``losses``, ``grad`` and ``change`` per leaf).  A leaf
    that one side lacks makes its number NaN.  ``loss_steps``: the 1-based
    steps whose losses count (all when None)."""
    out = {}
    gaps = {i + 1: abs(p - r) / abs(r)
            for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]))}
    if len(prog["losses"]) != len(ref["losses"]):
        gaps[0] = math.nan
    counted = {i: g for i, g in gaps.items()
               if loss_steps is None or i in loss_steps or i == 0}
    if not counted:
        counted = {0: math.nan}
    i = max(counted, key=lambda j: (math.isnan(counted[j]), counted[j]))
    out["loss_gap"] = (counted[i], f"step {i}")
    for key, name in (("grad", "grad_gap"), ("change", "change_gap")):
        if set(prog[key]) != set(ref[key]):
            out[name] = (math.nan, "leaves differ")
    if "grad_gap" not in out:
        out["grad_gap"] = worst_leaf(prog["grad"], ref["grad"], ref["grad"])
    if "change_gap" not in out:
        out["change_gap"] = worst_leaf(prog["change"], ref["change"],
                                       moved_leaves(ref))
    return out


def moved_leaves(ref: dict):
    """The leaves whose reference gradient is at least ``MOVED`` of the
    median leaf's: those whose change is compared."""
    med = _median(ref["grad"].values())
    return [n for n, g in ref["grad"].items() if g >= MOVED * med]


def load_limits(path: str, prefixes=("",)
                ) -> Tuple[Dict[str, Optional[float]], Dict[str, Optional[list]]]:
    """({number: limit}, {prefix: the steps whose losses count or None})
    for the numbers of each stage prefix ("" for a one-stage cell, ``s<k>.``
    for stage k of a cell that runs every stage); a number given no limit
    is reported, not judged."""
    if not os.path.exists(path):
        raise FileNotFoundError(f"no limits file {path}")
    with open(path) as f:
        raw = json.load(f)
    return ({p + n: raw[p + n].get("limit") for p in prefixes
             for n in NUMBERS if p + n in raw},
            {p: raw.get(p + "loss_gap", {}).get("steps") for p in prefixes})


def judge(nums: Dict[str, Tuple[float, str]],
          limits: Dict[str, Optional[float]]) -> Tuple[bool, Dict[str, dict]]:
    """(every judged number within its limit, {number: value and limit})."""
    ok, report = True, {}
    for n, (value, where) in nums.items():
        lim = limits.get(n)
        report[n] = {"value": value, "limit": lim, "at": where}
        if lim is not None:
            ok = ok and value <= lim       # False for NaN
    return ok, report
