"""The comparison that decides ``correct``.

The program's readings of its first steps (each step's loss, the first
step's gradient per leaf as the optimizer got it, each leaf's change over
the steps) against the reference's readings of the same steps from the same
weights and batches.  Three numbers, each held to its limit from
``bench/limits/<cell>.json``:

``loss_gap``    the largest relative gap of a step's loss.
``grad_gap``    over leaves, the largest gap between the program's and the
                reference's gradient norm, over the larger of the
                reference's norm of that leaf and of the median leaf.
``change_gap``  the same for the norm of each leaf's change over the steps,
                leaving out the leaves whose reference gradient is under a
                thousandth of the median leaf's (Adam moves those by
                round-off alone).
"""
from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE = 1e-3


def _worst(prog: dict, ref: dict, names) -> tuple[float, str]:
    med = float(np.median([ref[n] for n in names]))
    worst, where = 0.0, ""
    for n in names:
        if n not in prog or not math.isfinite(prog[n]):
            return math.inf, n
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if gap > worst:
            worst, where = gap, n
    return worst, where


def gaps(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref``: {"losses": [...], "grad": {leaf: norm},
    "change": {leaf: norm}}.  Returns {number: (value, where)}."""
    loss, at = 0.0, ""
    if len(prog["losses"]) != len(ref["losses"]):
        loss, at = math.inf, "step count"
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), start=1):
        g = abs(a - b) / abs(b) if math.isfinite(a) else math.inf
        if g > loss:
            loss, at = g, f"step {i}"
    names = sorted(ref["grad"])
    grad = _worst(prog["grad"], ref["grad"], names)
    med = float(np.median([ref["grad"][n] for n in names]))
    moved = [n for n in names if ref["grad"][n] >= NEGLIGIBLE * med]
    change = _worst(prog["change"], ref["change"], moved)
    return {"loss_gap": (loss, at), "grad_gap": grad, "change_gap": change}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value": v, "limit": l}}) for each limit."""
    out = {}
    for name in limits:
        v = numbers[name][0]
        out[name] = {"value": v if math.isfinite(v) else None,
                     "limit": limits[name]}
    ok = all(o["value"] is not None and o["value"] <= o["limit"]
             for o in out.values())
    return ok, out
