"""The numbers that decide ``correct``: the program's first training
steps against the plain reference's.

* ``loss_gap``: the largest relative gap of a checked step's loss.
* ``grad_gap``: the first gradient as the optimizer gets it (clipped),
  by the worst leaf: the gap between the program's norm of the leaf and
  the reference's, over the larger of the reference's norm of that leaf
  and the median leaf's.
* ``change_gap``: each leaf's change over the checked steps, measured
  the same way. Leaves whose reference gradient is under a thousandth of
  the median leaf's move by round-off alone (a key bias under softmax)
  and are left out.

A leaf of a stacked layer parameter is one layer's slice (``L3/ffn/w_up``).
"""
from __future__ import annotations

import math

import numpy as np

from bench.reference import AdamW

OPT = AdamW()            # lr 1e-3, clip 1.0: the launcher's defaults
NOUGHT = 1e-3            # of the median leaf's gradient norm


def _worst(prog: dict, ref: dict, leaves) -> tuple:
    leaves = list(leaves)
    if set(prog) != set(ref):
        missing = sorted(set(prog) ^ set(ref))[:3]
        return math.inf, f"leaves differ: {missing}"
    med = float(np.median([ref[k] for k in leaves]))
    worst, at = 0.0, ""
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, at = gap, k
    return worst, at


def readings(program: dict, reference: dict) -> dict:
    """{number: (value, where)}"""
    lp, lr = program["losses"], reference["losses"]
    loss = math.inf
    if lp and len(lp) == len(lr) and all(map(math.isfinite, lp)):
        loss = max(abs(a - b) / abs(b) for a, b in zip(lp, lr, strict=True))
    g = reference["grad"]
    med = float(np.median(list(g.values())))
    moved = [k for k, v in g.items() if v >= NOUGHT * med]
    return {"loss_gap": (loss, ""),
            "grad_gap": _worst(program["grad"], g, g),
            "change_gap": _worst(program["change"], reference["change"], moved)}


def compare(program: dict, reference: dict, limits: dict) -> dict:
    out = {}
    for name, (value, where) in readings(program, reference).items():
        if name in limits:
            out[name] = {"value": value, "limit": limits[name]}
            if where:
                out[name]["leaf"] = where
    return out
