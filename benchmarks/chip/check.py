"""The comparison that decides ``correct``.

Both sides give the same readings of the checked steps (see
``harness.readings``): each step's loss, the per-leaf sums of squares of
the first non-zero direction the optimizer got (``grad_sq``), and of the
parameters' change over the checked steps (``change_sq``).  Three numbers
are compared, each against the cell's limit in ``limits/<workload>.json``:

- ``loss_gap``: the largest ``|L - L_ref| / |L_ref|`` over the steps;
- ``grad_gap``: over the leaves, the largest gap between the program's and
  the reference's norm of the direction, over the larger of the reference
  leaf's norm and the median leaf's;
- ``change_gap``: the same for the parameters' change.

Leaves whose reference gradient is under ``NOUGHT`` of the median leaf's
(a key bias under softmax, which only round-off moves) are left out of
both norm gaps, by that rule and not by name.
"""

from __future__ import annotations

import math

import numpy as np

NOUGHT = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "change_gap")


def _leaf_gap(prog_sq, ref_sq, kept) -> float:
    a = np.sqrt(np.asarray(prog_sq, np.float64))
    b = np.sqrt(np.asarray(ref_sq, np.float64))
    m = float(np.median(b))
    den = np.maximum(b, m)
    g = np.abs(a - b)[kept] / den[kept]
    return float(np.max(g)) if g.size else math.inf


def gaps(prog: dict, ref: dict) -> dict:
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if lp.shape != lr.shape:
        loss_gap = math.inf
    else:
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    gref = np.sqrt(np.asarray(ref["grad_sq"], np.float64))
    kept = gref >= NOUGHT * np.median(gref)
    out = {"loss_gap": loss_gap,
           "grad_gap": _leaf_gap(prog["grad_sq"], ref["grad_sq"], kept),
           "change_gap": _leaf_gap(prog["change_sq"], ref["change_sq"],
                                   kept)}
    # a non-finite reading is a failed comparison, not a pass
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def decide(g: dict, limits: dict) -> tuple:
    """``(correct, {name: {"value", "limit"}})``."""
    rows = {k: {"value": g[k], "limit": float(limits[k])} for k in NUMBERS}
    ok = all(r["value"] <= r["limit"] for r in rows.values())
    return ok, rows


def excluded(ref: dict) -> int:
    gref = np.sqrt(np.asarray(ref["grad_sq"], np.float64))
    return int(np.sum(gref < NOUGHT * np.median(gref)))
