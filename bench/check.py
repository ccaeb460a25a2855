"""The numbers that decide ``correct``: how far the program's first steps
lie from the reference's, each held to its cell's limit.

* ``loss``: the largest gap, over the checked steps, between the program's
  loss (mean over workers) and the reference's, in nats.
* ``grad``: the first gradient as the optimizer got it (-V/eta after one
  step, per worker and leaf); the worst leaf's gap between the program's
  norm and the reference's, over the reference's norm of that leaf or of
  the median leaf, whichever is larger.
* ``change``: the same measure of each worker's weights' change over the
  checked steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone).
* ``center``: the same measure of the center's change.
"""
from __future__ import annotations

import math

import numpy as np

NAMES = ("loss", "grad", "change", "center")
STILL = 1e-3        # a leaf whose gradient is below this share of the
#                     median leaf's moves by round-off alone


def _worst(prog, ref, keep=None):
    p, r = np.asarray(prog, float), np.asarray(ref, float)
    floor = np.maximum(r, np.median(r))
    gap = np.abs(p - r) / floor
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    return float(np.max(gap)) if np.all(np.isfinite(p)) else math.inf


def gaps(prog: dict, ref: dict) -> dict:
    rg = np.asarray(ref["grad"], float)
    keep = rg >= STILL * np.median(rg)
    loss = max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"]))
    return {
        "loss": loss if math.isfinite(loss) else math.inf,
        "grad": _worst(prog["grad"], ref["grad"]),
        "change": _worst(prog["change"], ref["change"], keep),
        "center": _worst(prog["center_change"], ref["center_change"],
                         keep.any(axis=0)),
    }


def judge(values: dict, limits: dict, failed: int) -> tuple:
    """(correct, {name: {"value", "limit"}}) over the numbers that the
    cell's limits name, with the window's non-finite losses counted as one
    more number whose limit is 0. A number with no limit in the file is
    not compared (``PERF.md`` gives the readings that left it out)."""
    out = {k: {"value": values[k], "limit": limits[k]} for k in NAMES
           if k in limits}
    out["nonfinite_losses"] = {"value": failed, "limit": 0}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return ok, out
