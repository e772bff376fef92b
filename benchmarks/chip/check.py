"""The comparison that decides ``correct``: the timed path's first steps
against the plain reference (``reference.py``) on the same weights and rows.

Four numbers, each against the cell's limit (``limits/<cell>.json``):

  loss    the largest relative gap between the program's and the
          reference's loss over the checked steps;
  grad    the worst tensor's gap between the norms of the first step's
          gradient as the optimizer got it (clipped to the global norm;
          worked out from the program's m and GSNR momentum after one step)
          and the reference's;
  gsnr    the same for the first step's GSNR r (normalized by its tensor
          mean and clipped to [gamma, 1]; r = p / (1 - b3) after one step).
          The only number that sees the mean of squares: r cancels out of
          ``grad``, and Adam's normalization all but hides it from the
          parameters' change;
  change  the worst tensor's gap between the norms of the parameters'
          change over the checked steps.

A tensor gap is |program norm - reference norm| over the larger of the
reference's norm of that tensor and the median tensor's, since some
gradients are all but zero.  Layer tensors count one per layer.  ``change``
leaves out tensors whose reference gradient is under a thousandth of the
median tensor's: Adam moves those by round-off alone.  A cell's limits file
names every number; one whose ``limit`` is null has no reading that a limit
could be set below (the file says why) and is not compared.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

NUMBERS = ("loss", "grad", "gsnr", "change")
NEGLIGIBLE_GRAD = 1e-3


def tensor_gap(prog: Dict[str, float], ref: Dict[str, float], keep=None) -> float:
    names = [n for n in ref if keep is None or keep(n)]
    floor = float(np.median([ref[n] for n in ref]))
    worst = 0.0
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], floor, 1e-30)
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, gap)
    return worst


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """prog / ref: {"loss": [...], "grad" / "gsnr" / "change": {name: norm}}."""
    loss = max((abs(p - r) / max(abs(r), 1e-30) if math.isfinite(p) else math.inf)
               for p, r in zip(prog["loss"], ref["loss"]))
    gmed = float(np.median(list(ref["grad"].values())))
    live = {n for n, g in ref["grad"].items() if g >= NEGLIGIBLE_GRAD * gmed}
    return {
        "loss": loss,
        "grad": tensor_gap(prog["grad"], ref["grad"]),
        "gsnr": tensor_gap(prog["gsnr"], ref["gsnr"]),
        "change": tensor_gap(prog["change"], ref["change"], keep=lambda n: n in live),
    }


def judge(values: Dict[str, float], limits: Dict) -> Tuple[bool, Dict, List[str]]:
    """(correct, {"name": {"value", "limit"}}, printable lines)."""
    checks, lines, ok = {}, [], True
    for name in NUMBERS:
        if limits[name]["limit"] is None:
            continue
        v, lim = values[name], float(limits[name]["limit"])
        passed = v <= lim  # NaN fails
        ok &= passed
        checks[name] = {"value": v, "limit": lim}
        lines.append(f"check {name} {v:.6e} limit {lim:.6e} {'ok' if passed else 'FAIL'}")
    return ok, checks, lines
