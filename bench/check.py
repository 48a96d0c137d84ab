"""The numbers that decide ``correct``: the program's first training steps
against the plain reference's.

Three numbers, each a worst case over steps or over weight matrices
("leaves"):

* ``loss_gap``: each of the first steps' losses, relative to the
  reference's;
* ``grad_gap``: the first step's gradient, per leaf, as the gap between
  the two norms over the larger of the reference leaf's norm and the
  median leaf's norm (the program's gradient is read back from Adam's
  first moment after one step, ``mu / (1 - b1)``);
* ``change_gap``: the weights' change over the first steps, per leaf, the
  same way, and then the median over the leaves.  A leaf whose reference
  gradient is under a thousandth of the median leaf's moves under Adam by
  round-off alone and is left out.

The change is the median leaf's and not the worst: on the chip the worst
leaf's gap swings from seed to seed by more than ten times, from Adam's
second and third steps, where a gradient element near zero that rounding
moves changes its update by up to the learning rate.  The median leaf's
is steady, and the control still reads ten times it.
"""

from __future__ import annotations

import numpy as np

#: a leaf's gradient below this share of the median leaf's is round-off
FLAT_GRAD = 1e-3


def _norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in tree.items()}


def _norm_gaps(got: dict, want: dict, leaves) -> list[float]:
    g, w = _norms(got), _norms(want)
    floor = float(np.median([w[k] for k in want]))
    return [abs(g[k] - w[k]) / max(w[k], floor, 1e-30) for k in leaves]


def numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``loss`` (per step), ``grad1``, ``p0`` and
    ``pn`` as in :func:`reference.train_readings`."""
    if set(prog["grad1"]) != set(ref["grad1"]):
        raise ValueError(f"weight trees differ: {sorted(prog['grad1'])} "
                         f"against {sorted(ref['grad1'])}")
    loss_p, loss_r = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    loss_gap = float(np.max(np.abs(loss_p - loss_r) / np.abs(loss_r)))
    gn = _norms(ref["grad1"])
    median = float(np.median(list(gn.values())))
    moving = [k for k in gn if gn[k] >= FLAT_GRAD * median]
    change_p = {k: prog["pn"][k] - prog["p0"][k] for k in prog["pn"]}
    change_r = {k: ref["pn"][k] - ref["p0"][k] for k in ref["pn"]}
    return {
        "loss_gap": loss_gap,
        "grad_gap": max(_norm_gaps(prog["grad1"], ref["grad1"], list(gn))),
        "change_gap": float(np.median(_norm_gaps(change_p, change_r,
                                                 moving))),
    }


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: every number within its
    limit, and every number finite."""
    shown = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}
    ok = all(np.isfinite(v) and v <= limits[k] for k, v in nums.items())
    return ok, shown
