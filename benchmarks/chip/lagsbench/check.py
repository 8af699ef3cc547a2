"""The numbers that decide ``correct``, and their limits.

Three numbers compare the program's first steps with the reference's:

- ``loss_gap``: the largest relative gap of a step's loss.
- ``grad_norm_gap``: over named leaves, the gap between the program's
  and the reference's norm of the first gradient as the optimizer got it,
  over the reference's norm of that leaf or of the median leaf,
  whichever is larger.
- ``change_norm_gap``: the same for the norm of each leaf's change over
  the checked steps.  Leaves whose reference gradient is under a
  thousandth of the median leaf's move by rounding alone and are left
  out of it.

A run is correct when each number is at or under its limit
(``limits/<cell>.json``).
"""
from __future__ import annotations

import statistics

#: A leaf whose reference gradient norm is under this share of the
#: median leaf's is not counted in ``change_norm_gap``.
STILL_LEAF = 1e-3


def loss_gap(prog: list, ref: list) -> float:
    if len(prog) != len(ref):
        raise ValueError(f"{len(prog)} program losses, {len(ref)} reference")
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def leaf_gaps(prog: dict, ref: dict, names=None) -> dict:
    """Per leaf of ``names`` (default: every leaf): the gap of the two
    norms over the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = statistics.median(ref.values())
    return {n: abs(prog[n] - ref[n]) / max(ref[n], med)
            for n in (names if names is not None else ref)}


def norm_gap(prog: dict, ref: dict, names=None) -> tuple[float, str]:
    """(worst gap, its leaf) over ``names`` (default: every leaf)."""
    worst, at = -1.0, ""
    for n, gap in leaf_gaps(prog, ref, names).items():
        if not gap <= worst:            # a NaN reading is the worst
            worst, at = gap, n
    return worst, at


def moving_leaves(ref_grads: dict) -> list:
    med = statistics.median(ref_grads.values())
    return [n for n, g in ref_grads.items() if g >= STILL_LEAF * med]


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers from the program's and the reference's
    readings (``losses``, ``grad_norms``, ``change_norms``)."""
    moving = moving_leaves(ref["grad_norms"])
    g, g_at = norm_gap(prog["grad_norms"], ref["grad_norms"])
    c, c_at = norm_gap(prog["change_norms"], ref["change_norms"], moving)
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad_norm_gap": g, "change_norm_gap": c,
            "_worst_leaf": {"grad_norm_gap": g_at, "change_norm_gap": c_at}}


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); a NaN fails."""
    report = {}
    ok = True
    for name, lim in limits.items():
        v = nums[name]
        report[name] = {"value": v, "limit": lim}
        if not v <= lim:
            ok = False
    return ok, report
