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

A configuration adds numbers of its own through its reference module
(``configs/<reference>.py``): an optional ``numbers(prog, ref)`` returns
more of them, by name, from the same readings; ``prog["history"]``
holds the program's ``Session.run`` history entry of each checked step,
``ref["history"]`` the reference's (the step's loss, and under
``blocks`` what its ``block`` returned per layer and worker beyond the
layer's output and load-balance term).  Where the reference stands in
the program's place (``control.py``), ``prog`` is a reference's
readings.

A run is correct when each number its limits name (``limits/<cell>.json``)
was produced and is at or under its limit.
"""
from __future__ import annotations

import statistics

from lagsbench import reference

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


def numbers(prog: dict, ref: dict, config: dict) -> dict:
    """The compared numbers from the program's and the reference's
    readings (``losses``, ``grad_norms``, ``change_norms``, ``history``):
    the three above, then those of ``config``'s reference module."""
    moving = moving_leaves(ref["grad_norms"])
    g, g_at = norm_gap(prog["grad_norms"], ref["grad_norms"])
    c, c_at = norm_gap(prog["change_norms"], ref["change_norms"], moving)
    out = {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
           "grad_norm_gap": g, "change_norm_gap": c}
    more = getattr(reference.model_module(config), "numbers", None)
    if more is not None:
        for name, v in more(prog, ref).items():
            if name in out or name.startswith("_"):
                raise ValueError(f"configs/{config['reference']}.py "
                                 f"numbers() gives {name!r}, a name it "
                                 f"may not take")
            out[name] = v
    out["_worst_leaf"] = {"grad_norm_gap": g_at, "change_norm_gap": c_at}
    return out


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); a NaN fails, and so does a
    limit on a number that nothing produced (its value ``None``)."""
    report = {}
    ok = True
    for name, lim in limits.items():
        v = nums.get(name)
        report[name] = {"value": v, "limit": lim}
        if v is None or not v <= lim:
            ok = False
    return ok, report
