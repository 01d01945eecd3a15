"""What decides ``correct``: the program's first rounds against the plain
reference's replay of them.

Three numbers are compared, each against the configuration's limit:

``loss_rel``    the largest relative gap of a client's loss, over every
                client of every checked round;
``grad_rel``    the first gradient, as the optimizer got it (its first
                moment after one step over 1 - b1), by the worst leaf;
``change_rel``  the change of the trainables over the checked rounds, by
                the worst leaf (commits included).

A leaf is one adapter matrix of one layer, or the classifier.  "By the
worst leaf" is the largest gap between the program's norm and the
reference's, over the reference's norm of that leaf or of the client's
median leaf, whichever is larger.  Leaves whose reference gradient is
nought to rounding (under a thousandth of the median leaf's) are left out:
for the first gradient that is every LoRA A (B starts at zero), for the
change a leaf that no checked round gave a gradient.
"""
from __future__ import annotations

import math

import numpy as np

NOUGHT = 1e-3   # share of the median leaf's gradient below which it is nought


def _stack(tree: dict) -> np.ndarray:
    """``{leaf: (U, L or 1)}`` -> (U, n_entries) in a fixed leaf order."""
    return np.concatenate([np.asarray(tree[k], np.float64)
                           for k in sorted(tree)], axis=1)


def worst_leaf(prog: dict, ref: dict, gate: dict) -> float:
    """Largest |‖prog‖ - ‖ref‖| / max(‖ref‖, median leaf) over clients
    and the leaves the gate keeps."""
    if sorted(prog) != sorted(ref):
        return math.inf
    p, r, g = _stack(prog), _stack(ref), _stack(gate)
    if p.shape != r.shape:
        return math.inf
    worst = 0.0
    for pu, ru, gu in zip(p, r, g):
        keep = gu >= NOUGHT * np.median(gu)
        if not keep.any():
            return math.inf
        denom = np.maximum(ru[keep], np.median(ru[keep]))
        diff = np.abs(pu[keep] - ru[keep])
        with np.errstate(divide="ignore", invalid="ignore"):
            gap = np.where(diff == 0, 0.0, diff / denom)
        if np.isnan(gap).any():
            return math.inf
        worst = max(worst, float(gap.max()))
    return worst


def readings(prog: dict, ref: dict) -> dict:
    """The compared numbers of a candidate (the program, or the control)
    against the reference; each is ``inf`` where the candidate has none."""
    loss_p, loss_r = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    if loss_p.shape != loss_r.shape or not np.isfinite(loss_p).all():
        loss_rel = math.inf
    else:
        loss_rel = float(np.max(np.abs(loss_p - loss_r) / np.abs(loss_r)))
    return {"loss_rel": loss_rel,
            "grad_rel": worst_leaf(prog["grad1"], ref["grad1"], ref["grad1"]),
            "change_rel": worst_leaf(prog["change"], ref["change"],
                                     ref["grad_max"])}


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and ``{name: {"value", "limit"}}``; a number that is
    not finite fails."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
