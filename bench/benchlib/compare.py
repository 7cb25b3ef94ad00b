"""The comparison that decides ``correct`` for a training cell.

Three numbers, each a gap between the program's reading and the reference's
over the same first steps from the same weights and rows:

* ``loss_gap``: the widest relative gap between the two losses of a step;
* ``grad_gap``: the worst leaf's gap between the norms of the first clipped
  gradient, measured against that leaf's reference norm or the median
  leaf's, whichever is larger;
* ``change_gap``: the same for the norm of the parameters' change after the
  last step.  Leaves whose reference gradient is under a thousandth of the
  median leaf's (a key's bias, under softmax) move by round-off alone and
  are left out.

A leaf is one array, or one layer of an array stacked over layers.
"""
from __future__ import annotations

import numpy as np

ZERO_GRAD_SHARE = 1e-3


def _items(norms: dict) -> dict[str, float]:
    out = {}
    for k, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[k] = float(v)
        else:
            for i, x in enumerate(v.reshape(-1)):
                out[f"{k}[{i}]"] = float(x)
    return out


def _worst(got: dict, want: dict, keys) -> tuple[float, str]:
    med = float(np.median([want[k] for k in keys]))
    gaps = {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30) for k in keys}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def gaps(prog: dict, ref: dict) -> dict:
    """{name: gap} plus the leaf that set each leaf gap, under ``leaf``."""
    losses = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    g_ref, g_prog = _items(ref["grad"]), _items(prog["grad"])
    d_ref, d_prog = _items(ref["change"]), _items(prog["change"])
    med = float(np.median(list(g_ref.values())))
    live = [k for k in g_ref if g_ref[k] >= ZERO_GRAD_SHARE * med]
    grad_gap, grad_leaf = _worst(g_prog, g_ref, list(g_ref))
    change_gap, change_leaf = _worst(d_prog, d_ref, live)
    return {"loss_gap": max(losses), "grad_gap": grad_gap, "change_gap": change_gap,
            "leaf": {"grad_gap": grad_leaf, "change_gap": change_leaf}}


def judge(found: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {value, limit}}): every number at or under its limit."""
    checks = {k: {"value": found[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
