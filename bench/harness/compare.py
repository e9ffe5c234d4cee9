"""The comparison that decides ``correct``.

Three numbers are read; those the cell's workload file gives a limit are
compared against it (a number with no limit is printed, not compared):

* ``loss_gap``: over the checked steps, the largest |L_prog - L_ref| / |L_ref|.
* ``grad_gap``: over every leaf (one matrix or vector of one layer), the
  largest gap between the program's and the reference's norm of the first
  clipped gradient, over the larger of the reference's norm of that leaf
  and of the median leaf.
* ``change_gap``: the same for the change of each trainable after the
  checked steps.  Leaves whose reference gradient is under a thousandth of
  the median leaf's (moved by round-off alone under Adam) are left out.
"""
from __future__ import annotations

import math

import numpy as np

NAMES = ("loss_gap", "grad_gap", "change_gap")


def _flat(norms: dict) -> dict:
    out = {}
    for path, x in norms.items():
        x = np.atleast_1d(np.asarray(x, np.float64))
        for i, v in enumerate(x):
            out[f"{path}#{i}" if x.size > 1 else path] = float(v)
    return out


def _worst(prog: dict, ref: dict, keep=None) -> tuple:
    p, r = _flat(prog), _flat(ref)
    if set(p) != set(r):
        return math.inf, f"leaves differ: {sorted(set(p) ^ set(r))[:4]}"
    med = float(np.median(list(r.values())))
    worst, where = 0.0, ""
    for k in sorted(r):
        if keep is not None and k not in keep:
            continue
        gap = abs(p[k] - r[k]) / max(r[k], med, 1e-30)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def readings(prog: dict, ref: dict) -> dict:
    """{name: (value, worst leaf)} for the program's and the reference's
    readings (each: losses, grads, change)."""
    lp, lr = prog["losses"], ref["losses"]
    if len(lp) != len(lr):
        loss = (math.inf, "step count")
    else:
        gaps = [abs(a - b) / max(abs(b), 1e-30) for a, b in zip(lp, lr)]
        loss = (max(gaps) if all(map(math.isfinite, gaps)) else math.inf,
                f"step {int(np.argmax(gaps)) + 1}")
    rg = _flat(ref["grads"])
    med = float(np.median(list(rg.values())))
    keep = {k for k, v in rg.items() if v >= 1e-3 * med}
    return {"loss_gap": loss,
            "grad_gap": _worst(prog["grads"], ref["grads"]),
            "change_gap": _worst(prog["change"], ref["change"], keep)}


def verdict(read: dict, limits: dict) -> tuple:
    """(all within limits, {name: {"value", "limit"}}) over the numbers
    that have a limit."""
    checks = {n: {"value": read[n][0], "limit": float(limits[n])}
              for n in NAMES if n in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
