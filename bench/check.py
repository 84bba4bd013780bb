"""The comparison that decides `correct`.

The program's first three steps, taken in set-up through the window's own
call and feed, against the plain reference's first three steps from the
same seed, the same rows and the same seeded optimistic term (see
`reference.lookahead_probe`). `readings` gives every number below; a cell
compares those that its limits file (`bench/limits/<cell>.json`) names,
each held to its limit there:

  loss_gap     the widest gap over the three steps between the program's
               loss and the reference's, over the reference's mean |D(x)|
               on that step's reals: the loss is a batch mean of critic
               outputs, whose sign can cancel it to nearly nothing, so the
               gap is measured against the size of what was averaged.
  loss1_gap    the same for the first step alone, taken before any update.
  loss_mid_gap the middle of the three steps' gaps: a step whose loss one
               int8 rounding that went the other way moved (see below)
               sets the widest gap alone, while a field computed in another
               precision moves every step.
  update1_gap  the gap between the norm of the first update the optimizer
               applied, as the program's state after one step gives it,
               and the reference's, by the worst leaf, over the
               reference's norm of that leaf or of the median leaf,
               whichever is larger.
  change3_gap  the same for the parameters' change after three steps
               (w3 - w0), as the program's state holds it before step 4.
  change3_dir  the difference of the two changes projected on the
               reference's, |<p - r, r>| / |r|, by the worst leaf over the
               same norms: it sees a sign, so an update applied the wrong
               way reads 2 and one not applied reads 1.

Readings kept for the look only: each step's loss gap, `loss_steps`;
`*_diff`, the norm of the difference, which a single int8 rounding that
went the other way on either side sets (a whole quantization step of its
row), and the index and shape of the leaf that reads worst on it.

Leaves whose reference field is nought to rounding (a norm under a
thousandth of the median leaf's, as the critic's output bias under the
WGAN loss) move by round-off alone and are left out of the leaf readings.

Two more, with the limit 0: backend compiles or traces inside the measured
window, and non-finite values in the loss and parameters at its end.
"""
from __future__ import annotations

import math

import numpy as np

FIELD_FLOOR = 1e-3
WINDOW = ("window_compiles", "window_nonfinite")


def _norms(leaves):
    return [float(np.linalg.norm(x)) for x in leaves]


def leaf_gaps(prog_leaves, ref_leaves, keep) -> dict:
    """Worst-leaf readings of the program's leaves against the reference's,
    each over the larger of the reference leaf's norm and the median kept
    leaf's: "gap" between the two norms, "diff" the norm of the
    difference, "dir" the difference projected on the reference leaf;
    "diff_leaf" the index and shape of the leaf worst on "diff"."""
    p = [np.asarray(x, np.float64) for x in prog_leaves]
    r = [np.asarray(x, np.float64) for x in ref_leaves]
    rn, pn = _norms(r), _norms(p)
    dn = _norms([a - b for a, b in zip(p, r)])
    med = float(np.median([rn[i] for i in keep]))
    den = {i: max(rn[i], med) for i in keep}
    proj = {i: abs(float(np.vdot(p[i] - r[i], r[i]))) / max(rn[i], 1e-300)
            for i in keep}
    worst = max(keep, key=lambda i: dn[i] / den[i])
    return {"gap": max(abs(pn[i] - rn[i]) / den[i] for i in keep),
            "diff": dn[worst] / den[worst],
            "dir": max(proj[i] / den[i] for i in keep),
            "diff_leaf": [worst, list(r[worst].shape)]}


def kept_leaves(g1_norms):
    med = float(np.median(g1_norms))
    return [i for i, g in enumerate(g1_norms) if g >= FIELD_FLOOR * med]


def _diff(a, b):
    return [np.asarray(x, np.float64) - np.asarray(y, np.float64)
            for x, y in zip(a, b)]


def readings(prog: dict, ref: dict) -> dict:
    """prog: {"losses", "q1", "w0", "w3"} of the program; ref: the output
    of `reference.run_reference` (or another run of it, in its place).
    Every number of the module's docstring."""
    keep = kept_leaves(ref["g1_norms"])
    loss = [abs(lp - lr) / s for lp, lr, s in
            zip(prog["losses"], ref["losses"], ref["scales"])]
    out = {"loss_gap": max(loss), "loss1_gap": loss[0],
           "loss_mid_gap": float(np.median(loss)), "loss_steps": loss}
    for name, (p, r) in {
            "update1": (prog["q1"], ref["q1"]),
            "change3": (_diff(prog["w3"], prog["w0"]),
                        _diff(ref["w3"], ref["w0"]))}.items():
        for form, v in leaf_gaps(p, r, keep).items():
            out[f"{name}_{form}"] = v
    return out


def compared(r: dict, limits: dict) -> dict:
    """The readings that `limits` names."""
    return {k: r[k] for k in limits if k in r}


def numbers(prog: dict, ref: dict, limits: dict) -> dict:
    """The compared readings; a limit that names no reading and no number
    of the window is an error."""
    r = readings(prog, ref)
    unknown = set(limits) - set(r) - set(WINDOW)
    if unknown:
        raise KeyError(f"limits name no reading: {sorted(unknown)}")
    return compared(r, limits)


def judge(values: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit; a number that is not finite fails."""
    checks = {}
    for name, value in values.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r}")
        checks[name] = {"value": value, "limit": limits[name]}
    ok = all(isinstance(c["value"], (int, float))
             and math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
