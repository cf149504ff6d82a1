"""The comparison that decides ``correct``.

Both sides give, for the first steps of a run: each dispatch's loss, the
per-leaf norm of the optimizer's first moment after the first dispatch,
and the per-leaf norm of the parameters' change after the last step
followed.  The reference also gives the per-leaf norm of its first
gradient, by which leaves are left out of the change (see ``dparam``).

These numbers are read, and each that the cell's file gives a limit is
compared against it (``state`` only where the model keeps any):

``state``   the same measure as ``grad`` for the change of the model's
            non-trained state (BatchNorm's moving mean and variance): the
            one record of the forward pass that survives a dispatch;
``loss``    the widest relative gap between a dispatch's loss and the
            mean of the reference's losses over the same steps;
``grad``    the worst leaf's gap between the two norms of the first
            moment (for SGD after one step this is the gradient as the
            optimizer got it), against the reference's norm of that
            leaf or of the median leaf, whichever is larger;
``dparam``  the same for the parameters' change, over the leaves whose
            first gradient in the reference is at least a thousandth of
            the median leaf's (a leaf with no gradient moves under Adam
            by round-off alone).
"""

from __future__ import annotations

import statistics
from typing import Dict, List

QUIET_LEAF = 1e-3


def worst_leaf(got: Dict[str, float], ref: Dict[str, float],
               keep=None) -> Dict:
    names = [n for n in ref if keep is None or keep(n)]
    if not names or set(got) != set(ref):
        return {"value": float("inf"), "leaf": None}
    floor = statistics.median(ref[n] for n in names)
    gaps = {n: abs(got[n] - ref[n]) / max(ref[n], floor, 1e-30)
            for n in names}
    leaf = max(gaps, key=gaps.get)
    ranked = sorted(gaps.values())
    return {"value": gaps[leaf], "leaf": leaf, "floor": floor,
            "median_gap": statistics.median(ranked),
            "p90_gap": ranked[int(0.9 * (len(ranked) - 1))],
            "leaves": len(ranked)}


def compare(program: Dict, ref: Dict) -> Dict[str, Dict]:
    """The numbers compared, each with the leaf or step it was read at."""
    loss_gaps = []
    for lo, hi, value in program["loss"]:
        want = sum(ref["loss"][lo:hi]) / (hi - lo)
        loss_gaps.append((abs(value - want) / abs(want), f"steps {lo}-{hi}",
                          value, want))
    worst = max(loss_gaps) if loss_gaps else (float("inf"), None, None, None)
    g1 = ref["grad1_norm"]
    quiet = QUIET_LEAF * statistics.median(g1.values())
    out = {}
    if "dstate_norm" in ref:
        out["state"] = worst_leaf(program["dstate_norm"], ref["dstate_norm"])
    return {
        **out,
        "loss": {"value": worst[0], "at": worst[1], "program": worst[2],
                 "reference": worst[3],
                 "all": [[g, at] for g, at, _, _ in loss_gaps]},
        "grad": worst_leaf(program["moment_norm"], ref["moment_norm"]),
        "dparam": worst_leaf(program["dparam_norm"], ref["dparam_norm"],
                             keep=lambda n: g1[n] >= quiet),
    }


def judge(numbers: Dict[str, Dict], limits: Dict) -> Dict[str, Dict]:
    """Each number beside its limit.  A cell's file gives a limit as a
    number (held against ``value``: the worst leaf, the widest gap) or
    as ``{"of": "median_gap", "limit": x}`` (held against another
    reading of the same number); ``null`` keeps the number out of the
    comparison, and it is shown under ``observed`` only.  A number that
    is not finite fails."""
    compared, observed = {}, {}
    for key, rec in numbers.items():
        rule = limits.get(key)
        rest = dict(rec)
        if rule is None:
            observed[key] = rest
            continue
        of = rule.get("of", "value") if isinstance(rule, dict) else "value"
        limit = float(rule["limit"] if isinstance(rule, dict) else rule)
        value = float(rec[of])
        if of != "value":
            rest["worst"] = rest.pop("value")
            rest.pop(of)
        else:
            rest.pop("value")
        compared[key] = {"value": value, "limit": limit, "of": of,
                         "ok": bool(value == value and value <= limit),
                         **rest}
    return {"compared": compared, "observed": observed}
