"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against a limit of its own (set from measured readings,
see PERF.md), taken by the worst case:

* ``loss_rel``: the largest relative gap between the program's and the
  reference's loss over the checked steps;
* ``grad_norm_gap``: over the parameter leaves, the largest gap between the
  norms of the first gradient the optimizer was given, relative to the
  reference's norm of that leaf or of the median leaf, whichever is larger;
* ``update_norm_gap``: the same for the parameters' change over the checked
  steps, leaving out leaves whose reference gradient norm is under a
  thousandth of the median leaf's (Adam moves those by round-off alone).
"""
from __future__ import annotations

import math
from typing import Dict, List

# leaves whose first reference gradient is under this share of the median
# leaf's gradient norm move by round-off alone and are not compared
STILL_LEAF = 1e-3
NUMBERS = ("loss_rel", "grad_norm_gap", "update_norm_gap")


def _gap(p: Dict[str, float], r: Dict[str, float], keys) -> float:
    keys = list(keys)
    if not keys:
        return 0.0
    med = sorted(r[k] for k in keys)[len(keys) // 2]
    worst = 0.0
    for k in keys:
        d = abs(p[k] - r[k]) / max(r[k], med, 1e-30)
        worst = max(worst, d if math.isfinite(d) else math.inf)
    return worst


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers of one run (``inf`` where they cannot be)."""
    if set(prog["grads"]) != set(ref["grads"]) or \
            len(prog["losses"]) != len(ref["losses"]):
        return {n: math.inf for n in NUMBERS}
    loss = max((abs(a - b) / max(abs(b), 1e-30)
                for a, b in zip(prog["losses"], ref["losses"])), default=0.0)
    if not all(math.isfinite(x) for x in prog["losses"]):
        loss = math.inf
    g_ref = ref["grads"]
    med = sorted(g_ref.values())[len(g_ref) // 2]
    moving = [k for k in g_ref if g_ref[k] >= STILL_LEAF * med]
    return {
        "loss_rel": loss,
        "grad_norm_gap": _gap(prog["grads"], g_ref, g_ref),
        "update_norm_gap": _gap(prog["change"], ref["change"], moving),
    }


def compare(prog: Dict, ref: Dict, limits: Dict[str, float]) -> List[Dict]:
    """One entry per compared number: its value, its limit and the verdict."""
    out = []
    for name, value in readings(prog, ref).items():
        if name not in limits:
            continue
        out.append({"name": name, "value": value, "limit": limits[name],
                    "ok": bool(value <= limits[name])})
    return out
