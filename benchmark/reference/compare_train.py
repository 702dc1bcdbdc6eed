"""Numbers compared for a training cell, each a gap between the program's
reading and the reference's.

- ``loss_gap``: the widest |program - reference| over the steps followed, as a
  share of the reference's loss.
- ``grad_norm_gap`` and ``change_norm_gap``: by the worst leaf, the gap between
  the program's norm and the reference's (not the norm of their difference),
  against the reference's norm of that leaf or of the median leaf, whichever
  is larger.  Leaves whose reference gradient is under a thousandth of the
  median leaf's move under Adam by round-off alone and are left out of the
  change.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float], keep) -> Dict[str, Any]:
    med = statistics.median(ref.values())
    worst, at = 0.0, None
    for k, r in ref.items():
        if not keep(k):
            continue
        gap = abs(prog[k] - r) / max(r, med)
        if gap >= worst:
            worst, at = gap, k
    return {"gap": worst, "leaf": at}


def compare(program: Dict[str, Any], reference: Dict[str, Any]) -> Dict[str, Any]:
    steps = len(reference["losses"])
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(program["losses"][:steps], reference["losses"]))
    g_ref = reference["grad_norms"]
    g_med = statistics.median(g_ref.values())
    grad = _worst_leaf(program["grad_norms"], g_ref, lambda k: True)
    moved = lambda k: g_ref[k] >= 1e-3 * g_med  # noqa: E731
    change = _worst_leaf(program["change_norms"], reference["change_norms"], moved)
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": grad["gap"], "grad_norm_leaf": grad["leaf"],
        "change_norm_gap": change["gap"], "change_norm_leaf": change["leaf"],
        "left_out_of_change": sorted(k for k in g_ref if not moved(k)),
    }
