"""Stratified draws: the population is fixed, the generator passed only orders it.

For N draws the values are the distribution's quantiles at (i + 0.5) / N, so
two orders hold the same multiset of lengths.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def quantiles(dist: Dict, n: int) -> List[float]:
    us = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "log_uniform":
        lo, hi = float(dist["min"]), float(dist["max"])
        return [lo * (hi / lo) ** u for u in us]
    raise ValueError(f"unknown distribution {kind!r}")


def lengths(dist: Dict, n: int, rng: np.random.Generator) -> List[int]:
    """N whole-number lengths: the quantiles, rounded, in ``rng``'s order."""
    step = int(dist.get("round_to", 1))
    vals = [max(step, int(round(v / step)) * step) for v in quantiles(dist, n)]
    return [vals[i] for i in rng.permutation(n)]
