"""The benchmark's own arithmetic on samples."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional


def percentile(values: Iterable[Optional[float]], q: float, missing: float = math.inf) -> float:
    """Nearest-rank percentile over ALL samples; ``None`` (a request that
    failed, was shed or timed out) ranks above every success."""
    vals = sorted(missing if v is None else float(v) for v in values)
    if not vals:
        raise ValueError("percentile of no samples")
    return vals[max(0, math.ceil(q / 100.0 * len(vals)) - 1)]


def spread(values: List[float]) -> float:
    """Distance between the first and third quartile as a share of the median,
    as the driver reads it (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
