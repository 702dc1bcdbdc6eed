"""The least time the routed experts' grouped product can take on the chip for
one call, from its shapes, whatever implements it.

One call is the gated MLP of the experts held here over the rows they were
given: three matrices of ``hidden x width`` a row at 2 FLOPs a multiply-add (6
x rows x hidden x width).  Bytes: the three matrices of every expert that had a
row, once; each row in (``hidden``) and out (``hidden``) once, at the compute
dtype's size.  The intermediate of ``width`` a row, the sort, the gather and
the scatter count nothing: they are the implementation's.

``experts_hit`` is counted by the program (``moe_experts_hit``).
"""

from __future__ import annotations

from typing import Dict


def needs(rows: float, experts_hit: float, hidden: int, width: int, itemsize: int = 2) -> Dict[str, float]:
    weights = 3.0 * experts_hit * hidden * width * itemsize
    return {"flops": 6.0 * rows * hidden * width,
            "bytes": weights + 2.0 * rows * hidden * itemsize}


def least_seconds(rows: float, experts_hit: float, hidden: int, width: int, peak: Dict) -> Dict[str, float]:
    n = needs(rows, experts_hit, hidden, width)
    by_flops, by_bytes = n["flops"] / peak["flops_bf16"], n["bytes"] / peak["hbm_bytes_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory", **n}
