"""The least time the gated delta rule can take on the chip for one call over
``tokens`` positions of one sequence, from its shapes, whatever implements it.

Operations: the recurrent form, ``S k``, the rank-one update and ``S q``: three
products of ``dk x dv`` a head and token at 2 FLOPs a multiply-add.  Bytes:
``q``, ``k`` (dk), ``v``, ``o`` (dv) at the compute dtype's size and the two
float32 scalars ``alpha`` and ``beta`` a head and token, once; the float32 state
read once and written once a call.  What a chunkwise form adds (the triangular
system, its float32 intermediates) counts nothing: it is the implementation's.
"""

from __future__ import annotations

from typing import Dict


def needs(tokens: int, heads: int, key_dim: int, value_dim: int, itemsize: int = 2) -> Dict[str, float]:
    per_token = heads * ((2.0 * key_dim + 2.0 * value_dim) * itemsize + 2 * 4.0)
    state = 2.0 * heads * key_dim * value_dim * 4.0
    return {"flops": 6.0 * tokens * heads * key_dim * value_dim,
            "bytes": tokens * per_token + state}


def least_seconds(tokens: int, heads: int, key_dim: int, value_dim: int, peak: Dict) -> Dict[str, float]:
    n = needs(tokens, heads, key_dim, value_dim)
    by_flops, by_bytes = n["flops"] / peak["flops_bf16"], n["bytes"] / peak["hbm_bytes_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory", **n}
