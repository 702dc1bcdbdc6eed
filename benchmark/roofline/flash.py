"""The least time causal flash attention can take on the chip, forward and
backward, from its shapes.

Forward: QK^T and PV over the causal half, 4 * B * H * T^2 * d / 2 FLOPs.
Backward (dq and dkv together): 2.5 times the forward, as the algorithm needs
it (scores recomputed once); a split into two kernels that each recompute the
scores does 3.5 times, and the surplus counts nothing here.  Bytes: q and o
(and their gradients) at H heads, k and v (and theirs) at the KV heads, once.
"""

from __future__ import annotations

from typing import Dict


def needs(batch: int, seq: int, heads: int, kv_heads: int, head_dim: int,
          itemsize: int = 2) -> Dict[str, float]:
    unit = batch * heads * float(seq) * seq * head_dim / 2.0
    qo = 2.0 * batch * seq * heads * head_dim * itemsize
    kv = 2.0 * batch * seq * kv_heads * head_dim * itemsize
    return {
        "fwd_flops": 4.0 * unit, "bwd_flops": 10.0 * unit,
        "fwd_bytes": qo + kv, "bwd_bytes": 2.0 * (qo + kv) + qo / 2.0,
    }


def least_seconds(batch: int, seq: int, heads: int, kv_heads: int, head_dim: int,
                  peak: Dict) -> Dict[str, float]:
    """Forward plus backward of one layer's attention."""
    n = needs(batch, seq, heads, kv_heads, head_dim)
    flops = n["fwd_flops"] + n["bwd_flops"]
    nbytes = n["fwd_bytes"] + n["bwd_bytes"]
    by_flops, by_bytes = flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory",
            "flops": flops, "bytes": nbytes}
