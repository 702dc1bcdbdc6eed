"""The least time window attention can take on the chip for one call over a
prompt chunk, from what it admits, whatever implements it.

A query at position ``i`` admits the keys of ``(i - window, i]``.  One call
admits ``pairs`` (query, key) pairs in all (counted by the program from a
chunk's start and length: ``window_pairs``); each costs the score and the value
product, ``4 x heads x head_dim`` FLOPs.  Bytes: ``q`` and ``o`` at the query
heads and ``k`` and ``v`` at the KV heads, once, at the compute dtype's size:
the queries are at least ``pairs / window`` (a query admits at most ``window``
keys), the keys those and the ``window`` before the first.  Rows a padded shape
adds, the ring's turn into position order, masks and the softmax count nothing:
they are the implementation's.
"""

from __future__ import annotations

from typing import Dict


def needs(pairs: float, heads: int, kv_heads: int, head_dim: int, window: int,
          itemsize: int = 2) -> Dict[str, float]:
    queries = pairs / float(window)
    qo = 2.0 * queries * heads * head_dim * itemsize
    kv = 2.0 * (queries + window) * kv_heads * head_dim * itemsize
    return {"flops": 4.0 * pairs * heads * head_dim, "bytes": qo + kv}


def least_seconds(pairs: float, heads: int, kv_heads: int, head_dim: int, window: int,
                  peak: Dict) -> Dict[str, float]:
    n = needs(pairs, heads, kv_heads, head_dim, window)
    by_flops, by_bytes = n["flops"] / peak["flops_bf16"], n["bytes"] / peak["hbm_bytes_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory", **n}
