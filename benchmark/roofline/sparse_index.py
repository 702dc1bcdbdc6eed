"""The least time the lightning indexer's scores can take on the chip for one
call over a prompt chunk, from what it scores, whatever implements it.

A call scores ``pairs`` (query, key) pairs in all, every key a query may see
(counted by the program from a chunk's start and length: ``dsa_index_pairs``
by the rows of a chunk's shape); each costs ``2 x heads x head_dim`` FLOPs for
the dot products and ``2 x heads`` for the weighted sum over heads.  Bytes:
the queries ``qI [rows, heads, head_dim]`` at the compute dtype's size and
their head weights ``w [rows, heads]`` float32 once, the ``keys`` indexer keys
read (``[keys, head_dim]``), and one float32 score a pair written.  Rows a
padded shape adds to the pairs, keys past a row's position, the mask and the
ReLU count nothing: they are the implementation's.
"""

from __future__ import annotations

from typing import Dict


def needs(rows: float, pairs: float, keys: float, heads: int, head_dim: int,
          itemsize: int = 2) -> Dict[str, float]:
    flops = pairs * (2.0 * heads * head_dim + 2.0 * heads)
    moved = (rows * heads * (head_dim * itemsize + 4.0) + keys * head_dim * itemsize
             + 4.0 * pairs)
    return {"flops": flops, "bytes": moved}


def least_seconds(rows: float, pairs: float, keys: float, heads: int, head_dim: int,
                  peak: Dict) -> Dict[str, float]:
    n = needs(rows, pairs, keys, heads, head_dim)
    by_flops, by_bytes = n["flops"] / peak["flops_bf16"], n["bytes"] / peak["hbm_bytes_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory", **n}
