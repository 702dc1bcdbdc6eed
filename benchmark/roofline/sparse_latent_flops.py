"""Operations a latent-attention decoder with a lightning indexer and routed
experts needs, from its shapes, whatever form the program runs.

``latent_moe_flops``' count with three changes: the indexer's three matrices
(``WI_q``, ``WI_k``, ``WI_w``) are among the weights every token touches; the
indexer scores every (query, key) pair a query may see, ``2 x index_n_heads x
index_head_dim`` FLOPs for the dot products and ``2 x index_n_heads`` for the
weighted sum a pair and layer; and attention is counted over the SELECTED pairs
only, ``min(t + 1, index_topk)`` keys for the query at position ``t``.  The
selection itself, the masks, the norms and the softmax count nothing.
"""

from __future__ import annotations

from typing import Dict

from benchmark.roofline import latent_moe_flops

routed_flops = latent_moe_flops.routed_flops


def index_params(cfg: Dict) -> int:
    """The indexer's matrices in one layer."""
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * hi * di + cfg["hidden_size"] * (di + hi)


def fixed_params(cfg: Dict) -> int:
    """Weights EVERY token touches: all but the routed experts."""
    return latent_moe_flops.fixed_params(cfg) + cfg["num_hidden_layers"] * index_params(cfg)


def scored(first: int, count: int) -> float:
    """Pairs the indexer scores for the queries at ``[first, first + count)``:
    ``t + 1`` each."""
    return count * (2.0 * first + count + 1) / 2.0


def selected(first: int, count: int, k: int) -> float:
    """Keys the queries at ``[first, first + count)`` attend: ``min(t + 1, k)``
    each."""
    last = first + count
    if last <= k:
        return scored(first, count)
    if first >= k:
        return float(k) * count
    return scored(first, k - first) + float(k) * (last - k)


def _pairs_flops(cfg: Dict, first: int, count: int) -> float:
    index_unit = 2.0 * cfg["num_hidden_layers"] * cfg["index_n_heads"] * (cfg["index_head_dim"] + 1)
    return (index_unit * scored(first, count)
            + latent_moe_flops._attn_unit(cfg) * selected(first, count, cfg["index_topk"]))


def prefill_flops(cfg: Dict, new_tokens: int, start: int) -> float:
    """A prompt's ``new_tokens`` after ``start`` tokens already in the cache,
    the routed experts left out (``routed_flops``)."""
    return 2.0 * fixed_params(cfg) * new_tokens + _pairs_flops(cfg, start, new_tokens)


def decode_flops(cfg: Dict, tokens: int, start: int) -> float:
    """``tokens`` single-token passes at positions ``[start, start + tokens)``,
    the routed experts left out."""
    return 2.0 * fixed_params(cfg) * tokens + _pairs_flops(cfg, start, tokens)
