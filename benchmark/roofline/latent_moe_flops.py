"""Operations a latent-attention decoder with routed experts needs, from its
shapes, whatever form the program runs.

Counted, at 2 FLOPs per multiply-add: every weight a token touches ONCE (the
attention's five projections, ``W_kvb`` among them, in every layer; the dense
MLP of the leading layers; the router and the shared expert of every expert
layer; the unembedding), the ROUTED part from the rows the experts held here
were given (``moe_rows_held``: three matrices of ``hidden x moe_intermediate``
a row), and causal attention in the up-projected form, ``qk_nope + qk_rope``
columns for the score and ``v_head_dim`` for the value a head, query and key,
over the positions a token really attends to.  That a decode step folds
``W_kvb`` into the query (more multiply-adds a key, none a head and key of the
cache), that a chunk up-projects every row of the table again, the embedding
lookup, the norms, the sort and the softmax count nothing.
"""

from __future__ import annotations

from typing import Dict


def _layers(cfg: Dict):
    n_dense = min(cfg.get("first_k_dense_replace", 0), cfg["num_hidden_layers"])
    return n_dense, cfg["num_hidden_layers"] - n_dense


def attention_params(cfg: Dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv * h * (dn + dv) + h * dv * d


def expert_params(cfg: Dict) -> int:
    """One routed expert: the three matrices of its gated MLP."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_params(cfg: Dict) -> int:
    """Weights EVERY token touches: all but the routed experts."""
    d = cfg["hidden_size"]
    n_dense, n_exp = _layers(cfg)
    router = d * (cfg.get("router_width") or cfg["n_routed_experts"])
    per_expert_layer = router + cfg.get("n_shared_experts", 0) * expert_params(cfg)
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + n_dense * 3 * d * cfg["intermediate_size"]
            + n_exp * per_expert_layer + d * cfg["vocab_size"])


def routed_flops(cfg: Dict, rows_held: float) -> float:
    return 2.0 * expert_params(cfg) * rows_held


def _attn_unit(cfg: Dict) -> float:
    """FLOPs of the score and the value product for one query against one key."""
    per_head = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return 2.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * per_head


def decode_flops(cfg: Dict, tokens: float, context_sum: float) -> float:
    """``tokens`` single-token passes attending to ``context_sum`` keys in all,
    the routed experts left out (``routed_flops``)."""
    return 2.0 * fixed_params(cfg) * tokens + _attn_unit(cfg) * context_sum


def prefill_flops(cfg: Dict, new_tokens: float, start: float) -> float:
    """A prompt's ``new_tokens`` after ``start`` tokens already in the cache,
    the routed experts left out."""
    keys = new_tokens * start + new_tokens * new_tokens / 2.0
    return 2.0 * fixed_params(cfg) * new_tokens + _attn_unit(cfg) * keys
