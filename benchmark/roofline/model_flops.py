"""Operations the model needs, from its shapes (the benchmark's own copy of the
arithmetic in ``tracking/ledger.py:transformer_flops_per_token``, which may not
move with the program).

Counted: 2 FLOPs per multiply-add of every weight matmul (the embedding
lookup is a gather and counts nothing), and causal attention's two matmuls
over the positions a token really attends to.  Recomputation counts nothing.
The program's own figure counts the embedding table among the 6N and the
attention uncausally, so it reads higher than this one.
"""

from __future__ import annotations

from typing import Dict


def matmul_params(cfg: Dict) -> int:
    d, h, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    attn = d * hd * (2 * h + 2 * hkv)
    mlp = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def _attn_unit(cfg: Dict) -> float:
    """FLOPs of QK^T and PV for one query against one key, all layers."""
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    return 4.0 * cfg["num_hidden_layers"] * h * hd


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward + backward: 6 per matmul parameter, and three times the forward
    causal attention (a token attends to seq / 2 keys on average)."""
    return 6.0 * matmul_params(cfg) + 3.0 * _attn_unit(cfg) * seq / 2.0


def decode_flops(cfg: Dict, tokens: float, context_sum: float) -> float:
    """``tokens`` single-token forward passes attending to ``context_sum`` keys in all."""
    return 2.0 * matmul_params(cfg) * tokens + _attn_unit(cfg) * context_sum


def prefill_flops(cfg: Dict, new_tokens: float, start: float) -> float:
    """A prompt's ``new_tokens`` after ``start`` tokens already in the cache."""
    keys = new_tokens * start + new_tokens * new_tokens / 2.0
    return 2.0 * matmul_params(cfg) * new_tokens + _attn_unit(cfg) * keys
