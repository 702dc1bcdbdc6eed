"""Operations a hybrid decoder needs, from its shapes, by layer type.

Counted, at 2 FLOPs per multiply-add: every weight matmul of each kind of layer
(full attention: q, k, v, o; linear attention: q, k, v, the output gate, o and
the two per-head scalars; the MLP of every layer; the unembedding), causal
attention's two matmuls for the FULL layers only, over the positions a token
really attends to, and the gated delta rule in its recurrent form: ``S k``, the
rank-one update and ``S q``, each ``dk x dv`` multiply-adds per head and token.
The embedding lookup, the width-4 convolution, the norms and whatever a
chunkwise form computes beyond the recurrent one count nothing.
"""

from __future__ import annotations

from typing import Dict

FULL, LINEAR = "full_attention", "linear_attention"


def _layers(cfg: Dict):
    types = list(cfg["layer_types"])
    return types.count(FULL), types.count(LINEAR)


def matmul_params(cfg: Dict) -> int:
    d, h, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    n_full, n_lin = _layers(cfg)
    full = d * hd * (2 * h + 2 * hkv)
    hl, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"])
    linear = d * hl * (2 * dk + 3 * dv) + 2 * d * hl
    mlp = 3 * d * cfg["intermediate_size"]
    return (n_full * full + n_lin * linear + cfg["num_hidden_layers"] * mlp
            + d * cfg["vocab_size"])


def delta_rule_flops_per_token(cfg: Dict) -> float:
    """The recurrent form, all linear layers: three products of dk x dv a head."""
    _, n_lin = _layers(cfg)
    return 6.0 * n_lin * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] * cfg[
        "linear_value_head_dim"]


def _attn_unit(cfg: Dict) -> float:
    """FLOPs of QK^T and PV for one query against one key, the full layers."""
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    return 4.0 * _layers(cfg)[0] * h * hd


def _per_token(cfg: Dict) -> float:
    return 2.0 * matmul_params(cfg) + delta_rule_flops_per_token(cfg)


def decode_flops(cfg: Dict, tokens: float, context_sum: float) -> float:
    """``tokens`` single-token forward passes attending to ``context_sum`` keys in all."""
    return _per_token(cfg) * tokens + _attn_unit(cfg) * context_sum


def prefill_flops(cfg: Dict, new_tokens: float, start: float) -> float:
    """A prompt's ``new_tokens`` after ``start`` tokens already in the cache."""
    keys = new_tokens * start + new_tokens * new_tokens / 2.0
    return _per_token(cfg) * new_tokens + _attn_unit(cfg) * keys
