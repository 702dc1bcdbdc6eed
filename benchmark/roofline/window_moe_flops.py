"""Operations a decoder of window and full attention layers with routed experts
needs, from its shapes, whatever form the program runs.

Counted, at 2 FLOPs per multiply-add: every weight a token touches ONCE (each
layer's four attention projections at its own head count and its gate; the
dense MLP of the ``dense`` layers; the router and the shared expert of every
``sparse`` layer; the unembedding), the ROUTED part from the rows the experts
held here were given (``moe_rows_held``: three matrices of ``hidden x
moe_intermediate`` a row), full attention causal over the positions a token
really attends to, and window attention over ADMITTED pairs only: a query at
position ``i`` admits ``min(i + 1, sliding_window)`` keys.  A (query, key)
pair costs ``4 x heads x head_dim`` (score and value).  Pad rows, the
embedding lookup, the norms, the rotary embedding, masks, the sort and the
softmax count nothing.
"""

from __future__ import annotations

from typing import Dict

FULL, WINDOW = "full_attention", "sliding_attention"


def layers(cfg: Dict) -> Dict[str, int]:
    """Query heads of the layers of each kind, one entry a layer."""
    n = cfg["num_hidden_layers"]
    kinds = list(cfg["layer_types"])[:n]
    heads = list(cfg["num_attention_heads_per_layer"])[:n]
    return {kind: [h for k, h in zip(kinds, heads) if k == kind] for kind in (FULL, WINDOW)}


def attention_params(cfg: Dict, heads: int) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    gate = d * heads if cfg.get("gating") in ("per-head", "per_head", True) else 0
    return d * hd * (2 * heads + 2 * cfg["num_key_value_heads"]) + gate


def expert_params(cfg: Dict) -> int:
    """One routed expert: the three matrices of its gated MLP."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_params(cfg: Dict) -> int:
    """Weights EVERY token touches: all but the routed experts."""
    d = cfg["hidden_size"]
    mlps = list(cfg["mlp_layer_types"])[: cfg["num_hidden_layers"]]
    router = d * (cfg.get("router_width") or cfg["num_experts"])
    shared = 3 * d * cfg.get("shared_expert_intermediate_size", 0)
    by_kind = layers(cfg)
    return (sum(attention_params(cfg, h) for hs in by_kind.values() for h in hs)
            + mlps.count("dense") * 3 * d * cfg["intermediate_size"]
            + mlps.count("sparse") * (router + shared) + d * cfg["vocab_size"])


def routed_flops(cfg: Dict, rows_held: float) -> float:
    return 2.0 * expert_params(cfg) * rows_held


def _pair_units(cfg: Dict) -> Dict[str, float]:
    """FLOPs of one (query, key) pair summed over the layers of each kind."""
    return {kind: 4.0 * sum(hs) * cfg["head_dim"] for kind, hs in layers(cfg).items()}


def window_pairs(cfg: Dict, tokens: float, start: float) -> float:
    """Pairs ONE window layer admits for ``tokens`` positions from ``start``:
    ``min(i + 1, window)`` each; ``tokens`` and ``start`` may be fractions."""
    w = float(cfg["sliding_window"])
    end = start + tokens
    ramp_end = min(max(w - 1.0, start), end)  # below w - 1 a position admits i + 1
    ramp = (ramp_end - start) * (start + 1.0 + ramp_end) / 2.0
    return ramp + (end - ramp_end) * w


def decode_flops(cfg: Dict, tokens: float, context_sum: float, first_position: float) -> float:
    """``tokens`` single-token passes from ``first_position`` on, attending to
    ``context_sum`` keys in all in a full layer, the routed experts left out."""
    unit = _pair_units(cfg)
    return (2.0 * fixed_params(cfg) * tokens + unit[FULL] * context_sum
            + unit[WINDOW] * window_pairs(cfg, tokens, first_position))


def prefill_flops(cfg: Dict, new_tokens: float, start: float) -> float:
    """A prompt's ``new_tokens`` after ``start`` tokens already in the cache,
    the routed experts left out."""
    unit = _pair_units(cfg)
    keys = new_tokens * start + new_tokens * new_tokens / 2.0
    return (2.0 * fixed_params(cfg) * new_tokens + unit[FULL] * keys
            + unit[WINDOW] * window_pairs(cfg, new_tokens, start))
