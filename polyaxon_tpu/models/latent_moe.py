"""The latent stack: latent attention in every layer, a dense or an expert MLP.

A ``TransformerConfig`` with ``kv_lora_rank`` > 0 is served through the same
paged programs as the dense model (``models/decode.py`` hands over here); what
differs is the mixer, what a layer keeps per token, and the MLP of the layers
its ``layer_types`` call ``"expert_mlp"``.  The forms are the DeepSeek-V3
family's, under the published keys.

**The block** is the dense model's: ``x + attn(rmsnorm(x))`` then ``x +
mlp(rmsnorm(x))`` (``decode._prenorm_block``'s form), final norm, untied
unembedding.

**Latent attention.**  With ``h`` the normed input of a layer,

    c_q = rmsnorm(h W_qa);  q = c_q W_qb  ->  heads of [q_nope | q_rope]
    [c_raw | r_raw] = h W_kva;  c = rmsnorm(c_raw);  k_rope = rope(r_raw)
    [k_nope | v] per head = c W_kvb
    score = (q_nope . k_nope + rope(q_rope) . k_rope) / sqrt(nope + rope)

``k_rope`` is ONE rotary head shared by every query head, and ``[c | k_rope]``
(``kv_lora_rank + qk_rope_head_dim`` values) is all a token leaves behind: the
paged pool's row, leaf ``c [layers, blocks, block, row]``, written and read in
place through the block table as the dense model's K and V are.  Two forms of
the one equation.  A prompt chunk walks its block table by key tiles, first
tile to the one that holds its own last position (``decode._walk_table_tiles``:
the trip count is traced, one compilation a chunk bucket): a tile's rows are
gathered and up-projected to keys and values (fewer multiply-adds a key where
there are many queries) and attended in the flash forward kernel, whose scores
stay in VMEM, under a running ``(o, lse)`` (``mla.chunk_attend``,
``_chunk_mixer``); nothing has the table's width.  A decode step folds
``W_kvb`` into the query and the output and attends over the rows themselves
(``mla.step_attend``, the absorbed form: no per-head keys), read where they
lie: one kernel copies each lane's pages up to the one that holds its position
(``flash.paged_step_attend``, device operation ``paged_step_attend``; an int8
pool's rows are gathered over the whole table width and attended by
``_attend_absorbed``, the plain rule).  Rotary pairs are split by halves
(``transformer._rope``); with ``rope_yarn_factor`` the rotary columns turn at
the YaRN frequencies (``transformer.yarn_inv_freq``, the window stack's blend)
and the score scale carries ``mscale^2``.

**The lightning indexer** (``index_n_heads`` > 0, DeepSeek-V3.2's sparse
attention).  Beside the row ``[c | k_rope]`` a token leaves ONE indexer key,

    qI_j = rope(c_q WI_q)_j  (j < index_n_heads);  kI = rope(layernorm(h WI_k))
    w_j  = (h WI_w)_j / sqrt(index_n_heads x index_head_dim)      float32
    I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))                  s <= t

(the rotation over the first ``qk_rope_head_dim`` columns), kept in a second
block-addressed leaf of the pool, ``ik [layers, blocks, block, index_head_dim]``,
written where ``c`` is.  A query attends only the ``index_topk`` keys with the
largest ``I`` (every key while it sees no more).  A prompt chunk scores its
rows against its table tile by tile in a kernel (``flash.dsa_index``, device
operation ``dsa_index_<rows>``, span ``dsa.index``) into one float32 buffer
``[rows, table positions]``, takes each row's threshold, its ``index_topk``-th
largest score, by an exact radix select (``select_threshold``, span
``dsa.select``; ties at the threshold are admitted and counted,
``dsa_tie_keys``), and walks its tiles again in the flash kernel with the
tile's scores and the thresholds as a mask (``flash_block_fwd(select=)``,
device operation ``dsa_chunk_tile``): the published masked form for prefill.  A
decode step scores each lane's live keys through its table, takes the top
``index_topk`` and gathers those rows alone for the absorbed form.

**The expert MLP** (``parallel/experts.py``): a float32 sigmoid router over
all ``n_routed_experts`` with a selection bias, ``num_experts_per_tok``
chosen, dropless grouped product over the ``experts_held`` experts this chip
holds, plus ``n_shared_experts`` always-on experts as one gated MLP.  Each
program returns, beside its logits and the pool, what its expert layers routed
in this call (``COUNT_NAMES``).

The layer loop runs the pattern as RUNS of one kind, each a ``lax.scan`` that
carries ``(x, pool)``: a leading dense layer, then the expert layers.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from polyaxon_tpu.models import decode
from polyaxon_tpu.models.transformer import _rmsnorm, _rope, rotate, yarn_inv_freq
from polyaxon_tpu.parallel import flash
from polyaxon_tpu.parallel.experts import COUNT_NAMES, experts_mlp, route, route_softmax

DENSE = "dense_mlp"
EXPERTS = "expert_mlp"
_ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
_NORMS = ("attn_norm", "mlp_norm", "q_norm", "kv_norm")
#: The lightning indexer's weights, a layer each (``index_n_heads`` > 0).
_INDEX = ("index_wq", "index_wk", "index_ww", "index_norm_w", "index_norm_b")
#: What a program of a model with an indexer counts beside ``COUNT_NAMES``:
#: the keys its chunks admitted beyond ``index_topk`` a row, ties at a row's
#: threshold, summed over layers.
DSA_COUNT_NAMES = ("dsa_tie_keys",)

#: What this stack cannot follow yet, and what each would take.
REFUSED = {
    "spec_decode": "the verify step has no latent-attention form",
    "mesh": "the latent pool and the expert product have no sharding rules "
    "(the expert exchange across chips is not written)",
    "kv_quantize": "the lightning indexer's key leaf ik has no int8 form "
    "(a model with index_n_heads; models/decode.py:init_block_pool)",
}


class LatentStackError(ValueError):
    """An option of ``REFUSED``, asked of a latent-attention model: named in
    ``option``, raised where the engine is built."""

    def __init__(self, option: str) -> None:
        super().__init__(
            f"{option} is not supported for a latent-attention model: "
            f"{REFUSED[option]}"
        )
        self.option = option


#: What the engine raises for an option of ``REFUSED``.
refusal = LatentStackError


def check_config(cfg) -> None:
    """What ``TransformerConfig.__post_init__`` holds a latent stack to."""
    c = cfg
    types = c.layer_types
    if types is None or len(types) != c.n_layers:
        raise ValueError(
            f"a latent-attention model names its {c.n_layers} layers in "
            f"layer_types ({DENSE!r} / {EXPERTS!r}), got {types!r}"
        )
    unknown = sorted(set(types) - {DENSE, EXPERTS})
    if unknown:
        raise ValueError(f"unknown layer types {unknown} (one of {DENSE!r}, {EXPERTS!r})")
    if min(c.q_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) < 1:
        raise ValueError(
            "latent attention needs q_lora_rank, qk_nope_head_dim, "
            "qk_rope_head_dim and v_head_dim >= 1"
        )
    if c.qk_rope_head_dim % 2 or c.rope_theta is None:
        raise ValueError("latent attention rotates an even qk_rope_head_dim: rope_theta is needed")
    if c.n_experts:
        raise ValueError("n_experts is the training stack's switch MoE: name n_routed_experts")
    if EXPERTS in types:
        if min(c.n_routed_experts, c.num_experts_per_tok, c.moe_intermediate_size) < 1:
            raise ValueError(
                "expert_mlp layers need n_routed_experts, num_experts_per_tok "
                "and moe_intermediate_size >= 1"
            )
        if c.num_experts_per_tok > c.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")
        held = experts_held(c)
        if c.expert_offset < 0 or held < 1 or c.expert_offset + held > c.n_routed_experts:
            raise ValueError(
                f"experts [{c.expert_offset}, {c.expert_offset + held}) are not "
                f"among the router's {c.n_routed_experts}"
            )
        if c.n_group < 1 or c.n_routed_experts % c.n_group:
            raise ValueError(
                f"the router's {c.n_routed_experts} outputs do not form n_group "
                f"({c.n_group}) groups"
            )
        if c.n_group > 1 and not (
            1 <= c.topk_group <= c.n_group
            and c.topk_group * (c.n_routed_experts // c.n_group) >= c.num_experts_per_tok
        ):
            raise ValueError(
                f"topk_group ({c.topk_group}) groups of {c.n_routed_experts // c.n_group} "
                f"cannot hold num_experts_per_tok ({c.num_experts_per_tok})"
            )
    if c.rope_yarn_factor and c.rope_yarn_original_max < 1:
        raise ValueError("rope_yarn_factor needs rope_yarn_original_max")
    if c.index_n_heads:
        if c.index_head_dim < c.qk_rope_head_dim or c.index_topk < 1:
            raise ValueError(
                f"the indexer's heads of {c.index_head_dim} must hold the "
                f"{c.qk_rope_head_dim} rotary columns, and index_topk >= 1"
            )


def experts_held(cfg) -> int:
    return cfg.experts_held or cfg.n_routed_experts


def _counts(cfg) -> Tuple[int, int]:
    return cfg.layer_types.count(DENSE), cfg.layer_types.count(EXPERTS)


def expert_layers(cfg) -> int:
    return cfg.layer_types.count(EXPERTS)


def runs(cfg) -> List[Tuple[str, int]]:
    """The pattern as runs of one kind: ``[(kind, layers), ...]``."""
    out: List[Tuple[str, int]] = []
    for kind in cfg.layer_types:
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1] + 1)
        else:
            out.append((kind, 1))
    return out


def row_width(cfg) -> int:
    """Values of one token's pool row in one layer: ``[c | k_rope]``."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def pool_row_width(cfg) -> int:
    """What the pool holds a row at: the row padded to whole lane tiles of 128.
    At 576 (4.5 tiles) the TPU compiler keeps the donated pool in a layout of
    its own (blocks minor-most) and copies the whole pool into row-major and
    back in every program (chipless v5e compile: 2 pool-sized copies and 0.85
    GB more temporaries in a decode step; none at 640).  The pad is written as
    zeros and sliced off the gather."""
    return -(-row_width(cfg) // 128) * 128


def index_params(cfg) -> int:
    """The lightning indexer's parameters in one layer (0 without one):
    ``WI_q``, ``WI_k``, ``WI_w`` and the LayerNorm's weight and bias."""
    c = cfg
    Hi, di = c.index_n_heads, c.index_head_dim
    return c.q_lora_rank * Hi * di + c.d_model * di + c.d_model * Hi + 2 * di if Hi else 0


def n_params(cfg) -> int:
    c = cfg
    n_dense, n_exp = _counts(c)
    D, H = c.d_model, c.n_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    attn = (D * c.q_lora_rank + c.q_lora_rank * H * qk + D * row_width(c)
            + c.kv_lora_rank * H * (c.qk_nope_head_dim + c.v_head_dim)
            + H * c.v_head_dim * D + c.q_lora_rank + c.kv_lora_rank + 2 * D
            + index_params(c))
    Fe = c.moe_intermediate_size
    exp = (D * c.n_routed_experts + c.n_routed_experts
           + 3 * D * Fe * (experts_held(c) + c.n_shared_experts)) if n_exp else 0
    return (2 * c.vocab_size * D + D + c.n_layers * attn
            + n_dense * 3 * D * c.d_ff + n_exp * exp)


def init_params(key: jax.Array, cfg) -> Dict[str, Any]:
    """Seeded weights (normal, fan-in scaled; norms one; the router's
    selection bias normal with a standard deviation of 0.02, so that it moves
    some choices and not most).  The plain reference
    (``benchmark/reference/latent_moe_decoder.py``) makes the same draws in
    the same order.  The indexer's three matrices are drawn after every other
    weight, so that a model without one draws what it drew before (its
    LayerNorm weight ones, its bias zeros)."""
    c = cfg
    k = iter(jax.random.split(key, 32))
    dt = c.param_dtype

    def norm(*shape, scale):
        return jax.random.normal(next(k), shape, dt) * scale

    L, D, H, F = c.n_layers, c.d_model, c.n_heads, c.d_ff
    rq, rkv = c.q_lora_rank, c.kv_lora_rank
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    n_dense, n_exp = _counts(c)
    block: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, D), dt),
        "mlp_norm": jnp.ones((L, D), dt),
        "q_norm": jnp.ones((L, rq), dt),
        "kv_norm": jnp.ones((L, rkv), dt),
        "wq_a": norm(L, D, rq, scale=D**-0.5),
        "wq_b": norm(L, rq, H, dn + dr, scale=rq**-0.5),
        "wkv_a": norm(L, D, rkv + dr, scale=D**-0.5),
        "wkv_b": norm(L, rkv, H, dn + dv, scale=rkv**-0.5),
        "wo": norm(L, H, dv, D, scale=(H * dv) ** -0.5),
        "dense": {
            "wi": norm(n_dense, D, F, scale=D**-0.5),
            "wg": norm(n_dense, D, F, scale=D**-0.5),
            "wd": norm(n_dense, F, D, scale=F**-0.5),
        },
    }
    if n_exp:
        E, held, Fe = c.n_routed_experts, experts_held(c), c.moe_intermediate_size
        Fs = c.n_shared_experts * Fe
        block["experts"] = {
            "router": norm(n_exp, D, E, scale=D**-0.5),
            "router_bias": norm(n_exp, E, scale=0.02),
            "wi": norm(n_exp, held, D, Fe, scale=D**-0.5),
            "wg": norm(n_exp, held, D, Fe, scale=D**-0.5),
            "wd": norm(n_exp, held, Fe, D, scale=Fe**-0.5),
            "shared_wi": norm(n_exp, D, Fs, scale=D**-0.5),
            "shared_wg": norm(n_exp, D, Fs, scale=D**-0.5),
            "shared_wd": norm(n_exp, Fs, D, scale=max(Fs, 1) ** -0.5),
        }
    out = {
        "embed": norm(c.vocab_size, D, scale=1.0),
        "unembed": norm(D, c.vocab_size, scale=D**-0.5),
        "final_norm": jnp.ones((D,), dt),
        "block": block,
    }
    if c.index_n_heads:
        Hi, di = c.index_n_heads, c.index_head_dim
        block["index_wq"] = norm(L, rq, Hi, di, scale=rq**-0.5)
        block["index_wk"] = norm(L, D, di, scale=D**-0.5)
        block["index_ww"] = norm(L, D, Hi, scale=D**-0.5)
        block["index_norm_w"] = jnp.ones((L, di), dt)
        block["index_norm_b"] = jnp.zeros((L, di), dt)
    return out


#: The matmul weights ``quantize: int8`` covers, with their contraction dims
#: (the router and its bias stay float32: they choose).
_QUANTIZED = {
    "": {"wq_a": (1,), "wq_b": (1,), "wkv_a": (1,), "wkv_b": (1,), "wo": (1, 2)},
    "dense": {"wi": (1,), "wg": (1,), "wd": (1,)},
    "experts": {"wi": (2,), "wg": (2,), "wd": (2,),
                "shared_wi": (1,), "shared_wg": (1,), "shared_wd": (1,)},
}


def _kinds(blk):
    return [kind for kind in ("dense", "experts") if kind in blk]


def quantize_weights(params: Dict[str, Any], q) -> Dict[str, Any]:
    """The latent stack's int8 tree: ``q(weight, contraction axes)`` (the one
    ``decode.quantize_weights`` uses) over the attention projections, both
    kinds of MLP and the unembedding."""
    blk = params["block"]
    out: Dict[str, Any] = {n: q(blk[n], axes) for n, axes in _QUANTIZED[""].items()}
    for kind in _kinds(blk):
        out[kind] = {n: q(blk[kind][n], axes) for n, axes in _QUANTIZED[kind].items()}
    return {"block": out, "unembed": q(params["unembed"], (0,))}


def serving_params(params: Dict[str, Any], cast) -> Dict[str, Any]:
    """The latent stack's form of ``decode.serving_params``: ``cast`` over the
    embeddings and every matmul weight, the indexer's ``WI_q`` and ``WI_k``
    among them.  The norms, the router and its bias, and the indexer's head
    weights ``WI_w`` (they weigh the scores that select) keep their dtype."""
    blk = params["block"]
    out = {**blk, **{n: cast(blk[n]) for n in _QUANTIZED[""]}}
    out.update({n: cast(blk[n]) for n in ("index_wq", "index_wk") if n in blk})
    for kind in _kinds(blk):
        out[kind] = {**blk[kind], **{n: cast(blk[kind][n]) for n in _QUANTIZED[kind]}}
    return {
        **params,
        "embed": cast(params["embed"]),
        "unembed": cast(params["unembed"]),
        "block": out,
    }


def _with_qweights(params, qweights):
    """The block tree with the int8 pairs in the quantized weights' places."""
    blk = params["block"]
    if qweights is None:
        return blk, params["unembed"]
    qb = qweights["block"]
    merged = {**blk, **{n: qb[n] for n in _QUANTIZED[""]}}
    for kind in _kinds(blk):
        merged[kind] = {**blk[kind], **qb[kind]}
    return merged, qweights["unembed"]


# -- the layer ------------------------------------------------------------------


def _rotary(x, positions, cfg):
    """``x [B, T, H, d]`` rotated to ``positions [B, T]``: plain rotary at
    ``rope_theta``, or the YaRN frequencies where ``rope_yarn_factor`` is set
    (cos and sin unscaled: ``mscale / mscale_all_dim`` is 1)."""
    if not cfg.rope_yarn_factor:
        return _rope(x, positions, cfg.rope_theta)
    return rotate(x, positions, (yarn_inv_freq(cfg, cfg.qk_rope_head_dim), 1.0))


def score_scale(cfg) -> float:
    """The attention's score scale: ``(nope + rope)^-0.5``, times ``mscale^2``
    under YaRN with ``rope_yarn_mscale_all_dim``, ``mscale = 0.1 x
    mscale_all_dim x ln(factor) + 1`` (the published ``softmax_scale``)."""
    c = cfg
    scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
    if c.rope_yarn_factor > 1 and c.rope_yarn_mscale_all_dim:
        scale = scale * (0.1 * c.rope_yarn_mscale_all_dim * math.log(c.rope_yarn_factor) + 1.0) ** 2
    return scale


def _q_latent(h, layer):
    """``h [B, T, D]`` -> ``c_q = rmsnorm(h W_qa)`` ``[B, T, q_lora_rank]``."""
    cq = jnp.einsum("btd,dr->btr", h, decode._wdq(layer["wq_a"], h.dtype))
    return _rmsnorm(cq, layer["q_norm"])


def _queries_of(cq, layer, positions, cfg):
    """``c_q [B, T, q_lora_rank]`` -> ``(q_nope [B, T, H, nope], q_rope [B, T,
    H, rope])``, the second rotated to ``positions [B, T]``."""
    q = jnp.einsum("btr,rhe->bthe", cq, decode._wdq(layer["wq_b"], cq.dtype))
    dn = cfg.qk_nope_head_dim
    return q[..., :dn], _rotary(q[..., dn:], positions, cfg)


def _latent_row(h, layer, positions, cfg):
    """``h [B, T, D]`` -> the pool's row ``[c | k_rope]`` ``[B, T, row]``."""
    kv = jnp.einsum("btd,dr->btr", h, decode._wdq(layer["wkv_a"], h.dtype))
    rkv = cfg.kv_lora_rank
    c = _rmsnorm(kv[..., :rkv], layer["kv_norm"])
    k_rope = _rotary(kv[..., None, rkv:], positions, cfg)[:, :, 0]
    return jnp.concatenate([c, k_rope], axis=-1)


# -- the lightning indexer ------------------------------------------------------


def _index_queries(cq, h, layer, positions, cfg):
    """The indexer's queries ``qI [B, T, Hi, di]`` (rotated over their first
    ``qk_rope_head_dim`` columns) and head weights ``w [B, T, Hi]`` float32,
    ``(h WI_w) / sqrt(Hi x di)`` at ``highest``."""
    qi = jnp.einsum("btr,rhe->bthe", cq, decode._wdq(layer["index_wq"], cq.dtype))
    table = (yarn_inv_freq(cfg, cfg.qk_rope_head_dim), 1.0)
    w = jnp.einsum(
        "btd,dh->bth", h.astype(jnp.float32), layer["index_ww"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    return rotate(qi, positions, table), w * (cfg.index_n_heads * cfg.index_head_dim) ** -0.5


def _index_key(h, layer, positions, cfg):
    """The indexer's key a token ``kI [B, T, di]``: ``layernorm(h WI_k)``
    (float32 statistics, eps 1e-6), rotated over its first ``qk_rope_head_dim``
    columns."""
    k = jnp.einsum("btd,de->bte", h, decode._wdq(layer["index_wk"], h.dtype)).astype(jnp.float32)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = (k - mean) * lax.rsqrt(var + 1e-6) * layer["index_norm_w"] + layer["index_norm_b"]
    table = (yarn_inv_freq(cfg, cfg.qk_rope_head_dim), 1.0)
    return rotate(k.astype(h.dtype)[:, :, None], positions, table)[:, :, 0]


def select_threshold(scores, qpos, k: int):
    """Each row's ``k``-th largest score, EXACTLY, by a radix select over the
    scores' order-preserving 32-bit keys (32 passes of one count a row, no
    sort); ``-inf`` for a row at ``qpos`` that sees ``k`` keys or fewer.
    ``scores [R, N]`` float32 with the keys a row may not see at ``-inf``;
    ``qpos [R]``.  A key at or above its row's threshold is selected: ``k``
    keys a row, and more only where scores tie at the threshold."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    sign = jnp.uint32(0x80000000)
    keys = jnp.where(bits >= sign, ~bits, bits | sign)

    def bit(i, ans):
        cand = ans | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        above = jnp.sum((keys >= cand[:, None]).astype(jnp.int32), axis=1)
        return jnp.where(above >= k, cand, ans)

    ans = lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:1], jnp.uint32))
    back = jnp.where(ans >= sign, ans & ~sign, ~ans)
    return jnp.where(qpos + 1 > k, lax.bitcast_convert_type(back, jnp.float32), -jnp.inf)


def scored_keys(first: int, count: int) -> int:
    """(query, key) pairs the indexer scores for the queries at ``[first, first
    + count)`` in ONE layer: every key a query may see, ``t + 1``."""
    return (2 * first + count + 1) * count // 2


def selected_keys(cfg, first: int, count: int) -> int:
    """Keys the queries at positions ``[first, first + count)`` attend in ONE
    layer: ``min(t + 1, index_topk)`` each (ties at a threshold left out).  The
    host's count for ``/v1/stats``."""
    k, last = cfg.index_topk, first + count
    if last <= k:
        return scored_keys(first, count)
    if first >= k:
        return k * count
    return scored_keys(first, k - first) + k * (last - k)


def _softmax_over(s, mask, dtype):
    s = jnp.where(mask, s, -1e30)
    return jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dtype)


def _attend_absorbed(q_nope, q_rope, rows, mask, layer, cfg):
    """The same equation with ``W_kvb`` folded into the query and the output:
    scores against ``c`` itself, the value read from ``c`` and up-projected
    once a query.  No per-head keys for ``K`` rows."""
    dt = q_nope.dtype
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    scale = score_scale(cfg)
    wkv_b = decode._wdq(layer["wkv_b"], dt)
    c = rows[..., :rkv]
    q_c = jnp.einsum("bqhd,rhd->bqhr", q_nope, wkv_b[..., :dn])
    s = jnp.einsum("bqhr,bkr->bhqk", q_c, c)
    s = (s + jnp.einsum("bqhd,bkd->bhqk", q_rope, rows[..., rkv:])) * scale
    p = _softmax_over(s, mask, dt)
    o_c = jnp.einsum("bhqk,bkr->bqhr", p, c)
    return jnp.einsum("bqhr,rhd->bqhd", o_c, wkv_b[..., dn:])


def _expert_mlp(h, layer, valid, cfg, stacks, index):
    """``shared(h) + sum over chosen and held of gate * E(h)`` for ``h [B, T,
    D]``; ``valid [B, T]``.  ``layer`` holds the router and the shared expert;
    the routed experts' ``wi`` / ``wg`` / ``wd`` are layer ``index`` of
    ``stacks``, the stacks of all expert layers: float stacks are read in
    place (``experts_mlp``), int8 pairs are cut out and widened a layer at a
    time.  Returns the output and this layer's ``COUNT_NAMES``."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    ok = valid.reshape(B * T)
    with jax.named_scope("moe.route"):
        k, scale = cfg.num_experts_per_tok, cfg.routed_scaling_factor
        if "router_bias" in layer:
            chosen, gates = route(flat, layer["router"], layer["router_bias"], k, scale,
                                  cfg.n_group, cfg.topk_group)
        else:  # the window stack's layers: a softmax router, no selection bias
            chosen, gates = route_softmax(flat, layer["router"], k, scale)
    dt = h.dtype
    weights, at = [stacks[n] for n in ("wi", "wg", "wd")], index
    if isinstance(weights[0], tuple):
        weights, at = [jax.tree.map(lambda w: w[index], pair) for pair in weights], None
    with jax.named_scope("moe.experts"):
        y, rows = experts_mlp(
            flat, chosen, gates, ok, *(decode._wdq(w, dt) for w in weights),
            cfg.expert_offset, layer=at,
        )
    counts = jnp.stack([
        jnp.sum(ok.astype(jnp.int32)) * cfg.num_experts_per_tok,
        jnp.sum(rows), jnp.max(rows), jnp.sum((rows > 0).astype(jnp.int32)),
    ])
    if cfg.n_shared_experts:
        y = y + decode._gated_mlp(
            h, {n: layer["shared_" + n] for n in ("wi", "wg", "wd")}
        ).reshape(B * T, D)
    return y.reshape(B, T, D), counts


def _no_counts(n: int = len(COUNT_NAMES)):
    return jnp.zeros((n,), jnp.int32)


def count_names(cfg) -> Tuple[str, ...]:
    """What a paged program of ``cfg`` returns as its third value."""
    return COUNT_NAMES + (DSA_COUNT_NAMES if cfg.index_n_heads else ())


def _run_stack(x, blk, pool, cfg, attend, valid):
    """The layer loop: one ``lax.scan`` a run of the pattern, ``(x, pool)`` the
    carry, each layer the pre-norm block around ``attend(h, layer, layer index,
    pool) -> (output, pool)`` (``(output, pool, ties)`` with an indexer) and
    its kind's MLP.  Returns ``(x, pool, counts)``, ``counts`` what the expert
    layers routed (``COUNT_NAMES``) and, with an indexer, the ties its
    selection admitted (``DSA_COUNT_NAMES``)."""
    shared = {n: blk[n] for n in _ATTN + _NORMS + _INDEX if n in blk}
    first = {DENSE: 0, EXPERTS: 0}
    layer0 = 0
    counts = _no_counts(len(count_names(cfg)))
    for kind, n in runs(cfg):
        name = "dense" if kind == DENSE else "experts"
        lo = first[kind]
        # Every stack stays whole, closed over by the body, which reads its
        # layer at ``li`` / ``ki``: a stack cut to the run before the loop is
        # a copy of the run's layers written in every call.  The routed
        # experts' stacks go whole to the expert kernel, which reads at ``ki``.
        stacks = {w: blk[name][w] for w in ("wi", "wg", "wd")} if kind == EXPERTS else {}
        mlps = {w: v for w, v in blk[name].items() if w not in stacks}
        xs = (layer0 + jnp.arange(n), lo + jnp.arange(n))

        def body(carry, inputs, kind=kind, stacks=stacks, mlps=mlps):
            x, pool = carry
            li, ki = inputs
            layer = jax.tree.map(lambda w: w[li], shared)
            mlp = jax.tree.map(lambda w: w[ki], mlps)
            mix, pool, *ties = attend(_rmsnorm(x, layer["attn_norm"]), layer, li, pool)
            x = x + mix
            h = _rmsnorm(x, layer["mlp_norm"])
            if kind == DENSE:
                x, c = x + decode._gated_mlp(h, mlp), _no_counts()
            else:
                y, c = _expert_mlp(h, mlp, valid, cfg, stacks, ki)
                x = x + y
            return (x, pool), jnp.concatenate([c, *(t[None] for t in ties)]) if ties else c

        (x, pool), c = lax.scan(body, (x, pool), xs)
        counts = counts + jnp.sum(c, axis=0)
        first[kind] += n
        layer0 += n
    return x, pool, counts


def _chunk_mixer(cfg, qpos, table, write_blk, write_off, live_end, valid=None):
    """A prompt chunk's attention mixer ``(h [1, C, D], layer, layer index,
    pool) -> (output, pool)``: the chunk's rows written, then the chunk attends
    in the up-projected form by key tiles of ``table``, first tile to the one
    that holds ``live_end - 1`` (``decode._walk_table_tiles``).  A turn gathers
    one tile's rows, up-projects them to keys and values and attends them in
    the flash forward kernel (scores float32, in VMEM; the query's offset
    against the tile is its causal mask), and the tile's ``(o, lse)`` is merged
    into the running pair: no array has the table's width.  A row no tile
    admitted a key to (a chunk of length 0, the warm-up's) comes out zero.

    With an indexer the chunk's indexer keys are written too, and two walks
    run: the first scores every (row, key) pair of the tiles in the
    ``dsa_index`` kernel into a buffer ``[C, table positions]`` float32 (the
    only array of the table's width), each row's threshold is taken from it
    (:func:`select_threshold`), and the second is the walk above with the
    tile's scores and the thresholds masking the flash kernel
    (``dsa_chunk_tile``).  The mixer then returns the ties it admitted too."""
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    scale = score_scale(cfg)
    indexed = bool(cfg.index_n_heads)

    def attend(h, layer, li, pool):
        dt = h.dtype
        positions = qpos[None]  # formed before the queries: the order the digests hold
        cq = _q_latent(h, layer)
        q_nope, q_rope = _queries_of(cq, layer, positions, cfg)
        row = _latent_row(h, layer, qpos[None], cfg)[0]
        pool = decode._latent_append(pool, li, row, write_blk, write_off)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)[0].swapaxes(0, 1)  # [H, C, nope + rope]
        wkv_b = decode._wdq(layer["wkv_b"], dt)
        H, C = q.shape[:2]
        bs = decode.pool_geometry(pool)[0]
        if indexed:
            qi, w = _index_queries(cq, h, layer, qpos[None], cfg)
            pool = decode._pool_append(
                pool, "ik", li, _index_key(h, layer, qpos[None], cfg)[0], write_blk, write_off)
            scores, threshold, ties = _index_and_select(
                pool, li, table, bs, live_end, qi[0], w[0], qpos, valid, cfg)

        def turn(carry, blocks, k0):
            rows = decode._latent_gather(pool, li, blocks, dt, row.shape[-1])
            c, k_rope = rows[:, :rkv], rows[:, rkv:]
            kv = jnp.einsum("kr,rhe->hke", c, wkv_b)
            k_rope = jnp.broadcast_to(k_rope, (H, *k_rope.shape))
            select = None
            if indexed:
                keys = rows.shape[0]
                select = (lax.dynamic_slice(scores, (0, k0), (C, keys)), threshold)
            tile = flash.flash_block_fwd(
                q, jnp.concatenate([kv[..., :dn], k_rope], axis=-1), kv[..., dn:],
                causal=True, sm_scale=scale, q_offset=qpos[0] - k0,
                name="dsa_chunk_tile" if indexed else "mla_chunk_tile",
                **({"select": select, "block_q": 512} if indexed else {}),
            )
            return flash._merge(*carry, *tile)

        with jax.named_scope("mla.chunk_attend"):
            o, _ = decode._walk_table_tiles(
                table, bs, live_end, turn,
                (jnp.zeros((H, C, cfg.v_head_dim), jnp.float32), jnp.full((H, C), -jnp.inf)),
            )
        out = decode._attn_out(o.astype(dt).swapaxes(0, 1)[None], layer)
        return (out, pool, ties) if indexed else (out, pool)

    return attend


def _index_and_select(pool, li, table, bs, live_end, qi, w, qpos, valid, cfg):
    """A chunk's pass over its tiles with the indexer: ``(scores [C, table
    positions] float32, thresholds [C], ties)``.  ``qi [C, Hi, di]``, ``w [C,
    Hi]``; a key past a row's position, or past the walk, scores ``-inf``."""
    C, dt = qi.shape[0], qi.dtype

    def turn(buf, blocks, k0):
        ik = decode._pool_gather(pool, "ik", li, blocks, dt)
        tile = flash.dsa_index(qi, w, ik.reshape(-1, ik.shape[-1]), name=f"dsa_index_{C}")
        return lax.dynamic_update_slice(buf, tile, (0, k0))

    W = table.shape[0]
    positions = (W + -W % decode._tile_blocks(W, bs)) * bs
    with jax.named_scope("dsa.index"):
        scores = decode._walk_table_tiles(
            table, bs, live_end, turn, jnp.full((C, positions), -jnp.inf, jnp.float32))
        seen = jnp.arange(positions)[None, :] <= qpos[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
    with jax.named_scope("dsa.select"):
        threshold = select_threshold(scores, qpos, cfg.index_topk)
        admitted = jnp.sum(((scores >= threshold[:, None]) & seen).astype(jnp.int32), axis=1)
        want = jnp.minimum(qpos + 1, cfg.index_topk)
        ties = jnp.sum(jnp.where(valid, admitted - want, 0))
    return scores, threshold, ties


def _step_mixer(cfg, positions, tables, write_blk, write_off, pos):
    """A decode step's attention mixer ``(h [S, 1, D], layer, layer index,
    pool) -> (output, pool)`` in the absorbed form (``W_kvb`` folded into the
    query and the output, the rows themselves keys and values).  Every lane's
    row is written; then, by what the pool holds: rows in the compute dtype are
    read where they lie, ``[q_c | q_rope]`` against each lane's pages up to the
    one that holds ``pos`` (``flash.paged_step_attend``: nothing has the
    table's width); int8 rows under a scale a row are gathered over the whole
    table and attended by :func:`_attend_absorbed`, the plain rule.

    With an indexer the lane's indexer key is written too, every key a lane may
    see is scored through its table (plain XLA: ``ik`` gathered over the
    table's width), the ``index_topk`` best are taken, and only those rows are
    gathered and attended by :func:`_attend_absorbed`: a lane reads
    ``min(pos + 1, index_topk)`` rows a layer whatever its live end."""
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    scale = score_scale(cfg)

    def attend(h, layer, li, pool):
        dt = h.dtype
        cq = _q_latent(h, layer)
        q_nope, q_rope = _queries_of(cq, layer, positions, cfg)
        row = _latent_row(h, layer, positions, cfg)
        pool = decode._latent_append(pool, li, row[:, 0], write_blk, write_off)
        if cfg.index_n_heads:
            qi, w = _index_queries(cq, h, layer, positions, cfg)
            pool = decode._pool_append(
                pool, "ik", li, _index_key(h, layer, positions, cfg)[:, 0], write_blk, write_off)
            S, W = tables.shape
            bs = decode.pool_geometry(pool)[0]
            with jax.named_scope("dsa.index"):
                ik = decode._pool_gather(pool, "ik", li, tables, dt).reshape(S, W * bs, -1)
                s = jnp.einsum("shd,skd->shk", qi[:, 0], ik, preferred_element_type=jnp.float32)
                scores = jnp.einsum("shk,sh->sk", jnp.maximum(s, 0.0), w[:, 0],
                                    precision=lax.Precision.HIGHEST)
                scores = jnp.where(jnp.arange(W * bs)[None, :] <= pos[:, None], scores, -jnp.inf)
            with jax.named_scope("dsa.select"):
                _, keys = lax.top_k(scores, min(cfg.index_topk, W * bs))  # [S, k]
            with jax.named_scope("mla.step_attend"):
                blocks = jnp.take_along_axis(tables, keys // bs, axis=1)
                rows = pool["c"][li, blocks, keys % bs][..., : row.shape[-1]].astype(dt)
                mask = (keys <= pos[:, None])[:, None, None, :]
                attn = _attend_absorbed(q_nope, q_rope, rows, mask, layer, cfg)
            return decode._attn_out(attn, layer), pool, jnp.int32(0)
        with jax.named_scope("mla.step_attend"):
            if decode.is_quantized_pool(pool):
                rows = decode._latent_gather(pool, li, tables, dt, row.shape[-1])
                mask = (jnp.arange(rows.shape[1])[None, :] <= pos[:, None])[:, None, None, :]
                attn = _attend_absorbed(q_nope, q_rope, rows, mask, layer, cfg)
            else:
                wkv_b = decode._wdq(layer["wkv_b"], dt)
                q_c = jnp.einsum("bqhd,rhd->bqhr", q_nope, wkv_b[..., :dn])
                q = jnp.concatenate([q_c, q_rope], axis=-1)  # [S, 1, H, row]: one KV head, H its group
                q = jnp.pad(q, ((0, 0),) * 3 + ((0, pool["c"].shape[-1] - q.shape[-1]),))
                o_c = flash.paged_step_attend(
                    q, pool["c"], None, li, tables, pos + 1, sm_scale=scale, v_lanes=rkv
                ).astype(dt)
                attn = jnp.einsum("bqhr,rhd->bqhd", o_c, wkv_b[..., dn:])
        return decode._attn_out(attn, layer), pool

    return attend


# -- the two paged programs ------------------------------------------------------


def paged_prefill_chunk(params, pool, table, tokens, start, length, cfg):
    """``decode.paged_prefill_chunk`` for the latent stack: the chunk's rows
    ``[c | k_rope]`` are written at ``table``, then the chunk attends, tile by
    tile in the up-projected form (``_chunk_mixer``), to the rows the table
    holds up to ``start + length``.  Pad rows write to the trash block, are
    keys to no real row and route to no expert.  Returns ``(logits [vocab]
    f32, new_pool, counts)``."""
    c = cfg
    C = tokens.shape[0]
    qpos, valid, write_blk, write_off, _ = decode._chunk_addresses(
        pool, table, start, length, C
    )
    attend = _chunk_mixer(c, qpos, table, write_blk, write_off, start + length, valid)
    x = params["embed"].astype(c.dtype)[tokens][None]  # [1, C, D]
    x, pool, counts = _run_stack(x, params["block"], pool, c, attend, valid[None])
    # Only the last real token's logits are read: one row against the vocabulary.
    last = _rmsnorm(jnp.take(x[0], length - 1, axis=0), params["final_norm"])
    logits = jnp.einsum("d,dv->v", last, params["unembed"].astype(last.dtype))
    return logits.astype(jnp.float32), pool, counts


def paged_decode_step(params, pool, tables, tokens, pos, active, cfg, qweights=None):
    """``decode.paged_decode_step`` for the latent stack: every active slot one
    token, attention in the absorbed form over the rows its table gathers.
    Returns ``(logits [S, vocab] f32, new_pool, counts)``."""
    c = cfg
    S = tables.shape[0]
    bs = decode.pool_geometry(pool)[0]
    pos = jnp.where(active, pos, 0)
    write_blk = jnp.where(active, tables[jnp.arange(S), pos // bs], 0)
    write_off = jnp.where(active, pos % bs, 0)
    attend = _step_mixer(c, pos[:, None], tables, write_blk, write_off, pos)
    x = params["embed"].astype(c.dtype)[tokens][:, None, :]  # [S, 1, D]
    blk, unembed = _with_qweights(params, qweights)
    x, pool, counts = _run_stack(x, blk, pool, c, attend, active[:, None])
    logits = decode._unembed(x, params["final_norm"], unembed)
    return logits[:, 0].astype(jnp.float32), pool, counts
