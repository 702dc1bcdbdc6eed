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
(``transformer._rope``); no YaRN factor.

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

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from polyaxon_tpu.models import decode
from polyaxon_tpu.models.transformer import _rmsnorm, _rope
from polyaxon_tpu.parallel import flash
from polyaxon_tpu.parallel.experts import COUNT_NAMES, experts_mlp, route, route_softmax

DENSE = "dense_mlp"
EXPERTS = "expert_mlp"
_ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
_NORMS = ("attn_norm", "mlp_norm", "q_norm", "kv_norm")

#: What this stack cannot follow yet, and what each would take.
REFUSED = {
    "spec_decode": "the verify step has no latent-attention form",
    "mesh": "the latent pool and the expert product have no sharding rules "
    "(the expert exchange across chips is not written)",
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


def n_params(cfg) -> int:
    c = cfg
    n_dense, n_exp = _counts(c)
    D, H = c.d_model, c.n_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    attn = (D * c.q_lora_rank + c.q_lora_rank * H * qk + D * row_width(c)
            + c.kv_lora_rank * H * (c.qk_nope_head_dim + c.v_head_dim)
            + H * c.v_head_dim * D + c.q_lora_rank + c.kv_lora_rank + 2 * D)
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
    the same order."""
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
    return {
        "embed": norm(c.vocab_size, D, scale=1.0),
        "unembed": norm(D, c.vocab_size, scale=D**-0.5),
        "final_norm": jnp.ones((D,), dt),
        "block": block,
    }


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
    embeddings and every matmul weight.  The norms, the router and its bias
    keep their dtype."""
    blk = params["block"]
    out = {**blk, **{n: cast(blk[n]) for n in _QUANTIZED[""]}}
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


def _queries(h, layer, positions, cfg):
    """``h [B, T, D]`` -> ``(q_nope [B, T, H, nope], q_rope [B, T, H, rope])``,
    the second rotated to ``positions [B, T]``."""
    dt = h.dtype
    cq = jnp.einsum("btd,dr->btr", h, decode._wdq(layer["wq_a"], dt))
    cq = _rmsnorm(cq, layer["q_norm"])
    q = jnp.einsum("btr,rhe->bthe", cq, decode._wdq(layer["wq_b"], dt))
    dn = cfg.qk_nope_head_dim
    return q[..., :dn], _rope(q[..., dn:], positions, cfg.rope_theta)


def _latent_row(h, layer, positions, cfg):
    """``h [B, T, D]`` -> the pool's row ``[c | k_rope]`` ``[B, T, row]``."""
    kv = jnp.einsum("btd,dr->btr", h, decode._wdq(layer["wkv_a"], h.dtype))
    rkv = cfg.kv_lora_rank
    c = _rmsnorm(kv[..., :rkv], layer["kv_norm"])
    k_rope = _rope(kv[..., None, rkv:], positions, cfg.rope_theta)[:, :, 0]
    return jnp.concatenate([c, k_rope], axis=-1)


def _softmax_over(s, mask, dtype):
    s = jnp.where(mask, s, -1e30)
    return jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dtype)


def _attend_absorbed(q_nope, q_rope, rows, mask, layer, cfg):
    """The same equation with ``W_kvb`` folded into the query and the output:
    scores against ``c`` itself, the value read from ``c`` and up-projected
    once a query.  No per-head keys for ``K`` rows."""
    dt = q_nope.dtype
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    scale = (dn + cfg.qk_rope_head_dim) ** -0.5
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
            chosen, gates = route(flat, layer["router"], layer["router_bias"], k, scale)
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


def _no_counts():
    return jnp.zeros((len(COUNT_NAMES),), jnp.int32)


def _run_stack(x, blk, pool, cfg, attend, valid):
    """The layer loop: one ``lax.scan`` a run of the pattern, ``(x, pool)`` the
    carry, each layer the pre-norm block around ``attend(h, layer, layer index,
    pool) -> (output, pool)`` and its kind's MLP.  Returns ``(x, pool,
    counts)``, ``counts`` what the expert layers routed (``COUNT_NAMES``)."""
    shared = {n: blk[n] for n in _ATTN + _NORMS}
    first = {DENSE: 0, EXPERTS: 0}
    layer0 = 0
    counts = _no_counts()
    for kind, n in runs(cfg):
        name = "dense" if kind == DENSE else "experts"
        lo = first[kind]
        # The routed experts' stacks stay out of the scanned inputs: the loop
        # would cut a layer's copy out of them for every iteration.
        stacks = {w: blk[name][w] for w in ("wi", "wg", "wd")} if kind == EXPERTS else {}
        xs = (
            jax.tree.map(lambda w: w[layer0 : layer0 + n], shared),
            jax.tree.map(
                lambda w: w[lo : lo + n],
                {w: v for w, v in blk[name].items() if w not in stacks},
            ),
            layer0 + jnp.arange(n),
            lo + jnp.arange(n),
        )

        def body(carry, inputs, kind=kind, stacks=stacks):
            x, pool = carry
            layer, mlp, li, ki = inputs
            mix, pool = attend(_rmsnorm(x, layer["attn_norm"]), layer, li, pool)
            x = x + mix
            h = _rmsnorm(x, layer["mlp_norm"])
            if kind == DENSE:
                return (x + decode._gated_mlp(h, mlp), pool), _no_counts()
            y, c = _expert_mlp(h, mlp, valid, cfg, stacks, ki)
            return (x + y, pool), c

        (x, pool), c = lax.scan(body, (x, pool), xs)
        counts = counts + jnp.sum(c, axis=0)
        first[kind] += n
        layer0 += n
    return x, pool, counts


def _chunk_mixer(cfg, qpos, table, write_blk, write_off, live_end):
    """A prompt chunk's attention mixer ``(h [1, C, D], layer, layer index,
    pool) -> (output, pool)``: the chunk's rows written, then the chunk attends
    in the up-projected form by key tiles of ``table``, first tile to the one
    that holds ``live_end - 1`` (``decode._walk_table_tiles``).  A turn gathers
    one tile's rows, up-projects them to keys and values and attends them in
    the flash forward kernel (scores float32, in VMEM; the query's offset
    against the tile is its causal mask), and the tile's ``(o, lse)`` is merged
    into the running pair: no array has the table's width.  A row no tile
    admitted a key to (a chunk of length 0, the warm-up's) comes out zero."""
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    scale = (dn + cfg.qk_rope_head_dim) ** -0.5

    def attend(h, layer, li, pool):
        dt = h.dtype
        q_nope, q_rope = _queries(h, layer, qpos[None], cfg)
        row = _latent_row(h, layer, qpos[None], cfg)[0]
        pool = decode._latent_append(pool, li, row, write_blk, write_off)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)[0].swapaxes(0, 1)  # [H, C, nope + rope]
        wkv_b = decode._wdq(layer["wkv_b"], dt)
        H, C = q.shape[:2]

        def turn(carry, blocks, k0):
            rows = decode._latent_gather(pool, li, blocks, dt, row.shape[-1])
            c, k_rope = rows[:, :rkv], rows[:, rkv:]
            kv = jnp.einsum("kr,rhe->hke", c, wkv_b)
            k_rope = jnp.broadcast_to(k_rope, (H, *k_rope.shape))
            tile = flash.flash_block_fwd(
                q, jnp.concatenate([kv[..., :dn], k_rope], axis=-1), kv[..., dn:],
                causal=True, sm_scale=scale, q_offset=qpos[0] - k0, name="mla_chunk_tile",
            )
            return flash._merge(*carry, *tile)

        with jax.named_scope("mla.chunk_attend"):
            o, _ = decode._walk_table_tiles(
                table, decode.pool_geometry(pool)[0], live_end, turn,
                (jnp.zeros((H, C, cfg.v_head_dim), jnp.float32), jnp.full((H, C), -jnp.inf)),
            )
        return decode._attn_out(o.astype(dt).swapaxes(0, 1)[None], layer), pool

    return attend


def _step_mixer(cfg, positions, tables, write_blk, write_off, pos):
    """A decode step's attention mixer ``(h [S, 1, D], layer, layer index,
    pool) -> (output, pool)`` in the absorbed form (``W_kvb`` folded into the
    query and the output, the rows themselves keys and values).  Every lane's
    row is written; then, by what the pool holds: rows in the compute dtype are
    read where they lie, ``[q_c | q_rope]`` against each lane's pages up to the
    one that holds ``pos`` (``flash.paged_step_attend``: nothing has the
    table's width); int8 rows under a scale a row are gathered over the whole
    table and attended by :func:`_attend_absorbed`, the plain rule."""
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    scale = (dn + cfg.qk_rope_head_dim) ** -0.5

    def attend(h, layer, li, pool):
        dt = h.dtype
        q_nope, q_rope = _queries(h, layer, positions, cfg)
        row = _latent_row(h, layer, positions, cfg)
        pool = decode._latent_append(pool, li, row[:, 0], write_blk, write_off)
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
    attend = _chunk_mixer(c, qpos, table, write_blk, write_off, start + length)
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
