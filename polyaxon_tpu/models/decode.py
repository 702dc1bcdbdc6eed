"""Autoregressive decoding for the flagship LM: KV cache + sampling.

The inference half of the model family (the reference has no serving
story at all — its notebooks/tensorboards are the closest surface; this
is capability beyond parity).  TPU-first shape:

- **static-shape KV cache** — a [L, B, max_len, Hkv, d] ring of keys and
  values updated with ``lax.dynamic_update_slice`` at the current
  position; no dynamic shapes anywhere, so the whole decode loop is one
  compiled ``lax.scan``.
- **GQA-native cache** — the cache stores the UNEXPANDED KV heads
  (n_kv_heads), the dominant HBM saving of grouped-query attention at
  inference; broadcast to the query heads happens inside the per-token
  attention contraction.
- **prefill via one batched forward** over the prompt (MXU-shaped), then
  one-token steps; both paths share the same cache layout.

Decode is memory-bandwidth-bound (one token's FLOPs against the whole
cache), so attention here is plain einsum with a position mask — the
flash kernel's VMEM blocking buys nothing at query length 1.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from polyaxon_tpu.models.transformer import (
    TransformerConfig,
    _dense_attention,
    _rmsnorm,
    _rope,
    forward,
    stack_module,
)
from polyaxon_tpu.parallel import flash


def init_cache(
    cfg: TransformerConfig, batch: int, max_len: int
) -> Dict[str, jax.Array]:
    """Zeroed KV cache: k/v [L, B, max_len, Hkv, d] in the compute dtype."""
    c = cfg
    shape = (c.n_layers, batch, max_len, c.kv_heads, c.head_dim)
    return {
        "k": jnp.zeros(shape, c.dtype),
        "v": jnp.zeros(shape, c.dtype),
    }


#: Block matmul weights the int8 path quantizes — ONE list shared by
#: quantize_weights (emit) and decode_step (consume) so they can't drift.
#: Mapped to the contraction dims of each layout: [L,D,H,k] contracts D;
#: [L,H,k,D] contracts H,k; [L,D,F] contracts D; [L,F,D] contracts F.
QUANTIZED_BLOCK_WEIGHTS = {
    "wq": (1,),
    "wk": (1,),
    "wv": (1,),
    "wo": (1, 2),
    "wi": (1,),
    "wg": (1,),
    "wd": (1,),
}


def quantize_weights(params: Dict[str, Any]) -> Dict[str, Any]:
    """int8 weight-only quantization of the decode matmul weights.

    Decode is weight-HBM-bandwidth-bound (the whole parameter set streams
    per token while the MXU idles), so halving the bytes is ~linear
    speedup: measured 469 → 711 tok/s (+51%) GQA-8 and 295 → 419 (+42%)
    full-MHA on the 671M bench model (v5e); single-step fidelity: 2.4%
    relative logits error, top-1 intact (docs/bench-notes.md).
    Symmetric per-output-channel scales over each weight's CONTRACTION
    dims; norms and the embedding table stay full precision (tiny, and
    the gather is not a matmul).  Returns a tree of ``(int8_q,
    f32_scale)`` pairs the decode path consumes via :func:`_wdq`;
    training params are untouched — prefill still rides the
    full-precision forward.
    """
    import numpy as np

    def q(w, axes):
        w = np.asarray(w, np.float32)
        amax = np.max(np.abs(w), axis=axes, keepdims=True) + 1e-12
        scale = (amax / 127.0).astype(np.float32)
        qi = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
        return (jnp.asarray(qi), jnp.asarray(scale))

    blk = params["block"]
    if "linear" in blk:  # the hybrid stack: its own tree, both kinds of layer
        from polyaxon_tpu.models import hybrid

        return hybrid.quantize_weights(params, q)
    if "wkv_a" in blk:  # the latent stack: its own tree too
        from polyaxon_tpu.models import latent_moe

        return latent_moe.quantize_weights(params, q)
    if "window" in blk:  # the window stack: likewise
        from polyaxon_tpu.models import window_moe

        return window_moe.quantize_weights(params, q)
    out = {
        name: q(blk[name], axes)
        for name, axes in QUANTIZED_BLOCK_WEIGHTS.items()
    }
    out["unembed"] = q(params["unembed"], (0,))  # [D, V]: contract D
    return out


def serving_params(params: Dict[str, Any], cfg: TransformerConfig) -> Dict[str, Any]:
    """The tree the paged programs read, rounded to the compute dtype once.

    The programs read the embeddings and every matmul weight ONLY through
    ``.astype(cfg.dtype)`` / :func:`_wdq`; handed ``param_dtype`` float32
    leaves, XLA materialises those casts in HBM in every program (a decode
    step, a prefill chunk, a verify step: 9-12 ms each at 1.1-1.6 B
    parameters on a v5e).  Here the same ``astype`` runs once, one leaf at
    a time, so each program sees the operand bits it saw and its own
    ``astype`` lowers to nothing.  The norm scales stay as they are (a few
    KB, not read at ``cfg.dtype`` everywhere).  A leaf that is
    ``cfg.dtype`` already comes back itself: a second call, or a model at
    float32 compute, costs nothing.  The float32 tree is still what is made,
    restored and quantized (:func:`quantize_weights` reads it, not this).
    """
    dtype = jnp.dtype(cfg.dtype)

    def cast(w):
        return w if w.dtype == dtype else w.astype(dtype)

    blk = params["block"]
    if "linear" in blk:  # the hybrid stack: its own leaf list
        from polyaxon_tpu.models import hybrid

        return hybrid.serving_params(params, cast)
    if "wkv_a" in blk:  # the latent stack: its own leaf list
        from polyaxon_tpu.models import latent_moe

        return latent_moe.serving_params(params, cast)
    if "window" in blk:  # the window stack: its own leaf list
        from polyaxon_tpu.models import window_moe

        return window_moe.serving_params(params, cast)
    return {
        **params,
        "embed": cast(params["embed"]),
        "unembed": cast(params["unembed"]),
        "block": {**blk, **{n: cast(blk[n]) for n in QUANTIZED_BLOCK_WEIGHTS}},
    }


def _wdq(w, dtype):
    """Weight as compute dtype: dequantize ``(int8, scale)`` pairs (XLA
    fuses the convert+scale into the consuming matmul's operand read —
    the HBM stream stays int8) or plain astype."""
    if isinstance(w, tuple):
        qi, scale = w
        return qi.astype(dtype) * scale.astype(dtype)
    return w.astype(dtype)


# -- the served layer, piece by piece ---------------------------------------
# Every program below (and ``models/hybrid.py``'s two) is index arithmetic, a
# mixer of a few lines over these pieces, a runner and the unembedding.  A
# program's lowered text follows the ORDER in which the pieces emit operations
# (``tests/test_serving/test_dense_programs_unchanged.py`` holds its digests).


def _qk_norm(x, w):
    """RMSNorm over the whole projection (all heads), then split again."""
    shape = x.shape
    return _rmsnorm(x.reshape(shape[:-2] + (-1,)), w).reshape(shape)


def _qkv(h, layer):
    """One layer's projections of ``h [B, T, D]``: q ``[B, T, H, d]``, k and v
    ``[B, T, Hkv, d]``.  A layer that carries ``q_norm`` / ``k_norm`` (the
    hybrid stack's full-attention layers) normalises q and k over the whole
    projection before the heads are split."""
    dt = h.dtype
    q = jnp.einsum("btd,dhk->bthk", h, _wdq(layer["wq"], dt))
    if "q_norm" in layer:
        q = _qk_norm(q, layer["q_norm"])
    k = jnp.einsum("btd,dhk->bthk", h, _wdq(layer["wk"], dt))
    if "k_norm" in layer:
        k = _qk_norm(k, layer["k_norm"])
    v = jnp.einsum("btd,dhk->bthk", h, _wdq(layer["wv"], dt))
    return q, k, v


def _rotary(q, k, positions, theta):
    """q and k rotated to ``positions [B, T]``; ``theta`` None (a hybrid
    model's ``rope_theta``) applies no rotary embedding."""
    if theta is None:
        return q, k
    return _rope(q, positions, theta), _rope(k, positions, theta)


def _attn_out(attn, layer):
    """``W_o`` over the heads: attn ``[B, T, H, d]`` -> ``[B, T, D]``."""
    return jnp.einsum("bthk,hkd->btd", attn, _wdq(layer["wo"], attn.dtype))


def _gated_mlp(h, layer):
    """``W_d (silu(W_g h) * W_i h)`` for ``h [B, T, D]``."""
    dt = h.dtype
    up = jnp.einsum("btd,df->btf", h, _wdq(layer["wi"], dt))
    gate = jnp.einsum("btd,df->btf", h, _wdq(layer["wg"], dt))
    y = jax.nn.silu(gate) * up
    return jnp.einsum("btf,fd->btd", y, _wdq(layer["wd"], dt))


def _prenorm_block(x, layer, mixer):
    """The dense model's block, ``x + mixer(rmsnorm(x))`` then ``x +
    mlp(rmsnorm(x))``.  ``mixer(h) -> (output, what it updated)``: the cache
    slices of the sequential path, the pool of the paged programs."""
    mix, updated = mixer(_rmsnorm(x, layer["attn_norm"]))
    x = x + mix
    return x + _gated_mlp(_rmsnorm(x, layer["mlp_norm"]), layer), updated


def _unembed(x, final_norm, unembed):
    """Final norm and unembedding: ``x [B, T, D]`` -> logits ``[B, T, V]``."""
    x = _rmsnorm(x, final_norm)
    return jnp.einsum("btd,dv->btv", x, _wdq(unembed, x.dtype))


def _attend(q, ck, cv, group, valid_at):
    """The attention core of every program that reads a cache: q
    ``[B, T, H, d]`` over ck/cv ``[B, K, Hkv, d]``, masked float32 softmax.
    ``valid_at(K)`` is the program's mask, broadcastable to the scores
    ``[B, Hkv, g, T, K]``: a function, because the text the digests hold
    builds the mask after the scores."""
    B, K, Hkv, d = ck.shape
    T = q.shape[1]
    scale = d**-0.5
    # GQA stays grouped INSIDE the contraction — the cache is never
    # materialized at the query-head count, which is the point of storing
    # unexpanded heads in the bandwidth-bound decode loop.
    qg = q.reshape(B, T, Hkv, group, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck) * scale
    s = jnp.where(valid_at(K), s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, cv)
    return out.reshape(B, T, Hkv * group, d)


def _attend_cached(q, ck, cv, pos, group):
    """One-token attention against the cache.

    q: [B, 1, H, d]; ck/cv: [B, max_len, Hkv, d]; ``pos`` is the current
    absolute position (entries > pos are future/zero slots — masked).
    """
    return _attend(
        q, ck, cv, group,
        lambda L: (jnp.arange(L) <= pos)[None, None, None, None, :],
    )


def _with_qweights(params, qweights):
    """``(layer tree, unembedding)`` a dense program scans and reads: the
    float tree's, or with ``qweights`` (:func:`quantize_weights`) the int8
    pairs in the quantized weights' places."""
    blk = params["block"]
    if qweights is None:
        return blk, params["unembed"]
    # Quantized (q, scale) pairs are ordinary pytree leaves-of-tuples:
    # scan slices both halves per layer and _wdq sees the pair.
    layers = {n: blk[n] for n in ("attn_norm", "mlp_norm")}
    layers.update((n, qweights[n]) for n in QUANTIZED_BLOCK_WEIGHTS)
    return layers, qweights["unembed"]


def decode_step(
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    token: jax.Array,
    pos: jax.Array,
    cfg: TransformerConfig,
    qweights: Optional[Dict[str, Any]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """token [B] at absolute ``pos`` → (logits [B, vocab], updated cache).

    With ``qweights`` (from :func:`quantize_weights`) the matmul weights
    stream int8 from HBM, dequantized inside each contraction."""
    c = cfg
    x = params["embed"].astype(c.dtype)[token][:, None, :]  # [B,1,D]
    layers, unembed = _with_qweights(params, qweights)

    def layer_body(x, inputs):
        layer, ck, cv = inputs  # this layer's cache slices [B, max_len, Hkv, d]

        def mixer(h):
            q, k, v = _qkv(h, layer)
            q, k = _rotary(q, k, jnp.full((x.shape[0], 1), pos), c.rope_theta)
            # the token's KV rows land at ``pos``; rows beyond it are masked
            nk = lax.dynamic_update_slice(ck, k, (0, pos, 0, 0))
            nv = lax.dynamic_update_slice(cv, v, (0, pos, 0, 0))
            attn = _attend_cached(q, nk, nv, pos, c.n_heads // c.kv_heads)
            return _attn_out(attn, layer), (nk, nv)

        return _prenorm_block(x, layer, mixer)

    x, (new_ck, new_cv) = lax.scan(
        layer_body, x, (layers, cache["k"], cache["v"])
    )
    logits = _unembed(x, params["final_norm"], unembed)
    return logits[:, 0].astype(jnp.float32), {"k": new_ck, "v": new_cv}


def prefill(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: Dict[str, jax.Array],
    cfg: TransformerConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Run the prompt [B, T] through the model, filling cache[:, :, :T].

    Rides the TRAINING forward (``return_kv=True``) — one batched
    MXU-shaped pass whose block is the exact code training runs, so
    prefill can never drift from it; only the cache write lives here.
    """
    logits, (k, v) = forward(params, tokens, cfg, return_kv=True)
    ck = lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0, 0))
    cv = lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0, 0))
    return logits[:, -1], {"k": ck, "v": cv}


# -- paged (block-table) cache ops -----------------------------------------
# The serving engine (polyaxon_tpu/serving/engine.py) admits and retires
# requests at decode-step granularity over ONE pool of KV, vLLM-style: KV
# lives in fixed-size blocks [L, num_blocks, block_size, Hkv, d] and each
# in-flight sequence owns a BLOCK TABLE of physical block ids covering its
# logical positions.  Two consequences a cache row per sequence can't express:
#
# - **sharing** — two sequences with a common token prefix point their
#   leading table entries at the SAME physical blocks (the engine
#   ref-counts them; a block a sequence must WRITE into is copied first);
# - **chunked prefill** — a prompt is inserted C tokens at a time by
#   :func:`paged_prefill_chunk`, each chunk attending to the KV already in
#   the table, so a long prompt never stalls the decode loop for its full
#   length.
#
# Shapes stay static everywhere (pool size, table width, chunk bucket);
# tables, positions, and the active mask are DATA, so one compiled step
# serves any mix of in-flight requests and steady-state serving never
# recompiles.  Block 0 is reserved by the engine as a trash lane: inactive
# decode lanes and prompt-pad writes land there, and unset table entries
# point at it — every such read is masked by the position mask before it can
# influence a live row.

#: Pool leaves that hold a hybrid model's per-slot recurrent state
#: (``models/hybrid.py``), not KV blocks.
REC_LEAVES = ("rec_s", "rec_c")
#: Pool leaves that hold a window layer's per-slot ring of K and V rows
#: (``models/window_moe.py``), in the compute dtype or as int8 rows and scales.
WIN_LEAVES = ("win_k", "win_v")
WIN_LEAVES_INT8 = ("win_k_q", "win_k_scale", "win_v_q", "win_v_scale")
#: Every per-slot leaf: what a block copy leaves alone and a snapshot copies.
SLOT_LEAVES = REC_LEAVES + WIN_LEAVES + WIN_LEAVES_INT8


def init_block_pool(
    cfg: TransformerConfig,
    num_blocks: int,
    block_size: int,
    kv_dtype: Optional[str] = None,
) -> Dict[str, jax.Array]:
    """Zeroed paged KV pool: k/v [L, num_blocks, block_size, Hkv, d].

    A latent-attention model (``cfg.stack == "latent"``) keeps ONE row a token
    a layer instead, ``[c | k_rope]``: leaf ``c [L, num_blocks, block_size,
    kv_lora_rank + qk_rope_head_dim]``, no head axis (the block's tokens are
    the second-minor dimension, a whole bfloat16 tile of 16).  Its int8 form
    is ``c_q`` / ``c_scale``, one scale a row.  With a lightning indexer
    (``index_n_heads``) a second block-addressed leaf beside it, ``ik [L,
    num_blocks, block_size, index_head_dim]``, holds a token's indexer key; it
    has no int8 form, and ``kv_dtype="int8"`` is refused by name
    (``latent_moe.REFUSED``).

    With ``kv_dtype="int8"`` the pool instead stores symmetric-quantized
    rows plus their scales — ``k_q``/``v_q`` int8 [L, NB, bs, Hkv, d] and
    ``k_scale``/``v_scale`` f32 [L, NB, bs, Hkv] (one scale per appended
    row per kv-head, so appends quantize once and never touch rows
    already in the block).  At head_dim d that is (d + 4) bytes per head
    row versus 4d for an f32 pool — under 0.3× the HBM at the same
    ``num_blocks × block_size``, i.e. >2× the live blocks at a fixed
    memory budget.
    """
    c = cfg
    if kv_dtype is not None and str(kv_dtype) != "int8":
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r} (int8 or None)")
    if c.stack == "latent":
        from polyaxon_tpu.models.latent_moe import pool_row_width

        shape = (c.n_layers, num_blocks, block_size, pool_row_width(c))
        names = ("c",)
        if c.index_n_heads:
            from polyaxon_tpu.models.latent_moe import LatentStackError

            if kv_dtype is not None:
                raise LatentStackError("kv_quantize")
            return {"c": jnp.zeros(shape, c.dtype),
                    "ik": jnp.zeros(shape[:-1] + (c.index_head_dim,), c.dtype)}
    else:
        shape = (c.n_kv_layers, num_blocks, block_size, c.pool_kv_heads, c.head_dim)
        names = ("k", "v")
    if kv_dtype is None:
        return {n: jnp.zeros(shape, c.dtype) for n in names}
    pool = {}
    for n in names:
        pool[n + "_q"] = jnp.zeros(shape, jnp.int8)
        pool[n + "_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    return pool


def _row_leaf(pool: Dict[str, jax.Array]) -> jax.Array:
    """The leaf that holds a token's rows: K of a KV pool, ``c`` of a latent
    one, in either layout."""
    for name in ("k", "k_q", "c", "c_q"):
        if name in pool:
            return pool[name]
    raise KeyError(f"no row leaf among {sorted(pool)}")


def is_quantized_pool(pool: Dict[str, jax.Array]) -> bool:
    """True for the int8 pool layout (``k_q`` ... ``v_scale``, or ``c_q`` /
    ``c_scale``)."""
    return "k_q" in pool or "c_q" in pool


def pool_geometry(pool: Dict[str, jax.Array]) -> Tuple[int, int, int]:
    """(block_size, kv_heads, head_dim) for either pool layout; a latent
    pool's row counts as one head of the row's width."""
    leaf = _row_leaf(pool)
    return leaf.shape[2], (leaf.shape[3] if leaf.ndim == 5 else 1), leaf.shape[-1]


def kv_block_bytes(
    cfg: TransformerConfig, block_size: int, kv_dtype: Optional[str] = None
) -> int:
    """Device bytes ONE pool block costs (all layers, k+v, incl. scales).

    The sizing primitive for fixed-HBM capacity math: at a fixed byte
    budget B the pool holds ``B // kv_block_bytes(...)`` blocks.
    """
    c = cfg
    if kv_dtype is not None and str(kv_dtype) != "int8":
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r} (int8 or None)")
    if c.stack == "latent":  # one row a token a layer, no k+v pair
        from polyaxon_tpu.models.latent_moe import pool_row_width

        # ... and the indexer's key beside it, where there is an indexer
        rows, width = c.n_layers * block_size, pool_row_width(c) + c.index_head_dim * bool(
            c.index_n_heads)
    else:
        rows = 2 * c.n_kv_layers * block_size * c.pool_kv_heads  # head-rows, k+v
        width = c.head_dim
    if kv_dtype is None:
        return rows * width * jnp.dtype(c.dtype).itemsize
    return rows * (width + 4)  # int8 row + one f32 scale


def _kv_quant(rows: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 per-head-row quantization: rows [..., Hkv, d] →
    (int8 [..., Hkv, d], f32 scale [..., Hkv]).  Zero rows (trash-lane
    writes, padding) get scale 0 and dequantize back to exact zeros."""
    r = rows.astype(jnp.float32)
    scale = jnp.max(jnp.abs(r), axis=-1) / 127.0
    q = jnp.round(r / jnp.where(scale > 0, scale, 1.0)[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def _kv_dequant(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Fused-into-the-read dequant (the ``_wdq`` pattern for KV): the
    gather streams int8 + one scale per head row; XLA fuses the widen
    and multiply into the attention einsum's operand read."""
    return q.astype(dtype) * scale[..., None].astype(dtype)


def _pool_append(
    pool: Dict[str, jax.Array],
    name: str,
    layer_idx: jax.Array,
    rows: jax.Array,
    write_blk: jax.Array,
    write_off: jax.Array,
) -> Dict[str, jax.Array]:
    """Scatter freshly-computed KV rows for layer ``layer_idx`` into the
    STACKED pool leaves at (layer_idx, write_blk, write_off), quantizing
    on append for the int8 layout.  ``rows``: [..., Hkv, d] aligned with
    write_blk/write_off [...] — one index pair per row, any leading shape
    (a decode step's [S], a verify step's [S, T]).  The pool is the layer
    loop's carry, so the scatter updates the donated buffer in place: no
    per-layer slice is ever cut out or stacked back."""
    at = (layer_idx, write_blk, write_off)
    if name + "_q" in pool:
        q, scale = _kv_quant(rows)
        return {
            **pool,
            name + "_q": pool[name + "_q"].at[at].set(q),
            name + "_scale": pool[name + "_scale"].at[at].set(scale),
        }
    leaf = pool[name]
    return {**pool, name: leaf.at[at].set(rows.astype(leaf.dtype))}


def _pool_gather(
    pool: Dict[str, jax.Array],
    name: str,
    layer_idx: jax.Array,
    table: jax.Array,
    dtype,
) -> jax.Array:
    """Gather layer ``layer_idx``'s KV rows for a block table straight out
    of the stacked pool leaves — ONE gather addressed by (layer, block) —
    dequantizing int8 leaves fused into the read.
    table [..., W] → [..., W, bs, Hkv, d]."""
    if name + "_q" in pool:
        return _kv_dequant(
            pool[name + "_q"][layer_idx, table],
            pool[name + "_scale"][layer_idx, table],
            dtype,
        )
    return pool[name][layer_idx, table]


def _kv_through_table(pool, layer_idx, k, v, table, write_blk, write_off, dtype):
    """How every paged program of both stacks reads KV through a block table:
    append this call's rows to layer ``layer_idx``, then gather the whole
    table width.  ROADMAP S1 (attend without gathering every position of the
    table) is a change to this function and the attention it feeds.

    k, v: ``[B, T, Hkv, d]`` as projected.  ``write_blk`` / ``write_off`` hold
    one address per row and lack the axis of size one: a chunk's batch
    (``table [W]``, addresses ``[C]``), a decode step's T (``table [S, W]``,
    addresses ``[S]``); a verify step's ``[S, T]`` has both.  Where a pool row
    holds more heads than the model (``TransformerConfig.pool_kv_heads``) the
    rows go in padded with zeros and come back sliced.  Returns ``(pool, ck,
    cv)``, ck/cv ``[B, W * bs, Hkv, d]`` in logical-position order at
    ``dtype``; the rows just written are among them, which makes a chunk's
    and a verify run's own tokens their causal keys.
    """
    bs, Hp, d = pool_geometry(pool)
    Hkv = k.shape[-2]
    lanes = 1 if table.ndim == 1 else table.shape[0]

    def rows(new):
        if table.ndim == 1:
            new = new[0]
        elif write_blk.ndim == 1:
            new = new[:, 0]
        if Hp == Hkv:
            return new
        return jnp.pad(new, ((0, 0),) * (new.ndim - 2) + ((0, Hp - Hkv), (0, 0)))

    def gathered(pool, name):
        got = _pool_gather(pool, name, layer_idx, table, dtype)
        got = got.reshape(lanes, table.shape[-1] * bs, Hp, d)
        return got if Hp == Hkv else got[:, :, :Hkv]

    # V's rows are cut out after K's are in: the order of the text the digests hold.
    pool = _pool_append(pool, "k", layer_idx, rows(k), write_blk, write_off)
    pool = _pool_append(pool, "v", layer_idx, rows(v), write_blk, write_off)
    return pool, gathered(pool, "k"), gathered(pool, "v")


#: Keys of one tile of a prompt chunk's walk through its block table
#: (:func:`_walk_table_tiles`).  A constant of the shapes: the engine's five
#: chunk buckets stay all that mints a compilation.
TILE_KEYS = 1024


def _tile_blocks(table_width: int, block_size: int) -> int:
    """Blocks of one key tile: ``TILE_KEYS`` keys, or the whole of a narrower
    table."""
    return min(max(TILE_KEYS // block_size, 1), table_width)


def chunk_keys_attended(
    cfg: TransformerConfig, live_end: int, table_width: int, block_size: int
) -> int:
    """Key positions a prompt chunk that ends at ``live_end`` (``start +
    length``) attends, by the program that serves ``cfg``: the latent stack
    walks whole tiles, first to the one that holds ``live_end - 1``
    (:func:`_walk_table_tiles`), and so do the window stack's full layers;
    the dense and the hybrid stack attend the whole table.  The host's count
    for ``/v1/stats``."""
    if cfg.stack not in ("latent", "window"):
        return table_width * block_size
    tile = _tile_blocks(table_width, block_size) * block_size
    return -(-live_end // tile) * tile


def step_keys_attended(
    cfg: TransformerConfig,
    live_ends,
    table_width: int,
    block_size: int,
    kv_dtype: Optional[str] = None,
) -> int:
    """Key positions ONE decode step attends over its active lanes, whose keys
    number ``live_ends`` (each lane's position + 1), by the program that serves
    ``cfg``: the latent stack and the window stack's full layers read each
    lane's pages by whole compute blocks up to the one that holds its live end
    (``flash.paged_step_attend``); the dense and the hybrid stack, and any
    stack over an int8 pool, gather the whole table a lane; a latent stack
    with an indexer attends the ``index_topk`` rows it selected, or all while
    a lane sees no more.  The host's count for ``/v1/stats``, beside
    :func:`chunk_keys_attended`."""
    if cfg.stack == "latent" and cfg.index_n_heads:
        return sum(min(int(end), cfg.index_topk) for end in live_ends)
    if cfg.stack not in ("latent", "window") or kv_dtype is not None:
        return len(live_ends) * table_width * block_size
    block = flash.step_block_pages(table_width, block_size) * block_size
    return sum(-(-int(end) // block) * block for end in live_ends)


def _walk_table_tiles(table, block_size, live_end, turn, carry):
    """Fold ``turn(carry, blocks [tile blocks], first key position) -> carry``
    over the key tiles of one sequence's ``table [W]``, from the first tile to
    the one that holds position ``live_end - 1``.  The trip count is a traced
    scalar (a ``while`` in the program), so one compilation serves every live
    end and nothing of the table's width is formed; a table that is no whole
    number of tiles is padded with the trash block, whose positions no query
    reaches."""
    W = table.shape[0]
    tb = _tile_blocks(W, block_size)
    table = jnp.pad(table, (0, -W % tb))
    keys = tb * block_size

    def body(t, carry):
        return turn(carry, lax.dynamic_slice(table, (t * tb,), (tb,)), t * keys)

    return lax.fori_loop(0, -(-live_end // keys), body, carry)


def _kv_append(pool, layer_idx, k, v, write_blk, write_off):
    """Append rows ``k`` / ``v [..., Hkv, d]`` of layer ``layer_idx`` at one
    address a row, padded with zero heads where the pool's rows hold more
    (:func:`_kv_through_table`'s first half, for a caller that reads the table
    by tiles instead of whole)."""
    pad = pool_geometry(pool)[1] - k.shape[-2]
    for name, new in (("k", k), ("v", v)):
        if pad:
            new = jnp.pad(new, ((0, 0),) * (new.ndim - 2) + ((0, pad), (0, 0)))
        pool = _pool_append(pool, name, layer_idx, new, write_blk, write_off)
    return pool


def _kv_tile(pool, layer_idx, blocks, dtype, kv_heads):
    """K and V of layer ``layer_idx`` for one key tile's ``blocks [tile
    blocks]``, each ``[Hkv, tile keys, d]`` in logical-position order at
    ``dtype``: what a turn of :func:`_walk_table_tiles` attends where the pool
    keeps K and V (int8 rows dequantised in the read, heads the pool's rows
    hold beyond the model's sliced off)."""
    def one(name):
        got = _pool_gather(pool, name, layer_idx, blocks, dtype)
        got = got.reshape((-1,) + got.shape[2:])[:, :kv_heads]
        return got.swapaxes(0, 1)

    return one("k"), one("v")


def _latent_append(pool, layer_idx, new, write_blk, write_off):
    """Write rows ``new [..., width]`` of layer ``layer_idx`` into a latent
    pool's one leaf, ``c``, padded with zeros to the width the pool holds."""
    pad = pool_geometry(pool)[2] - new.shape[-1]
    if pad:
        new = jnp.pad(new, ((0, 0),) * (new.ndim - 1) + ((0, pad),))
    return _pool_append(pool, "c", layer_idx, new, write_blk, write_off)


def _latent_gather(pool, layer_idx, table, dtype, used):
    """Layer ``layer_idx``'s rows for ``table [..., W]`` (a whole table, a
    tile's blocks) in logical-position order, ``[..., W * bs, used]``: the
    pool's pad sliced off."""
    got = _pool_gather(pool, "c", layer_idx, table, dtype)
    return got.reshape(*table.shape[:-1], -1, got.shape[-1])[..., :used]


def _kv_leaves(pool: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """The leaves addressed by block: all but the per-slot rows of a hybrid or
    a window model (``SLOT_LEAVES``), which ride the same dict."""
    return {n: leaf for n, leaf in pool.items() if n not in SLOT_LEAVES}


def take_snapshot(store, pool, slot, idx, names):
    """Copy slot ``slot``'s per-slot rows (leaves ``names``, slots on axis 1)
    out of the pool into place ``idx`` of the snapshot store (jit with the
    STORE donated; the pool is only read)."""
    return {
        name: lax.dynamic_update_slice_in_dim(
            store[name], lax.dynamic_slice_in_dim(pool[name], slot, 1, axis=1),
            idx, axis=1)
        for name in names
    }


def restore_snapshot(pool, store, idx, slot, names):
    """Copy place ``idx`` of the snapshot store into slot ``slot``'s per-slot
    rows (jit with the POOL donated)."""
    out = dict(pool)
    for name in names:
        out[name] = lax.dynamic_update_slice_in_dim(
            pool[name], lax.dynamic_slice_in_dim(store[name], idx, 1, axis=1),
            slot, axis=1)
    return out


def copy_block(
    pool: Dict[str, jax.Array], src: jax.Array, dst: jax.Array
) -> Dict[str, jax.Array]:
    """Copy one physical block's KV rows (all layers) — the copy-on-write
    primitive: a shared block a sequence must write into is duplicated
    into a private block first.  ``src``/``dst`` are traced scalars, so
    every COW reuses one compilation.  Generic over the pool layout: an
    int8 pool's quantized rows and scales copy bit-exact, so a COW'd
    block dequantizes identically to the shared original."""
    out = dict(pool)
    for name, leaf in _kv_leaves(pool).items():
        sl = lax.dynamic_slice_in_dim(leaf, src, 1, axis=1)
        idx = (0, dst) + (0,) * (leaf.ndim - 2)
        out[name] = lax.dynamic_update_slice(leaf, sl, idx)
    return out


def export_block(
    pool: Dict[str, jax.Array], src: jax.Array
) -> Dict[str, jax.Array]:
    """Slice one physical block's KV rows (all layers) OUT of the pool —
    the device→host half of the hierarchical-KV spill path.  ``src`` is a
    traced scalar, so every spill reuses one compilation.  Returns
    ``{leaf: [L, block_size, ...]}`` in the pool's own storage dtypes
    (an int8 pool exports int8 rows + f32 scales), so a spilled block's
    payload is the block's bits, never a requantization.

    Jit this WITHOUT donation: the engine donates the pool to every
    subsequent step/chunk/import call, and a non-donating jitted slice
    returns independent buffers — the runtime orders the read before any
    later donated write, so the device→host copy can drain asynchronously
    while serving moves on (materialize with ``np.asarray`` when the
    payload is actually needed)."""
    return {
        name: lax.dynamic_slice_in_dim(leaf, src, 1, axis=1)[:, 0]
        for name, leaf in _kv_leaves(pool).items()
    }


def import_block(
    pool: Dict[str, jax.Array],
    data: Dict[str, jax.Array],
    dst: jax.Array,
) -> Dict[str, jax.Array]:
    """Write an :func:`export_block` payload back into the pool at block
    ``dst`` — the host→device half of spill/restore.  ``dst`` is a traced
    scalar and ``data`` leaves keep the pool's storage dtypes, so the
    round trip is bit-exact for both pool layouts (values never
    requantize; only the block's address changes).  Jit with the pool
    donated, like every other pool-mutating fn."""
    out = dict(pool)
    for name, leaf in _kv_leaves(pool).items():
        blk = jnp.expand_dims(data[name].astype(leaf.dtype), 1)
        idx = (0, dst) + (0,) * (leaf.ndim - 2)
        out[name] = lax.dynamic_update_slice(leaf, blk, idx)
    return out


def _chunk_addresses(pool, table, start, length, C):
    """A chunk's C rows: their absolute positions ``qpos``, which of them are
    real (``valid``), the ``(block, offset)`` each is written at, and the
    positions ``kpos [1, W * bs]`` of the keys the table gathers."""
    W = table.shape[0]
    bs = pool_geometry(pool)[0]
    qpos = start + jnp.arange(C)  # [C] absolute positions
    valid = jnp.arange(C) < length
    # Pad writes are redirected to the trash block: their logical blocks
    # may not be allocated yet (they belong to future generation).
    write_blk = jnp.where(valid, table[jnp.clip(qpos // bs, 0, W - 1)], 0)
    write_off = jnp.where(valid, qpos % bs, 0)
    kpos = jnp.arange(W * bs)[None]  # gathered keys sit in logical order
    return qpos, valid, write_blk, write_off, kpos


def _chunk_mixer(cfg, table, positions, kpos, write_blk, write_off):
    """The attention mixer of a prompt chunk, for the dense layers and the
    hybrid stack's full layers.  The chunk's rows are written, then attended
    with everything else in the table, in the training ``forward``'s own form
    (GQA heads broadcast, ``_dense_attention``'s masked f32 softmax): greedy
    outputs stay token-identical to the sequential :func:`generate` path."""
    group = cfg.n_heads // cfg.kv_heads

    def mixer(h, layer, li, pool):
        q, k, v = _qkv(h, layer)
        q, k = _rotary(q, k, positions, cfg.rope_theta)
        pool, ck, cv = _kv_through_table(
            pool, li, k, v, table, write_blk, write_off, h.dtype
        )
        if group > 1:
            ck = jnp.repeat(ck, group, axis=2)
            cv = jnp.repeat(cv, group, axis=2)
        attn = _dense_attention(q, ck, cv, positions, kpos)
        return _attn_out(attn, layer), pool

    return mixer


def _run_uniform_stack(x, layers, pool, n_layers, mixer):
    """The dense programs' layer loop: a scan over the weights and the layer
    index that CARRIES ``(x, pool)``, each layer the pre-norm block around the
    program's ``mixer(h, layer, layer index, pool) -> (output, pool)``.  Each
    layer writes and reads the stacked leaves at ``[layer, block, offset]`` in
    place, so with the pool donated the buffer that enters is the one that
    leaves: no per-layer slice, no second stacked pool."""

    def body(carry, inputs):
        x, pool = carry  # the WHOLE pool rides the loop: [L, NB, bs, Hkv, ...]
        layer, li = inputs
        return _prenorm_block(x, layer, lambda h: mixer(h, layer, li, pool)), None

    (x, pool), _ = lax.scan(body, (x, pool), (layers, jnp.arange(n_layers)))
    return x, pool


def paged_prefill_chunk(
    params: Dict[str, Any],
    pool: Dict[str, jax.Array],
    table: jax.Array,
    tokens: jax.Array,
    start: jax.Array,
    length: jax.Array,
    cfg: TransformerConfig,
    slot: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Insert one prompt chunk into a paged cache and return the logits of
    its last REAL token.

    tokens: [C] (right-padded to the engine's chunk bucket); ``start`` is
    the chunk's absolute start position, ``length`` the valid count (both
    traced scalars — only C mints a compilation).  ``table`` [W] maps the
    sequence's logical blocks to pool blocks; blocks covering
    [start, start+length) must already be allocated (and private — the
    chunk WRITES its KV rows there).  The chunk attends to everything the
    table already holds (a reused shared prefix, earlier chunks) plus
    itself, causally — which is what makes chunked prefill and
    prefix-reuse recompute the same operation.  Pad positions write their
    garbage rows to trash block 0 and are masked out of attention.

    Numerics mirror the training ``forward`` block (:func:`_chunk_mixer`);
    the layer loop is :func:`_run_uniform_stack`.

    A model with a layer pattern (``cfg.layer_types``) goes through
    ``models/hybrid.py``'s form of this program instead: it also needs the
    ``slot`` whose recurrent rows (leaves of the same pool) the chunk
    advances; so does a model with window layers (``models/window_moe.py``:
    the slot's rings, and the expert counts as a third value).  A
    latent-attention model (``cfg.kv_lora_rank``) goes through
    ``models/latent_moe.py``'s, which returns a third value beside these two:
    what its expert layers routed in this call (``latent_moe.COUNT_NAMES``).
    """
    if cfg.stack == "latent":
        from polyaxon_tpu.models import latent_moe

        return latent_moe.paged_prefill_chunk(
            params, pool, table, tokens, start, length, cfg
        )
    if cfg.stack in ("hybrid", "window"):  # per-slot rows beside the blocks
        return stack_module(cfg).paged_prefill_chunk(
            params, pool, table, tokens, start, length, slot, cfg
        )
    c = cfg
    qpos, _, write_blk, write_off, kpos = _chunk_addresses(
        pool, table, start, length, tokens.shape[0]
    )
    x = params["embed"].astype(c.dtype)[tokens][None]  # [1, C, D]
    mixer = _chunk_mixer(c, table, qpos[None], kpos, write_blk, write_off)
    x, new_pool = _run_uniform_stack(x, params["block"], pool, c.n_layers, mixer)
    logits = _unembed(x, params["final_norm"], params["unembed"])
    last = jnp.take(logits[0], length - 1, axis=0)
    return last.astype(jnp.float32), new_pool


def _attend_paged(q, ck, cv, pos, group):
    """One-token attention over block-table-gathered KV.

    q: [S, 1, H, d]; ck/cv: [S, W*bs, Hkv, d] in logical-position order;
    pos: [S] per-slot absolute positions (entries > pos[s] in lane s are
    future or stale rows — masked).  The contraction is
    :func:`_attend_cached`'s — the gather changed where keys LIVE, not how a
    row attends — which is what keeps paged greedy outputs token-identical
    to the sequential path.
    """
    return _attend(
        q, ck, cv, group,
        lambda K: (jnp.arange(K)[None, :] <= pos[:, None])[:, None, None, None, :],
    )


def paged_decode_step(
    params: Dict[str, Any],
    pool: Dict[str, jax.Array],
    tables: jax.Array,
    tokens: jax.Array,
    pos: jax.Array,
    active: jax.Array,
    cfg: TransformerConfig,
    qweights: Optional[Dict[str, Any]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Advance a mixed batch one token against the paged pool.

    tables: [S, W] physical block ids per slot (the engine maps unset
    entries to trash block 0); slot s feeds ``tokens[s]`` at absolute
    position ``pos[s]``, and ``active`` [S] bool says which slots hold a
    request.  Inactive lanes write their garbage row to block 0 offset 0 —
    an idle slot costs one wasted lane of compute and can never corrupt a
    live block — and every gathered position beyond a slot's ``pos`` is
    masked.  Shapes depend only on (slots, pool size, table width):
    steady-state serving never recompiles, whichever requests come and go
    or how their blocks are scattered across the pool.  Returns
    ``(logits [S, vocab] f32, new_pool)``; the layer loop is
    :func:`_run_uniform_stack`.
    """
    if cfg.stack != "uniform":
        return stack_module(cfg).paged_decode_step(
            params, pool, tables, tokens, pos, active, cfg, qweights=qweights
        )
    c = cfg
    S = tables.shape[0]
    bs = pool_geometry(pool)[0]
    pos = jnp.where(active, pos, 0)
    write_blk = jnp.where(active, tables[jnp.arange(S), pos // bs], 0)
    write_off = jnp.where(active, pos % bs, 0)

    x = params["embed"].astype(c.dtype)[tokens][:, None, :]  # [S,1,D]
    layers, unembed = _with_qweights(params, qweights)

    def mixer(h, layer, li, pool):
        q, k, v = _qkv(h, layer)
        q, k = _rotary(q, k, pos[:, None], c.rope_theta)
        pool, ck, cv = _kv_through_table(
            pool, li, k, v, tables, write_blk, write_off, h.dtype
        )
        attn = _attend_paged(q, ck, cv, pos, c.n_heads // c.kv_heads)
        return _attn_out(attn, layer), pool

    x, new_pool = _run_uniform_stack(x, layers, pool, c.n_layers, mixer)
    logits = _unembed(x, params["final_norm"], unembed)
    return logits[:, 0].astype(jnp.float32), new_pool


def _attend_spec(q, ck, cv, qpos, group):
    """Multi-query-row attention over block-table-gathered KV.

    The T-row generalization of :func:`_attend_paged` for speculative
    verification: q [S, T, H, d] carries one query row per drafted token,
    ck/cv [S, W*bs, Hkv, d] sit in logical-position order, and qpos
    [S, T] gives each row's absolute position.  The core is the T=1 step's
    (:func:`_attend`), which keeps a verify row's logits bit-identical to
    the single-token decode step that would have produced them — the
    foundation of the greedy parity guarantee.
    """

    def valid_at(K):
        valid = jnp.arange(K)[None, None, :] <= qpos[:, :, None]  # [S,T,K]
        return valid[:, None, None, :, :]

    return _attend(q, ck, cv, group, valid_at)


def paged_verify_step(
    params: Dict[str, Any],
    pool: Dict[str, jax.Array],
    tables: jax.Array,
    tokens: jax.Array,
    pos: jax.Array,
    n_tok: jax.Array,
    active: jax.Array,
    cfg: TransformerConfig,
    qweights: Optional[Dict[str, Any]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Score a batch of drafted token runs in ONE forward pass.

    The speculative-decoding verify kernel: ``tokens`` [S, T] holds, per
    lane, the next token to feed followed by up to T-1 drafted
    continuations (right-padded); ``pos`` [S] is the absolute position
    of ``tokens[:, 0]`` and ``n_tok`` [S] the valid token count (1 for a
    lane taking a plain single-token step, up to T for a fully drafted
    lane — both are DATA, so one compilation serves every draft-length
    mix).  Rows beyond ``n_tok`` (and every row of inactive lanes) write
    their garbage KV to trash block 0; valid rows land at their real
    (block, offset) exactly like :func:`paged_prefill_chunk`, and each
    row attends causally to the whole table plus the rows written before
    it in this same call.

    Returns ``(logits [S, T, vocab] f32, new_pool)``.  ``logits[s, j]``
    is the model's next-token distribution AFTER feeding
    ``tokens[s, :j+1]``, so the caller accepts draft ``tokens[s, j+1]``
    iff it equals ``argmax(logits[s, j])`` — the accept mask — and row
    ``n_accept`` yields the bonus/correction token.  Rejected rows leave
    stale KV beyond the lane's rolled-back position: masked out of every
    later attention (position mask) and overwritten in place as decoding
    proceeds; whole tail blocks are freed host-side
    (:func:`~polyaxon_tpu.serving.paging.truncate_table`).

    The layer is :func:`paged_decode_step`'s, piece for piece (int8
    qweights and int8 KV pools compose the same way), so greedy outputs
    stay token-identical to the non-speculative path.
    """
    if cfg.stack != "uniform":  # hybrid, latent, window: each refuses it, typed
        raise stack_module(cfg).refusal("spec_decode")
    c = cfg
    S, W = tables.shape
    T = tokens.shape[1]
    bs = pool_geometry(pool)[0]
    pos = jnp.where(active, pos, 0)
    qpos = pos[:, None] + jnp.arange(T)[None, :]  # [S, T] absolute
    row_ok = active[:, None] & (jnp.arange(T)[None, :] < n_tok[:, None])
    write_blk = jnp.where(
        row_ok,
        tables[jnp.arange(S)[:, None], jnp.clip(qpos // bs, 0, W - 1)],
        0,
    )
    write_off = jnp.where(row_ok, qpos % bs, 0)

    x = params["embed"].astype(c.dtype)[tokens]  # [S, T, D]
    layers, unembed = _with_qweights(params, qweights)

    def mixer(h, layer, li, pool):
        q, k, v = _qkv(h, layer)
        q, k = _rotary(q, k, qpos, c.rope_theta)
        pool, ck, cv = _kv_through_table(
            pool, li, k, v, tables, write_blk, write_off, h.dtype
        )
        attn = _attend_spec(q, ck, cv, qpos, c.n_heads // c.kv_heads)
        return _attn_out(attn, layer), pool

    x, new_pool = _run_uniform_stack(x, layers, pool, c.n_layers, mixer)
    logits = _unembed(x, params["final_norm"], unembed)
    return logits.astype(jnp.float32), new_pool


def _fit_spec(spec, leaf, mesh_shape):
    """Drop sharding on axes whose mesh size doesn't divide the leaf's
    actual dimension (shape-aware replication fallback)."""
    import math

    from jax.sharding import PartitionSpec

    names = tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec)))
    out = []
    for dim, name in zip(leaf.shape, names):
        if name is None:
            out.append(None)
            continue
        axes = name if isinstance(name, (tuple, list)) else (name,)
        total = math.prod(mesh_shape[a] for a in axes)
        out.append(name if total and dim % total == 0 else None)
    return PartitionSpec(*out)


def quantized_weight_shardings(cfg: TransformerConfig, mesh, template, qweights):
    """NamedShardings for a :func:`quantize_weights` tree: each ``(q,
    scale)`` pair inherits its source weight's logical axes — the int8
    tensor shards exactly like the full-precision weight it replaced,
    and the keepdims-1 scale dims fall back to replication via the
    shape-aware fit.  This is what lets int8 and tensor-parallel serving
    COMPOSE: every chip streams only its head-shard's int8 bytes."""
    from polyaxon_tpu.models.transformer import param_axes
    from polyaxon_tpu.parallel.axes import tree_shardings, tree_specs

    mesh_shape = dict(mesh.shape)
    axes = param_axes(cfg)
    name_axes = {k: axes["block"][k] for k in QUANTIZED_BLOCK_WEIGHTS}
    name_axes["unembed"] = axes["unembed"]
    base_specs = tree_specs(name_axes, template.rules, mesh_shape)
    fitted = {
        name: tuple(
            _fit_spec(base_specs[name], leaf, mesh_shape)
            for leaf in qweights[name]
        )
        for name in qweights
    }
    return tree_shardings(mesh, fitted)


def decode_param_shardings(
    cfg: TransformerConfig, mesh, template, params: Optional[Any] = None
):
    """NamedShardings for the weights under a template's rules (what the
    serving path places restored checkpoints with).

    When ``params`` (or any same-shaped tree) is given, axes whose mesh
    size doesn't divide the actual dimension fall back to replication —
    e.g. a GQA model with ``n_kv_heads: 1`` under ``tp=2`` keeps its KV
    projections replicated while the query-side weights still shard.
    Serving must degrade to replication, not crash, for any model the
    spec accepts."""
    from jax.sharding import PartitionSpec

    from polyaxon_tpu.models.transformer import param_axes
    from polyaxon_tpu.parallel.axes import tree_shardings, tree_specs

    mesh_shape = dict(mesh.shape)
    specs = tree_specs(param_axes(cfg), template.rules, mesh_shape)
    if params is not None:
        specs = jax.tree.map(
            lambda spec, leaf: _fit_spec(spec, leaf, mesh_shape),
            specs,
            params,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
    return tree_shardings(mesh, specs)


def sharded_generate_fn(
    cfg: TransformerConfig,
    mesh,
    template,
    *,
    max_new_tokens: int,
    greedy: bool = True,
    params: Optional[Any] = None,
    param_shardings: Optional[Any] = None,
    qweights_shardings: Optional[Any] = None,
):
    """(jitted fn, param_shardings) for MULTI-CHIP decode under a template.

    TP-native serving: the template's rules shard every weight (heads on
    the tensor axis under ``tp``), and GSPMD propagates those shardings
    through the decode scan — the KV cache lands heads-sharded, each
    chip attending over its own head group, with one collective per
    token for the logit reduction.  The caller places restored params
    with the returned shardings and invokes ``fn(params, prompt, key,
    temperature, qweights)``; prompt/key/temperature replicate (decode
    batches are small — sharding model weights, not the batch, is what
    scales).  ``qweights_shardings`` (from
    :func:`quantized_weight_shardings`) composes int8 with the sharding:
    pass the placed quantized tree as the 5th argument, or None.
    Sharded-vs-single-device token parity is asserted in
    ``tests/test_parallel/test_decode_sharded.py``.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    # Callers that already placed their weights pass the shardings in —
    # recomputing the fitted tree per compiled shape would be waste.
    param_sh = (
        param_shardings
        if param_shardings is not None
        else decode_param_shardings(cfg, mesh, template, params=params)
    )
    repl = NamedSharding(mesh, PartitionSpec())

    def _run(p, prompt, key, temp, qw):
        return generate(
            p,
            prompt,
            cfg,
            max_new_tokens=max_new_tokens,
            temperature=0.0 if greedy else temp,
            rng=key,
            qweights=qw,
        )

    fn = jax.jit(
        _run,
        in_shardings=(param_sh, repl, repl, repl, qweights_shardings),
    )
    return fn, param_sh


def generate(
    params: Dict[str, Any],
    prompt: jax.Array,
    cfg: TransformerConfig,
    *,
    max_new_tokens: int,
    temperature: Any = 0.0,
    rng: Optional[jax.Array] = None,
    qweights: Optional[Dict[str, Any]] = None,
) -> jax.Array:
    """prompt [B, T] → generated tokens [B, max_new_tokens].

    Greedy when ``temperature == 0``; otherwise temperature sampling.
    ``temperature`` may be a traced array (a jitted caller can pass it as
    an argument rather than baking each value into a fresh compilation);
    a traced value always takes the sampling branch — greedy-vs-sampling
    is the only Python-level fork.  ``qweights`` (precompute once with
    :func:`quantize_weights`) switches the per-token loop to int8 weight
    streaming (+51% measured); prefill stays full-precision — it is
    MXU-bound, not bandwidth-bound.  The whole decode loop is one
    ``lax.scan`` of compiled one-token steps — no host round-trips
    between tokens.
    """
    if cfg.n_experts:
        raise NotImplementedError("MoE decoding is not supported yet")
    if cfg.layer_types is not None:
        raise NotImplementedError(
            "a model with a layer pattern is served through the paged "
            "programs (ServingEngine), not through generate()"
        )
    B, T = prompt.shape
    max_len = T + max_new_tokens
    if max_len > cfg.max_seq:
        raise ValueError(
            f"prompt ({T}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_seq ({cfg.max_seq})"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    cache = init_cache(cfg, B, max_len)
    logits, cache = prefill(params, prompt, cache, cfg)

    # Concrete zeros of ANY scalar flavor (python float, np.float32,
    # jnp scalar) select the greedy branch — only a TRACED temperature is
    # forced down the sampling path (a tracer has no concrete value to
    # fork on, and dividing by a concrete 0.0 would NaN the logits).
    greedy = (
        not isinstance(temperature, jax.core.Tracer)
        and float(temperature) <= 0.0
    )

    def pick(logits, key):
        if greedy:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(key, logits / temperature, axis=-1)

    def step(carry, i):
        logits, cache, key = carry
        key, sub = jax.random.split(key)
        token = pick(logits, sub)
        logits, cache = decode_step(
            params, cache, token, T + i, cfg, qweights=qweights
        )
        return (logits, cache, key), token

    # N-1 scanned steps; the final token needs only a pick, not another
    # full decode_step whose logits nobody reads.
    (logits, _, key), tokens = lax.scan(
        step, (logits, cache, rng), jnp.arange(max_new_tokens - 1)
    )
    last = pick(logits, jax.random.split(key)[1])
    return jnp.concatenate([tokens.T, last[:, None]], axis=1)
