"""The hybrid stack: gated-delta-rule layers beside full-attention layers.

A ``TransformerConfig`` with a ``layer_types`` pattern is served through the
same three paged programs as the dense model (``models/decode.py`` hands over
here); what differs is the block and what a layer keeps per sequence.

**The block** (the Olmo 2/3 form): no input norm, the sublayer's OUTPUT is
normalised before the residual add,

    h = x + rmsnorm(mixer(x));  h = h + rmsnorm(mlp(h))

**Full-attention layers** normalise ``q`` and ``k`` over the whole projection
(before the heads are split) and, with ``rope_theta=None``, apply no rotary
embedding.  Their KV goes through the paged pool exactly as the dense model's:
written and read in place at ``[kv layer, block, offset]``.

**Linear-attention layers** (the gated delta rule, ``parallel/delta_rule.py``):
per head a float32 state ``S [dv, dk]`` and, for the width-``K`` depthwise
causal convolution in front of ``q, k, v``, the last ``K - 1`` positions of the
three projections.  Both live in the pool beside the KV leaves, per SLOT
instead of per block:

    rec_s [linear layers, slots, heads, dv, dk]   float32
    rec_c [linear layers, slots, K - 1, channels] float32

Prefill reads a slot's rows, runs the chunkwise rule and writes them back;
decode advances every active slot one recurrent step and leaves the others'
rows alone.  A chunk that starts at position 0 starts from zeros, so a slot
needs no clearing between requests.  Weights are the compute dtype in the
matmuls; the convolution, the L2 norms, the decays and the state are float32.

The layer loop scans over PERIODS of the pattern (its shortest repeating
unit) and unrolls the layers of one period inside the body.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from polyaxon_tpu.models import decode
from polyaxon_tpu.models.transformer import _rmsnorm
from polyaxon_tpu.parallel.delta_rule import gated_delta_prefill, gated_delta_step

FULL = "full_attention"
LINEAR = "linear_attention"
_SHARED = ("mixer_norm", "mlp_norm", "wi", "wg", "wd")


#: The options that move or roll back a sequence's KV, and what each would
#: have to do to its recurrent rows as well.  None does yet.
REFUSED = {
    "spec_decode": "a rejected draft would have to roll the recurrent state back",
    "kv_offload": "a spilled sequence's recurrent rows and a demoted prefix's "
    "snapshots would have to move to the host tier with the blocks",
    "kv_persist_dir": "persisted prefix blocks without their state snapshots "
    "cannot be resumed",
    "mesh": "the recurrent rows and the kernel have no sharding rules",
}


class RecurrentStateError(ValueError):
    """An option of ``REFUSED``, asked of a model with linear-attention
    layers: named in ``option``, raised where the engine is built rather than
    run on the KV alone."""

    def __init__(self, option: str) -> None:
        super().__init__(
            f"{option} is not supported for a model with linear-attention "
            f"layers: {REFUSED[option]}"
        )
        self.option = option


#: What the engine raises for an option of ``REFUSED``.
refusal = RecurrentStateError


def check_config(cfg) -> None:
    """What ``TransformerConfig.__post_init__`` holds a layer pattern to."""
    types = cfg.layer_types
    if len(types) != cfg.n_layers:
        raise ValueError(
            f"layer_types names {len(types)} layers, n_layers is {cfg.n_layers}"
        )
    unknown = sorted(set(types) - {FULL, LINEAR})
    if unknown:
        raise ValueError(f"unknown layer types {unknown} (one of {FULL!r}, {LINEAR!r})")
    if cfg.n_experts:
        raise ValueError("a layer pattern and MoE do not combine")
    if LINEAR in types:
        sizes = (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
                 cfg.linear_key_head_dim, cfg.linear_value_head_dim)
        if min(sizes) < 1 or cfg.linear_conv_kernel_dim < 2:
            raise ValueError(
                "linear_attention layers need linear_num_key_heads, "
                "linear_num_value_heads, linear_key_head_dim, "
                "linear_value_head_dim >= 1 and linear_conv_kernel_dim >= 2"
            )
        if cfg.linear_num_key_heads != cfg.linear_num_value_heads:
            raise ValueError(
                "linear_num_key_heads must equal linear_num_value_heads "
                f"({cfg.linear_num_key_heads} != {cfg.linear_num_value_heads})"
            )


def period(cfg) -> Tuple[str, ...]:
    """The pattern's shortest repeating unit."""
    types = tuple(cfg.layer_types)
    for p in range(1, len(types) + 1):
        if len(types) % p == 0 and types == types[:p] * (len(types) // p):
            return types[:p]
    return types


def _counts(cfg) -> Tuple[int, int]:
    types = cfg.layer_types
    return types.count(FULL), types.count(LINEAR)


def conv_channels(cfg) -> int:
    return (2 * cfg.linear_num_key_heads * cfg.linear_key_head_dim
            + cfg.linear_num_value_heads * cfg.linear_value_head_dim)


def n_params(cfg) -> int:
    c = cfg
    n_full, n_lin = _counts(c)
    D, H, hd = c.d_model, c.n_heads, c.head_dim
    full = D * hd * (2 * H + 2 * c.kv_heads) + hd * (H + c.kv_heads)
    Hl, dk, dv = c.linear_num_value_heads, c.linear_key_head_dim, c.linear_value_head_dim
    lin = (D * Hl * (2 * dk + 3 * dv) + 2 * D * Hl
           + c.linear_conv_kernel_dim * conv_channels(c) + 2 * Hl + dv)
    shared = 3 * D * c.d_ff + 2 * D
    return (2 * c.vocab_size * D + D + c.n_layers * shared
            + n_full * full + n_lin * lin)


def init_params(key: jax.Array, cfg) -> Dict[str, Any]:
    """Seeded weights (normal, fan-in scaled; norms one).  ``A_log`` and
    ``dt_bias`` as GatedDeltaNet draws them: ``A`` uniform in (0, 16), ``dt``
    log-uniform in [0.001, 0.1] and ``dt_bias`` its inverse softplus.  The
    plain reference (``benchmark/reference/hybrid_decoder.py``) makes the same
    draws in the same order."""
    c = cfg
    k = iter(jax.random.split(key, 32))
    dt = c.param_dtype

    def norm(*shape, scale):
        return jax.random.normal(next(k), shape, dt) * scale

    L, D, H, hd, F, Hkv = c.n_layers, c.d_model, c.n_heads, c.head_dim, c.d_ff, c.kv_heads
    n_full, n_lin = _counts(c)
    Hl, dk, dv, K = (c.linear_num_value_heads, c.linear_key_head_dim,
                     c.linear_value_head_dim, c.linear_conv_kernel_dim)
    full = {
        "wq": norm(n_full, D, H, hd, scale=D**-0.5),
        "wk": norm(n_full, D, Hkv, hd, scale=D**-0.5),
        "wv": norm(n_full, D, Hkv, hd, scale=D**-0.5),
        "wo": norm(n_full, H, hd, D, scale=(H * hd) ** -0.5),
        "q_norm": jnp.ones((n_full, H * hd), dt),
        "k_norm": jnp.ones((n_full, Hkv * hd), dt),
    }
    linear = {
        "wq": norm(n_lin, D, Hl, dk, scale=D**-0.5),
        "wk": norm(n_lin, D, Hl, dk, scale=D**-0.5),
        "wv": norm(n_lin, D, Hl, dv, scale=D**-0.5),
        "wg": norm(n_lin, D, Hl, dv, scale=D**-0.5),
        "wo": norm(n_lin, Hl, dv, D, scale=(Hl * dv) ** -0.5),
        "wa": norm(n_lin, D, Hl, scale=D**-0.5),
        "wb": norm(n_lin, D, Hl, scale=D**-0.5),
        "conv_q": norm(n_lin, K, Hl * dk, scale=K**-0.5),
        "conv_k": norm(n_lin, K, Hl * dk, scale=K**-0.5),
        "conv_v": norm(n_lin, K, Hl * dv, scale=K**-0.5),
    }
    a = jax.random.uniform(next(k), (n_lin, Hl), jnp.float32, 0.0, 16.0)
    u = jax.random.uniform(next(k), (n_lin, Hl), jnp.float32)
    step = jnp.exp(u * (np.log(0.1) - np.log(0.001)) + np.log(0.001))
    linear["A_log"] = jnp.log(jnp.maximum(a, 1e-4)).astype(dt)
    linear["dt_bias"] = (step + jnp.log(-jnp.expm1(-step))).astype(dt)
    linear["o_norm"] = jnp.ones((n_lin, dv), dt)
    block = {
        "mixer_norm": jnp.ones((L, D), dt),
        "mlp_norm": jnp.ones((L, D), dt),
        "full": full,
        "linear": linear,
        "wi": norm(L, D, F, scale=D**-0.5),
        "wg": norm(L, D, F, scale=D**-0.5),
        "wd": norm(L, F, D, scale=F**-0.5),
    }
    return {
        "embed": norm(c.vocab_size, D, scale=1.0),
        "unembed": norm(D, c.vocab_size, scale=D**-0.5),
        "final_norm": jnp.ones((D,), dt),
        "block": block,
    }


#: The matmul weights ``quantize: int8`` covers, with their contraction dims
#: (the decays' ``wa``/``wb`` are 30 columns wide and feed an exponent: kept).
_QUANTIZED = {
    "": {"wi": (1,), "wg": (1,), "wd": (1,)},
    "full": {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2)},
    "linear": {"wq": (1,), "wk": (1,), "wv": (1,), "wg": (1,), "wo": (1, 2)},
}


def quantize_weights(params: Dict[str, Any], q) -> Dict[str, Any]:
    """The hybrid stack's int8 tree: ``q(weight, contraction axes)`` (the one
    ``decode.quantize_weights`` uses) over every projection of both kinds of
    layer, the MLP and the unembedding."""
    blk = params["block"]
    out: Dict[str, Any] = {
        name: q(blk[name], axes) for name, axes in _QUANTIZED[""].items()
    }
    for kind in ("full", "linear"):
        out[kind] = {
            name: q(blk[kind][name], axes) for name, axes in _QUANTIZED[kind].items()
        }
    return {"block": out, "unembed": q(params["unembed"], (0,))}


def serving_params(params: Dict[str, Any], cast) -> Dict[str, Any]:
    """The hybrid stack's form of ``decode.serving_params``: ``cast`` (the one
    it uses) over the embeddings, the MLP and every projection of both kinds
    of layer, the decays' ``wa`` / ``wb`` too.  What the programs read in
    float32 keeps its dtype: ``A_log``, ``dt_bias``, the convolutions
    (``_conv_weights``) and ``o_norm`` (applied to the float32 rule output);
    so do the other norms."""
    blk = params["block"]
    out = {**blk, **{n: cast(blk[n]) for n in _QUANTIZED[""]}}
    for kind, names in (("full", _QUANTIZED["full"]),
                        ("linear", (*_QUANTIZED["linear"], "wa", "wb"))):
        out[kind] = {**blk[kind], **{n: cast(blk[kind][n]) for n in names}}
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
    merged = {**blk, **{k: v for k, v in qb.items() if k not in ("full", "linear")}}
    for kind in ("full", "linear"):
        merged[kind] = {**blk[kind], **qb[kind]}
    return merged, qweights["unembed"]


def init_rec_state(cfg, rows: int, kv_dtype=None) -> Dict[str, jax.Array]:
    """Zeroed recurrent state for ``rows`` sequences (the engine's slots, or
    the places of its snapshot store): the ``decode.REC_LEAVES``, float32
    whatever ``kv_dtype`` the pool's blocks are kept at."""
    c = cfg
    _, n_lin = _counts(c)
    return {
        "rec_s": jnp.zeros(
            (n_lin, rows, c.linear_num_value_heads, c.linear_value_head_dim,
             c.linear_key_head_dim), jnp.float32),
        "rec_c": jnp.zeros(
            (n_lin, rows, c.linear_conv_kernel_dim - 1, conv_channels(c)),
            jnp.float32),
    }


def rec_row_bytes(cfg, kv_dtype=None) -> int:
    """Device bytes of ONE sequence's recurrent state (one snapshot)."""
    c = cfg
    _, n_lin = _counts(c)
    state = c.linear_num_value_heads * c.linear_value_head_dim * c.linear_key_head_dim
    return 4 * n_lin * (state + (c.linear_conv_kernel_dim - 1) * conv_channels(c))


def take_snapshot(store, pool, slot, idx):
    """Copy slot ``slot``'s recurrent rows out of the pool into place ``idx``
    of the snapshot store (jit with the STORE donated; the pool is only read)."""
    return decode.take_snapshot(store, pool, slot, idx, decode.REC_LEAVES)


def restore_snapshot(pool, store, idx, slot):
    """Copy place ``idx`` of the snapshot store into slot ``slot``'s recurrent
    rows (jit with the POOL donated)."""
    return decode.restore_snapshot(pool, store, idx, slot, decode.REC_LEAVES)


# -- the layers -----------------------------------------------------------------


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _linear_inputs(h, lp, cfg):
    """What the rule needs of one layer, from its input ``h [..., D]``: the
    pre-convolution ``q|k|v`` channels (float32), the gate, log alpha, beta."""
    c = cfg
    dt = h.dtype
    f32 = jnp.float32
    lead = h.shape[:-1]
    qkv = jnp.concatenate([
        jnp.einsum("...d,dhk->...hk", h, decode._wdq(lp[n], dt)).reshape(lead + (-1,))
        for n in ("wq", "wk", "wv")
    ], axis=-1).astype(f32)
    gate = jnp.einsum("...d,dhk->...hk", h, decode._wdq(lp["wg"], dt))
    a = jnp.einsum("...d,dh->...h", h, lp["wa"].astype(dt)).astype(f32)
    b = jnp.einsum("...d,dh->...h", h, lp["wb"].astype(dt)).astype(f32)
    g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(a + lp["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(b) * (2.0 if c.linear_allow_neg_eigval else 1.0)
    return qkv, gate, g, beta


def _split_qkv(y, cfg):
    """Convolved ``q|k|v`` channels -> silu, heads, L2 norms, the query scale."""
    c = cfg
    H, dk, dv = c.linear_num_value_heads, c.linear_key_head_dim, c.linear_value_head_dim
    y = jax.nn.silu(y)
    lead = y.shape[:-1]
    q = _l2norm(y[..., : H * dk].reshape(lead + (H, dk))) * dk**-0.5
    k = _l2norm(y[..., H * dk : 2 * H * dk].reshape(lead + (H, dk)))
    v = y[..., 2 * H * dk :].reshape(lead + (H, dv))
    return q, k, v


def _conv_weights(lp):
    return jnp.concatenate(
        [lp["conv_q"], lp["conv_k"], lp["conv_v"]], axis=-1
    ).astype(jnp.float32)  # [K, channels]


def _linear_out(o, gate, lp, dtype):
    """``W_o (rmsnorm(o) * w * silu(gate))``, the norm over each head."""
    y = _rmsnorm(o, lp["o_norm"]).astype(dtype) * jax.nn.silu(gate)
    return jnp.einsum("...hv,hvd->...d", y, decode._wdq(lp["wo"], dtype))


def _run_stack(x, blk, pool, cfg, full_fn, linear_fn):
    """The layer loop: a scan over periods, the period's layers unrolled.
    ``full_fn`` / ``linear_fn`` ``(x, layer weights, index among its kind,
    pool) -> (mixer output, pool)``; the pool (KV and recurrent leaves) is the
    carry, updated in place.  (The dense model's loop, a layer an iteration,
    is ``decode._run_uniform_stack``.)"""
    per = period(cfg)
    n_periods = cfg.n_layers // len(per)
    n_full, n_lin = per.count(FULL), per.count(LINEAR)

    def by_period(tree, n):
        return jax.tree.map(
            lambda w: w.reshape((n_periods, n) + w.shape[1:]), tree)

    def at(tree, i):
        return jax.tree.map(lambda w: w[i], tree)

    xs = (
        by_period({k: blk[k] for k in _SHARED}, len(per)),
        by_period(blk["full"], n_full) if n_full else None,
        by_period(blk["linear"], n_lin) if n_lin else None,
        jnp.arange(n_periods),
    )

    def body(carry, inputs):
        x, pool = carry
        shared, full, linear, pi = inputs
        jf = jl = 0
        for j, kind in enumerate(per):
            lay = at(shared, j)
            if kind == FULL:
                mix, pool = full_fn(x, at(full, jf), pi * n_full + jf, pool)
                jf += 1
            else:
                mix, pool = linear_fn(x, at(linear, jl), pi * n_lin + jl, pool)
                jl += 1
            x = x + _rmsnorm(mix, lay["mixer_norm"])
            x = x + _rmsnorm(decode._gated_mlp(x, lay), lay["mlp_norm"])
        return (x, pool), None

    (x, pool), _ = lax.scan(body, (x, pool), xs)
    return x, pool


def _row(leaf, li, slot):
    """``leaf[li, slot]`` for traced indices, as one dynamic slice."""
    start = (li, slot) + (0,) * (leaf.ndim - 2)
    return lax.dynamic_slice(leaf, start, (1, 1) + leaf.shape[2:])[0, 0]


def _set_row(leaf, li, slot, value):
    start = (li, slot) + (0,) * (leaf.ndim - 2)
    return lax.dynamic_update_slice(leaf, value[None, None].astype(leaf.dtype), start)


# -- the two paged programs ------------------------------------------------------


def paged_prefill_chunk(params, pool, table, tokens, start, length, slot, cfg):
    """``decode.paged_prefill_chunk`` for the hybrid stack: one prompt chunk of
    the sequence in ``slot``.  The full layers write and read the KV pool at
    ``table``; the linear layers take ``slot``'s recurrent rows through the
    chunkwise rule (from zeros where ``start == 0``) and put them back.  Pad
    positions write KV to the trash block and leave the recurrent rows as the
    last real token left them."""
    c = cfg
    C = tokens.shape[0]
    K = c.linear_conv_kernel_dim
    qpos, valid, write_blk, write_off, kpos = decode._chunk_addresses(
        pool, table, start, length, C
    )
    positions = qpos[None]
    fresh = start == 0

    x = params["embed"].astype(c.dtype)[tokens][None]  # [1, C, D]

    full_fn = decode._chunk_mixer(c, table, positions, kpos, write_blk, write_off)

    def linear_fn(x, lp, li, pool):
        h = x[0]
        qkv, gate, g, beta = _linear_inputs(h, lp, c)
        tail = jnp.where(fresh, 0.0, _row(pool["rec_c"], li, slot).astype(jnp.float32))
        s0 = jnp.where(fresh, 0.0, _row(pool["rec_s"], li, slot).astype(jnp.float32))
        ext = jnp.concatenate([tail, qkv], axis=0)  # [K-1+C, ch]
        w = _conv_weights(lp)
        y = sum(ext[j : j + C] * w[j] for j in range(K))
        q, k, v = _split_qkv(y, c)
        g = jnp.where(valid[:, None], g, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
        o, s = gated_delta_prefill(q, k, v, g, beta, s0)
        pool = {
            **pool,
            "rec_s": _set_row(pool["rec_s"], li, slot, s),
            "rec_c": _set_row(
                pool["rec_c"], li, slot,
                lax.dynamic_slice_in_dim(ext, length, K - 1, axis=0)),
        }
        return _linear_out(o, gate, lp, x.dtype)[None], pool

    x, pool = _run_stack(x, params["block"], pool, c, full_fn, linear_fn)
    # Only the last real token's logits are read: unembed that one row, not
    # the chunk's C rows against the whole vocabulary.
    last = _rmsnorm(jnp.take(x[0], length - 1, axis=0), params["final_norm"])
    logits = jnp.einsum("d,dv->v", last, params["unembed"].astype(last.dtype))
    return logits.astype(jnp.float32), pool


def paged_decode_step(params, pool, tables, tokens, pos, active, cfg, qweights=None):
    """``decode.paged_decode_step`` for the hybrid stack: every active slot one
    token.  The linear layers advance each active slot's recurrent rows one
    step of the rule; an inactive (free or parked) slot keeps its rows."""
    c = cfg
    S = tables.shape[0]
    bs = decode.pool_geometry(pool)[0]
    pos = jnp.where(active, pos, 0)
    write_blk = jnp.where(active, tables[jnp.arange(S), pos // bs], 0)
    write_off = jnp.where(active, pos % bs, 0)
    positions = pos[:, None]

    x = params["embed"].astype(c.dtype)[tokens][:, None, :]  # [S, 1, D]
    blk, unembed = _with_qweights(params, qweights)

    def full_fn(x, lp, li, pool):
        q, k, v = decode._qkv(x, lp)
        q, k = decode._rotary(q, k, positions, c.rope_theta)
        pool, ck, cv = decode._kv_through_table(
            pool, li, k, v, tables, write_blk, write_off, x.dtype
        )
        attn = decode._attend_paged(q, ck, cv, pos, c.n_heads // c.kv_heads)
        return decode._attn_out(attn, lp), pool

    def linear_fn(x, lp, li, pool):
        qkv, gate, g, beta = _linear_inputs(x[:, 0], lp, c)
        rec_s, rec_c = pool["rec_s"], pool["rec_c"]
        tail = lax.dynamic_index_in_dim(rec_c, li, 0, keepdims=False).astype(jnp.float32)
        s0 = lax.dynamic_index_in_dim(rec_s, li, 0, keepdims=False).astype(jnp.float32)
        window = jnp.concatenate([tail, qkv[:, None]], axis=1)  # [S, K, ch]
        q, k, v = _split_qkv(jnp.sum(window * _conv_weights(lp), axis=1), c)
        o, s = gated_delta_step(q, k, v, g, beta, s0)
        keep = active[:, None, None]
        pool = {
            **pool,
            "rec_s": lax.dynamic_update_index_in_dim(
                rec_s, jnp.where(keep[..., None], s, s0).astype(rec_s.dtype), li, 0),
            "rec_c": lax.dynamic_update_index_in_dim(
                rec_c, jnp.where(keep, window[:, 1:], tail).astype(rec_c.dtype), li, 0),
        }
        return _linear_out(o, gate, lp, x.dtype)[:, None], pool

    x, pool = _run_stack(x, blk, pool, c, full_fn, linear_fn)
    logits = decode._unembed(x, params["final_norm"], unembed)
    return logits[:, 0].astype(jnp.float32), pool
