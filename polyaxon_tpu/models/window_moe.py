"""The window stack: window and full attention mixed, a dense or an expert MLP.

A ``TransformerConfig`` that names ``mlp_layer_types`` describes each layer by
its mixer (``layer_types``: ``"full_attention"`` / ``"sliding_attention"``) AND
its MLP (``"dense"`` / ``"sparse"``), as the published configs of the Laguna
family do, and is served through the same paged programs as the dense model
(``models/decode.py`` hands over here).

**The block** is the dense model's: ``x + attn(rmsnorm(x))`` then ``x +
mlp(rmsnorm(x))``, final norm, untied unembedding.

**Attention.**  ``q = h W_q [H, d]``, ``k = h W_k``, ``v = h W_v [Hkv, d]``, no
biases, no QK-norm; ``H`` is ``n_heads`` in a full layer and
``sliding_n_heads`` in a window layer, KV heads and ``d`` the same in both.
Rotary by layer kind (:func:`rope_table`): the full layers rotate the first
``partial_rotary_factor`` of each head under YaRN frequencies, cos and sin
times ``rope_attention_factor``; the window layers the whole head at
``sliding_rope_theta``.  A query at ``i`` admits key ``j`` if ``j <= i``
(full) or ``j <= i and i - j < sliding_window`` (window).  With ``head_gate``
each head's output is multiplied by ``sigmoid(h W_gate)[head]`` before ``W_o``.

**What a layer keeps.**  A FULL layer keeps K and V of every position in the
paged pool, written and read in place through the block table as the dense
model's.  A decode step appends its rows and reads each lane's pages where they
lie, up to the one that holds the lane's position, in one kernel
(``flash.paged_step_attend``, device operation ``paged_step_attend``; over an
int8 pool it gathers the table's width, ``decode._kv_through_table`` /
``_attend``); a prompt chunk appends its rows, then walks its table by key
tiles up to the tile that holds its own last position
(``decode._walk_table_tiles``), each tile's K and V through the flash forward
kernel under the query's offset (device operation ``full_chunk_tile``), the
tiles' ``(o, lse)`` merged: no array has the table's width.  A WINDOW layer
keeps, a SLOT, a ring of ``sliding_window`` rows of K and V, position ``p`` at
row ``p mod sliding_window``: fixed-size per-sequence state, leaves of the pool
dict beside the blocks as the hybrid stack's recurrent rows are

    win_k, win_v [window layers, slots, sliding_window, Hkv, d]   compute dtype

(int8 rows ``win_k_q`` ... and one float32 scale a head row under
``kv_quantize``), snapshotted and restored by the prefix cache's rules
(:func:`take_snapshot`, :func:`restore_snapshot`).  A decode step writes its
row and attends the slot's ring under the window mask.  A prompt chunk attends
the ring, turned into position order, and its own keys in ONE call of the flash
forward kernel with the bound from below (``flash_block_fwd(window=...)``,
device operation ``window_chunk_<rows of the chunk's shape>``: key tiles wholly
behind a query tile's window are skipped), then leaves its last ``sliding_window`` rows in the ring.
A chunk that starts at position 0 admits no ring row, so a slot needs no
clearing between requests.

**The expert MLP** is the latent stack's (``latent_moe._expert_mlp``,
``parallel/experts.py``) under the SOFTMAX router: float32 at ``highest``,
``top_k`` of ``softmax`` over all ``n_routed_experts``, gates normalised over
the chosen and scaled, dropless grouped product over the ``experts_held``
experts this chip holds, one ungated shared expert.  Each program returns what
its expert layers routed in the call (``experts.COUNT_NAMES``).

The layer loop runs the two lists as RUNS of one kind of layer (mixer, MLP),
each a ``lax.scan`` that carries ``(x, pool)``: window and full layers cannot
share one scan, their ``W_q``, ``W_o`` and gate differ in shape.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from polyaxon_tpu.models import decode
from polyaxon_tpu.models.latent_moe import _expert_mlp, _no_counts, experts_held
from polyaxon_tpu.models.transformer import _rmsnorm
from polyaxon_tpu.parallel import flash

FULL = "full_attention"
WINDOW = "sliding_attention"
DENSE = "dense"
SPARSE = "sparse"
#: Where each kind of layer keeps its weights in ``params["block"]``.
_TREE = {FULL: "full", WINDOW: "window", DENSE: "dense", SPARSE: "experts"}

#: What this stack cannot follow yet, and the module that would have to change.
REFUSED = {
    "spec_decode": "a rejected draft would have to roll the window layers' "
    "rings back, and the verify step has no window form "
    "(models/decode.py:paged_verify_step)",
    "kv_offload": "a spilled sequence's rings and a demoted prefix's ring "
    "snapshots would have to move to the host tier with the blocks "
    "(serving/engine.py's spill path, serving/paging.py:HostKVTier)",
    "kv_persist_dir": "persisted prefix blocks without their ring snapshots "
    "cannot be resumed (serving/kvstore.py)",
    "mesh": "the rings, the window kernel and the expert product have no "
    "sharding rules (models/decode.py:decode_param_shardings, "
    "parallel/experts.py: the expert exchange is not written)",
    "forward": "there is no training forward: the model is served through the "
    "paged programs (models/transformer.py:forward)",
}


class WindowStackError(ValueError):
    """An option of ``REFUSED``, asked of a model with window layers: named in
    ``option``, raised where the engine is built."""

    def __init__(self, option: str) -> None:
        super().__init__(
            f"{option} is not supported for a model with window layers: "
            f"{REFUSED[option]}"
        )
        self.option = option


#: What the engine raises for an option of ``REFUSED``.
refusal = WindowStackError


def check_config(cfg) -> None:
    """What ``TransformerConfig.__post_init__`` holds a window stack to."""
    c = cfg
    types, mlps = c.layer_types, c.mlp_layer_types
    for name, got, allowed in (
        ("layer_types", types, (FULL, WINDOW)),
        ("mlp_layer_types", mlps, (DENSE, SPARSE)),
    ):
        if got is None or len(got) != c.n_layers:
            raise ValueError(
                f"a model with mlp_layer_types names its {c.n_layers} layers in "
                f"{name} ({allowed[0]!r} / {allowed[1]!r}), got {got!r}"
            )
        unknown = sorted(set(got) - set(allowed))
        if unknown:
            raise ValueError(f"unknown {name} {unknown} (one of {allowed})")
    if c.kv_lora_rank or c.n_experts:
        raise ValueError("latent attention and the switch MoE do not combine with window layers")
    if c.rope_theta is None:
        raise ValueError("the window stack rotates every layer: rope_theta is needed")
    rot = int(c.head_dim * c.partial_rotary_factor)
    if rot < 2 or rot % 2 or c.head_dim % 2:
        raise ValueError(
            f"partial_rotary_factor {c.partial_rotary_factor} of head_dim "
            f"{c.head_dim} must be an even number of columns"
        )
    if c.rope_yarn_factor and c.rope_yarn_original_max < 1:
        raise ValueError("rope_yarn_factor needs rope_yarn_original_max")
    if WINDOW in types:
        if c.sliding_window < 8 or c.sliding_window % 8:
            raise ValueError(
                f"sliding_window ({c.sliding_window}) must be a positive multiple "
                "of 8: the window kernel tiles the ring"
            )
        if heads(c, WINDOW) % c.kv_heads:
            raise ValueError(
                f"sliding_n_heads ({heads(c, WINDOW)}) must be divisible by "
                f"n_kv_heads ({c.kv_heads})"
            )
    if SPARSE in mlps:
        if min(c.n_routed_experts, c.num_experts_per_tok, c.moe_intermediate_size) < 1:
            raise ValueError(
                "sparse layers need n_routed_experts, num_experts_per_tok and "
                "moe_intermediate_size >= 1"
            )
        if c.num_experts_per_tok > c.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")
        held = experts_held(c)
        if c.expert_offset < 0 or held < 1 or c.expert_offset + held > c.n_routed_experts:
            raise ValueError(
                f"experts [{c.expert_offset}, {c.expert_offset + held}) are not "
                f"among the router's {c.n_routed_experts}"
            )


def heads(cfg, kind: str) -> int:
    """Query heads of a layer of ``kind``."""
    return cfg.sliding_n_heads if kind == WINDOW and cfg.sliding_n_heads else cfg.n_heads


def _count(cfg) -> Dict[str, int]:
    c = cfg
    return {
        FULL: c.layer_types.count(FULL), WINDOW: c.layer_types.count(WINDOW),
        DENSE: c.mlp_layer_types.count(DENSE), SPARSE: c.mlp_layer_types.count(SPARSE),
    }


def expert_layers(cfg) -> int:
    return cfg.mlp_layer_types.count(SPARSE)


def runs(cfg) -> List[Tuple[str, str, int]]:
    """The two lists as runs of one kind of layer: ``[(mixer, mlp, layers), ...]``."""
    out: List[Tuple[str, str, int]] = []
    for kind in zip(cfg.layer_types, cfg.mlp_layer_types):
        if out and out[-1][:2] == kind:
            out[-1] = (*kind, out[-1][2] + 1)
        else:
            out.append((*kind, 1))
    return out


def n_params(cfg) -> int:
    c = cfg
    n = _count(c)
    D, d, Hkv = c.d_model, c.head_dim, c.kv_heads

    def attn(kind):
        H = heads(c, kind)
        return D * d * (2 * H + 2 * Hkv) + (D * H if c.head_gate else 0)

    Fe = c.moe_intermediate_size
    exp = (D * c.n_routed_experts
           + 3 * D * Fe * (experts_held(c) + c.n_shared_experts)) if n[SPARSE] else 0
    return (2 * c.vocab_size * D + D + c.n_layers * 2 * D
            + n[FULL] * attn(FULL) + n[WINDOW] * attn(WINDOW)
            + n[DENSE] * 3 * D * c.d_ff + n[SPARSE] * exp)


def init_params(key: jax.Array, cfg) -> Dict[str, Any]:
    """Seeded weights (normal, fan-in scaled; norms one).  The plain reference
    (``benchmark/reference/window_moe_decoder.py``) makes the same draws in the
    same order."""
    c = cfg
    k = iter(jax.random.split(key, 32))
    dt = c.param_dtype

    def norm(*shape, scale):
        return jax.random.normal(next(k), shape, dt) * scale

    L, D, d, Hkv, F = c.n_layers, c.d_model, c.head_dim, c.kv_heads, c.d_ff
    n = _count(c)

    def attn(kind):
        m, H = n[kind], heads(c, kind)
        out = {
            "wq": norm(m, D, H, d, scale=D**-0.5),
            "wk": norm(m, D, Hkv, d, scale=D**-0.5),
            "wv": norm(m, D, Hkv, d, scale=D**-0.5),
            "wo": norm(m, H, d, D, scale=(H * d) ** -0.5),
        }
        if c.head_gate:
            out["gate"] = norm(m, D, H, scale=D**-0.5)
        return out

    block: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, D), dt),
        "mlp_norm": jnp.ones((L, D), dt),
        "full": attn(FULL),
        "window": attn(WINDOW),
        "dense": {
            "wi": norm(n[DENSE], D, F, scale=D**-0.5),
            "wg": norm(n[DENSE], D, F, scale=D**-0.5),
            "wd": norm(n[DENSE], F, D, scale=F**-0.5),
        },
    }
    if n[SPARSE]:
        ne, E, held, Fe = n[SPARSE], c.n_routed_experts, experts_held(c), c.moe_intermediate_size
        Fs = c.n_shared_experts * Fe
        block["experts"] = {
            "router": norm(ne, D, E, scale=D**-0.5),
            "wi": norm(ne, held, D, Fe, scale=D**-0.5),
            "wg": norm(ne, held, D, Fe, scale=D**-0.5),
            "wd": norm(ne, held, Fe, D, scale=Fe**-0.5),
            "shared_wi": norm(ne, D, Fs, scale=D**-0.5),
            "shared_wg": norm(ne, D, Fs, scale=D**-0.5),
            "shared_wd": norm(ne, Fs, D, scale=max(Fs, 1) ** -0.5),
        }
    return {
        "embed": norm(c.vocab_size, D, scale=1.0),
        "unembed": norm(D, c.vocab_size, scale=D**-0.5),
        "final_norm": jnp.ones((D,), dt),
        "block": block,
    }


#: The matmul weights ``quantize: int8`` covers, with their contraction dims
#: (the router stays float32: it chooses; the gate, ``H`` columns wide, feeds a
#: sigmoid and is kept as the hybrid stack keeps its decays).
_QUANTIZED = {
    "full": {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2)},
    "window": {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2)},
    "dense": {"wi": (1,), "wg": (1,), "wd": (1,)},
    "experts": {"wi": (2,), "wg": (2,), "wd": (2,),
                "shared_wi": (1,), "shared_wg": (1,), "shared_wd": (1,)},
}


def _trees(blk):
    return [name for name in _QUANTIZED if name in blk]


def quantize_weights(params: Dict[str, Any], q) -> Dict[str, Any]:
    """The window stack's int8 tree: ``q(weight, contraction axes)`` (the one
    ``decode.quantize_weights`` uses) over the attention projections of both
    kinds of layer, both kinds of MLP and the unembedding."""
    blk = params["block"]
    out = {
        name: {n: q(blk[name][n], axes) for n, axes in _QUANTIZED[name].items()}
        for name in _trees(blk)
    }
    return {"block": out, "unembed": q(params["unembed"], (0,))}


def serving_params(params: Dict[str, Any], cast) -> Dict[str, Any]:
    """The window stack's form of ``decode.serving_params``: ``cast`` over the
    embeddings and every matmul weight, the gate too.  The norms and the
    router keep their dtype."""
    blk = params["block"]
    out = dict(blk)
    for name in _trees(blk):
        names = [*_QUANTIZED[name], *(["gate"] if "gate" in blk[name] else [])]
        out[name] = {**blk[name], **{n: cast(blk[name][n]) for n in names}}
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
    merged = dict(blk)
    for name in _trees(blk):
        merged[name] = {**blk[name], **qweights["block"][name]}
    return merged, qweights["unembed"]


# -- rotary, by layer kind --------------------------------------------------------


def rope_table(cfg, kind: str) -> Tuple[np.ndarray, float]:
    """``(inverse frequencies [rotated columns / 2] float32, factor on cos and
    sin)`` of a layer of ``kind``.  Window layers: the whole head at
    ``sliding_rope_theta``.  Full layers: the first ``partial_rotary_factor``
    of the head at ``rope_theta``; with ``rope_yarn_factor`` the YaRN blend
    (``transformers``' ``_compute_yarn_parameters``): frequency ``i`` is
    ``theta^(-2i/dim)`` where it turns more than ``rope_yarn_beta_fast`` times
    over ``rope_yarn_original_max`` positions, that over the factor where it
    turns fewer than ``rope_yarn_beta_slow`` times, a linear ramp between (the
    correction dims truncated), and cos and sin carry
    ``rope_attention_factor``."""
    c = cfg
    if kind == WINDOW:
        half = c.head_dim // 2
        return (c.sliding_rope_theta ** (-np.arange(half, dtype=np.float64) / half)).astype(
            np.float32), 1.0
    dim = int(c.head_dim * c.partial_rotary_factor)
    base = float(c.rope_theta)
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not c.rope_yarn_factor:
        return plain.astype(np.float32), float(c.rope_attention_factor)

    def correction_dim(rotations):
        return dim * math.log(c.rope_yarn_original_max / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(c.rope_yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(c.rope_yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    inv = plain / c.rope_yarn_factor * ramp + plain * (1.0 - ramp)
    return inv.astype(np.float32), float(c.rope_attention_factor)


def _rotate(x, positions, table):
    """``x [B, T, H, d]`` rotated to ``positions [B, T]``: the first ``2 x
    len(inverse frequencies)`` columns, pairs split by halves; the rest pass."""
    inv, factor = table
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(inv)
    cos = (jnp.cos(angles) * factor)[:, :, None, :].astype(x.dtype)
    sin = (jnp.sin(angles) * factor)[:, :, None, :].astype(x.dtype)
    half = inv.shape[0]
    x1, x2, rest = x[..., :half], x[..., half : 2 * half], x[..., 2 * half :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _qkv_rotated(h, layer, positions, cfg, kind):
    q, k, v = decode._qkv(h, layer)
    table = rope_table(cfg, kind)
    return _rotate(q, positions, table), _rotate(k, positions, table), v


def _gated_out(attn, h, layer):
    """``W_o`` over the heads, each first multiplied by its gate ``sigmoid(h
    W_gate)`` where the layer has one.  ``attn [B, T, H, d]``, ``h [B, T, D]``."""
    if "gate" in layer:
        g = jnp.einsum("btd,dh->bth", h, layer["gate"].astype(h.dtype))
        attn = attn * jax.nn.sigmoid(g.astype(jnp.float32)).astype(attn.dtype)[..., None]
    return decode._attn_out(attn, layer)


# -- the rings --------------------------------------------------------------------


def _ring_leaves(tree) -> Tuple[str, ...]:
    return tuple(n for n in decode.WIN_LEAVES + decode.WIN_LEAVES_INT8 if n in tree)


def init_rec_state(cfg, rows: int, kv_dtype: Optional[str] = None) -> Dict[str, jax.Array]:
    """Zeroed rings for ``rows`` sequences (the engine's slots, or the places
    of its snapshot store): ``decode.WIN_LEAVES``, or under ``kv_dtype="int8"``
    ``decode.WIN_LEAVES_INT8`` (one float32 scale a head row, as the pool's)."""
    c = cfg
    shape = (_count(c)[WINDOW], rows, c.sliding_window, c.kv_heads, c.head_dim)
    if kv_dtype is None:
        return {n: jnp.zeros(shape, c.dtype) for n in decode.WIN_LEAVES}
    out = {}
    for n in decode.WIN_LEAVES:
        out[n + "_q"] = jnp.zeros(shape, jnp.int8)
        out[n + "_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    return out


def rec_row_bytes(cfg, kv_dtype: Optional[str] = None) -> int:
    """Device bytes of ONE sequence's rings (one snapshot)."""
    c = cfg
    head_rows = 2 * _count(c)[WINDOW] * c.sliding_window * c.kv_heads
    if kv_dtype is None:
        return head_rows * c.head_dim * jnp.dtype(c.dtype).itemsize
    return head_rows * (c.head_dim + 4)


def take_snapshot(store, pool, slot, idx):
    """Copy slot ``slot``'s rings out of the pool into place ``idx`` of the
    snapshot store (jit with the STORE donated; the pool is only read)."""
    return decode.take_snapshot(store, pool, slot, idx, _ring_leaves(store))


def restore_snapshot(pool, store, idx, slot):
    """Copy place ``idx`` of the snapshot store into slot ``slot``'s rings (jit
    with the POOL donated)."""
    return decode.restore_snapshot(pool, store, idx, slot, _ring_leaves(store))


def chunk_window_pairs(cfg, start: int, length: int) -> int:
    """(query, key) pairs ONE window layer admits for a prompt chunk of
    ``length`` tokens from ``start``: the query at ``i`` admits ``min(i + 1,
    sliding_window)`` keys.  The host's count for ``/v1/stats``."""
    W = cfg.sliding_window
    ramp_end = min(max(W - 1, start), start + length)  # positions below W - 1 admit i + 1
    ramp = (ramp_end - start) * (start + 1 + ramp_end) // 2
    return ramp + (start + length - ramp_end) * W


def _ring(pool, name, index, dtype):
    """Ring leaf ``name`` (``"win_k"`` / ``"win_v"``) at ``index`` (traced):
    ``(layer, slot)`` gives one slot's ring ``[window, Hkv, d]``, ``(layer,)``
    every slot's ``[slots, window, Hkv, d]``; at ``dtype``, int8 rows
    dequantised in the read."""

    def part(leaf):
        start = index + (0,) * (leaf.ndim - len(index))
        size = (1,) * len(index) + leaf.shape[len(index):]
        return lax.dynamic_slice(leaf, start, size).reshape(leaf.shape[len(index):])

    if name + "_q" in pool:
        return decode._kv_dequant(part(pool[name + "_q"]), part(pool[name + "_scale"]), dtype)
    return part(pool[name]).astype(dtype)


def _ring_put(pool, name, index, take, new):
    """The pool with ``new`` written into ring leaf ``name`` where ``take``
    holds and the ring left as it is elsewhere, in the leaf's own storage (int8
    rows are quantised once, on the way in).  ``index`` addresses the part of
    the leaf that ``take [..., window]`` and ``new [..., window, Hkv, d]``
    cover: ``(layer, slot)`` for a chunk, ``(layer,)`` for a step's slots."""

    def put(leaf, value):
        start = index + (0,) * (leaf.ndim - len(index))
        size = (1,) * len(index) + leaf.shape[len(index):]
        old = lax.dynamic_slice(leaf, start, size).reshape(value.shape)
        keep = take.reshape(take.shape + (1,) * (value.ndim - take.ndim))
        value = jnp.where(keep, value.astype(leaf.dtype), old)
        return lax.dynamic_update_slice(leaf, value.reshape(size), start)

    if name + "_q" in pool:
        q, scale = decode._kv_quant(new)
        return {**pool, name + "_q": put(pool[name + "_q"], q),
                name + "_scale": put(pool[name + "_scale"], scale)}
    return {**pool, name: put(pool[name], new)}


# -- the mixers -------------------------------------------------------------------


def _full_chunk_mixer(cfg, qpos, table, write_blk, write_off, live_end):
    """A prompt chunk's full-attention mixer ``(h [1, C, D], layer, index among
    the full layers, pool) -> (output, pool)``: the chunk's K and V appended,
    then attended by key tiles of ``table``, first tile to the one that holds
    ``live_end - 1``, each tile gathered and attended in the flash forward
    kernel (scores float32, in VMEM; the query's offset against the tile is
    its causal mask; KV heads read in place by their query group), the tiles'
    ``(o, lse)`` merged."""
    c = cfg
    H, Hkv, d = heads(c, FULL), c.kv_heads, c.head_dim

    def attend(h, layer, li, pool):
        dt = h.dtype
        q, k, v = _qkv_rotated(h, layer, qpos[None], c, FULL)
        pool = decode._kv_append(pool, li, k[0], v[0], write_blk, write_off)
        qh = q[0].swapaxes(0, 1)  # [H, C, d]

        def turn(carry, blocks, k0):
            kt, vt = decode._kv_tile(pool, li, blocks, dt, Hkv)
            tile = flash.flash_block_fwd(
                qh, kt, vt, causal=True, sm_scale=d**-0.5, q_offset=qpos[0] - k0,
                group=H // Hkv, name="full_chunk_tile",
            )
            return flash._merge(*carry, *tile)

        with jax.named_scope("window_moe.full_chunk_attend"):
            o, _ = decode._walk_table_tiles(
                table, decode.pool_geometry(pool)[0], live_end, turn,
                (jnp.zeros((H, qh.shape[1], d), jnp.float32),
                 jnp.full((H, qh.shape[1]), -jnp.inf)),
            )
        return _gated_out(o.astype(dt).swapaxes(0, 1)[None], h, layer), pool

    return attend


def _window_chunk_mixer(cfg, qpos, start, length, slot):
    """A prompt chunk's window mixer ``(h [1, C, D], layer, index among the
    window layers, pool) -> (output, pool)``.  The slot's ring, turned so that
    its rows stand in position order (``start - window`` first), and the
    chunk's own keys are one key axis whose first position lies ``window``
    before the first query's; the flash forward kernel admits ``(i - window,
    i]`` and nothing before position 0 (``k_first``: a ring not yet filled
    holds another sequence's rows there).  Then the newest ``window`` positions
    up to ``start + length - 1`` are left in the ring: the chunk's own where it
    reached them, the ring's where it did not."""
    c = cfg
    W, H, Hkv, d = c.sliding_window, heads(c, WINDOW), c.kv_heads, c.head_dim
    edge = max(W, 128)  # tiles of the window's size: two of three key tiles a query tile
    end = start + length
    ring_rows = jnp.arange(W)
    newest = end - 1 - (end - 1 - ring_rows) % W  # newest position <= end - 1 at each ring row
    from_chunk = newest >= start
    chunk_row = jnp.clip(newest - start, 0, qpos.shape[0] - 1)

    def attend(h, layer, li, pool):
        dt = h.dtype
        q, k, v = _qkv_rotated(h, layer, qpos[None], c, WINDOW)

        def keys(name, own):
            ring = jnp.roll(_ring(pool, name, (li, slot), dt), -(start % W), axis=0)
            return jnp.concatenate([ring, own], axis=0).swapaxes(0, 1)  # [Hkv, W + C, d]

        with jax.named_scope("window_moe.window_chunk_attend"):
            o, _ = flash.flash_block_fwd(
                q[0].swapaxes(0, 1), keys("win_k", k[0]), keys("win_v", v[0]),
                causal=True, sm_scale=d**-0.5, q_offset=W, window=W,
                k_first=jnp.maximum(W - start, 0), group=H // Hkv,
                block_q=edge, block_k=edge, name=f"window_chunk_{qpos.shape[0]}",
            )
        for name, own in (("win_k", k[0]), ("win_v", v[0])):
            pool = _ring_put(pool, name, (li, slot), from_chunk, own[chunk_row])
        return _gated_out(o.astype(dt).swapaxes(0, 1)[None], h, layer), pool

    return attend


def _full_step_mixer(cfg, positions, tables, write_blk, write_off, pos):
    """A decode step's full-attention mixer ``(h [S, 1, D], layer, index among
    the full layers, pool) -> (output, pool)``, and the gate.  By what the pool
    holds: K and V in the compute dtype are appended and read where they lie,
    each lane's pages up to the one that holds ``pos``, a KV head's query group
    the rows of one product (``flash.paged_step_attend``: nothing has the
    table's width, no view of a pool leaf is formed); int8 rows
    under their scales take the dense step's gather and ``decode._attend_paged``."""
    c = cfg
    H, Hkv, d = heads(c, FULL), c.kv_heads, c.head_dim
    group = H // Hkv

    def attend(h, layer, li, pool):
        q, k, v = _qkv_rotated(h, layer, positions, c, FULL)
        if decode.is_quantized_pool(pool):
            pool, ck, cv = decode._kv_through_table(
                pool, li, k, v, tables, write_blk, write_off, h.dtype
            )
            return _gated_out(decode._attend_paged(q, ck, cv, pos, group), h, layer), pool
        pool = decode._kv_append(pool, li, k[:, 0], v[:, 0], write_blk, write_off)
        with jax.named_scope("window_moe.full_step_attend"):
            qg = q[:, 0].reshape(-1, Hkv, group, d)
            qg = jnp.pad(qg, ((0, 0), (0, 0), (0, -group % 8), (0, 0)))  # whole sublane tiles a head
            o = flash.paged_step_attend(
                qg, pool["k"], pool["v"], li, tables, pos + 1, sm_scale=d**-0.5
            )
        attn = o[:, :, :group].astype(h.dtype).reshape(-1, 1, H, d)
        return _gated_out(attn, h, layer), pool

    return attend


def _window_step_mixer(cfg, positions, pos, active):
    """A decode step's window mixer: every active lane's row written at ``pos
    mod window`` of its slot's ring, then the ring attended; a ring row counts
    where the newest position it can hold is one the sequence has had."""
    c = cfg
    W = c.sliding_window
    ring_rows = jnp.arange(W)
    write = (ring_rows[None] == (pos % W)[:, None]) & active[:, None]  # [S, W]
    held = pos[:, None] - (pos[:, None] - ring_rows[None]) % W >= 0  # [S, W]

    def attend(h, layer, li, pool):
        dt = h.dtype
        q, k, v = _qkv_rotated(h, layer, positions, c, WINDOW)
        for name, new in (("win_k", k), ("win_v", v)):
            pool = _ring_put(pool, name, (li,), write, jnp.broadcast_to(
                new, (new.shape[0], W) + new.shape[2:]))

        with jax.named_scope("window_moe.window_step_attend"):
            attn = decode._attend(
                q, _ring(pool, "win_k", (li,), dt), _ring(pool, "win_v", (li,), dt),
                heads(c, WINDOW) // c.kv_heads, lambda K: held[:, None, None, None, :],
            )
        return _gated_out(attn, h, layer), pool

    return attend


# -- the loop and the two paged programs -------------------------------------------


def _run_stack(x, blk, pool, cfg, mixers, valid):
    """The layer loop: one ``lax.scan`` a run of one kind of layer, ``(x,
    pool)`` the carry, each layer the pre-norm block around ``mixers[mixer
    kind](h, layer, index among its kind, pool) -> (output, pool)`` and its
    kind's MLP.  Returns ``(x, pool, counts)``, ``counts`` what the expert
    layers routed (``experts.COUNT_NAMES``)."""
    norms = {n: blk[n] for n in ("attn_norm", "mlp_norm")}
    first = dict.fromkeys(_TREE, 0)
    layer0 = 0
    counts = _no_counts()
    for mix, mlp, n in runs(cfg):
        lo_mix, lo_mlp = first[mix], first[mlp]
        # The routed experts' stacks stay out of the scanned inputs: the loop
        # would cut a layer's copy out of them for every iteration.
        stacks = {w: blk["experts"][w] for w in ("wi", "wg", "wd")} if mlp == SPARSE else {}
        xs = (
            jax.tree.map(lambda w: w[layer0 : layer0 + n], norms),
            jax.tree.map(lambda w: w[lo_mix : lo_mix + n], blk[_TREE[mix]]),
            jax.tree.map(
                lambda w: w[lo_mlp : lo_mlp + n],
                {w: v for w, v in blk[_TREE[mlp]].items() if w not in stacks},
            ),
            lo_mix + jnp.arange(n),
            lo_mlp + jnp.arange(n),
        )

        def body(carry, inputs, mix=mix, mlp=mlp, stacks=stacks):
            x, pool = carry
            norm, layer, mlp_w, mi, ki = inputs
            mixed, pool = mixers[mix](_rmsnorm(x, norm["attn_norm"]), layer, mi, pool)
            x = x + mixed
            h = _rmsnorm(x, norm["mlp_norm"])
            if mlp == DENSE:
                return (x + decode._gated_mlp(h, mlp_w), pool), _no_counts()
            y, c = _expert_mlp(h, mlp_w, valid, cfg, stacks, ki)
            return (x + y, pool), c

        (x, pool), c = lax.scan(body, (x, pool), xs)
        counts = counts + jnp.sum(c, axis=0)
        first[mix] += n
        first[mlp] += n
        layer0 += n
    return x, pool, counts


def paged_prefill_chunk(params, pool, table, tokens, start, length, slot, cfg):
    """``decode.paged_prefill_chunk`` for the window stack: one prompt chunk of
    the sequence in ``slot``.  The full layers write the KV pool at ``table``
    and attend it by key tiles up to ``start + length``; the window layers
    attend ``slot``'s rings and the chunk itself and leave the newest rows in
    the rings.  Pad rows write KV to the trash block, reach no ring, are keys
    to no real row and route to no expert.  Returns ``(logits [vocab] f32,
    new_pool, counts)``."""
    c = cfg
    C = tokens.shape[0]
    qpos, valid, write_blk, write_off, _ = decode._chunk_addresses(
        pool, table, start, length, C
    )
    mixers = {
        FULL: _full_chunk_mixer(c, qpos, table, write_blk, write_off, start + length),
        WINDOW: _window_chunk_mixer(c, qpos, start, length, slot),
    }
    x = params["embed"].astype(c.dtype)[tokens][None]  # [1, C, D]
    x, pool, counts = _run_stack(x, params["block"], pool, c, mixers, valid[None])
    # Only the last real token's logits are read: one row against the vocabulary.
    last = _rmsnorm(jnp.take(x[0], length - 1, axis=0), params["final_norm"])
    logits = jnp.einsum("d,dv->v", last, params["unembed"].astype(last.dtype))
    return logits.astype(jnp.float32), pool, counts


def paged_decode_step(params, pool, tables, tokens, pos, active, cfg, qweights=None):
    """``decode.paged_decode_step`` for the window stack: every active slot one
    token; an inactive (free or parked) slot keeps its rings.  Returns
    ``(logits [S, vocab] f32, new_pool, counts)``."""
    c = cfg
    S = tables.shape[0]
    bs = decode.pool_geometry(pool)[0]
    pos = jnp.where(active, pos, 0)
    write_blk = jnp.where(active, tables[jnp.arange(S), pos // bs], 0)
    write_off = jnp.where(active, pos % bs, 0)
    positions = pos[:, None]
    mixers = {
        FULL: _full_step_mixer(c, positions, tables, write_blk, write_off, pos),
        WINDOW: _window_step_mixer(c, positions, pos, active),
    }
    x = params["embed"].astype(c.dtype)[tokens][:, None, :]  # [S, 1, D]
    blk, unembed = _with_qweights(params, qweights)
    x, pool, counts = _run_stack(x, blk, pool, c, mixers, active[:, None])
    logits = decode._unembed(x, params["final_norm"], unembed)
    return logits[:, 0].astype(jnp.float32), pool, counts
