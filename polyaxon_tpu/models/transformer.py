"""Flagship model: a decoder-only transformer LM, TPU-first.

The reference platform ships no models (training code lives in user
containers — SURVEY §2.8); the TPU framework needs a first-class flagship
so sharding templates, benchmarks, and the driver hooks have a real
workload.  Design choices are all MXU/HBM-driven:

- **bfloat16 compute, float32 params/accumulation** — MXU-native.
- **einsum everywhere** — large, fusable contractions XLA tiles onto the
  systolic array; no per-head Python loops.
- **stacked layer parameters + ``lax.scan``** — one compiled block body
  regardless of depth (fast compiles), and the leading ``layers`` axis IS
  the pipeline-stage axis for pp sharding.
- **logical axis names on every parameter** (``param_axes``) — the
  parallelism templates (``polyaxon_tpu.parallel.templates``) map them onto
  any mesh; the model never mentions a mesh axis.
- optional **MoE MLP** (top-1 switch routing, einsum dispatch/combine) for
  expert parallelism; optional **ring attention** for sequence parallelism.
- ``jax.checkpoint`` on the block body (``remat=True``) to trade FLOPs for
  HBM on long sequences.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from polyaxon_tpu.parallel.axes import AxisRules, with_logical_constraint


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    head_dim: int = 64
    d_ff: int = 2048
    max_seq: int = 1024
    #: ``None`` = no rotary embedding (the hybrid stack only: there the
    #: recurrent layers carry the positions).
    rope_theta: Optional[float] = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: 0 = dense MLP; >0 = MoE with this many experts (top-1 switch routing)
    n_experts: int = 0
    #: per-expert capacity = capacity_factor * tokens / n_experts
    capacity_factor: float = 1.25
    remat: bool = False
    #: What the checkpointed block may KEEP across the bwd recompute:
    #: "none" (recompute everything — max memory savings), "dots" (keep
    #: matmul outputs), "dots_no_batch" (keep batch-free matmuls),
    #: "save_attn" (keep the attention output — skips re-running the
    #: attention subgraph; the measured v5e sweet spot, docs/bench-notes),
    #: "save_attn_mlp" (also keep the post-activation MLP product).
    remat_policy: str = "none"

    #: Pallas flash kernel tile edge (block_q = block_k); a VMEM-budget
    #: knob.  1024 is the measured v5e optimum — 3.9x the throughput of
    #: 128 at T=8192; 2048 exceeds the 16M scoped-vmem limit
    #: (docs/bench-notes.md).
    flash_block: int = 1024
    #: Grouped-query attention: number of K/V heads (None = n_heads, i.e.
    #: full multi-head).  Fewer KV heads shrink the KV params/optimizer
    #: state and — under sp_ring — the per-hop ppermute payload by
    #: n_heads/n_kv_heads (the ring rotates UNEXPANDED KV blocks and
    #: broadcasts them to the query heads only inside the kernel call).
    n_kv_heads: Optional[int] = None

    def __post_init__(self) -> None:
        allowed = (
            "none", "dots", "dots_no_batch", "save_attn", "save_attn_mlp",
            "save_qkv_attn",
        )
        if self.remat_policy not in allowed:
            raise ValueError(
                f"Unknown remat_policy {self.remat_policy!r} (one of {allowed})"
            )
        if self.n_kv_heads is not None and not (
            0 < self.n_kv_heads <= self.n_heads
        ):
            raise ValueError(
                f"n_kv_heads ({self.n_kv_heads}) must be in [1, n_heads="
                f"{self.n_heads}]"
            )
        if self.n_heads % self.kv_heads:
            raise ValueError(
                f"n_heads ({self.n_heads}) must be divisible by n_kv_heads "
                f"({self.kv_heads})"
            )
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.mlp_layer_types is not None:
            object.__setattr__(self, "mlp_layer_types", tuple(self.mlp_layer_types))
            from polyaxon_tpu.models.window_moe import check_config

            check_config(self)
        elif self.kv_lora_rank:
            from polyaxon_tpu.models.latent_moe import check_config

            check_config(self)
        elif self.layer_types is not None:
            from polyaxon_tpu.models.hybrid import check_config

            check_config(self)
        elif self.rope_theta is None:
            raise ValueError(
                "rope_theta=None (no rotary embedding) needs a layer pattern: "
                "the dense block has no other source of positions"
            )

    @property
    def kv_heads(self) -> int:
        return self.n_heads if self.n_kv_heads is None else self.n_kv_heads
    #: "auto" = pallas flash kernel on single-device TPU, XLA attention
    #: elsewhere; "dense" forces XLA; "flash" forces the pallas kernel.
    #: (A pallas call is a custom call GSPMD can't partition, so the
    #: unsharded flash path is only taken when attention runs on one
    #: device.  With a ring template the value selects the RING body
    #: instead: flash-per-block inside shard_map — sharded long context
    #: runs the O(T_local) kernel per shard; see parallel/flash.py.)
    attention_impl: str = "auto"
    #: Blockwise cross-entropy sequence-chunk size (0 = off).  When set
    #: (and T divides evenly), loss_fn never materializes the full
    #: [B,T,vocab] f32 logits — the step's single largest activation
    #: (2.1G at the bench shape) — computing logsumexp + target logit one
    #: [B,chunk] slice at a time under jax.checkpoint, so the backward
    #: recomputes each chunk's logits instead of keeping them resident.
    ce_chunk: int = 0
    #: A layer pattern, one entry per layer, served through the paged programs
    #: only (:attr:`stack`).  The HYBRID stack of ``models/hybrid.py``:
    #: ``"linear_attention"`` (gated delta rule, sized by the ``linear_*``
    #: fields below, named as the published configs name them) or
    #: ``"full_attention"``, a whole number of periods.  The LATENT stack of
    #: ``models/latent_moe.py`` (``kv_lora_rank`` > 0): ``"dense_mlp"`` or
    #: ``"expert_mlp"``, every mixer latent attention.  ``None`` = every
    #: layer the dense block of this module.
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    #: beta in (0, 2) instead of (0, 1): the state transition may reflect.
    linear_allow_neg_eigval: bool = False
    #: Latent attention (``models/latent_moe.py``), sized as the published
    #: configs of the DeepSeek-V3 family name it.  ``kv_lora_rank`` > 0 makes
    #: every mixer latent: queries through a ``q_lora_rank`` bottleneck, per
    #: head ``qk_nope_head_dim`` unrotated + ``qk_rope_head_dim`` rotated
    #: columns; keys and values up-projected from ONE row a token of
    #: ``kv_lora_rank`` + ``qk_rope_head_dim`` values, which is what the paged
    #: pool holds (``n_kv_heads`` and ``head_dim`` size nothing here).
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: The ``"expert_mlp"`` layers of that stack: a sigmoid router over
    #: ``n_routed_experts`` with a selection bias, ``num_experts_per_tok``
    #: chosen, their weights normalised and scaled by
    #: ``routed_scaling_factor``, experts and ``n_shared_experts`` always-on
    #: experts gated SiLU MLPs of width ``moe_intermediate_size``.  This chip
    #: HOLDS experts ``[expert_offset, expert_offset + experts_held)`` of a
    #: layer (0 = all): it routes over all of them and computes its own.
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    experts_held: int = 0
    expert_offset: int = 0
    #: The WINDOW stack of ``models/window_moe.py``: a layer is described by its
    #: mixer AND its MLP, one list each, as the published configs name them.
    #: ``layer_types``: ``"full_attention"`` or ``"sliding_attention"`` (a query
    #: at ``i`` admits the keys of ``(i - sliding_window, i]``);
    #: ``mlp_layer_types`` (naming it selects this stack): ``"dense"`` (``d_ff``
    #: wide) or ``"sparse"`` (the routed experts sized above, under a SOFTMAX
    #: router without a selection bias).  ``n_heads`` query heads in the full
    #: layers, ``sliding_n_heads`` in the window layers (0 = the same), KV heads
    #: and ``head_dim`` the same in both; ``head_gate``: one sigmoid scalar a
    #: head from the layer's normed input, on the attention output before
    #: ``W_o``.
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 0
    sliding_n_heads: int = 0
    head_gate: bool = False
    #: Rotary embedding by layer kind.  The full layers rotate the first
    #: ``partial_rotary_factor`` of each head at ``rope_theta``, under YaRN
    #: where ``rope_yarn_factor`` > 0 (frequencies blended between
    #: ``rope_theta`` and ``rope_theta`` stretched by the factor, by the linear
    #: ramp between the correction dims of ``rope_yarn_beta_fast`` /
    #: ``_beta_slow`` at ``rope_yarn_original_max`` positions; cos and sin times
    #: ``rope_attention_factor``).  The window layers rotate the whole head at
    #: ``sliding_rope_theta``, plainly.
    partial_rotary_factor: float = 1.0
    rope_yarn_factor: float = 0.0
    rope_yarn_original_max: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_attention_factor: float = 1.0
    sliding_rope_theta: float = 10000.0

    @property
    def stack(self) -> str:
        """Whose programs serve this configuration: ``"window"``
        (``models/window_moe.py``), ``"latent"`` (``models/latent_moe.py``),
        ``"hybrid"`` (``models/hybrid.py``) or ``"uniform"``
        (``models/decode.py``'s own)."""
        if self.mlp_layer_types is not None:
            return "window"
        if self.kv_lora_rank:
            return "latent"
        return "uniform" if self.layer_types is None else "hybrid"

    def scaled(self, **overrides) -> "TransformerConfig":
        return replace(self, **overrides)

    @property
    def n_kv_layers(self) -> int:
        """Layers that keep KV in the paged pool: the full-attention ones (a
        linear or a window layer keeps fixed-size rows a sequence instead)."""
        if self.stack not in ("hybrid", "window"):
            return self.n_layers
        return sum(1 for t in self.layer_types if t == "full_attention")

    @property
    def pool_kv_heads(self) -> int:
        """KV heads a paged-pool row holds.  The hybrid stack pads them to a
        multiple of 8: with 30 heads the TPU compiler keeps the pool in a
        layout of its own choosing and copies the whole pool in and out of
        every program to get there (chipless v5e compile: 6 pool-sized copies
        in a decode step, 8 in a chunk; none at 32).  The dense model's pool
        is as it was."""
        if self.stack not in ("hybrid", "window"):
            return self.kv_heads
        return -(-self.kv_heads // 8) * 8

    @property
    def n_params(self) -> int:
        """Parameter count (for MFU math)."""
        c = self
        if c.stack != "uniform":
            return stack_module(c).n_params(c)
        attn = c.d_model * c.head_dim * (2 * c.n_heads + 2 * c.kv_heads)
        if c.n_experts:
            mlp = c.d_model * c.n_experts + c.n_experts * c.d_model * c.d_ff * 3
        else:
            mlp = c.d_model * c.d_ff * 3
        per_layer = attn + mlp + 2 * c.d_model
        return c.vocab_size * c.d_model * 2 + c.n_layers * per_layer + c.d_model


def stack_module(cfg: TransformerConfig):
    """The model file of a configuration whose ``stack`` is not ``"uniform"``:
    its ``init_params``, ``n_params`` and paged programs."""
    from polyaxon_tpu.models import hybrid, latent_moe, window_moe

    return {"latent": latent_moe, "hybrid": hybrid, "window": window_moe}[cfg.stack]


def param_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Logical axis names for every parameter (mirrors ``init_params``)."""
    block: Dict[str, Any] = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "heads", "head_dim"),
        "wv": ("layers", "embed", "heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if cfg.n_experts:
        block.update(
            router=("layers", "embed", "experts"),
            wi=("layers", "experts", "embed", "mlp"),
            wg=("layers", "experts", "embed", "mlp"),
            wd=("layers", "experts", "mlp", "embed"),
        )
    else:
        block.update(
            wi=("layers", "embed", "mlp"),
            wg=("layers", "embed", "mlp"),
            wd=("layers", "mlp", "embed"),
        )
    return {
        "embed": ("vocab", "embed"),
        "unembed": ("embed", "vocab"),
        "final_norm": ("embed",),
        "block": block,
    }


def init_params(key: jax.Array, cfg: TransformerConfig) -> Dict[str, Any]:
    c = cfg
    if c.stack != "uniform":
        return stack_module(c).init_params(key, c)
    k = iter(jax.random.split(key, 16))
    dt = c.param_dtype

    def norm(*shape, scale):
        return jax.random.normal(next(k), shape, dt) * scale

    L, D, H, hd, F = c.n_layers, c.d_model, c.n_heads, c.head_dim, c.d_ff
    Hkv = c.kv_heads
    block: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, D), dt),
        "wq": norm(L, D, H, hd, scale=D**-0.5),
        "wk": norm(L, D, Hkv, hd, scale=D**-0.5),
        "wv": norm(L, D, Hkv, hd, scale=D**-0.5),
        "wo": norm(L, H, hd, D, scale=(H * hd) ** -0.5),
        "mlp_norm": jnp.ones((L, D), dt),
    }
    if c.n_experts:
        E = c.n_experts
        block.update(
            router=norm(L, D, E, scale=D**-0.5),
            wi=norm(L, E, D, F, scale=D**-0.5),
            wg=norm(L, E, D, F, scale=D**-0.5),
            wd=norm(L, E, F, D, scale=F**-0.5),
        )
    else:
        block.update(
            wi=norm(L, D, F, scale=D**-0.5),
            wg=norm(L, D, F, scale=D**-0.5),
            wd=norm(L, F, D, scale=F**-0.5),
        )
    return {
        "embed": norm(c.vocab_size, D, scale=1.0),
        "unembed": norm(D, c.vocab_size, scale=D**-0.5),
        "final_norm": jnp.ones((D,), dt),
        "block": block,
    }


def _rmsnorm(x: jax.Array, w: jax.Array) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + 1e-6).astype(x.dtype)) * w.astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over the last (head_dim) axis. x: [B,T,H,d]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,d/2]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _dense_attention(q, k, v, q_pos, k_pos):
    """Causal attention. q:[B,Tq,H,d] k,v:[B,Tk,H,d] → [B,Tq,H,d]."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = q_pos[:, None, :, None] >= k_pos[:, None, None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_attention(q, k, v, block: int = 1024):
    """Pallas fused causal attention (TPU): O(T) memory, no [T,T] scores.

    The HBM-bandwidth win the reference could never express (its compute
    lived in user containers): the score matrix never leaves VMEM, so long
    sequences fit without remat.  Uses the framework's own kernel
    (parallel/flash.py — the ring body's block kernel over the full
    sequence): measured 1.9x the jax-bundled pallas kernel in full train
    steps at T=8192 on v5e.
    """
    from polyaxon_tpu.parallel.flash import flash_attention, pallas_interpret

    cfg = (q.shape[-1] ** -0.5, block, block, pallas_interpret())
    return flash_attention(cfg, q, k, v)


def _platform_is_tpu() -> bool:
    from polyaxon_tpu.parallel.flash import on_tpu

    return on_tpu()


def _use_flash(
    cfg: TransformerConfig, mesh, ring_axis, pipeline_axis, seq_len: int
) -> bool:
    if cfg.attention_impl == "dense" or ring_axis is not None:
        return False
    if cfg.attention_impl == "flash":
        return True
    # auto: whenever attention runs unsharded on a TPU backend. With
    # 1024-edge tiles the in-house kernel beats XLA's dense path at EVERY
    # measured shape on v5e full train steps (remat, 671M params):
    # 0.554 vs 0.529 at T=1024, 0.507 vs 0.394 at T=2048, 0.482 vs 0.325
    # at T=4096, and past the dense HBM wall it is the only thing that
    # runs (0.459 at T=8192, 0.405 at T=16384 via sp_ring n=1) — see
    # docs/bench-notes.md for the sweep.
    if pipeline_axis is not None or (mesh is not None and mesh.size > 1):
        return False
    return _platform_is_tpu()


def _moe_mlp(x, layer, cfg: TransformerConfig, rules: AxisRules, mesh):
    """Top-1 (switch) MoE with einsum dispatch/combine.

    Token dispatch is expressed as dense einsums over a capacity-bounded
    one-hot: with ``experts``→``expert`` sharding, XLA lowers the dispatch/
    combine contractions into the expert all-to-alls — no manual comms.
    """
    B, T, D = x.shape
    E = cfg.n_experts
    tokens = B * T
    capacity = max(1, int(cfg.capacity_factor * tokens / E))

    logits = jnp.einsum("btd,de->bte", x, layer["router"].astype(x.dtype))
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [B,T,E]
    flat_gates = gates.reshape(tokens, E)
    expert_idx = jnp.argmax(flat_gates, axis=-1)  # [tokens]
    gate_val = jnp.take_along_axis(flat_gates, expert_idx[:, None], axis=1)[:, 0]

    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [tokens,E]
    position = jnp.cumsum(onehot, axis=0) * onehot - 1.0  # rank within expert
    keep = (position < capacity) & (onehot > 0)
    pos_onehot = jax.nn.one_hot(
        jnp.where(keep.any(-1), position.max(-1), -1).astype(jnp.int32),
        capacity,
        dtype=jnp.float32,
    )  # [tokens, C]
    dispatch = (onehot * keep)[:, :, None] * pos_onehot[:, None, :]  # [tokens,E,C]
    combine = dispatch * gate_val[:, None, None]

    xf = x.reshape(tokens, D)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xf)
    expert_in = with_logical_constraint(expert_in, ("experts",), rules, mesh)
    h = jnp.einsum("ecd,edf->ecf", expert_in, layer["wi"].astype(x.dtype))
    g = jnp.einsum("ecd,edf->ecf", expert_in, layer["wg"].astype(x.dtype))
    h = jax.nn.silu(g) * h
    out = jnp.einsum("ecf,efd->ecd", h, layer["wd"].astype(x.dtype))
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), out)
    return y.reshape(B, T, D), gates, expert_idx.reshape(B, T)


def moe_aux_loss(gates: jax.Array, expert_idx: jax.Array, n_experts: int) -> jax.Array:
    """Switch-transformer load-balancing loss (mean over layers outside)."""
    me = jnp.mean(gates.reshape(-1, n_experts), axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(expert_idx.reshape(-1), n_experts, dtype=jnp.float32), axis=0
    )
    return n_experts * jnp.sum(me * ce)


def forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: TransformerConfig,
    template=None,
    mesh=None,
    positions: Optional[jax.Array] = None,
    return_kv: bool = False,
    return_hidden: bool = False,
) -> jax.Array:
    """tokens [B,T] → logits [B,T,vocab] (float32).

    ``return_kv`` additionally returns the per-layer POST-rope,
    UNEXPANDED (GQA) key/value stacks ``[L,B,T,Hkv,d]`` — the decode
    prefill (``models/decode.py``) rides this so the cache layout comes
    from the SAME block the training forward runs, instead of a
    duplicated one.  Plain-scan single-program path only (no template),
    dense MLP only.

    ``template`` (a :class:`~polyaxon_tpu.parallel.StrategyTemplate`) plus
    ``mesh`` activate logical sharding constraints and select the attention/
    layer-evaluation path: ring attention when ``template.ring_axis`` is
    set, the GPipe schedule when ``template.pipeline_axis`` is set, plain
    scan otherwise.  With a sequence-sharded template, ``positions`` carries
    each shard's global token positions.
    """
    c = cfg
    if c.stack == "window":
        from polyaxon_tpu.models.window_moe import WindowStackError

        raise WindowStackError("forward")
    if c.layer_types is not None:
        raise NotImplementedError(
            "a model with a layer pattern has no training forward: it is "
            "served through the paged programs (models/hybrid.py)"
        )
    rules: AxisRules = template.rules if template is not None else {}
    ring_axis = template.ring_axis if template is not None else None
    pipeline_axis = template.pipeline_axis if template is not None else None
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))

    # Inside a fully-manual pipeline shard_map, sharding constraints must
    # be inert; the composed mode (pp_tp) keeps the other mesh axes auto,
    # so constraints stay live and GSPMD shards the stage body over them.
    composed = bool(template is not None and template.pipeline_composed)
    cmesh = None if (pipeline_axis and not composed) else mesh
    use_flash = _use_flash(c, mesh, ring_axis, pipeline_axis, T)
    if return_kv and (template is not None or c.n_experts):
        raise NotImplementedError(
            "return_kv supports the plain-scan dense path only (no "
            "parallelism template, no MoE)"
        )
    # Ulysses long-context: the flash kernel can't ride GSPMD (a pallas
    # call is an unpartitionable custom call), so past the dense memory
    # wall (or when forced) the attention goes through the EXPLICIT
    # all-to-all shard_map twin instead of the attn_heads constraints.
    ulysses_axis = template.ulysses_axis if template is not None else None
    ulysses_flash = bool(
        ulysses_axis is not None
        and pipeline_axis is None
        and (
            c.attention_impl == "flash"
            or (c.attention_impl == "auto" and T >= 8192 and _platform_is_tpu())
        )
    )

    table = params["embed"].astype(c.dtype)
    if cmesh is not None and cmesh.size > 1 and (
        rules.get("vocab") or rules.get("embed")
    ):
        # Sharded table: express the lookup as a one-hot matmul (iota
        # embed).  A gather's transpose is a scatter-add, and SPMD's
        # scatter partitioner cannot place batch-sharded updates into an
        # embed/vocab-sharded table without an involuntary full
        # rematerialization (replicate dx, then repartition — an
        # all-gather of [B,T,D] over the whole mesh, DCN included, every
        # step).  The one-hot contraction instead yields partial grads
        # that reduce-scatter into the param placement like every other
        # matmul.  Single-device keeps the free gather.
        onehot = jax.nn.one_hot(tokens, c.vocab_size, dtype=c.dtype)
        x = jnp.einsum("btv,vd->btd", onehot, table)
    else:
        x = table[tokens]  # [B,T,D]
    x = with_logical_constraint(x, ("batch", "seq", None), rules, cmesh)

    def norm_w(w):
        # Replicate norm weights at point of use: under fsdp their embed
        # dim is sharded over a data-like axis, and if that sharding rides
        # into the scan's saved residual, the backward multiplies a
        # batch-sharded cotangent with an embed-sharded [1,1,D] tensor —
        # SPMD resolves that with an involuntary full rematerialization
        # (replicate-then-repartition of the whole activation, every
        # layer).  An explicit replicate of D floats is noise and keeps
        # the residual conflict-free; the weight GRAD still reduces into
        # the sharded param placement.
        return with_logical_constraint(w, (None,), rules, cmesh)

    def block(x, pos, layer):
        h = _rmsnorm(x, norm_w(layer["attn_norm"]))
        q = jnp.einsum("btd,dhk->bthk", h, layer["wq"].astype(h.dtype))
        k = jnp.einsum("btd,dhk->bthk", h, layer["wk"].astype(h.dtype))
        v = jnp.einsum("btd,dhk->bthk", h, layer["wv"].astype(h.dtype))
        q = _rope(q, pos, c.rope_theta)
        k = _rope(k, pos, c.rope_theta)
        # GQA: the ring carries UNEXPANDED KV (its ppermute payload shrinks
        # by n_heads/n_kv_heads and the ring broadcasts inside the kernel
        # call); every other path broadcasts KV heads to the query heads
        # here — the einsum/flash/Ulysses machinery then sees plain MHA.
        group = c.n_heads // c.kv_heads
        kv_cache_k, kv_cache_v = k, v  # post-rope, pre-broadcast (GQA)
        if group > 1 and ring_axis is None:
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        if not ulysses_flash and ring_axis is None:
            # Ulysses switch-point (GSPMD/dense form): constraining
            # attn_heads re-shards heads across the sequence axis (XLA
            # inserts the all-to-all).  The flash form does its own
            # all-to-alls inside shard_map, and the RING likewise wants
            # its seq-sharded inputs untouched — for both, constraining
            # here would force a redundant gather/reshard round-trip
            # (sp_ring maps no attn_heads rule, so the constraint would
            # degrade to "replicate the sequence dim").
            q = with_logical_constraint(q, ("batch", None, "attn_heads", None), rules, cmesh)
            k = with_logical_constraint(k, ("batch", None, "attn_heads", None), rules, cmesh)
            v = with_logical_constraint(v, ("batch", None, "attn_heads", None), rules, cmesh)
        # Named AFTER the attn_heads constraint so remat policies save the
        # post-reshard tensors: under Ulysses the bwd recompute must not
        # re-run the all-to-alls the save exists to skip.
        q = checkpoint_name(q, "q_proj")
        k = checkpoint_name(k, "k_proj")
        v = checkpoint_name(v, "v_proj")
        kv_out = (kv_cache_k, kv_cache_v) if return_kv else None
        if ulysses_flash:
            from polyaxon_tpu.parallel.ulysses import ulysses_attention_sharded

            attn = ulysses_attention_sharded(
                q, k, v, mesh, ulysses_axis,
                batch_axes=rules.get("batch"),
                block_q=c.flash_block,
                block_k=c.flash_block,
            )
        elif ring_axis is not None:
            from polyaxon_tpu.parallel.ring import ring_attention_sharded

            # The ring resolves its own kernel: pallas flash per block on
            # TPU (O(T_local) memory per shard), dense blockwise elsewhere.
            attn = ring_attention_sharded(
                q, k, v, mesh, ring_axis,
                batch_axes=rules.get("batch"),
                impl=c.attention_impl,
                block_q=c.flash_block,
                block_k=c.flash_block,
            )
        elif use_flash:
            attn = _flash_attention(q, k, v, block=c.flash_block)
        else:
            attn = _dense_attention(q, k, v, pos, pos)
        attn = with_logical_constraint(
            attn, ("batch", "seq", "attn_heads", None), rules, cmesh
        )
        # Named for remat policies: saving the attention OUTPUT (O(B·T·D),
        # cheap) lets the checkpointed block skip re-running the whole
        # attention kernel during its backward-pass recompute.
        attn = checkpoint_name(attn, "attn_out")
        x = x + jnp.einsum("bthk,hkd->btd", attn, layer["wo"].astype(h.dtype))

        h = _rmsnorm(x, norm_w(layer["mlp_norm"]))
        if c.n_experts:
            y, gates, idx = _moe_mlp(h, layer, c, rules, cmesh)
            x = x + y
            return x, (gates, idx)
        up = jnp.einsum("btd,df->btf", h, layer["wi"].astype(h.dtype))
        gate = jnp.einsum("btd,df->btf", h, layer["wg"].astype(h.dtype))
        y = jax.nn.silu(gate) * up
        y = with_logical_constraint(y, ("batch", "seq", "act_mlp"), rules, cmesh)
        # Saving this one [B,T,F] product (policy save_attn_mlp) spares the
        # recompute of BOTH up/gate projections — 2 of the 3 MLP matmuls.
        y = checkpoint_name(y, "mlp_act")
        x = x + jnp.einsum("btf,fd->btd", y, layer["wd"].astype(h.dtype))
        x = with_logical_constraint(x, ("batch", "seq", None), rules, cmesh)
        return x, kv_out

    if c.remat:
        # The policy trades HBM for recompute FLOPs: keeping dot outputs
        # skips re-running the MXU-heavy contractions in the bwd pass.
        policies = {
            "dots": jax.checkpoint_policies.dots_saveable,
            "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "save_attn": jax.checkpoint_policies.save_only_these_names("attn_out"),
            "save_attn_mlp": jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mlp_act"
            ),
            "save_qkv_attn": jax.checkpoint_policies.save_only_these_names(
                "q_proj", "k_proj", "v_proj", "attn_out"
            ),
        }
        policy = policies.get(c.remat_policy)
        body = (
            jax.checkpoint(block, policy=policy) if policy else jax.checkpoint(block)
        )
    else:
        body = block

    aux = None
    if pipeline_axis is not None:
        from polyaxon_tpu.parallel.pipeline import (
            pipeline_scan,
            pipeline_scan_composed,
        )

        # pp×MoE: the balance loss is reduced to a scalar INSIDE the
        # schedule (per stage, valid ticks only) because the raw gate
        # tensors live per-microbatch inside the shard_map.
        aux_fn = (
            (lambda a: moe_aux_loss(a[0], a[1], c.n_experts))
            if c.n_experts
            else None
        )
        if composed:
            x, pp_aux = pipeline_scan_composed(
                body,
                x,
                positions,
                params["block"],
                mesh,
                axis=pipeline_axis,
                num_microbatches=template.num_microbatches,
                aux_fn=aux_fn,
            )
        else:
            x, pp_aux = pipeline_scan(
                body,
                x,
                positions,
                params["block"],
                mesh,
                axis=pipeline_axis,
                num_microbatches=template.num_microbatches,
                batch_axes=rules.get("batch"),
                aux_fn=aux_fn,
            )
        if c.n_experts:
            aux = {"aux_loss": pp_aux}
    else:
        x, scan_aux = lax.scan(
            lambda carry, layer: body(carry, positions, layer), x, params["block"]
        )
        # scan_aux is the per-layer stack of whatever ``block`` returned as
        # its aux: MoE gate stats when n_experts, else the (k, v) cache
        # rows when return_kv (each [L, B, T, Hkv, d] after stacking).
        if c.n_experts or return_kv:
            aux = scan_aux

    x = _rmsnorm(x, norm_w(params["final_norm"]))
    if return_hidden:
        # Pre-unembed hidden states for the blockwise cross-entropy
        # (loss_fn's ce_chunk path): the [B,T,vocab] f32 logits tensor —
        # the single largest activation of the whole step — is never
        # materialized; the caller contracts x against ``unembed`` one
        # sequence chunk at a time.
        if c.n_experts and aux is not None:
            return x, aux
        return x
    logits = jnp.einsum("btd,dv->btv", x, params["unembed"].astype(x.dtype))
    logits = with_logical_constraint(logits, ("batch", "seq", None), rules, cmesh)
    if c.n_experts and aux is not None:
        return logits.astype(jnp.float32), aux
    if return_kv:
        return logits.astype(jnp.float32), aux
    return logits.astype(jnp.float32)


def _blockwise_ce(
    x: jax.Array,
    unembed: jax.Array,
    targets: jax.Array,
    mask: Optional[jax.Array],
    chunk: int,
) -> jax.Array:
    """Mean masked next-token NLL without materializing [B,T,V] logits.

    Scans over T/chunk sequence slices; each body projects one [B,C,D]
    slice to logits, reduces to logsumexp + the target logit, and drops
    the logits again.  ``jax.checkpoint`` makes the backward RECOMPUTE
    each chunk's logits rather than saving them — peak CE memory falls
    from O(B·T·V) to O(B·chunk·V) in both passes, trading one extra
    [B,C,D]×[D,V] matmul per chunk (MXU-shaped, cheap next to the HBM
    traffic it saves).  The d(unembed) grads accumulate across chunks
    inside the scan like any scanned-weight gradient.
    """
    B, T, D = x.shape
    n = T // chunk
    xs = jnp.moveaxis(x.reshape(B, n, chunk, D), 1, 0)  # [n,B,C,D]
    ts = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)
    m = (
        jnp.ones((B, T), jnp.float32)
        if mask is None
        else mask.astype(jnp.float32)
    )
    ms = jnp.moveaxis(m.reshape(B, n, chunk), 1, 0)

    @jax.checkpoint
    def body(carry, inp):
        xc, tc, mc = inp
        logits = jnp.einsum(
            "bcd,dv->bcv", xc, unembed.astype(xc.dtype)
        ).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll_sum, cnt = carry
        return (
            nll_sum + jnp.sum((lse - tl) * mc),
            cnt + jnp.sum(mc),
        ), None

    (nll_sum, cnt), _ = lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (xs, ts, ms)
    )
    return nll_sum / jnp.maximum(cnt, 1.0)


def loss_fn(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    cfg: TransformerConfig,
    template=None,
    mesh=None,
    aux_weight: float = 0.01,
) -> jax.Array:
    """Next-token cross-entropy (+ MoE balance loss when configured)."""
    targets = batch["targets"]
    mask = batch.get("mask")
    chunked = bool(
        cfg.ce_chunk and targets.shape[-1] % cfg.ce_chunk == 0
    )
    out = forward(
        params,
        batch["tokens"],
        cfg,
        template=template,
        mesh=mesh,
        positions=batch.get("positions"),
        return_hidden=chunked,
    )
    if cfg.n_experts:
        hidden_or_logits, aux = out
    else:
        hidden_or_logits = out
    if chunked:
        loss = _blockwise_ce(
            hidden_or_logits, params["unembed"], targets, mask, cfg.ce_chunk
        )
    else:
        logits = hidden_or_logits
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        if mask is None:
            loss = jnp.mean(nll)
        else:
            loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    if cfg.n_experts:
        if isinstance(aux, dict):
            # Pipeline path: already reduced inside the GPipe schedule.
            aux_loss = aux["aux_loss"]
        else:
            gates, idx = aux
            aux_loss = jnp.mean(
                jax.vmap(partial(moe_aux_loss, n_experts=cfg.n_experts))(gates, idx)
            )
        loss = loss + aux_weight * aux_loss
    return loss
