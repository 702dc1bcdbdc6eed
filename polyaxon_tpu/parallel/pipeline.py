"""Pipeline parallelism: stacked transformer layers as GPipe stages.

The reference's only notion of "pipeline" is workflow DAGs (``polyflow/``);
model pipeline parallelism has no analogue there (SURVEY §2.8).  TPU-native
design: the model's stacked-layer leading axis is sharded over the
``pipeline`` mesh axis (each device holds L/S contiguous layers), and a
``shard_map`` runs the GPipe schedule — microbatches march through stages,
activations hop stage→stage on one ICI link via ``lax.ppermute``.  The
schedule is a static ``fori_loop`` of M + S - 1 ticks, fully compiled; no
host round-trips.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from polyaxon_tpu.exceptions import RuntimeLayerError


def _pp_body(
    x: jax.Array,
    positions: jax.Array,
    layers: Any,
    *,
    block: Callable,
    axis: str,
    n_micro: int,
    aux_fn: Any = None,
    batch_axis_names: Tuple[str, ...] = (),
    stage_ids: Any = None,
):
    """Per-device GPipe schedule. x: [B_local, T, D]; layers: local stages.

    ``aux_fn(aux) -> scalar`` (optional) reduces a block's per-layer aux
    output (e.g. MoE gate statistics) to a scalar loss; the schedule
    accumulates it only on a stage's VALID ticks — bubble ticks run the
    body on stale state and must not pollute the sum.

    ``stage_ids`` (optional [1] int array, P(axis)-sharded from a global
    arange) replaces ``lax.axis_index`` for the PARTIAL-manual caller
    (``pipeline_scan_composed``), which hands every stage its index as
    data instead of asking the partitioner for it.
    """
    S = lax.psum(1, axis)
    stage = lax.axis_index(axis) if stage_ids is None else stage_ids[0]
    B, T, D = x.shape
    mb = x.reshape(n_micro, B // n_micro, T, D)
    pos_mb = positions.reshape(n_micro, B // n_micro, T)
    perm = [(j, (j + 1) % S) for j in range(S)]

    def run_stage(inp, pos):
        def scan_body(c, layer):
            out, aux = block(c, pos, layer)
            return out, (aux_fn(aux) if aux_fn is not None else 0.0)

        out, layer_aux = lax.scan(scan_body, inp, layers)
        return out, jnp.mean(layer_aux) if aux_fn is not None else 0.0

    outputs = jnp.zeros_like(mb)
    state = jnp.zeros_like(mb[0])
    # The aux rides as shape [1] across the manual boundary; callers
    # squeeze the singleton axis off.
    aux_acc = jnp.zeros((1,), jnp.float32)

    def tick(i, carry):
        outputs, state, aux_acc = carry
        feed = jnp.clip(i, 0, n_micro - 1)
        inp = jnp.where(
            stage == 0, lax.dynamic_index_in_dim(mb, feed, 0, keepdims=False), state
        )
        pos = lax.dynamic_index_in_dim(pos_mb, feed, 0, keepdims=False)
        # Positions are identical across microbatches for standard LM
        # batches; stage>0 reuses the fed index's positions safely.
        out, stage_aux = run_stage(inp, pos)
        # Stage s processes real microbatches exactly on ticks [s, s+M).
        valid = (i >= stage) & (i < stage + n_micro)
        aux_acc = aux_acc + jnp.where(valid, stage_aux, 0.0)
        j = i - (S - 1)
        jc = jnp.clip(j, 0, n_micro - 1)
        cur = lax.dynamic_index_in_dim(outputs, jc, 0, keepdims=False)
        val = jnp.where((stage == S - 1) & (j >= 0), out, cur)
        outputs = lax.dynamic_update_index_in_dim(outputs, val, jc, 0)
        state = lax.ppermute(out, axis, perm)
        return outputs, state, aux_acc

    outputs, _, aux_acc = lax.fori_loop(
        0, n_micro + S - 1, tick, (outputs, state, aux_acc)
    )
    # Only the last stage holds real outputs; broadcast over the pipeline
    # axis so downstream (final norm + unembed) sees replicated activations.
    # The psum rides f32: a bf16 all-reduce over a manual axis inside a
    # PARTIAL-manual shard_map hard-crashes XLA CPU ("Invalid binary
    # instruction opcode copy"), and the one-pass cast on the final
    # activations is noise. (Full-manual pp doesn't hit the bug; the shared
    # body takes the safe path for both.)
    out_dtype = outputs.dtype
    outputs = lax.psum(
        jnp.where(stage == S - 1, outputs, 0.0).astype(jnp.float32), axis
    ).astype(out_dtype)
    # Mean over stages (each holds L/S layers) and microbatches; the aux
    # claims replication in out_specs, so it must also be averaged over any
    # batch-sharding axes (each data shard saw different tokens).
    aux = lax.psum(aux_acc, axis) / (S * n_micro)
    if batch_axis_names:
        aux = lax.pmean(aux, batch_axis_names)
    return outputs.reshape(B, T, D), aux  # aux: [1], squeezed by wrappers


def pipeline_scan_composed(
    block: Callable,
    x: jax.Array,
    positions: jax.Array,
    stacked_layers: Any,
    mesh,
    *,
    axis: str = "pipeline",
    num_microbatches: int = 1,
    aux_fn: Any = None,
) -> Tuple[jax.Array, jax.Array]:
    """GPipe over ``axis`` with every OTHER mesh axis left to GSPMD.

    The composition mode (dp×tp×pp): ``jax.shard_map`` is manual over the
    pipeline axis only, so inside each stage the block's logical sharding
    constraints stay live and XLA shards attention/MLP over ``tensor`` and
    the batch over ``data`` exactly as in the non-pipelined path.  Layer
    stacks are manually split over stages (P(axis) leading dim) while their
    tensor-sharded trailing dims ride through as auto axes.
    """
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis]
    n_layers = jax.tree.leaves(stacked_layers)[0].shape[0]
    if n_layers % n_stages:
        raise RuntimeLayerError(
            f"{n_layers} layers not divisible into {n_stages} pipeline stages"
        )
    if x.shape[0] % num_microbatches:
        raise RuntimeLayerError(
            f"Global batch {x.shape[0]} not divisible by "
            f"{num_microbatches} microbatches"
        )
    layer_spec = jax.tree.map(lambda _: P(axis), stacked_layers)
    x_dtype = x.dtype

    def body_f32(x32, positions, layers, stage_ids):
        # The region boundary rides f32: XLA CPU hard-crashes on a bf16
        # all-reduce over a manual axis inside a PARTIAL-manual shard_map
        # ("Invalid binary instruction opcode copy") — and AD generates
        # exactly that psum for the cotangent of the replicated-in x.
        # Compute stays in the model dtype inside the body.
        out, aux = _pp_body(
            x32.astype(x_dtype),
            positions,
            layers,
            block=block,
            axis=axis,
            n_micro=num_microbatches,
            aux_fn=aux_fn,
            # Auto axes are GSPMD-global inside the body: the aux scalar is
            # already a full-batch value, no pmean over data needed.
            batch_axis_names=(),
            stage_ids=stage_ids,
        )
        return out.astype(jnp.float32), aux

    fn = jax.shard_map(
        body_f32,
        mesh=mesh,
        in_specs=(P(), P(), layer_spec, P(axis)),
        out_specs=(P(), P()),
        axis_names={axis},
        check_vma=False,
    )
    stage_ids = jnp.arange(n_stages, dtype=jnp.int32)
    out, aux = fn(x.astype(jnp.float32), positions, stacked_layers, stage_ids)
    return out.astype(x_dtype), aux[0]


def pipeline_scan(
    block: Callable,
    x: jax.Array,
    positions: jax.Array,
    stacked_layers: Any,
    mesh,
    *,
    axis: str = "pipeline",
    num_microbatches: int = 1,
    batch_axes: Union[str, Tuple[str, ...], None] = None,
    aux_fn: Any = None,
) -> Tuple[jax.Array, jax.Array]:
    """Drop-in replacement for the layer ``lax.scan``, pipelined over ``axis``.

    ``block(x, positions, layer) -> (x, aux)`` is the same body the dense
    path scans. The stacked ``layers`` leading dim must divide by the
    pipeline axis size, and the local batch by ``num_microbatches``.
    Returns ``(outputs, aux_scalar)`` — aux is the mean of
    ``aux_fn(block_aux)`` over layers and microbatches (0.0 without aux_fn),
    which is how MoE's load-balancing loss crosses the shard_map boundary.
    """
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis]
    n_layers = jax.tree.leaves(stacked_layers)[0].shape[0]
    if n_layers % n_stages:
        raise RuntimeLayerError(
            f"{n_layers} layers not divisible into {n_stages} pipeline stages"
        )
    batch = x.shape[0]
    import numpy as np

    data_size = int(
        np.prod([mesh.shape[a] for a in (batch_axes or ()) if a in mesh.shape])
        if not isinstance(batch_axes, str)
        else mesh.shape.get(batch_axes, 1)
    )
    local_batch = batch // max(1, data_size)
    if local_batch % num_microbatches:
        raise RuntimeLayerError(
            f"Local batch {local_batch} not divisible by {num_microbatches} microbatches"
        )

    x_spec = P(batch_axes, None, None)
    pos_spec = P(batch_axes, None)
    layer_spec = jax.tree.map(lambda _: P(axis), stacked_layers)
    batch_axis_names = (
        (batch_axes,)
        if isinstance(batch_axes, str)
        else tuple(a for a in (batch_axes or ()) if a in mesh.shape)
    )
    fn = jax.shard_map(
        partial(
            _pp_body,
            block=block,
            axis=axis,
            n_micro=num_microbatches,
            aux_fn=aux_fn,
            batch_axis_names=batch_axis_names,
        ),
        mesh=mesh,
        in_specs=(x_spec, pos_spec, layer_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    out, aux = fn(x, positions, stacked_layers)
    return out, aux[0]
