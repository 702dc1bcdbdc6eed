"""Ulysses sequence parallelism with flash attention per head shard.

The GSPMD formulation (``templates.py``: constrain ``attn_heads`` to the
sequence axis and let XLA insert the all-to-alls) is elegant but pins
attention to XLA's dense path — a pallas call is a custom call GSPMD
cannot partition, so long-context Ulysses paid O(T²) score memory while
the ring had the flash kernel.  This module is the manual twin: an
explicit ``shard_map`` whose body performs the two DeepSpeed-Ulysses
all-to-alls itself (seq-sharded → head-sharded and back, each one ICI
all-to-all) and runs the framework's flash kernel (``parallel/flash.py``)
over the FULL sequence per head shard — O(T) memory, same numerics.

Autodiff needs no custom VJP here: ``lax.all_to_all`` is linear (its
transpose is the reverse all-to-all) and the flash call carries its own
flash-2 VJP, so gradients compose through the shard_map.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple, Union

import jax
from jax import lax

from polyaxon_tpu.parallel.flash import flash_attention, pallas_interpret


def _ulysses_body(q, k, v, *, axis_name, cfg):
    """Per-shard body. q/k/v: [B, T_local, H, d] (contiguous seq shards)."""
    # seq-sharded → head-sharded: split the heads axis over the group,
    # concatenate the sequence axis (one all-to-all each).
    swap = partial(
        lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1,
        tiled=True,
    )
    qh, kh, vh = swap(q), swap(k), swap(v)  # [B, T, H/n, d]
    o = flash_attention(cfg, qh, kh, vh)
    # head-sharded → seq-sharded (the reverse all-to-all).
    return lax.all_to_all(
        o, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    seq_axis: str,
    batch_axes: Union[str, Tuple[str, ...], None] = None,
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """Global-view entry: q/k/v [B, T, H, d] with T sharded on ``seq_axis``
    and H divisible by the axis size."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[seq_axis]
    H = q.shape[2]
    if H % n:
        raise ValueError(
            f"Ulysses needs heads ({H}) divisible by the '{seq_axis}' axis ({n})"
        )
    d = q.shape[-1]
    cfg = (d**-0.5, block_q, block_k, pallas_interpret())
    spec = P(batch_axes, seq_axis, None, None)
    fn = jax.shard_map(
        partial(_ulysses_body, axis_name=seq_axis, cfg=cfg),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
