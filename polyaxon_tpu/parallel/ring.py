"""Ring attention: causal attention with the sequence sharded over a mesh axis.

Long-context sequence parallelism (SURVEY §5 names it as a required gap —
the reference has no analogue).  K/V blocks rotate around the mesh axis via
``lax.ppermute`` (each hop rides one ICI link) while every device keeps its
query shard resident; softmax is accumulated online (flash-style running
max/denominator), so the full [T, T] score matrix never materializes and
per-device HBM stays O(T_local).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax


def _ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, axis_name: str
) -> jax.Array:
    """Per-shard body. q/k/v: [B, T_local, H, d], contiguous seq shards."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Tl, H, d = q.shape
    scale = d**-0.5
    q32 = q.astype(jnp.float32)

    q_pos = idx * Tl + jnp.arange(Tl)  # global positions of local queries
    m = jnp.full((B, H, Tl), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, H, Tl), jnp.float32)
    o = jnp.zeros((B, Tl, H, d), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        o, l, m, k, v = carry
        # After i hops along perm j->j+1, this device holds block (idx - i).
        src = (idx - i) % n
        k_pos = src * Tl + jnp.arange(Tl)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, k.astype(jnp.float32)) * scale
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # Fully-masked-so-far rows keep m == -inf; guard the NaN-producing
        # exp(-inf - -inf) paths.
        alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_new))
        p = jnp.where(
            jnp.isneginf(m_new)[..., None], 0.0, jnp.exp(s - m_new[..., None])
        )
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
        )
        k, v = lax.ppermute((k, v), axis_name, perm)
        return o, l, m_new, k, v

    o, l, m, k, v = lax.fori_loop(0, n, body, (o, l, m, k, v))
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    seq_axis: str,
    batch_axes: Union[str, Tuple[str, ...], None] = None,
    impl: str = "auto",
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """Global-view entry: q/k/v [B, T, H, d] with T sharded on ``seq_axis``.

    ``impl`` selects the per-shard body: ``"flash"`` runs the pallas flash
    kernel per ring block (O(T_local) memory — scores never leave VMEM;
    interpreted on the cpu backend only), ``"dense"`` the jnp blockwise
    body, ``"auto"`` flash on TPU and dense elsewhere.
    """
    from jax.sharding import PartitionSpec as P

    from polyaxon_tpu.parallel.flash import on_tpu, pallas_interpret

    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"query heads ({q.shape[2]}) must be divisible by KV heads "
            f"({k.shape[2]}) for grouped-query attention"
        )
    if impl == "auto":
        impl = "flash" if on_tpu() else "dense"
    if impl == "flash":
        from polyaxon_tpu.parallel.flash import ring_flash_attention

        d = q.shape[-1]
        cfg = (seq_axis, d**-0.5, block_q, block_k, pallas_interpret())
        body = partial(ring_flash_attention, cfg)
    elif impl == "dense":
        # The dense blockwise body is plain MHA; broadcast GQA KV heads to
        # the query heads up front (the flash body instead broadcasts
        # per hop so the ppermute payload stays Hkv-sized).
        group = q.shape[2] // k.shape[2]
        if group > 1:
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        body = partial(_ring_attention, axis_name=seq_axis)
    else:
        raise ValueError(f"Unknown ring attention impl {impl!r}")

    spec = P(batch_axes, seq_axis, None, None)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
