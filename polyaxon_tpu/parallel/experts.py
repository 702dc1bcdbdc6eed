"""Routed experts, dropless: the rows sorted by expert and one grouped matrix
product over the experts this chip holds.

A token's ``k`` choices become ``k`` ROWS.  The rows are sorted by expert, so
that each expert's rows stand together, and the three matrices of the gated
MLP run as ``lax.ragged_dot`` over the groups: the product an expert sees is
``[its rows, D] x [D, F]``, whatever its load.  No capacity and no dropping;
the imbalance shows as group sizes, which are returned for the counters.  On a
TPU XLA runs ``ragged_dot`` as a grouped-matmul kernel of its own (the device
operation ``ragged-dot*``); on the CPU it is expanded, which the tests' sizes
bear.

**The chip's share.**  The router (``route``) scores ALL experts of the layer
and chooses among all of them.  ``experts_mlp`` is told which experts are held
here, ``[offset, offset + held)``, computes their part of the result and
leaves the rest out: rows that fell to an absent expert, and the rows of
tokens that are padding, sort behind the last group and are never multiplied.
On one chip the layer therefore runs without an exchange; across chips each
would compute its own part and the parts would be summed.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


#: What a program's expert layers routed in one call, summed over the layers:
#: int32 ``[rows routed (token x choice; padding and idle lanes left out), rows
#: that fell to experts held here, the busiest expert's rows, experts that had
#: a row]``.  The third value the paged programs of a model with routed experts
#: return; the engine reads it with the call's result and ``/v1/stats`` carries
#: the totals under these names.
COUNT_NAMES = ("moe_rows_routed", "moe_rows_held", "moe_rows_busiest", "moe_experts_hit")


def _router_logits(h, router):
    return jnp.einsum(
        "nd,de->ne", h.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )


def _gates(s, chosen, scale: float):
    w = jnp.take_along_axis(s, chosen, axis=-1)
    gates = scale * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), gates


def route(h, router, bias, k: int, scale: float, n_group: int = 1, topk_group: int = 1):
    """The sigmoid router with a selection bias (``noaux_tc``).

    ``h [N, D]``; ``router [D, E]`` and ``bias [E]`` float32.  Input, product
    and selection are float32 at ``highest``: a near-tie among the top ``k``
    must fall as it does in the plain reference.  The bias picks, the unbiased
    score weighs: returns ``(chosen [N, k] int32, gates [N, k] float32)`` with
    ``gates = scale * s / (sum of the chosen s + 1e-20)``.  With ``n_group`` >
    1 the group limit of the published ``Gate`` comes first: the ``E`` biased
    scores form ``n_group`` groups of ``E / n_group``, a group scores the sum
    of its two largest, and every expert outside the ``topk_group`` best groups
    is masked to ``-inf``."""
    s = jax.nn.sigmoid(_router_logits(h, router))
    biased = s + bias.astype(jnp.float32)
    if n_group > 1:
        N, E = biased.shape
        grouped = biased.reshape(N, n_group, E // n_group)
        _, best = lax.top_k(jnp.sum(lax.top_k(grouped, 2)[0], axis=-1), topk_group)
        kept = jnp.zeros((N, n_group), jnp.bool_).at[jnp.arange(N)[:, None], best].set(True)
        biased = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(N, E)
    _, chosen = lax.top_k(biased, k)
    return _gates(s, chosen, scale)


def route_softmax(h, router, k: int, scale: float):
    """The softmax router (the Qwen-MoE family's, ``norm_topk_prob``): ``s =
    softmax(h W_r)`` over ALL experts, ``chosen = top_k(s)``, no selection
    bias.  The contract is :func:`route`'s: float32 at ``highest``, returns
    ``(chosen [N, k] int32, gates [N, k] float32)`` with ``gates = scale * s /
    (sum of the chosen s + 1e-20)``."""
    s = jax.nn.softmax(_router_logits(h, router), axis=-1)
    _, chosen = lax.top_k(s, k)
    return _gates(s, chosen, scale)


def experts_mlp(
    h, chosen, gates, valid, wi, wg, wd, offset: int, layer=None
) -> Tuple[jax.Array, jax.Array]:
    """``sum over the chosen AND held experts of gate * E(h)``.

    ``h [N, D]``; ``chosen`` / ``gates [N, k]`` from :func:`route`; ``valid
    [N]`` bool (padding and idle lanes route nowhere); ``wi`` / ``wg [held, D,
    F]``, ``wd [held, F, D]``: the experts ``[offset, offset + held)``.
    Returns ``(y [N, D] at h's dtype, rows [held] int32)``, ``rows`` the rows
    each held expert was given.

    With ``layer`` (a traced index) the weights are the STACKS of all expert
    layers, ``[layers, held, ...]``, and the product runs over ``layers x
    held`` groups of which only ``layer``'s have rows: the grouped kernel reads
    a layer's experts where they lie, instead of a copy of them cut out of the
    stack for every call (3 x 201 MB a layer at 64 experts of 2,048 x 768)."""
    N, D = h.shape
    k = chosen.shape[1]
    held = wi.shape[-3]
    local = chosen - offset
    here = (local >= 0) & (local < held) & valid[:, None]
    # Rows of absent experts and of padding go to group ``held``: behind every
    # real group once sorted, outside ``rows`` and so outside the product.
    group = jnp.where(here, local, held).reshape(N * k)
    order = jnp.argsort(group, stable=True)
    rows = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    sizes = rows
    if layer is not None:
        sizes = lax.dynamic_update_slice(
            jnp.zeros((wi.shape[0] * held,), jnp.int32), rows, (layer * held,)
        )
        wi, wg, wd = (w.reshape((-1,) + w.shape[2:]) for w in (wi, wg, wd))
    x = h[order // k]  # [N * k, D], each expert's rows together
    up = lax.ragged_dot(x, wi, sizes)
    gate = lax.ragged_dot(x, wg, sizes)
    out = lax.ragged_dot(jax.nn.silu(gate) * up, wd, sizes)  # [N * k, D]
    back = jnp.argsort(order)  # row of (token, choice) in the sorted order
    out = out[back].reshape(N, k, D).astype(jnp.float32)
    # ``where``, not a product with 0: what stands behind the last group is
    # whatever the grouped product left there.
    out = jnp.where(here[..., None], out * gates[..., None], 0.0)
    return jnp.sum(out, axis=1).astype(h.dtype), rows
