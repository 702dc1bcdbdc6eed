"""The plain reference's side of a training cell: the first steps of AdamW
on the configuration's ``loss_row``, one batch row at a time.

Follows ``steps`` steps of ``optax.adamw(lr)`` as ``lm_train`` builds it (b1
0.9, b2 0.999, eps 1e-8, weight decay 1e-4, float32 state) on the mean loss
over the batch's rows, and returns what is compared with the program: each
step's loss, the per-leaf norm of the first gradient, and the per-leaf norm of
the parameters' change after the steps.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS, WEIGHT_DECAY = 0.9, 0.999, 1e-8, 1e-4


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {
        "/".join(str(getattr(k, "key", k)) for k in path):
            float(jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32)))))
        for path, leaf in flat
    }


@partial(jax.jit, donate_argnums=(0, 2, 3), static_argnums=(5,))
def _adamw_leaf(p, g, m, v, t, lr):
    m = B1 * m + (1 - B1) * g
    v = B2 * v + (1 - B2) * g * g
    mhat = m / (1 - B1 ** t)
    vhat = v / (1 - B2 ** t)
    return p - lr * (mhat / (jnp.sqrt(vhat) + EPS) + WEIGHT_DECAY * p), m, v


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)


def follow(ref, cfg: Dict[str, Any], seed: int, tokens: np.ndarray, steps: int,
           lr: float, mode: str = "float32") -> Dict[str, Any]:
    """``tokens`` is the [B, T + 1] batch the program was fed.

    Memory: the device holds the parameters, one gradient accumulator and one
    row's activations; AdamW's two moments wait on the host between steps and
    are brought up one leaf at a time."""
    params = ref.init_params(seed, cfg)
    rows = tokens.shape[0]

    @partial(jax.jit, donate_argnums=(1,))
    def grad_row(p, acc, x, y):
        loss, g = jax.value_and_grad(lambda q: ref.loss_row(q, x, y, cfg, mode))(p)
        return loss, jax.tree.map(lambda a, b: a + b / rows, acc, g)

    leaves, treedef = jax.tree_util.tree_flatten(params)
    moments = [None] * len(leaves)  # per leaf (m, v) on the host
    losses, first_grad = [], None
    for step in range(steps):
        grads = jax.tree.map(jnp.zeros_like, params)
        loss = 0.0
        for r in range(rows):
            l, grads = grad_row(params, grads, jnp.asarray(tokens[r, :-1]),
                                jnp.asarray(tokens[r, 1:]))
            loss += float(l) / rows
        losses.append(loss)
        if step == 0:
            first_grad = leaf_norms(grads)
        p_leaves = jax.tree_util.tree_leaves(params)
        g_leaves = jax.tree_util.tree_leaves(grads)
        del params, grads
        for i in range(len(p_leaves)):
            if moments[i] is None:
                m, v = jnp.zeros_like(p_leaves[i]), jnp.zeros_like(p_leaves[i])
            else:
                m, v = jnp.asarray(moments[i][0]), jnp.asarray(moments[i][1])
            p_leaves[i], m, v = _adamw_leaf(p_leaves[i], g_leaves[i], m, v,
                                            jnp.float32(step + 1), lr)
            g_leaves[i] = None
            moments[i] = (np.asarray(m), np.asarray(v)) if step + 1 < steps else None
            del m, v
        params = jax.tree_util.tree_unflatten(treedef, p_leaves)
        del p_leaves, g_leaves
    start = ref.init_params(seed, cfg)
    flat = jax.tree_util.tree_flatten_with_path(_diff_norms(params, start))[0]
    change = {"/".join(str(getattr(k, "key", k)) for k in path): float(x)
              for path, x in flat}
    return {"losses": losses, "grad_norms": first_grad, "change_norms": change}
