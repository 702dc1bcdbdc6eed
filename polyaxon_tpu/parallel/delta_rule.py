"""The gated delta rule of the linear-attention layers: chunkwise for prefill,
one step for decode.

Per head, with a state ``S`` of ``[dv, dk]`` (float32):

    S_t = alpha_t * S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

Token by token that is ``T`` dependent rank-one updates of a 72 KiB state: no
matmul anywhere.  The chunkwise (WY) form makes a sub-chunk of ``c`` tokens a
handful of matmuls.  With ``g`` the running sum of ``log alpha`` inside the
sub-chunk and ``A[i, j] = beta_i (k_i . k_j) exp(g_i - g_j)`` for ``j < i``:

    (I + A) U = beta * V,   (I + A) W = beta * exp(g) * K        (per sub-chunk)
    V~ = U - W S0^T                                               (sequential)
    O  = (exp(g) * Q) S0^T + (tril(Q K^T) * exp(g_i - g_j)) V~
    S^T <- exp(g_c) S0^T + (exp(g_c - g) * K)^T V~

The first line is independent per sub-chunk and runs as batched XLA ops (the
unit-triangular solve by forward substitution: a Neumann product of powers of
``A`` cancels catastrophically where keys repeat).  The other three carry the
state from one sub-chunk to the next: that is the Pallas kernel
``gated_delta_chunk``, which keeps ``S^T`` in VMEM over the whole chunk.

Everything here is float32 and its matmuls run at ``highest``: the state lives
for a whole document, and a bfloat16 state (or a one-pass bfloat16 product
with it) accumulates what the comparison against the plain reference refuses.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from polyaxon_tpu.parallel.flash import pallas_interpret

HIGHEST = lax.Precision.HIGHEST
#: Tokens per sub-chunk of the WY form: one MXU-sized triangular system.
SUB_CHUNK = 64


def gated_delta_step(q, k, v, g, beta, s):
    """One token per lane.  q, k ``[B, H, dk]``, v ``[B, H, dv]``, ``g`` (log
    alpha) and ``beta`` ``[B, H]``, s ``[B, H, dv, dk]`` -> ``(o [B, H, dv], s)``.
    Multiply-and-sum on the vector unit: the step reads and writes the whole
    state once and is bound by that, and nothing rounds to bfloat16."""
    kb = k[:, :, None, :]
    sk = jnp.sum(s * kb, axis=-1, keepdims=True)  # S k  [B, H, dv, 1]
    b = beta[:, :, None, None]
    s = jnp.exp(g)[:, :, None, None] * (s - b * sk * kb) + b * v[..., None] * kb
    return jnp.sum(s * q[:, :, None, :], axis=-1), s


def _solve_unit_lower(a, rhs):
    """``(I + a) x = rhs`` for strictly lower triangular ``a [..., c, c]`` by
    forward substitution, row by row (``c`` dependent steps, each one
    multiply-and-sum over the rows already solved)."""
    c = a.shape[-1]

    def row(i, x):
        a_i = lax.dynamic_index_in_dim(a, i, axis=-2, keepdims=False)  # [..., c]
        r_i = lax.dynamic_index_in_dim(rhs, i, axis=-2, keepdims=False)
        x_i = r_i - jnp.sum(a_i[..., :, None] * x, axis=-2)
        return lax.dynamic_update_index_in_dim(x, x_i, i, axis=-2)

    return lax.fori_loop(0, c, row, jnp.zeros_like(rhs))


def _dot(a, b):
    return lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )


def _chunk_kernel(w_ref, u_ref, qg_ref, p_ref, kdt_ref, eg_ref, s0_ref, o_ref,
                  s_scr, *, n_sub, dk, c):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _load():
        s_scr[...] = jnp.zeros_like(s_scr)
        s_scr[:dk, :] = s0_ref[0]

    @pl.when(n < n_sub)
    def _advance():
        s = s_scr[:dk, :]  # S^T [dk, dv]
        v_new = u_ref[0, 0] - _dot(w_ref[0, 0], s)
        o_ref[0, 0] = _dot(qg_ref[0, 0], s) + _dot(p_ref[0, 0], v_new)
        s_scr[:dk, :] = eg_ref[0, 0] * s + _dot(kdt_ref[0, 0], v_new)

    # The state leaves through the same output, as the row blocks after the
    # last sub-chunk: one result, so the compiled call's name carries the
    # shape its roofline is computed from.
    @pl.when(n >= n_sub)
    def _store():
        start = pl.multiple_of((n - n_sub) * c, c)
        o_ref[0, 0] = s_scr[pl.ds(start, c), :]


def gated_delta_chunk(w, u, qg, p, kdt, eg, s0t, *, interpret=None):
    """The sequential part of the chunkwise rule, state resident in VMEM.

    Per head ``h`` and sub-chunk ``n``: w, qg ``[H, N, c, dk]``, u
    ``[H, N, c, dv]``, p ``[H, N, c, c]``, kdt ``[H, N, dk, c]``, eg
    ``[H, N, 1, dv]`` (the sub-chunk's whole decay, lane-replicated), s0t
    ``[H, dk, dv]``.  Returns ``[H, N + E, c, dv]``: the outputs of the ``N``
    sub-chunks, then the final ``S^T`` in ``E = ceil(dk / c)`` row blocks.
    """
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = pallas_interpret()
    H, N, c, dk = w.shape
    dv = u.shape[-1]
    extra = -(-dk // c)
    last = N - 1

    def sub(h, n):
        return (h, jnp.minimum(n, last), 0, 0)

    kernel = functools.partial(_chunk_kernel, n_sub=N, dk=dk, c=c)
    return pl.pallas_call(
        kernel,
        grid=(H, N + extra),
        in_specs=[
            pl.BlockSpec((1, 1, c, dk), sub),
            pl.BlockSpec((1, 1, c, dv), sub),
            pl.BlockSpec((1, 1, c, dk), sub),
            pl.BlockSpec((1, 1, c, c), sub),
            pl.BlockSpec((1, 1, dk, c), sub),
            pl.BlockSpec((1, 1, 1, dv), sub),
            pl.BlockSpec((1, dk, dv), lambda h, n: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c, dv), lambda h, n: (h, n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((H, N + extra, c, dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((extra * c, dv), jnp.float32)],
        interpret=interpret,
        name="gated_delta_chunk",
    )(w, u, qg, p, kdt, eg, s0t)


def gated_delta_prefill(
    q, k, v, g, beta, s0, *, sub_chunk: int = SUB_CHUNK, interpret=None
) -> Tuple[jax.Array, jax.Array]:
    """One sequence's chunk through the rule.  q, k ``[T, H, dk]`` (normalised,
    q scaled), v ``[T, H, dv]``, ``g`` (log alpha, <= 0) and ``beta``
    ``[T, H]``, s0 ``[H, dv, dk]`` -> ``(o [T, H, dv], s [H, dv, dk])``.  A
    position with ``g = 0`` and ``beta = 0`` leaves the state as it was: that
    is how padding (the caller's, and this function's own up to a whole
    sub-chunk) passes through."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    c = min(int(sub_chunk), -(-T // 8) * 8)
    pad = -T % c
    N = (T + pad) // c

    def heads_first(x):  # [T, H, d] -> [H, N, c, d]
        x = jnp.pad(x.astype(jnp.float32), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x.reshape(N, c, H, -1), 2, 0)

    q, k, v = heads_first(q), heads_first(k), heads_first(v)
    g = heads_first(g[..., None])[..., 0]  # [H, N, c]
    beta = heads_first(beta[..., None])  # [H, N, c, 1]

    gc = jnp.cumsum(g, axis=-1)
    g_last = gc[..., -1:]
    rows = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # exp only of what is <= 0: the decay from a key to a LATER query.
    decay = jnp.exp(jnp.where(rows >= cols, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    kk = jnp.einsum("hnid,hnjd->hnij", k, k, precision=HIGHEST)
    a = jnp.where(rows > cols, beta * kk * decay, 0.0)
    eg = jnp.exp(gc)[..., None]
    x = _solve_unit_lower(a, jnp.concatenate([beta * v, beta * eg * k], axis=-1))
    u, w = x[..., :dv], x[..., dv:]
    p = jnp.einsum("hnid,hnjd->hnij", q, k, precision=HIGHEST) * decay
    kdt = jnp.swapaxes(k * jnp.exp(g_last - gc)[..., None], -1, -2)
    eg_last = jnp.broadcast_to(jnp.exp(g_last)[..., None], (H, N, 1, dv))

    out = gated_delta_chunk(
        w, u, q * eg, p, kdt, eg_last, jnp.swapaxes(s0.astype(jnp.float32), -1, -2),
        interpret=interpret,
    )
    o = jnp.moveaxis(out[:, :N], 0, 2).reshape(N * c, H, dv)[:T]
    s_t = out[:, N:].reshape(H, -1, dv)[:, :dk]
    return o, jnp.swapaxes(s_t, -1, -2)
