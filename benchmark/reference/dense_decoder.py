"""The plain reference of a dense decoder-only LM with RMSNorm, grouped-query
attention, rotary positions (half-split), a gated SiLU MLP and untied
embeddings, as the published models describe it.  Each configuration's own
reference file (``configs/<config>_reference.py``) is this module under the
configuration's name.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``; no
kernels, no cache, no batching: one sequence at a time, attention and the
per-token parts in blocks of rows so that long sequences fit.  It imports
nothing of the program and takes nothing the program made: the weights are
drawn here from the seed (normal, fan-in scaled; the same draws the program
makes, in the same order).

Departures from the published model, both listed under ``assumed`` in the
configuration's file: ``rope_theta`` and ``rms_norm_eps`` are the program's
(10000, 1e-6), because ``lm_train``/``lm_server`` take neither.

``mode="int8"`` is the lower-precision control: every weight matmul with int8
operands (weights per output channel, activations per token, absmax) and
float32 accumulation — the step below bfloat16 that would tempt a later PR.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return (cfg["num_hidden_layers"], d, h, cfg["num_key_value_heads"], hd,
            cfg["intermediate_size"], cfg["vocab_size"])


def init_params(seed: int, cfg: dict) -> dict:
    L, D, H, Hkv, hd, F, V = dims(cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    block = {
        "attn_norm": jnp.ones((L, D), jnp.float32),
        "wq": normal((L, D, H, hd), D ** -0.5),
        "wk": normal((L, D, Hkv, hd), D ** -0.5),
        "wv": normal((L, D, Hkv, hd), D ** -0.5),
        "wo": normal((L, H, hd, D), (H * hd) ** -0.5),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "wi": normal((L, D, F), D ** -0.5),
        "wg": normal((L, D, F), D ** -0.5),
        "wd": normal((L, F, D), F ** -0.5),
    }
    return {
        "embed": normal((V, D), 1.0),
        "unembed": normal((D, V), D ** -0.5),
        "final_norm": jnp.ones((D,), jnp.float32),
        "block": block,
    }


def _fake_int8(x, axes):
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    # Straight-through, so the control's gradients are those of int8 training.
    return x + lax.stop_gradient(jnp.round(x / scale) * scale - x)


def _mm(spec: str, a, w, mode: str, a_axes, w_axes):
    """One weight matmul; ``a_axes``/``w_axes`` are the contracted axes."""
    if mode == "int8":
        a = _fake_int8(a, a_axes)
        w = _fake_int8(w, w_axes)
    return jnp.einsum(spec, a, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _blocks(n: int, want: int) -> int:
    b = min(want, n)
    while n % b:
        b -= 1
    return b


def _attention(q, k, v, q_block: int, seg=None):
    """Causal softmax attention, query rows in blocks.  q [T,H,d], k/v [T,Hkv,d].
    With ``seg`` [T] a row also sees only segment 0 and its own segment: several
    continuations of one shared prefix, laid out one after the other."""
    T, H, hd = q.shape
    Hkv = k.shape[1]
    qb = _blocks(T, q_block)
    qg = q.reshape(T // qb, qb, Hkv, H // Hkv, hd)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def one(args):
        qi, start = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HIGHEST) * hd ** -0.5
        rows = start + jnp.arange(qb)
        mask = rows[:, None] >= kpos[None, :]
        if seg is not None:
            mask &= (seg[None, :] == 0) | (seg[None, :] == seg[rows][:, None])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)

    out = lax.map(one, (qg, jnp.arange(T // qb) * qb))
    return out.reshape(T, H, hd)


def _layer(x, lp, cfg, mode, q_block, row_block, pos, seg):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    T, D = x.shape
    h = _rms(x, lp["attn_norm"], eps)
    q = _rope(_mm("td,dhk->thk", h, lp["wq"], mode, -1, 0), pos, theta)
    k = _rope(_mm("td,dhk->thk", h, lp["wk"], mode, -1, 0), pos, theta)
    v = _mm("td,dhk->thk", h, lp["wv"], mode, -1, 0)
    a = _attention(q, k, v, q_block, seg)
    x = x + _mm("thk,hkd->td", a, lp["wo"], mode, (-2, -1), (0, 1))

    @jax.checkpoint
    def mlp(xb):
        hb = _rms(xb, lp["mlp_norm"], eps)
        y = jax.nn.silu(_mm("td,df->tf", hb, lp["wg"], mode, -1, 0)) * _mm(
            "td,df->tf", hb, lp["wi"], mode, -1, 0)
        return xb + _mm("tf,fd->td", y, lp["wd"], mode, -1, 0)

    rb = _blocks(T, row_block)
    return lax.map(mlp, x.reshape(T // rb, rb, D)).reshape(T, D)


def hidden(params, tokens, cfg, mode="float32", q_block=512, row_block=2048,
           pos=None, seg=None):
    """tokens [T] -> final-normed hidden states [T, D].  ``pos`` [T] are the
    rotary positions (default: 0..T-1) and ``seg`` the segments of
    ``_attention``."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[0]) if pos is None else pos
    body = jax.checkpoint(
        lambda c, lp: (_layer(c, lp, cfg, mode, q_block, row_block, pos, seg), None))
    x, _ = lax.scan(body, x, params["block"])
    return _rms(x, params["final_norm"], cfg["rms_norm_eps"])


def logits_at(params, tokens, rows, cfg, mode="float32", pos=None, seg=None):
    """Logits [len(rows), V] of one sequence at the given rows."""
    x = hidden(params, tokens, cfg, mode, pos=pos, seg=seg)[rows]
    return _mm("td,dv->tv", x, params["unembed"], mode, -1, 0)


def loss_row(params, tokens, targets, cfg, mode="float32", row_block=2048):
    """Mean next-token cross-entropy of one sequence, logits in row blocks."""
    x = hidden(params, tokens, cfg, mode)
    T, D = x.shape
    rb = _blocks(T, row_block)

    @jax.checkpoint
    def nll(args):
        xb, tb = args
        lg = _mm("td,dv->tv", xb, params["unembed"], mode, -1, 0)
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, tb[:, None], axis=-1)[:, 0]

    return jnp.mean(lax.map(nll, (x.reshape(T // rb, rb, D),
                                  targets.reshape(T // rb, rb))))
