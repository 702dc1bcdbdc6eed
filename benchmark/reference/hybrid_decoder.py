"""The plain reference of a hybrid decoder-only LM: gated-delta-rule
("linear_attention") layers beside full-attention layers in the pattern the
configuration's ``layer_types`` gives, gated SiLU MLP, untied embeddings, as
``allenai/Olmo-Hybrid-7B``'s ``config.json`` describes it.  Each
configuration's own reference file (``configs/<config>.reference.py``) is this
module under the configuration's name.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``; no
kernel, no cache, no batching: one sequence at a time, the recurrence token by
token in a ``lax.scan`` exactly as the equations read, the per-token parts in
blocks of rows so that 16k tokens fit beside the weights.  It imports nothing
of the program and takes nothing the program made: the weights are drawn here
from the seed (the same draws the program makes, in the same order).

A linear layer, per head, ``x_t`` its input (keys as in HF's GatedDeltaNet):

    q, k, v = silu(conv(W_q x)), silu(conv(W_k x)), silu(conv(W_v x))
              (depthwise, causal, over the last ``linear_conv_kernel_dim``
              positions); q, k L2-normalised per head, q scaled by dk ** -0.5
    beta  = (2 if linear_allow_neg_eigval else 1) * sigmoid(W_b x)
    alpha = exp(-exp(A_log) * softplus(W_a x + dt_bias))
    S_t   = alpha_t * S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T   [dv, dk]
    o_t   = S_t q_t
    y_t   = W_o (rmsnorm(o_t) * w * silu(W_g x_t))     the norm over each head

Set by the family's convention, not by ``config.json``, and listed under
``assumed`` in the configuration's file: each sublayer's OUTPUT is normalised
before the residual add (``h = x + norm(mixer(x)); h = h + norm(mlp(h))``, the
Olmo 2/3 block), the full layers normalise ``q`` and ``k`` (RMSNorm over the
whole projection, before the heads are split), and with ``rope_theta`` null
they apply no rotary embedding.  ``A_log`` and ``dt_bias`` are drawn as
GatedDeltaNet draws them (``A`` uniform in (0, 16), ``dt`` log-uniform in
[0.001, 0.1], ``dt_bias`` its inverse softplus); the convolution has no bias
and the L2 norm's epsilon is 1e-6.

``seg`` (see ``served_gap.py``): several continuations of one shared prefix,
laid out one after the other.  A full layer's row sees segment 0 and its own
segment; a linear layer's state and convolution tail restart, at every change
of segment, from where the END of segment 0 left them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FULL, LINEAR = "full_attention", "linear_attention"


def dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "D": d, "H": h, "Hkv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // h, "F": cfg["intermediate_size"],
        "V": cfg["vocab_size"], "Hl": cfg["linear_num_value_heads"],
        "dk": cfg["linear_key_head_dim"], "dv": cfg["linear_value_head_dim"],
        "K": cfg["linear_conv_kernel_dim"],
        "n_full": list(cfg["layer_types"]).count(FULL),
        "n_lin": list(cfg["layer_types"]).count(LINEAR),
    }


def init_params(seed: int, cfg: dict) -> dict:
    z = dims(cfg)
    L, D, H, Hkv, hd, F, V = z["L"], z["D"], z["H"], z["Hkv"], z["hd"], z["F"], z["V"]
    Hl, dk, dv, K, nf, nl = z["Hl"], z["dk"], z["dv"], z["K"], z["n_full"], z["n_lin"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    full = {
        "wq": normal((nf, D, H, hd), D ** -0.5),
        "wk": normal((nf, D, Hkv, hd), D ** -0.5),
        "wv": normal((nf, D, Hkv, hd), D ** -0.5),
        "wo": normal((nf, H, hd, D), (H * hd) ** -0.5),
        "q_norm": jnp.ones((nf, H * hd), jnp.float32),
        "k_norm": jnp.ones((nf, Hkv * hd), jnp.float32),
    }
    linear = {
        "wq": normal((nl, D, Hl, dk), D ** -0.5),
        "wk": normal((nl, D, Hl, dk), D ** -0.5),
        "wv": normal((nl, D, Hl, dv), D ** -0.5),
        "wg": normal((nl, D, Hl, dv), D ** -0.5),
        "wo": normal((nl, Hl, dv, D), (Hl * dv) ** -0.5),
        "wa": normal((nl, D, Hl), D ** -0.5),
        "wb": normal((nl, D, Hl), D ** -0.5),
        "conv_q": normal((nl, K, Hl * dk), K ** -0.5),
        "conv_k": normal((nl, K, Hl * dk), K ** -0.5),
        "conv_v": normal((nl, K, Hl * dv), K ** -0.5),
    }
    a = jax.random.uniform(next(keys), (nl, Hl), jnp.float32, 0.0, 16.0)
    u = jax.random.uniform(next(keys), (nl, Hl), jnp.float32)
    step = jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    linear["A_log"] = jnp.log(jnp.maximum(a, 1e-4))
    linear["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
    linear["o_norm"] = jnp.ones((nl, dv), jnp.float32)
    block = {
        "mixer_norm": jnp.ones((L, D), jnp.float32),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "full": full,
        "linear": linear,
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


def _mm(spec: str, a, w):
    return jnp.einsum(spec, a, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


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


def _by_rows(fn, x, row_block: int):
    """``fn`` over ``x [T, ...]`` in blocks of rows; ``fn`` may return a tuple."""
    T = x.shape[0]
    rb = _blocks(T, row_block)
    out = lax.map(fn, x.reshape((T // rb, rb) + x.shape[1:]))
    return jax.tree.map(lambda o: o.reshape((T,) + o.shape[2:]), out)


def _attention(q, k, v, q_block: int, seg=None):
    """Causal softmax attention, query rows in blocks.  q [T,H,d], k/v [T,Hkv,d];
    with ``seg`` a row also sees only segment 0 and its own segment."""
    T, H, hd = q.shape
    Hkv = k.shape[1]
    qb = _blocks(T, q_block)
    qg = q.reshape(T // qb, qb, Hkv, H // Hkv, hd)
    kpos = jnp.arange(T)

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


def _full_mixer(x, lp, cfg, q_block, row_block, pos, seg):
    eps, theta = cfg["rms_norm_eps"], cfg.get("rope_theta")

    def project(xb):
        q = _mm("td,dhk->thk", xb, lp["wq"])
        k = _mm("td,dhk->thk", xb, lp["wk"])
        rows = xb.shape[0]
        q = _rms(q.reshape(rows, -1), lp["q_norm"], eps).reshape(q.shape)
        k = _rms(k.reshape(rows, -1), lp["k_norm"], eps).reshape(k.shape)
        return q, k, _mm("td,dhk->thk", xb, lp["wv"])

    q, k, v = _by_rows(project, x, row_block)
    if theta is not None:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    a = _attention(q, k, v, q_block, seg)
    return _by_rows(lambda ab: _mm("thk,hkd->td", ab, lp["wo"]), a, row_block)


def _linear_mixer(x, lp, cfg, row_block, seg):
    z = dims(cfg)
    Hl, dk, dv, K = z["Hl"], z["dk"], z["dv"], z["K"]
    eps = cfg["rms_norm_eps"]
    beta_max = 2.0 if cfg.get("linear_allow_neg_eigval") else 1.0
    T = x.shape[0]

    def project(xb):
        rows = xb.shape[0]
        qkv = jnp.concatenate([
            _mm("td,dhk->thk", xb, lp[n]).reshape(rows, -1) for n in ("wq", "wk", "wv")
        ], axis=-1)
        return (qkv, _mm("td,dhk->thk", xb, lp["wg"]),
                _mm("td,dh->th", xb, lp["wa"]), _mm("td,dh->th", xb, lp["wb"]))

    qkv, gate, a, b = _by_rows(project, x, row_block)
    conv_w = jnp.concatenate([lp["conv_q"], lp["conv_k"], lp["conv_v"]], axis=-1)  # [K, ch]
    seg = jnp.zeros(T, jnp.int32) if seg is None else seg
    decay_rate = jnp.exp(lp["A_log"])

    def step(carry, inp):
        S, S_end0, tail, tail_end0, seg_prev = carry
        z_t, a_t, b_t, seg_t = inp
        # Where segment 0 ends, keep what it left; where a segment starts,
        # start from that.
        leaving0 = (seg_prev == 0) & (seg_t != 0)
        S_end0 = jnp.where(leaving0, S, S_end0)
        tail_end0 = jnp.where(leaving0, tail, tail_end0)
        restart = seg_t != seg_prev
        S = jnp.where(restart, S_end0, S)
        tail = jnp.where(restart, tail_end0, tail)
        window = jnp.concatenate([tail, z_t[None]], axis=0)  # [K, ch]
        y = jax.nn.silu(jnp.sum(window * conv_w, axis=0))
        q = _l2(y[: Hl * dk].reshape(Hl, dk)) * dk ** -0.5
        k = _l2(y[Hl * dk: 2 * Hl * dk].reshape(Hl, dk))
        v = y[2 * Hl * dk:].reshape(Hl, dv)
        alpha = jnp.exp(-decay_rate * jax.nn.softplus(a_t + lp["dt_bias"]))
        beta = beta_max * jax.nn.sigmoid(b_t)
        Sk = jnp.einsum("hvk,hk->hv", S, k, precision=HIGHEST)
        S = alpha[:, None, None] * (S - beta[:, None, None] * Sk[:, :, None] * k[:, None, :]) \
            + beta[:, None, None] * v[:, :, None] * k[:, None, :]
        o = jnp.einsum("hvk,hk->hv", S, q, precision=HIGHEST)
        return (S, S_end0, window[1:], tail_end0, seg_t), o

    S0 = jnp.zeros((Hl, dv, dk), jnp.float32)
    tail0 = jnp.zeros((K - 1, conv_w.shape[1]), jnp.float32)
    _, o = lax.scan(step, (S0, S0, tail0, tail0, seg[0]), (qkv, a, b, seg))

    def out(args):
        ob, gb = args
        return _mm("thv,hvd->td", _rms(ob, lp["o_norm"], eps) * jax.nn.silu(gb), lp["wo"])

    rb = _blocks(T, row_block)
    y = lax.map(out, (o.reshape((T // rb, rb) + o.shape[1:]),
                      gate.reshape((T // rb, rb) + gate.shape[1:])))
    return y.reshape(T, -1)


def hidden(params, tokens, cfg, mode="float32", q_block=512, row_block=2048,
           pos=None, seg=None):
    """tokens [T] -> final-normed hidden states [T, D].  ``pos`` [T] are the
    rotary positions (unused where ``rope_theta`` is null) and ``seg`` the
    segments of the module's docstring."""
    if mode != "float32":
        raise ValueError(f"the hybrid reference computes in float32, not {mode!r}")
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[0]) if pos is None else pos
    blk = params["block"]
    at = lambda tree, i: jax.tree.map(lambda w: w[i], tree)  # noqa: E731
    n_full = n_lin = 0
    for layer, kind in enumerate(cfg["layer_types"]):
        if kind == FULL:
            mix = _full_mixer(x, at(blk["full"], n_full), cfg, q_block, row_block, pos, seg)
            n_full += 1
        elif kind == LINEAR:
            mix = _linear_mixer(x, at(blk["linear"], n_lin), cfg, row_block, seg)
            n_lin += 1
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        x = x + _rms(mix, blk["mixer_norm"][layer], eps)

        def mlp(xb, layer=layer):
            y = jax.nn.silu(_mm("td,df->tf", xb, blk["wg"][layer])) * _mm(
                "td,df->tf", xb, blk["wi"][layer])
            return _mm("tf,fd->td", y, blk["wd"][layer])

        x = x + _rms(_by_rows(mlp, x, row_block), blk["mlp_norm"][layer], eps)
    return _rms(x, params["final_norm"], eps)


def logits_at(params, tokens, rows, cfg, mode="float32", pos=None, seg=None):
    """Logits [len(rows), V] of one sequence at the given rows."""
    x = hidden(params, tokens, cfg, mode, pos=pos, seg=seg)[rows]
    return _mm("td,dv->tv", x, params["unembed"])


def loss_row(params, tokens, targets, cfg, mode="float32", row_block=2048):
    """Mean next-token cross-entropy of one sequence, logits in row blocks."""
    x = hidden(params, tokens, cfg, mode)
    T, D = x.shape
    rb = _blocks(T, row_block)

    def nll(args):
        xb, tb = args
        lg = _mm("td,dv->tv", xb, params["unembed"])
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, tb[:, None], axis=-1)[:, 0]

    return jnp.mean(lax.map(nll, (x.reshape(T // rb, rb, D),
                                  targets.reshape(T // rb, rb))))
