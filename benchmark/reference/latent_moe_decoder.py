"""The plain reference of a decoder-only LM with latent attention in every layer
and, after ``first_k_dense_replace`` leading dense layers, a routed-expert MLP
with a shared expert: the DeepSeek-V3 family's forms under the keys of
``jdopensource/JoyAI-LLM-Flash``'s ``config.json``.  Each configuration's own
reference file (``configs/<config>.reference.py``) is this module under the
configuration's name.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``; no kernel,
no cache, no batching, no sort and no grouping: one sequence at a time, the
per-token parts in blocks of rows so that 18k tokens fit beside the weights,
EVERY held expert over EVERY row, weighted by its gate, which is zero where the
expert was not chosen.  It imports nothing of the program and takes nothing the
program made: the weights are drawn here from the seed (the same draws the
program makes, in the same order).

The block, pre-norm: ``x = x + attn(norm(x)); x = x + mlp(norm(x))``; final
norm; untied unembedding.  With ``h`` a layer's normed input:

    c_q = rmsnorm(h W_qa);  q = c_q W_qb   -> heads of [q_nope | q_rope]
    [c_raw | r_raw] = h W_kva;  c = rmsnorm(c_raw);  k_rope = rope(r_raw, pos)
    [k_nope | v] per head = c W_kvb
    score = (q_nope . k_nope + rope(q_rope, pos) . k_rope) / sqrt(nope + rope)
    out = concat_heads(softmax(score) v) W_o           causal, one k_rope for all heads

    s = sigmoid(h W_r);  chosen = top_k(s + b);  g_i = scale * s_i / (sum_chosen s + 1e-20)
    y = shared(h) + sum over i chosen AND held of g_i E_i(h)

``shared`` and ``E_i`` gated SiLU MLPs.  **The cut**: the router is as wide as
published (``router_width``) and chooses ``num_experts_per_tok``; experts
``[expert_offset, expert_offset + n_routed_experts)`` are held (the file's
``n_routed_experts`` counts the experts held); what the absent experts would
have added is left out, and the normalisation is over all chosen, as published.

Departures from the published model, listed under ``assumed`` in the
configuration's file: rotary pairs split by halves (the published interleaved
layout is the same function under a fixed permutation of the 64 rotary columns
of random ``W_qb`` / ``W_kva``); no YaRN factor (``rope_scaling`` null); the
selection bias ``b`` drawn from the seed; the multi-token-prediction module is
not held.

``seg`` (see ``served_gap.py``): several continuations of one shared prefix,
laid out one after the other.  A row sees segment 0 and its own segment.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def dims(cfg: dict):
    L = cfg["num_hidden_layers"]
    n_dense = min(cfg.get("first_k_dense_replace", 0), L)
    held = cfg["n_routed_experts"]
    return {
        "L": L, "D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "rq": cfg["q_lora_rank"], "rkv": cfg["kv_lora_rank"],
        "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "n_dense": n_dense, "n_exp": L - n_dense,
        "E": cfg.get("router_width") or held, "held": held,
        "offset": cfg.get("expert_offset", 0), "k": cfg["num_experts_per_tok"],
        "Fe": cfg["moe_intermediate_size"], "n_shared": cfg.get("n_shared_experts", 0),
    }


def init_params(seed: int, cfg: dict) -> dict:
    z = dims(cfg)
    L, D, H, F, V = z["L"], z["D"], z["H"], z["F"], z["V"]
    rq, rkv, dn, dr, dv = z["rq"], z["rkv"], z["dn"], z["dr"], z["dv"]
    nd, ne, E, held, Fe = z["n_dense"], z["n_exp"], z["E"], z["held"], z["Fe"]
    Fs = z["n_shared"] * Fe
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    block = {
        "attn_norm": jnp.ones((L, D), jnp.float32),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "q_norm": jnp.ones((L, rq), jnp.float32),
        "kv_norm": jnp.ones((L, rkv), jnp.float32),
        "wq_a": normal((L, D, rq), D ** -0.5),
        "wq_b": normal((L, rq, H, dn + dr), rq ** -0.5),
        "wkv_a": normal((L, D, rkv + dr), D ** -0.5),
        "wkv_b": normal((L, rkv, H, dn + dv), rkv ** -0.5),
        "wo": normal((L, H, dv, D), (H * dv) ** -0.5),
        "dense": {
            "wi": normal((nd, D, F), D ** -0.5),
            "wg": normal((nd, D, F), D ** -0.5),
            "wd": normal((nd, F, D), F ** -0.5),
        },
    }
    if ne:
        block["experts"] = {
            "router": normal((ne, D, E), D ** -0.5),
            "router_bias": normal((ne, E), 0.02),
            "wi": normal((ne, held, D, Fe), D ** -0.5),
            "wg": normal((ne, held, D, Fe), D ** -0.5),
            "wd": normal((ne, held, Fe, D), Fe ** -0.5),
            "shared_wi": normal((ne, D, Fs), D ** -0.5),
            "shared_wg": normal((ne, D, Fs), D ** -0.5),
            "shared_wd": normal((ne, Fs, D), max(Fs, 1) ** -0.5),
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


def _rope(x, pos, theta):
    """x [T, ..., d] rotated to ``pos [T]``, pairs split by halves."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
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


def _attention(q_nope, q_rope, k_nope, k_rope, v, q_block: int, seg=None):
    """Causal softmax attention, query rows in blocks.  q_nope [T,H,dn], q_rope
    [T,H,dr], k_nope [T,H,dn], k_rope [T,dr] (one head for all), v [T,H,dv]; with
    ``seg`` a row also sees only segment 0 and its own segment."""
    T, H, dn = q_nope.shape
    scale = (dn + q_rope.shape[-1]) ** -0.5
    qb = _blocks(T, q_block)
    kpos = jnp.arange(T)

    def one(args):
        qn, qr, start = args
        s = jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=HIGHEST)
        s = (s + jnp.einsum("qhd,kd->hqk", qr, k_rope, precision=HIGHEST)) * scale
        rows = start + jnp.arange(qb)
        mask = rows[:, None] >= kpos[None, :]
        if seg is not None:
            mask &= (seg[None, :] == 0) | (seg[None, :] == seg[rows][:, None])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = lax.map(one, (q_nope.reshape(T // qb, qb, H, dn),
                        q_rope.reshape(T // qb, qb, H, -1), jnp.arange(T // qb) * qb))
    return out.reshape(T, H, -1)


def _mixer(x, lp, cfg, q_block, row_block, pos, seg):
    z = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    rkv, dn = z["rkv"], z["dn"]

    def project(xb):
        cq = _rms(_mm("td,dr->tr", xb, lp["wq_a"]), lp["q_norm"], eps)
        q = _mm("tr,rhe->the", cq, lp["wq_b"])
        kv = _mm("td,dr->tr", xb, lp["wkv_a"])
        c = _rms(kv[:, :rkv], lp["kv_norm"], eps)
        up = _mm("tr,rhe->the", c, lp["wkv_b"])
        return q[..., :dn], q[..., dn:], up[..., :dn], kv[:, rkv:], up[..., dn:]

    q_nope, q_rope, k_nope, r_raw, v = _by_rows(project, x, row_block)
    a = _attention(q_nope, _rope(q_rope, pos, theta), k_nope, _rope(r_raw, pos, theta),
                   v, q_block, seg)
    return _by_rows(lambda ab: _mm("thv,hvd->td", ab, lp["wo"]), a, row_block)


def _gated(xb, wi, wg, wd):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", xb, wg)) * _mm("td,df->tf", xb, wi), wd)


def gates(h, ep, cfg):
    """The router: ``h [T, D]`` -> the gate of EVERY expert of the layer ``[T,
    router_width]``, zero where it was not chosen."""
    z = dims(cfg)
    s = jax.nn.sigmoid(_mm("td,de->te", h, ep["router"]))
    _, chosen = lax.top_k(s + ep["router_bias"], z["k"])
    picked = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], chosen].set(1.0)
    w = s * picked
    return float(cfg["routed_scaling_factor"]) * w / (
        jnp.sum(w, axis=-1, keepdims=True) + 1e-20)


def _expert_mlp(h, ep, cfg, row_block):
    """Every held expert over every row, weighted by its gate."""
    z = dims(cfg)
    g = gates(h, ep, cfg)[:, z["offset"]: z["offset"] + z["held"]]  # [T, held]

    def one_expert(acc, e):
        wi, wg, wd, ge = e
        y = _by_rows(lambda xb: _gated(xb, wi, wg, wd), h, row_block)
        return acc + ge[:, None] * y, None

    y, _ = lax.scan(one_expert, jnp.zeros_like(h), (ep["wi"], ep["wg"], ep["wd"], g.T))
    if z["n_shared"]:
        y = y + _by_rows(
            lambda xb: _gated(xb, ep["shared_wi"], ep["shared_wg"], ep["shared_wd"]),
            h, row_block)
    return y


def hidden(params, tokens, cfg, mode="float32", q_block=512, row_block=2048,
           pos=None, seg=None):
    """tokens [T] -> final-normed hidden states [T, D].  ``pos`` [T] are the
    rotary positions and ``seg`` the segments of the module's docstring."""
    if mode != "float32":
        raise ValueError(f"the latent reference computes in float32, not {mode!r}")
    z = dims(cfg)
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[0]) if pos is None else pos
    blk = params["block"]
    at = lambda tree, i: jax.tree.map(lambda w: w[i], tree)  # noqa: E731
    shared = {n: blk[n] for n in ("q_norm", "kv_norm", "wq_a", "wq_b", "wkv_a", "wkv_b", "wo")}
    for layer in range(z["L"]):
        h = _rms(x, blk["attn_norm"][layer], eps)
        x = x + _mixer(h, at(shared, layer), cfg, q_block, row_block, pos, seg)
        h = _rms(x, blk["mlp_norm"][layer], eps)
        if layer < z["n_dense"]:
            dp = at(blk["dense"], layer)
            x = x + _by_rows(lambda xb, dp=dp: _gated(xb, dp["wi"], dp["wg"], dp["wd"]),
                             h, row_block)
        else:
            x = x + _expert_mlp(h, at(blk["experts"], layer - z["n_dense"]), cfg, row_block)
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
