"""The plain reference of a decoder-only LM whose layers mix window and full
attention (different query head counts, a gate a head) and whose MLPs are one
dense gated SiLU MLP or routed experts under a softmax router with one shared
expert: the forms of ``poolside/Laguna-S-2.1``'s ``config.json`` (``model_type:
laguna``).  Each configuration's own reference file
(``configs/<config>.reference.py``) is this module under the configuration's name.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``; whole
sequences under explicit masks: no ring, no cache, no kernel, no batching, no
sort and no grouping.  One sequence at a time, the per-token parts in blocks of
rows and the attention in blocks of query rows (each block against ALL keys)
so that 18k tokens fit beside 9 GB of weights; EVERY held expert over EVERY
row, weighted by its gate, which is zero where the expert was not chosen.  It imports nothing of the program and
takes nothing the program made: the weights are drawn here from the seed (the
same draws the program makes, in the same order).

The block, pre-norm: ``x = x + attn(norm(x)); x = x + mlp(norm(x))``; final
norm; untied unembedding.  With ``h`` a layer's normed input, ``H`` the layer's
query heads (``num_attention_heads_per_layer``), ``Hkv`` KV heads of ``d``:

    q = h W_q [H, d];  k = h W_k [Hkv, d];  v = h W_v [Hkv, d]      no biases, no QK-norm
    q, k = rotary(q, k, pos)        by layer kind, below
    score = q . k / sqrt(d)         query head against KV head  head // (H / Hkv)
    key j admitted for query i:  j <= i (full_attention)
                                 j <= i and pos_i - pos_j < sliding_window (sliding_attention)
    o = softmax(score) v;  g = sigmoid(h W_gate) [H];  out = concat_heads(g * o) W_o

    dense:   W_down(silu(h W_gate) * (h W_up))
    sparse:  s = softmax(h W_r) over router_width;  chosen = top_k(s)
             g_e = scale * s_e / (sum of the chosen s + 1e-20)
             y = S(h) + sum over e chosen AND held of g_e E_e(h)      S, E_e gated SiLU MLPs

**Rotary** (``rope_parameters``, one group a layer kind): the first
``partial_rotary_factor`` of each head is rotated, pairs split by halves, the
rest passes.  ``rope_type: default``: frequencies ``theta^(-2i/dim)``.
``rope_type: yarn`` (as ``transformers``' ``_compute_yarn_parameters`` over the
rotary width ``dim``): frequency ``i`` is ``theta^(-2i/dim)`` where dimension
``i`` lies below the correction dim of ``beta_fast`` rotations over
``original_max_position_embeddings``, that over ``factor`` above the correction
dim of ``beta_slow``, a linear ramp between (the two dims truncated, floor and
ceiling); cos and sin are multiplied by ``attention_factor``.

**The cut**: the router is as wide as published (``router_width``) and chooses
``num_experts_per_tok``; experts ``[expert_offset, expert_offset +
num_experts)`` are held (the file's ``num_experts`` counts the experts held);
what the absent experts would have added is left out, and the normalisation is
over all chosen, as published.

``seg`` (see ``served_gap.py``): several continuations of one shared prefix,
laid out one after the other.  A row sees segment 0 and its own segment, and
the window is measured in POSITIONS (``pos``), not rows.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FULL, WINDOW = "full_attention", "sliding_attention"


def dims(cfg: dict):
    L = cfg["num_hidden_layers"]
    kinds = list(cfg["layer_types"])[:L]
    mlps = list(cfg["mlp_layer_types"])[:L]
    per_layer = list(cfg["num_attention_heads_per_layer"])[:L]
    heads = {}
    for kind, h in zip(kinds, per_layer):
        if heads.setdefault(kind, h) != h:
            raise ValueError(f"{kind} layers with different head counts: {per_layer}")
    held = cfg["num_experts"]
    return {
        "L": L, "D": cfg["hidden_size"], "Hkv": cfg["num_key_value_heads"],
        "d": cfg["head_dim"], "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "kinds": kinds, "mlps": mlps, "heads": heads,
        "n": {k: kinds.count(k) for k in (FULL, WINDOW)},
        "n_dense": mlps.count("dense"), "n_sparse": mlps.count("sparse"),
        "E": cfg.get("router_width") or held, "held": held,
        "offset": cfg.get("expert_offset", 0), "k": cfg["num_experts_per_tok"],
        "Fe": cfg["moe_intermediate_size"],
        "Fs": cfg.get("shared_expert_intermediate_size", 0),
        "gated": cfg.get("gating") in ("per-head", "per_head", True),
        "window": cfg["sliding_window"],
    }


def init_params(seed: int, cfg: dict) -> dict:
    z = dims(cfg)
    L, D, Hkv, d, F, V = z["L"], z["D"], z["Hkv"], z["d"], z["F"], z["V"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def attention(kind):
        m, H = z["n"][kind], z["heads"].get(kind, cfg["num_attention_heads"])
        out = {
            "wq": normal((m, D, H, d), D ** -0.5),
            "wk": normal((m, D, Hkv, d), D ** -0.5),
            "wv": normal((m, D, Hkv, d), D ** -0.5),
            "wo": normal((m, H, d, D), (H * d) ** -0.5),
        }
        if z["gated"]:
            out["gate"] = normal((m, D, H), D ** -0.5)
        return out

    block = {
        "attn_norm": jnp.ones((L, D), jnp.float32),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "full": attention(FULL),
        "window": attention(WINDOW),
        "dense": {
            "wi": normal((z["n_dense"], D, F), D ** -0.5),
            "wg": normal((z["n_dense"], D, F), D ** -0.5),
            "wd": normal((z["n_dense"], F, D), F ** -0.5),
        },
    }
    if z["n_sparse"]:
        ne, E, held, Fe, Fs = z["n_sparse"], z["E"], z["held"], z["Fe"], z["Fs"]
        block["experts"] = {
            "router": normal((ne, D, E), D ** -0.5),
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


def rotary_frequencies(group: dict, head_dim: int):
    """``(inverse frequencies [dim / 2], factor on cos and sin)`` of one group of
    ``rope_parameters``, ``dim`` the rotated columns of a head."""
    dim = int(head_dim * group.get("partial_rotary_factor", 1))
    base = float(group["rope_theta"])
    plain = np.array([base ** (-2.0 * i / dim) for i in range(dim // 2)])
    if group.get("rope_type", "default") == "default":
        return plain, 1.0
    if group["rope_type"] != "yarn":
        raise ValueError(f"rope_type {group['rope_type']!r}")
    turns_at = float(group["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(turns_at / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(group["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(group["beta_slow"])), dim - 1)
    if low == high:
        high = high + 0.001
    inv = np.empty(dim // 2)
    for i in range(dim // 2):
        stretched = min(max((i - low) / (high - low), 0.0), 1.0)  # 0: as it is, 1: over the factor
        inv[i] = plain[i] * (1.0 - stretched) + plain[i] / float(group["factor"]) * stretched
    return inv, float(group.get("attention_factor", 1.0))


def _rope(x, pos, inv, factor):
    """x [T, H, d] rotated to ``pos [T]``: the first ``2 x len(inv)`` columns,
    pairs split by halves; the rest pass."""
    half = len(inv)
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


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


def _mixer(x, lp, kind, cfg, q_block, row_block, pos, seg):
    """One attention sublayer over ``x [T, D]`` (the layer's normed input)
    under an explicit mask.  Keys and values of every row first; then the query
    rows in blocks, each block its own projection, rotation, scores against
    ALL keys, softmax, gate and output projection, so that nothing of ``[T,
    heads, d]`` stands beside the weights.  Query head ``h`` reads KV head ``h
    // (H / Hkv)``."""
    z = dims(cfg)
    d, Hkv = z["d"], z["Hkv"]
    window = z["window"] if kind == WINDOW else None
    inv, factor = rotary_frequencies(cfg["rope_parameters"][kind], d)
    T = x.shape[0]
    qb = _blocks(T, q_block)
    rows_all = jnp.arange(T)

    def keys_values(args):
        xb, pb = args
        return (_rope(_mm("td,dhe->the", xb, lp["wk"]), pb, inv, factor),
                _mm("td,dhe->the", xb, lp["wv"]))

    rb = _blocks(T, row_block)
    k, v = jax.tree.map(
        lambda o: o.reshape((T,) + o.shape[2:]),
        lax.map(keys_values, (x.reshape(T // rb, rb, -1), pos.reshape(T // rb, rb))))

    def one(args):
        xb, start = args
        rows = start + jnp.arange(qb)
        q = _rope(_mm("td,dhe->the", xb, lp["wq"]), pos[rows], inv, factor)
        H = q.shape[1]
        s = jnp.einsum("qhgd,khd->hgqk", q.reshape(qb, Hkv, H // Hkv, d), k,
                       precision=HIGHEST) * d ** -0.5
        mask = rows[:, None] >= rows_all[None, :]
        if window is not None:
            mask &= pos[rows][:, None] - pos[None, :] < window
        if seg is not None:
            mask &= (seg[None, :] == 0) | (seg[None, :] == seg[rows][:, None])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST).reshape(qb, H, d)
        if "gate" in lp:
            o = o * jax.nn.sigmoid(_mm("td,dh->th", xb, lp["gate"]))[..., None]
        return _mm("the,hed->td", o, lp["wo"])

    out = lax.map(one, (x.reshape(T // qb, qb, -1), jnp.arange(T // qb) * qb))
    return out.reshape(T, -1)


def _gated(xb, wi, wg, wd):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", xb, wg)) * _mm("td,df->tf", xb, wi), wd)


def gates(h, ep, cfg):
    """The router: ``h [T, D]`` -> the gate of EVERY expert of the layer ``[T,
    router_width]``, zero where it was not chosen."""
    z = dims(cfg)
    s = jax.nn.softmax(_mm("td,de->te", h, ep["router"]), axis=-1)
    _, chosen = lax.top_k(s, z["k"])
    picked = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], chosen].set(1.0)
    w = s * picked
    if not cfg.get("norm_topk_prob", True):
        return float(cfg["moe_routed_scaling_factor"]) * w
    return float(cfg["moe_routed_scaling_factor"]) * w / (
        jnp.sum(w, axis=-1, keepdims=True) + 1e-20)


def _expert_mlp(h, ep, cfg, row_block):
    """Every held expert over every row, weighted by its gate; the shared
    expert added ungated."""
    z = dims(cfg)
    g = gates(h, ep, cfg)[:, z["offset"]: z["offset"] + z["held"]]  # [T, held]

    def one_expert(acc, e):
        wi, wg, wd, ge = e
        y = _by_rows(lambda xb: _gated(xb, wi, wg, wd), h, row_block)
        return acc + ge[:, None] * y, None

    y, _ = lax.scan(one_expert, jnp.zeros_like(h), (ep["wi"], ep["wg"], ep["wd"], g.T))
    if z["Fs"]:
        y = y + _by_rows(
            lambda xb: _gated(xb, ep["shared_wi"], ep["shared_wg"], ep["shared_wd"]),
            h, row_block)
    return y


def _layer(x, norms, ap, mp, kind, mlp, cfg, q_block, row_block, pos, seg):
    eps = cfg["rms_norm_eps"]
    x = x + _mixer(_rms(x, norms["attn_norm"], eps), ap, kind, cfg, q_block, row_block, pos, seg)
    h = _rms(x, norms["mlp_norm"], eps)
    if mlp == "dense":
        return x + _by_rows(lambda xb: _gated(xb, mp["wi"], mp["wg"], mp["wd"]), h, row_block)
    return x + _expert_mlp(h, mp, cfg, row_block)


def hidden(params, tokens, cfg, mode="float32", q_block=128, row_block=2048,
           pos=None, seg=None):
    """tokens [T] -> final-normed hidden states [T, D].  ``pos`` [T] are the
    rotary positions and ``seg`` the segments of the module's docstring.
    Layers that follow each other and are of one kind (mixer and MLP) run as
    one loop over their stacked weights: one layer's code for the run."""
    if mode != "float32":
        raise ValueError(f"the window reference computes in float32, not {mode!r}")
    z = dims(cfg)
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[0]) if pos is None else pos
    blk = params["block"]
    seen = {"full": 0, "window": 0, "dense": 0, "experts": 0}
    layer = 0
    while layer < z["L"]:
        kind, mlp = z["kinds"][layer], z["mlps"][layer]
        n = 1
        while layer + n < z["L"] and (z["kinds"][layer + n], z["mlps"][layer + n]) == (kind, mlp):
            n += 1
        at, mt = ("full" if kind == FULL else "window"), ("dense" if mlp == "dense" else "experts")
        norms = {k: blk[k] for k in ("attn_norm", "mlp_norm")}

        def step(x, i, kind=kind, mlp=mlp, firsts=(layer, seen[at], seen[mt]), at=at, mt=mt):
            # one layer's weights cut out of the stacks where they are needed, never a run's
            pick = lambda tree, lo: jax.tree.map(  # noqa: E731
                lambda w: lax.dynamic_index_in_dim(w, lo + i, keepdims=False), tree)
            weights = (pick(norms, firsts[0]), pick(blk[at], firsts[1]), pick(blk[mt], firsts[2]))
            return _layer(x, *weights, kind, mlp, cfg, q_block, row_block, pos, seg), None

        x, _ = lax.scan(step, x, jnp.arange(n))
        seen[at] += n
        seen[mt] += n
        layer += n
    return _rms(x, params["final_norm"], cfg["rms_norm_eps"])


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
