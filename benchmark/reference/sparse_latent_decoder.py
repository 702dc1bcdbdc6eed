"""The plain reference of a decoder-only LM with latent attention through a
learned key selection (DeepSeek-V3.2's sparse attention) and group-limited
routed experts: the forms of ``deepseek-ai/DeepSeek-V3.2``'s published
inference code (``MLA``, ``Indexer``, ``Gate``) under the keys of its
``config.json``.  Each configuration's own reference file
(``configs/<config>.reference.py``) is this module under the configuration's
name.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``; no kernel,
no cache, no batching, no sort of experts and no grouping of rows: one sequence
at a time, query rows in blocks, EVERY held expert over EVERY row, weighted by
its gate, zero where the expert was not chosen.  It imports nothing of the
program and takes nothing the program made: the weights are drawn here from the
seed (the same draws the program makes, in the same order: the latent
decoder's, ``latent_moe_decoder.py``, then the indexer's three matrices).  The
helpers of ``latent_moe_decoder.py`` are used as they are.

With ``h`` a layer's normed input (``rope`` YaRN's over the first
``qk_rope_head_dim`` columns, pairs split by halves):

    c_q = rmsnorm(h W_qa);  q = c_q W_qb       -> heads of [q_nope | q_rope]
    [c_raw | r] = h W_kva;  c = rmsnorm(c_raw);  k_rope = rope(r)
    [k_nope | v] per head = c W_kvb
    qI_j = rope(c_q WI_q)_j;  kI = rope(layernorm(h WI_k));  w_j = (h WI_w)_j / sqrt(Hi x di)
    I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))           over the keys t may see
    S_t = the index_topk keys of largest I (lax.top_k; all while t sees no more)
    out(t) = softmax over S_t of (q_nope . k_nope + rope(q_rope) . k_rope) x scale, of v
    scale = (nope + rope)^-0.5 x mscale^2, mscale = 0.1 x mscale_all_dim x ln(factor) + 1

    s = sigmoid(h W_r);  b = s + bias, in n_group groups; a group scores the sum
    of its two largest b; every expert outside the topk_group best groups: -inf;
    chosen = top_k(b);  g_i = scale * s_i / (sum_chosen s + 1e-20)

**The cut** is the latent decoder's: the router is as wide as published
(``router_width``), experts ``[expert_offset, expert_offset + n_routed_experts)``
are held, what the absent experts would add is left out.

**Where the weights live.**  Held whole in float32 they would not leave room on
one chip for the activations of a long document.  Where the device has a host
memory (``pinned_host``), ``init_params`` draws each weight on the device and
moves it there at once, arranged as two stacks of layers (the leading dense
layers, the expert layers) with the embedding and the head beside them; the
forward pass moves one layer's weights to the device at a time inside a
``lax.scan`` over each stack.  Its peak on the device is then a layer's weights
(two while the next is fetched), the embedding and the head, and one query
block's temporaries.

Departures from the published model, listed under ``assumed`` in the
configuration's file: rotary pairs split by halves; the indexer's Hadamard
rotation left out (orthogonal: ``qI . kI`` is unchanged); the indexer in
float32 (the published one in FP8); the selection bias drawn from the seed; the
multi-token-prediction module not held.

``seg`` (see ``served_gap.py``): several continuations of one shared prefix,
laid out one after the other.  A row sees segment 0 and its own segment.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import latent_moe_decoder as _latent
from benchmark.reference.latent_moe_decoder import HIGHEST, _by_rows, _gated, _mm, _rms

_ATTN = ("attn_norm", "mlp_norm", "q_norm", "kv_norm", "wq_a", "wq_b", "wkv_a", "wkv_b", "wo",
         "index_wq", "index_wk", "index_ww", "index_norm_w", "index_norm_b")


def dims(cfg: dict):
    z = _latent.dims(cfg)
    z.update(Hi=cfg["index_n_heads"], di=cfg["index_head_dim"], topk=cfg["index_topk"],
             n_group=cfg.get("n_group", 1), topk_group=cfg.get("topk_group", 1))
    return z


def rotary(cfg: dict):
    """``(inverse frequencies [qk_rope_head_dim / 2] float32, factor on the
    score scale)``: YaRN's (the published ``precompute_freqs_cis``) where
    ``rope_scaling`` is given."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    freqs = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = cfg.get("rope_scaling")
    if not rs:
        return freqs.astype(np.float32), 1.0
    factor, turns_at = float(rs["factor"]), rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(turns_at / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    freqs = freqs / factor * ramp + freqs * (1.0 - ramp)
    mscale = 1.0
    if rs.get("mscale_all_dim") and factor > 1:
        mscale = 0.1 * float(rs["mscale_all_dim"]) * math.log(factor) + 1.0
    return freqs.astype(np.float32), mscale * mscale


def _rope(x, pos, freqs):
    """x [T, ..., d] rotated to ``pos [T]`` over its first ``2 x len(freqs)``
    columns, pairs split by halves; the rest pass."""
    half = freqs.shape[0]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half : 2 * half], x[..., 2 * half :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _draws(cfg: dict):
    """Every weight's ``(path, shape, scale)`` in the order the program draws
    them, one key of ``split(seed, 32)`` each; ``scale`` None: ones, 0: zeros."""
    z = dims(cfg)
    L, D, H, F, V = z["L"], z["D"], z["H"], z["F"], z["V"]
    rq, rkv, dn, dr, dv = z["rq"], z["rkv"], z["dn"], z["dr"], z["dv"]
    nd, ne, E, held, Fe = z["n_dense"], z["n_exp"], z["E"], z["held"], z["Fe"]
    Fs, Hi, di = z["n_shared"] * Fe, z["Hi"], z["di"]
    out = [("wq_a", (L, D, rq), D ** -0.5), ("wq_b", (L, rq, H, dn + dr), rq ** -0.5),
           ("wkv_a", (L, D, rkv + dr), D ** -0.5), ("wkv_b", (L, rkv, H, dn + dv), rkv ** -0.5),
           ("wo", (L, H, dv, D), (H * dv) ** -0.5),
           ("dense/wi", (nd, D, F), D ** -0.5), ("dense/wg", (nd, D, F), D ** -0.5),
           ("dense/wd", (nd, F, D), F ** -0.5)]
    if ne:
        out += [("experts/router", (ne, D, E), D ** -0.5), ("experts/router_bias", (ne, E), 0.02),
                ("experts/wi", (ne, held, D, Fe), D ** -0.5),
                ("experts/wg", (ne, held, D, Fe), D ** -0.5),
                ("experts/wd", (ne, held, Fe, D), Fe ** -0.5),
                ("experts/shared_wi", (ne, D, Fs), D ** -0.5),
                ("experts/shared_wg", (ne, D, Fs), D ** -0.5),
                ("experts/shared_wd", (ne, Fs, D), max(Fs, 1) ** -0.5)]
    out += [("embed", (V, D), 1.0), ("unembed", (D, V), D ** -0.5),
            ("index_wq", (L, rq, Hi, di), rq ** -0.5), ("index_wk", (L, D, di), D ** -0.5),
            ("index_ww", (L, D, Hi), D ** -0.5)]
    return out + [(n, (L, w), None) for n, w in (("attn_norm", D), ("mlp_norm", D),
                                                  ("q_norm", rq), ("kv_norm", rkv),
                                                  ("index_norm_w", di))] + [
        ("index_norm_b", (L, di), 0), ("final_norm", (D,), None)]


def host_sharding():
    """Where the weights are kept: the device's host memory where it has one."""
    dev = jax.devices()[0]
    kinds = {m.kind for m in dev.addressable_memories()}
    return jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host") if (
        "pinned_host" in kinds) else None


def init_params(seed: int, cfg: dict) -> dict:
    """``{"embed", "unembed", "final_norm", "dense": {...} [n_dense, ...],
    "experts": {...} [n_exp, ...]}``: each stack holds its layers' attention,
    indexer and MLP weights; every weight drawn on the device and moved to the
    host memory (:func:`host_sharding`), its layers split between the two
    stacks, before the next is drawn."""
    z = dims(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), 32)
    place = host_sharding()
    nd = z["n_dense"]

    def put(a):
        return jax.device_put(a, place) if place else jnp.asarray(a)

    params = {"dense": {}, "experts": {}}
    i = 0
    for path, shape, scale in _draws(cfg):
        if scale is None:
            w = np.ones(shape, np.float32)
        elif scale == 0:
            w = np.zeros(shape, np.float32)
        else:
            w = np.asarray(jax.random.normal(keys[i], shape, jnp.float32) * scale)
            i += 1
        kind, _, name = path.rpartition("/")
        if kind:
            params[kind][name] = put(w)
        elif name in _ATTN:  # a layer each: the leading dense layers, then the expert layers
            params["dense"][name], params["experts"][name] = put(w[:nd]), put(w[nd:])
        else:
            params[name] = put(w)
        del w
    if not z["n_exp"]:
        del params["experts"]
    return params


def _on_device(tree):
    if host_sharding() is None:
        return tree
    return jax.device_put(tree, jax.memory.Space.Device)


def gates(h, lp, cfg):
    """The group-limited router: ``h [T, D]`` -> the gate of EVERY expert of
    the layer ``[T, router_width]``, zero where it was not chosen."""
    z = dims(cfg)
    s = jax.nn.sigmoid(_mm("td,de->te", h, lp["router"]))
    b = s + lp["router_bias"]
    T, E = b.shape
    if z["n_group"] > 1:
        g = b.reshape(T, z["n_group"], E // z["n_group"])
        group_score = jnp.sum(lax.top_k(g, 2)[0], axis=-1)
        _, best = lax.top_k(group_score, z["topk_group"])
        kept = jnp.zeros((T, z["n_group"]), bool).at[jnp.arange(T)[:, None], best].set(True)
        b = jnp.where(kept[..., None], g, -jnp.inf).reshape(T, E)
    _, chosen = lax.top_k(b, z["k"])
    picked = jnp.zeros_like(s).at[jnp.arange(T)[:, None], chosen].set(1.0)
    w = s * picked
    return float(cfg["routed_scaling_factor"]) * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)


def _expert_mlp(h, lp, cfg, row_block):
    z = dims(cfg)
    g = gates(h, lp, cfg)[:, z["offset"]: z["offset"] + z["held"]]

    def one_expert(acc, e):
        wi, wg, wd, ge = e
        return acc + ge[:, None] * _by_rows(lambda xb: _gated(xb, wi, wg, wd), h, row_block), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(h), (lp["wi"], lp["wg"], lp["wd"], g.T))
    if z["n_shared"]:
        y = y + _by_rows(
            lambda xb: _gated(xb, lp["shared_wi"], lp["shared_wg"], lp["shared_wd"]), h, row_block)
    return y


def _sparse_attention(proj, index, cfg, q_block, seg):
    """Query rows in blocks: the indexer's scores over the keys a row may see,
    the row's top ``index_topk`` of them, softmax attention over those alone."""
    z = dims(cfg)
    q_nope, q_rope, k_nope, k_rope, v = proj
    qi, w, ki = index
    T, H, dn = q_nope.shape
    scale = (dn + q_rope.shape[-1]) ** -0.5 * rotary(cfg)[1]
    qb = _latent._blocks(T, q_block)
    kpos = jnp.arange(T)
    k = min(z["topk"], T)

    def one(args):
        qn, qr, qib, wb, start = args
        rows = start + jnp.arange(qb)
        mask = rows[:, None] >= kpos[None, :]
        if seg is not None:
            mask &= (seg[None, :] == 0) | (seg[None, :] == seg[rows][:, None])
        dots = jnp.einsum("qjd,kd->qjk", qib, ki, precision=HIGHEST)
        score = jnp.einsum("qjk,qj->qk", jnp.maximum(dots, 0.0), wb, precision=HIGHEST)
        _, top = lax.top_k(jnp.where(mask, score, -jnp.inf), k)
        chosen = jnp.zeros((qb, T), bool).at[jnp.arange(qb)[:, None], top].set(True) & mask
        s = jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=HIGHEST)
        s = (s + jnp.einsum("qhd,kd->hqk", qr, k_rope, precision=HIGHEST)) * scale
        p = jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = lax.map(one, (q_nope.reshape(T // qb, qb, H, dn), q_rope.reshape(T // qb, qb, H, -1),
                        qi.reshape(T // qb, qb, *qi.shape[1:]), w.reshape(T // qb, qb, -1),
                        jnp.arange(T // qb) * qb))
    return out.reshape(T, H, -1)


def _mixer(x, lp, cfg, q_block, row_block, pos, seg):
    z = dims(cfg)
    eps = cfg["rms_norm_eps"]
    rkv, dn, dr = z["rkv"], z["dn"], z["dr"]
    freqs = rotary(cfg)[0]

    def project(xb):
        cq = _rms(_mm("td,dr->tr", xb, lp["wq_a"]), lp["q_norm"], eps)
        q = _mm("tr,rhe->the", cq, lp["wq_b"])
        kv = _mm("td,dr->tr", xb, lp["wkv_a"])
        c = _rms(kv[:, :rkv], lp["kv_norm"], eps)
        up = _mm("tr,rhe->the", c, lp["wkv_b"])
        qi = _mm("tr,rhe->the", cq, lp["index_wq"])
        ki = _mm("td,de->te", xb, lp["index_wk"])
        mean = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
        ki = (ki - mean) * lax.rsqrt(var + 1e-6) * lp["index_norm_w"] + lp["index_norm_b"]
        w = _mm("td,dh->th", xb, lp["index_ww"]) * (z["Hi"] * z["di"]) ** -0.5
        return q[..., :dn], q[..., dn:], up[..., :dn], kv[:, rkv:], up[..., dn:], qi, w, ki

    q_nope, q_rope, k_nope, r_raw, v, qi, w, ki = _by_rows(project, x, row_block)
    proj = (q_nope, _rope(q_rope, pos, freqs), k_nope, _rope(r_raw, pos, freqs), v)
    index = (_rope(qi, pos, freqs), w, _rope(ki, pos, freqs))
    a = _sparse_attention(proj, index, cfg, q_block, seg)
    assert dr == 2 * freqs.shape[0]
    return _by_rows(lambda ab: _mm("thv,hvd->td", ab, lp["wo"]), a, row_block)


def hidden(params, tokens, cfg, mode="float32", q_block=64, row_block=2048, pos=None, seg=None):
    """tokens [T] -> final-normed hidden states [T, D].  ``pos`` [T] are the
    rotary positions and ``seg`` the segments of the module's docstring."""
    if mode != "float32":
        raise ValueError(f"the sparse latent reference computes in float32, not {mode!r}")
    eps = cfg["rms_norm_eps"]
    x = _on_device(params["embed"])[tokens]
    pos = jnp.arange(tokens.shape[0]) if pos is None else pos

    def layer(x, lp, dense):
        lp = _on_device(lp)
        h = _rms(x, lp["attn_norm"], eps)
        x = x + _mixer(h, lp, cfg, q_block, row_block, pos, seg)
        h = _rms(x, lp["mlp_norm"], eps)
        if dense:
            return x + _by_rows(lambda xb: _gated(xb, lp["wi"], lp["wg"], lp["wd"]), h, row_block)
        return x + _expert_mlp(h, lp, cfg, row_block)

    x, _ = lax.scan(lambda x, lp: (layer(x, lp, True), None), x, params["dense"])
    if "experts" in params:
        x, _ = lax.scan(lambda x, lp: (layer(x, lp, False), None), x, params["experts"])
    return _rms(x, _on_device(params["final_norm"]), eps)


def logits_at(params, tokens, rows, cfg, mode="float32", pos=None, seg=None):
    """Logits [len(rows), V] of one sequence at the given rows."""
    x = hidden(params, tokens, cfg, mode, pos=pos, seg=seg)[rows]
    return _mm("td,dv->tv", x, _on_device(params["unembed"]))


def loss_row(params, tokens, targets, cfg, mode="float32", row_block=2048):
    """Mean next-token cross-entropy of one sequence, logits in row blocks."""
    x = hidden(params, tokens, cfg, mode)
    T, D = x.shape
    rb = _latent._blocks(T, row_block)
    unembed = _on_device(params["unembed"])

    def nll(args):
        xb, tb = args
        lg = _mm("td,dv->tv", xb, unembed)
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]

    return jnp.mean(lax.map(nll, (x.reshape(T // rb, rb, D), targets.reshape(T // rb, rb))))
