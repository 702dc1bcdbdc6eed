"""A latent-attention model with routed experts through the paged programs and
the engine: logits against the plain reference, the two forms of the attention
and a chunk's walk by key tiles against the plain rule over the whole table,
the dropless expert product against "every expert over every row", the chip's
share against the uncut layer, prefix hits and copy-on-write through the latent
pool, the counters, and the options refused.

Sizes are tiny and compute is float32 on seeded random weights, so the program
and the reference (``benchmark/reference/latent_moe_decoder.py``: float32,
matmuls at ``highest``, no cache, no sort, no grouping) differ by float32
summation order only.  ``LOGIT_TOL`` = 5e-5 absolute on logits of magnitude 3:
over twenty times what the two read apart here (1e-6 to 2e-6), and far under
what one token routed to another expert reads (asserted below: over 1e-3).
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import latent_moe_decoder as ref
from polyaxon_tpu.models import TransformerConfig, decode, init_params, latent_moe
from polyaxon_tpu.models.latent_moe import LatentStackError
from polyaxon_tpu.parallel import experts
from polyaxon_tpu.serving import ServingEngine

LOGIT_TOL = 5e-5
#: The reference's keys.  ``n_routed_experts`` counts the experts HELD (8 of the
#: router's 16, from the fifth on): the cut the benchmark's configuration makes.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "intermediate_size": 128, "rms_norm_eps": 1e-6, "rope_theta": 32e6,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "first_k_dense_replace": 1, "n_routed_experts": 8, "router_width": 16,
    "expert_offset": 4, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
}
BS, W, SLOTS = 8, 16, 3
SEED = 2**31 + 7


def make_cfg(z, seq=BS * W, dtype=jnp.float32, **over):
    n_dense = z["first_k_dense_replace"]
    fields = dict(
        vocab_size=z["vocab_size"], d_model=z["hidden_size"], n_layers=z["num_hidden_layers"],
        n_heads=z["num_attention_heads"], head_dim=16, d_ff=z["intermediate_size"],
        max_seq=seq, dtype=dtype, rope_theta=z["rope_theta"],
        layer_types=("dense_mlp",) * n_dense
        + ("expert_mlp",) * (z["num_hidden_layers"] - n_dense),
        n_routed_experts=z["router_width"], experts_held=z["n_routed_experts"],
        **{k: z[k] for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "expert_offset", "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts",
            "routed_scaling_factor")})
    return TransformerConfig(**{**fields, **over})


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def tiny():
    cfg = make_cfg(TINY)
    return cfg, init_params(jax.random.PRNGKey(SEED), cfg), ref.init_params(SEED, TINY)


_REFERENCE = {}
_PROGRAMS = {}


def reference_logits(mine, tokens, rows):
    padded = np.zeros(128, np.int32)
    padded[: len(tokens)] = tokens
    at = np.zeros(16, np.int32)
    at[: len(rows)] = rows
    fn = _REFERENCE.setdefault("tiny", jax.jit(lambda p, t, r: ref.logits_at(p, t, r, TINY)))
    return fn(mine, jnp.asarray(padded), jnp.asarray(at))[: len(rows)]


def _pool(cfg, kv_dtype=None, width=W):
    return decode.init_block_pool(cfg, 1 + SLOTS * width, BS, kv_dtype=kv_dtype)


def _serve_through_the_programs(cfg, params, tokens, n_prompt, chunks, pool, slot=1):
    """Prefill ``tokens[:n_prompt]`` in ``chunks`` [(start, n, padded)], then decode
    the rest one token a step in ``slot`` beside two inactive lanes; the tables are
    as wide as ``pool`` gives each lane blocks.  Returns the logits and what the
    calls' expert layers routed in all (``latent_moe.COUNT_NAMES``)."""
    chunk, step = _PROGRAMS.setdefault(cfg, (
        jax.jit(partial(decode.paged_prefill_chunk, cfg=cfg)),
        jax.jit(partial(decode.paged_decode_step, cfg=cfg))))
    W = (decode._row_leaf(pool).shape[1] - 1) // SLOTS
    table = np.zeros(W, np.int32)
    table[: -(-len(tokens) // BS)] = 1 + slot * W + np.arange(-(-len(tokens) // BS))
    out, routed = [], np.zeros(4, np.int64)
    for start, n, padded in chunks:
        buf = np.zeros(padded, np.int32)
        buf[:n] = tokens[start : start + n]
        logits, pool, counts = chunk(params, pool, jnp.asarray(table), jnp.asarray(buf),
                                     jnp.int32(start), jnp.int32(n))
        routed += np.asarray(counts)
    out.append(logits)
    tables = np.zeros((SLOTS, W), np.int32)
    tables[slot] = table
    active = np.arange(SLOTS) == slot
    for i in range(n_prompt, len(tokens)):
        logits, pool, counts = step(
            params, pool, jnp.asarray(tables),
            jnp.asarray(np.where(active, tokens[i], 0).astype(np.int32)),
            jnp.asarray(np.where(active, i, 0).astype(np.int32)), jnp.asarray(active))
        routed += np.asarray(counts)
        out.append(logits[slot])
    return jnp.stack(out), pool, routed


def test_program_draws_the_references_weights(tiny):
    cfg, params, mine = tiny
    ours, theirs = jax.tree.leaves(params), jax.tree.leaves(mine)
    assert len(ours) == len(theirs) and cfg.n_params == sum(x.size for x in ours)
    for a, b in zip(ours, theirs):
        assert a.shape == b.shape and bool(jnp.all(a == b))


CHUNKS = [(0, 32, 32), (32, 32, 32), (64, 13, 16)]  # the last in a bucket of 16
#: Blocks of a table three key tiles wide (``decode.TILE_KEYS`` a tile).
W_TILES = 3 * decode.TILE_KEYS // BS


def test_whole_prefill_chunked_prefill_and_decode_agree_with_the_references_forward(tiny):
    cfg, params, mine = tiny
    tokens = np.random.default_rng(0).integers(0, 256, 77 + 6)
    want = reference_logits(mine, tokens, np.arange(76, 83))
    whole, pool_a, counts_a = _serve_through_the_programs(
        cfg, params, tokens, 77, [(0, 77, 128)], _pool(cfg))
    chunked, _, counts_b = _serve_through_the_programs(cfg, params, tokens, 77, CHUNKS, _pool(cfg))
    # a table two tiles wider than what is live: the chunks walk its first tile only
    wide, _, counts_c = _serve_through_the_programs(
        cfg, params, tokens, 77, CHUNKS, _pool(cfg, width=W_TILES))
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert float(jnp.max(jnp.abs(whole - want))) < LOGIT_TOL
    assert float(jnp.max(jnp.abs(chunked - want))) < LOGIT_TOL
    assert float(jnp.max(jnp.abs(wide - want))) < LOGIT_TOL and list(counts_c) == list(counts_b)
    # the pool holds one row a token a layer, the row padded to whole lane tiles
    assert pool_a["c"].shape == (3, 1 + SLOTS * W, BS, 128)
    assert latent_moe.row_width(cfg) == 40 and not bool(jnp.any(pool_a["c"][..., 40:]))
    # pad rows and idle lanes routed nowhere: 83 tokens x 4 choices x 2 expert layers
    # (1 or 3 chunks and 6 steps, each through 2 expert layers of the 8 experts held)
    for (routed, held, busiest, hit), calls in ((counts_a, 7), (counts_b, 9)):
        assert routed == 83 * 4 * 2 and 0 < busiest <= held < routed
        assert 0 < hit <= min(held, 8 * 2 * calls)
    assert counts_a[1] == counts_b[1]  # the same rows fall to the same experts, however cut


def _attend_up_projected(q_nope, q_rope, rows, mask, layer, cfg):
    """The plain rule a prompt chunk is held to, the equation as it reads: keys
    and values up-projected from ALL the rows a table gathers ``[B, K, row]``,
    one softmax over them; ``mask`` broadcasts to ``[B, H, T, K]``."""
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    scale = (dn + cfg.qk_rope_head_dim) ** -0.5
    kv = jnp.einsum("bkr,rhe->bkhe", rows[..., :rkv], layer["wkv_b"])
    s = jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv[..., :dn])
    s = (s + jnp.einsum("bqhd,bkd->bhqk", q_rope, rows[..., rkv:])) * scale
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., dn:])


def _attention_layer(params, at=1):
    return jax.tree.map(lambda w: w[at], {n: params["block"][n] for n in latent_moe._ATTN + latent_moe._NORMS})


def test_the_absorbed_form_is_the_up_projected_form(tiny):
    cfg, params, _ = tiny
    rng = np.random.default_rng(2)
    layer = _attention_layer(params)
    q_nope = jnp.asarray(rng.normal(size=(2, 3, 4, 16)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(2, 3, 4, 8)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(2, 24, 40)), jnp.float32)
    mask = jnp.asarray(rng.random((2, 1, 3, 24)) < 0.7).at[..., 0].set(True)
    up = _attend_up_projected(q_nope, q_rope, rows, mask, layer, cfg)
    absorbed = latent_moe._attend_absorbed(q_nope, q_rope, rows, mask, layer, cfg)
    assert up.shape == (2, 3, 4, 16) and float(jnp.max(jnp.abs(up))) > 0.1
    assert float(jnp.max(jnp.abs(up - absorbed))) < 1e-5


TILE = decode.TILE_KEYS


@pytest.mark.parametrize("start,length,C,width,kv_dtype", [
    (0, 32, 32, W_TILES, None),                # the first tile of three, from position 0
    (TILE - 14, 32, 32, W_TILES, None),        # across a tile edge
    (TILE + 3, 29, 32, W_TILES, None),         # off a block edge, pad rows in the bucket
    (2 * TILE - 32, 32, 32, W_TILES, None),    # the live end exactly on a tile edge
    (3 * TILE - 40, 13, 16, W_TILES, None),    # the table's last tile
    (TILE + 200, 32, 32, W_TILES // 2, None),  # a table of one tile and a half
    (64, 32, 32, W, None),                     # a table narrower than a tile: one tile
    (TILE - 14, 32, 32, W_TILES, "int8"),      # the int8 latent pool, across a tile edge
], ids=["first-tile", "tile-edge", "block-edge-pad", "ends-on-edge", "last-tile",
        "ragged-table", "single-tile", "int8-pool"])
def test_a_chunk_walking_its_table_by_tiles_is_the_plain_rule_over_the_whole_table(
        tiny, start, length, C, width, kv_dtype):
    """``_chunk_mixer`` (rows written, then key tiles up to the live end under a
    running softmax) against the rows written the same way, ALL of the table
    gathered and one softmax under the mask ``qpos >= kpos``."""
    cfg, params, _ = tiny
    rng = np.random.default_rng(start + length)
    layer, li = _attention_layer(params), jnp.int32(1)
    # every block holds rows already: normal values, or int8 values under scales near 1 / 127
    pool = {
        name: jnp.asarray(
            rng.integers(-127, 128, leaf.shape) if leaf.dtype == jnp.int8
            else rng.uniform(0.5, 1.5, leaf.shape) / 127 if name.endswith("_scale")
            else rng.normal(size=leaf.shape), leaf.dtype)
        for name, leaf in decode.init_block_pool(cfg, 1 + width, BS, kv_dtype=kv_dtype).items()}
    table = jnp.asarray(1 + rng.permutation(width), jnp.int32)
    h = jnp.asarray(rng.normal(size=(1, C, 64)), jnp.float32)
    qpos, valid, write_blk, write_off, kpos = decode._chunk_addresses(
        pool, table, jnp.int32(start), jnp.int32(length), C)

    @jax.jit
    def tiled(h, pool):
        attend = latent_moe._chunk_mixer(cfg, qpos, table, write_blk, write_off, start + length)
        return attend(h, layer, li, pool)

    @jax.jit
    def plain(h, pool):
        q_nope, q_rope = latent_moe._queries(h, layer, qpos[None], cfg)
        row = latent_moe._latent_row(h, layer, qpos[None], cfg)
        pool = decode._latent_append(pool, li, row[0], write_blk, write_off)
        rows = decode._latent_gather(pool, li, table, h.dtype, row.shape[-1])[None]
        mask = qpos[None, None, :, None] >= kpos[:, None, None, :]
        attn = _attend_up_projected(q_nope, q_rope, rows, mask, layer, cfg)
        return decode._attn_out(attn, layer), pool

    (got, pool_a), (want, pool_b) = tiled(h, pool), plain(h, pool)
    assert want.shape == (1, C, 64) and float(jnp.max(jnp.abs(want[0, :length]))) > 0.1
    assert float(jnp.max(jnp.abs(got[0, :length] - want[0, :length]))) < LOGIT_TOL
    assert bool(jnp.all(jnp.isfinite(got)))  # pad rows: not read, and never NaN into the pool
    for a, b in zip(jax.tree.leaves(pool_a), jax.tree.leaves(pool_b)):
        assert bool(jnp.all(a == b))
    # and a chunk of length 0, the warm-up's: no tile walked, zeros out
    nothing = jax.jit(lambda h, pool: latent_moe._chunk_mixer(
        cfg, qpos, table, write_blk * 0, write_off * 0, 0)(h, layer, li, pool))(h, pool)[0]
    assert not bool(jnp.any(nothing))


def test_the_chunk_program_forms_nothing_of_the_tables_width_and_compiles_once(tiny, monkeypatch):
    """No array of the chunk program has the table's ``W * bs`` positions as a
    dimension (the scores ``heads x C x (W * bs)`` and the keys and values ``(W
    * bs) x heads x (nope + v)`` among them); lowered for the TPU a tile's
    scores are not in the text either, they stay in the kernel; and the walk's
    trip count is data: one compilation serves every ``start``."""
    from jax import export

    from polyaxon_tpu.parallel import flash

    cfg, params, _ = tiny
    C, positions = 16, W_TILES * BS  # 16 rows: no other array is heads x 16 x a tile
    pool = _pool(cfg, width=W_TILES)
    fn = jax.jit(partial(decode.paged_prefill_chunk, cfg=cfg))
    table = jnp.asarray(1 + np.arange(W_TILES), jnp.int32)
    args = (params, pool, table, jnp.zeros(C, jnp.int32))

    def shapes(text):
        assert "stablehlo.while" in text
        found = set(re.findall(r"tensor<([0-9x]+)x[a-z]", text))
        assert str(W_TILES) in found and f"{TILE}x{latent_moe.row_width(cfg)}" in found  # table, a tile's rows
        assert not [s for s in found if str(positions) in s.split("x")], found
        return found

    shapes(fn.lower(*args, jnp.int32(0), jnp.int32(C)).as_text())
    out = [fn(*args, jnp.int32(start), jnp.int32(C))[0] for start in (0, 2 * TILE + 8)]
    assert fn._cache_size() == 1 and float(jnp.max(jnp.abs(out[0] - out[1]))) > 0
    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)
    on_tpu = export.export(
        jax.jit(partial(decode.paged_prefill_chunk, cfg=cfg)), platforms=["tpu"]
    )(*args, jnp.int32(0), jnp.int32(C)).mlir_module()
    assert 'kernel_name = "mla_chunk_tile"' in on_tpu
    assert f"{cfg.n_heads}x{C}x{TILE}" not in shapes(on_tpu)


def _step_program_checks(cfg, params, pool_of, monkeypatch, wide=200):
    """What both stacks' decode step is held to (``test_window_moe_serving.py``
    calls this too): over a float pool no array of the program has the table's
    ``wide * BS`` positions as a dimension, on the CPU or lowered for the TPU,
    where the kernel is a named device operation; one compilation serves two
    different ``pos`` vectors; over an int8 pool the program still gathers the
    table's width, and its logits stay close to the float pool's."""
    from jax import export

    from polyaxon_tpu.parallel import flash

    positions = wide * BS  # 1,600: a number no other axis has
    fn = jax.jit(partial(decode.paged_decode_step, cfg=cfg))
    tables = jnp.asarray(1 + np.arange(SLOTS * wide).reshape(SLOTS, wide) % wide, jnp.int32)
    toks, on = jnp.asarray([3, 5, 7], jnp.int32), jnp.asarray([True, True, False])
    args = (params, pool_of(None, wide), tables, toks)

    def dims(text):
        return set(re.findall(r"tensor<([0-9x]+)x[a-z]", text))

    def widths(text):
        return [s for s in dims(text) if str(positions) in s.split("x")]

    assert str(wide) in "x".join(dims(fn.lower(*args, toks * 0 + 9, on).as_text())).split("x")
    assert not widths(fn.lower(*args, toks * 0 + 9, on).as_text())
    out = [fn(*args, jnp.asarray(pos, jnp.int32), on)[0] for pos in ([9, 700, 0], [1500, 64, 0])]
    assert fn._cache_size() == 1 and float(jnp.max(jnp.abs(out[0] - out[1]))) > 0
    int8 = fn.lower(params, pool_of("int8", wide), tables, toks, toks * 0 + 9, on).as_text()
    assert widths(int8) and "paged_step_attend" not in int8
    close = fn(params, pool_of("int8", wide), tables, toks, jnp.asarray([9, 700, 0], jnp.int32), on)[0]
    assert float(jnp.max(jnp.abs(close[:2] - out[0][:2]))) < 0.05
    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)
    on_tpu = export.export(
        jax.jit(partial(decode.paged_decode_step, cfg=cfg)), platforms=["tpu"]
    )(*args, toks * 0 + 9, on).mlir_module()
    assert 'kernel_name = "paged_step_attend"' in on_tpu and not widths(on_tpu)


def test_the_step_program_forms_nothing_of_the_tables_width_and_compiles_once(tiny, monkeypatch):
    cfg, params, _ = tiny
    _step_program_checks(
        cfg, params, lambda kv_dtype, wide: decode.init_block_pool(cfg, 1 + wide, BS, kv_dtype),
        monkeypatch)


def _every_expert_over_every_row(h, chosen, gates, valid, wi, wg, wd, offset):
    """The plain form: each held expert's MLP over all rows, weighted by its
    gate, which is zero where it was not chosen."""
    y = jnp.zeros_like(h)
    rows = []
    for e in range(wi.shape[0]):
        g = jnp.sum(jnp.where((chosen == e + offset) & valid[:, None], gates, 0.0), axis=-1)
        out = (jax.nn.silu(h @ wg[e]) * (h @ wi[e])) @ wd[e]
        y = y + g[:, None] * out
        rows.append(int(jnp.sum((chosen == e + offset) & valid[:, None])))
    return y, rows


def test_the_dropless_product_under_a_skewed_router_is_every_expert_over_every_row():
    """One expert with no rows, one with most of them, rows of absent experts
    and of padding: no token is lost and none is counted twice."""
    rng = np.random.default_rng(3)
    N, D, F, held, offset, k = 40, 16, 24, 6, 2, 3
    h = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    wi, wg = (jnp.asarray(rng.normal(size=(held, D, F)) * 0.3, jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(held, F, D)) * 0.3, jnp.float32)
    # expert 4 (local 2) takes a choice of every token, expert 5 (local 3) none;
    # the other two choices fall anywhere among the router's 12 but those two
    others = [e for e in range(12) if e not in (4, 5)]
    chosen = np.stack([np.full(N, 4)] + [rng.choice(others, N) for _ in range(k - 1)], axis=1)
    chosen[chosen[:, 1] == chosen[:, 2], 2] = 11  # top-k never names an expert twice
    chosen = jnp.asarray(chosen, jnp.int32)
    gates = jnp.asarray(rng.random((N, k)) + 0.1, jnp.float32)
    valid = jnp.asarray(np.arange(N) < 33)
    y, rows = jax.jit(partial(experts.experts_mlp, offset=offset))(
        h, chosen, gates, valid, wi, wg, wd)
    want, want_rows = _every_expert_over_every_row(h, chosen, gates, valid, wi, wg, wd, offset)
    assert list(np.asarray(rows)) == want_rows
    assert want_rows[2] == 33 and want_rows[3] == 0 and sum(want_rows) < 33 * k
    assert float(jnp.max(jnp.abs(want[:33]))) > 0.5
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5
    assert not bool(jnp.any(y[33:]))  # padding got nothing


def test_the_router_picks_by_the_biased_score_and_weighs_by_the_unbiased():
    h = jnp.eye(4, dtype=jnp.float32)
    router = jnp.asarray([[3.0, 2.0, 1.0, 0.0, -1.0]] * 4, jnp.float32)
    bias = jnp.asarray([-10.0, 0.0, 0.0, 0.0, 10.0], jnp.float32)
    chosen, gates = experts.route(h, router, bias, 2, 2.5)
    s = jax.nn.sigmoid(router[0])
    assert sorted(np.asarray(chosen[0]).tolist()) == [1, 4]  # the bias moved both places
    order = np.asarray(chosen[0]).tolist()
    want = 2.5 * s[jnp.asarray(order)] / (s[1] + s[4])
    assert float(jnp.max(jnp.abs(gates[0] - want))) < 1e-6
    assert float(jnp.sum(gates[0])) == pytest.approx(2.5, abs=1e-5)


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """The router's 16 experts over four chips, 4 each: the four routed parts
    plus the shared expert counted once are the reference's uncut layer."""
    uncut = {**TINY, "n_routed_experts": 16, "router_width": 16, "expert_offset": 0}
    mine = ref.init_params(SEED, uncut)
    ep = jax.tree.map(lambda w: w[0], mine["block"]["experts"])
    h = jnp.asarray(np.random.default_rng(5).normal(size=(1, 24, 64)), jnp.float32)
    whole = ref._expert_mlp(h[0], ep, uncut, 2048)
    shared = ref._gated(h[0], ep["shared_wi"], ep["shared_wg"], ep["shared_wd"])
    total = jnp.zeros_like(whole)
    routed = held = 0
    for chip in range(4):
        cfg = make_cfg(uncut, experts_held=4, expert_offset=4 * chip)
        part = {n: ep[n][None, 4 * chip : 4 * chip + 4] for n in ("wi", "wg", "wd")}
        y, counts = latent_moe._expert_mlp(h, ep, jnp.ones((1, 24), bool), cfg, part, 0)
        total = total + (y[0] - shared)  # every chip computes the shared expert alike
        routed, held = int(counts[0]), held + int(counts[1])
    assert held == routed == 24 * 4  # every chosen row fell to exactly one chip
    assert float(jnp.max(jnp.abs(whole))) > 0.5
    assert float(jnp.max(jnp.abs(total + shared - whole))) < 1e-5
    # and the cut the benchmark makes is one such share, in the reference alike
    cut = ref._expert_mlp(h[0], {**ep, **{n: ep[n][4:12] for n in ("wi", "wg", "wd")}},
                          {**TINY}, 2048)
    cfg = make_cfg(uncut, experts_held=8, expert_offset=4)
    y, _ = latent_moe._expert_mlp(
        h, ep, jnp.ones((1, 24), bool), cfg, {n: ep[n][None, 4:12] for n in ("wi", "wg", "wd")}, 0)
    assert float(jnp.max(jnp.abs(y[0] - cut))) < 1e-5


def test_one_token_sent_to_another_expert_is_outside_the_tolerance(tiny):
    cfg, params, mine = tiny
    tokens = np.random.default_rng(0).integers(0, 256, 40)
    want = reference_logits(mine, tokens, np.arange(39, 40))
    moved = jax.tree.map(lambda w: w, params)
    bias = moved["block"]["experts"]["router_bias"]
    moved["block"]["experts"]["router_bias"] = bias.at[0, 5].add(10.0)  # expert 5 always chosen
    got, *_ = _serve_through_the_programs(cfg, moved, tokens, 40, [(0, 40, 64)], _pool(cfg))
    assert float(jnp.max(jnp.abs(got - want))) > 20 * LOGIT_TOL


# -- through the engine -----------------------------------------------------------


def _engine(cfg, params, **kw):
    kw = {"slots": SLOTS, "block_size": BS, "num_blocks": 1 + 64, "prefill_chunk": 32,
          "warmup": False, **kw}
    return ServingEngine(params, cfg, **kw).start()


def _gap(mine, prompt, served):
    """How far each served token's logit lies below the reference's best."""
    seq = list(prompt) + list(served)
    logits = reference_logits(mine, seq[:-1], np.arange(len(prompt) - 1, len(seq) - 1))
    return float(jnp.max(jnp.max(logits, axis=-1) - logits[jnp.arange(len(served)),
                                                            jnp.asarray(served)]))


@pytest.fixture(scope="module")
def served(tiny):
    cfg, params, mine = tiny
    warm = _engine(cfg, params)
    rng = np.random.default_rng(1)
    doc = rng.integers(0, 256, 100).tolist()
    first = doc + rng.integers(0, 256, 6).tolist()
    assert _gap(mine, first, warm.generate(first, 8, timeout=300)) < LOGIT_TOL
    yield warm, doc, rng, mine
    warm.stop()


def test_a_prefix_hit_through_the_latent_pool_gives_what_it_gives_cold(served):
    warm, doc, rng, mine = served
    prompt = doc + rng.integers(0, 256, 7).tolist()
    before = warm.stats()
    tokens = warm.generate(prompt, 8, timeout=300)
    after = warm.stats()
    assert after["prefix_cache_hits"] - before["prefix_cache_hits"] == 12  # 96 of 100 tokens
    assert _gap(mine, prompt, tokens) < LOGIT_TOL
    # only the 11 tokens past the hit and the 7 decoded were routed again
    assert after["moe_rows_routed"] - before["moe_rows_routed"] == (11 + 7) * 4 * 2


def test_a_copy_on_write_through_the_latent_pool_leaves_the_shared_block_alone(served):
    """A prompt that IS the cached prefix's first 96 tokens: every block hits, the
    last token is computed again into a private copy of the last block."""
    warm, doc, _, mine = served
    before = warm.stats()
    tokens = warm.generate(doc[:96], 8, timeout=300)
    after = warm.stats()
    assert after["cow_copies"] - before["cow_copies"] == 1
    assert _gap(mine, doc[:96], tokens) < LOGIT_TOL
    # and the shared blocks still serve the longer prompt
    again = doc + [7, 8, 9]
    assert _gap(mine, again, warm.generate(again, 4, timeout=300)) < LOGIT_TOL


def test_stats_carry_the_expert_counters_and_the_row_bytes(served):
    warm = served[0]
    stats = warm.stats()
    assert stats["kv_row_bytes"] == 3 * 128 * 4 == decode.kv_block_bytes(warm.cfg, BS) // BS
    assert stats["kv_pool_bytes"] == 65 * BS * stats["kv_row_bytes"]
    assert 0 < stats["moe_rows_busiest"] <= stats["moe_rows_held"] < stats["moe_rows_routed"]
    assert stats["moe_rows_routed"] % (4 * 2) == 0
    # by the rows of a call's shape: the product's calls, the rows held and the experts hit
    shapes = stats["moe_call_shapes"]
    assert str(SLOTS * 4) in shapes and all(int(rows) % 4 == 0 for rows in shapes)
    assert sum(s["rows_held"] for s in shapes.values()) == stats["moe_rows_held"]
    assert sum(s["experts_hit"] for s in shapes.values()) == stats["moe_experts_hit"]
    assert all(0 < s["experts_hit"] <= 8 * s["calls"] and s["calls"] % 2 == 0
               for s in shapes.values())
    phases = sum(v for k, v in stats.items()
                 if k.startswith("loop_") and k.endswith("_s") and k != "loop_wall_s")
    assert phases == pytest.approx(stats["loop_wall_s"], abs=1e-4)


def test_the_read_that_brings_the_expert_counts_is_a_counted_read(served):
    stats = served[0].stats()
    assert stats["device_reads"] == stats["loop_device_wait_n"] > 0
    assert 0 <= stats["device_reads_ready"] <= stats["device_reads"]
    assert 0.0 < stats["uncovered_s"] <= stats["loop_wall_s"] - stats["loop_idle_s"] + 2e-5
    laps = sum(stats[f"decode_host_{lap}_s"] for lap in ("inputs", "key", "upload", "dispatch"))
    assert 0.0 < laps <= stats["loop_decode_host_s"] + 1e-5


@pytest.mark.parametrize("stack", ["latent", "dense"])
def test_stats_count_the_keys_a_chunk_attended_against_its_tables_width(tiny, stack):
    """A table three tiles wide and a prompt of two chunks inside its first tile:
    the latent stack's chunks attend one tile each, a dense model's the whole table."""
    if stack == "latent":
        cfg, params = make_cfg(TINY, seq=W_TILES * BS), tiny[1]
    else:
        cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
                                d_ff=64, n_kv_heads=2, max_seq=W_TILES * BS, dtype=jnp.float32)
        params = init_params(jax.random.PRNGKey(0), cfg)
    engine = _engine(cfg, params, slots=2)
    try:
        assert engine.stats()["prefill_keys_table"] == engine.stats()["prefill_keys_attended"] == 0
        engine.generate(np.random.default_rng(6).integers(0, 64, 40).tolist(), 2, timeout=300)
        stats = engine.stats()
    finally:
        engine.stop()
    assert stats["prefill_keys_table"] == 2 * W_TILES * BS  # chunks of 32 and 8 rows
    attended = 2 * TILE if stack == "latent" else 2 * W_TILES * BS
    assert stats["prefill_keys_attended"] == attended <= stats["prefill_keys_table"]


@pytest.mark.parametrize("stack", ["latent", "window", "dense", "hybrid"])
def test_stats_count_the_keys_a_step_attended_against_its_tables_width(tiny, stack):
    """A table six compute blocks wide and a reply decoded inside its first:
    the latent stack's steps and the window stack's full layers attend one
    block an active lane, a dense or a hybrid model's the whole table."""
    from polyaxon_tpu.parallel import flash

    seq, kw = W_TILES * BS, {}
    if stack == "latent":
        cfg, params = make_cfg(TINY, seq=seq), tiny[1]
    elif stack == "dense":
        cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
                                d_ff=64, n_kv_heads=2, max_seq=seq, dtype=jnp.float32)
    else:
        from tests.test_serving import test_hybrid_serving, test_window_moe_serving

        other = test_window_moe_serving if stack == "window" else test_hybrid_serving
        cfg = other.make_cfg(other.TINY, seq=seq)
        kw = {"state_snapshot_every": 32, "state_snapshots": 8}
    if stack != "latent":
        params = init_params(jax.random.PRNGKey(0), cfg)
    engine = _engine(cfg, params, slots=2, **kw)
    try:
        assert engine.stats()["decode_keys_table"] == engine.stats()["decode_keys_attended"] == 0
        engine.generate(np.random.default_rng(6).integers(0, 64, 40).tolist(), 5, timeout=300)
        stats = engine.stats()
    finally:
        engine.stop()
    steps = stats["decode_keys_table"] // (W_TILES * BS)  # one active lane a step
    assert steps >= 3 and stats["decode_keys_table"] == steps * W_TILES * BS
    if stack in ("latent", "window"):
        assert stats["decode_keys_attended"] == steps * flash.STEP_BLOCK_KEYS
        assert stats["decode_keys_attended"] / stats["decode_keys_table"] < 0.2
    else:
        assert stats["decode_keys_attended"] == stats["decode_keys_table"]


def test_a_dense_model_reports_row_bytes_and_no_expert_counters():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
                            d_ff=64, n_kv_heads=2, max_seq=64, dtype=jnp.float32)
    engine = ServingEngine(init_params(jax.random.PRNGKey(0), cfg), cfg, slots=2,
                           block_size=BS, warmup=False)
    stats = engine.stats()
    assert stats["kv_row_bytes"] == 2 * 2 * 2 * 8 * 4
    assert not any(k.startswith("moe_") for k in stats)
    assert set(engine._pool) == {"k", "v"}


def test_a_warm_engine_compiles_nothing_later_and_spills_through_the_host_tier(tiny):
    """``kv_offload`` needs no refusal: ``export_block`` / ``import_block`` carry
    whatever leaves the pool addresses by block, the latent leaf among them."""
    cfg, params, mine = tiny
    engine = _engine(cfg, params, warmup=True, slots=2, kv_offload=True)
    try:
        assert engine.wait_ready(300), engine.start_error
        prompt = np.random.default_rng(4).integers(0, 256, 50).tolist()
        assert _gap(mine, prompt, engine.generate(prompt, 4, timeout=300)) < LOGIT_TOL
        assert engine.stats()["steady_state_compiles"] == 0
        pool = engine._pool
        out = decode.export_block(pool, jnp.int32(3))
        assert set(out) == {"c"} and out["c"].shape == (3, BS, 128)
        back = decode.import_block(pool, out, jnp.int32(5))
        assert bool(jnp.all(back["c"][:, 5] == pool["c"][:, 3]))
        assert set(pool) == {"c"}  # the counts ride no pool leaf
    finally:
        engine.stop()


# -- refused, by name ----------------------------------------------------------------


@pytest.mark.parametrize("option,kwargs", [
    ("spec_decode", {"spec_decode": True, "spec_k": 2, "spec_min_ngram": 2}),
    ("mesh", {"mesh": object()}),
])
def test_options_the_latent_stack_cannot_follow_are_refused_by_name(tiny, option, kwargs):
    cfg, params, _ = tiny
    with pytest.raises(LatentStackError) as err:
        ServingEngine(params, cfg, slots=2, block_size=BS, warmup=False, **kwargs)
    assert err.value.option == option and option in str(err.value)


def test_the_verify_program_refuses_latent_attention_and_kv_quantize_is_taken(tiny):
    cfg, params, _ = tiny
    with pytest.raises(LatentStackError) as err:
        decode.paged_verify_step(params, _pool(cfg), None, None, None, None, None, cfg)
    assert err.value.option == "spec_decode"
    engine = ServingEngine(params, cfg, slots=2, block_size=BS, kv_quantize="int8", warmup=False)
    assert engine._pool["c_q"].dtype == jnp.int8
    assert engine._pool["c_scale"].shape == engine._pool["c_q"].shape[:-1]  # one scale a row
    assert engine.kv_row_bytes == 3 * (128 + 4)
    # int8 rows are not the same numbers, and not another model's
    tokens = np.random.default_rng(0).integers(0, 256, 46)
    full, *_ = _serve_through_the_programs(cfg, params, tokens, 40, [(0, 40, 64)], _pool(cfg))
    int8, *_ = _serve_through_the_programs(
        cfg, params, tokens, 40, [(0, 40, 64)], _pool(cfg, "int8"))
    assert LOGIT_TOL < float(jnp.max(jnp.abs(full - int8))) < 0.5


@pytest.mark.parametrize("fields,match", [
    ({"layer_types": None}, "names its 3 layers"),
    ({"layer_types": ("dense_mlp", "full_attention", "expert_mlp")}, "unknown layer types"),
    ({"q_lora_rank": 0}, "q_lora_rank"),
    ({"rope_theta": None}, "rope_theta"),
    ({"n_routed_experts": 0}, "n_routed_experts"),
    ({"experts_held": 8, "expert_offset": 12}, r"experts \[12, 20\)"),
    ({"n_experts": 4}, "n_routed_experts"),
])
def test_a_latent_configuration_that_does_not_add_up_is_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        make_cfg(TINY, **fields)


def test_int8_weights_cover_the_attention_and_both_kinds_of_mlp(tiny):
    cfg, params, _ = tiny
    q = decode.quantize_weights(params)
    assert set(q["block"]["experts"]) == {"wi", "wg", "wd", "shared_wi", "shared_wg", "shared_wd"}
    assert set(q["block"]["dense"]) == {"wi", "wg", "wd"}
    assert q["block"]["experts"]["wi"][1].shape == (2, 8, 1, 32)  # a scale an expert and column
    assert all(q["block"][n][0].dtype == jnp.int8 for n in latent_moe._ATTN)
    served = decode.serving_params(params, cfg.scaled(dtype=jnp.bfloat16))
    assert served["block"]["wkv_b"].dtype == jnp.bfloat16
    assert served["block"]["experts"]["router"].dtype == jnp.float32  # the router chooses
    tokens = np.random.default_rng(0).integers(0, 256, 40)
    step = jax.jit(partial(decode.paged_decode_step, cfg=cfg))
    tables = jnp.asarray(np.arange(1, 1 + SLOTS * W).reshape(SLOTS, W), jnp.int32)
    args = (tables, jnp.asarray(tokens[:SLOTS], jnp.int32), jnp.zeros(SLOTS, jnp.int32),
            jnp.ones(SLOTS, bool))
    full, *_ = step(params, _pool(cfg), *args)
    int8, *_ = step(params, _pool(cfg), *args, qweights=q)
    assert LOGIT_TOL < float(jnp.max(jnp.abs(full - int8))) < 0.5
