"""A latent-attention model with a lightning indexer, group-limited routed
experts and YaRN through the paged programs and the engine: logits against the
plain reference past ``index_topk``, the selection switched off against the
same reference, the indexer's kernel and the selecting flash kernel against
the plain rule, the exact radix select, the group limit against a loop over
groups, the chip's share against the uncut layer, the counters, the int8 pool
refused, and the layer loop taking the stacked weights whole (this model's and
JoyAI-LLM-Flash's toy).

Sizes are tiny and compute is float32 on seeded random weights, so the program
and the reference (``benchmark/reference/sparse_latent_decoder.py``: float32,
matmuls at ``highest``, an explicit top-k a row, no cache) differ by float32
summation order only: 1e-6 to 4e-6 here.  ``LOGIT_TOL`` = 5e-5 absolute, as
the latent stack's own tests hold it; a query attending keys outside its
selection reads over a hundred times that (asserted below).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sparse_latent_decoder as ref
from polyaxon_tpu.models import TransformerConfig, decode, init_params, latent_moe
from polyaxon_tpu.models.latent_moe import LatentStackError
from polyaxon_tpu.models.transformer import yarn_inv_freq
from polyaxon_tpu.parallel import experts, flash
from polyaxon_tpu.serving import ServingEngine
from tests.test_serving.test_latent_window_programs_unchanged import JOYAI

LOGIT_TOL = 5e-5
TOPK = 12
#: The reference's keys.  ``n_routed_experts`` counts the experts HELD (8 of the
#: router's 16 in 4 groups, from the fifth on); YaRN over 64 original positions,
#: so that it turns the slow columns at these lengths.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "intermediate_size": 128, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "first_k_dense_replace": 1, "n_routed_experts": 8, "router_width": 16,
    "expert_offset": 4, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5, "n_group": 4, "topk_group": 2,
    "index_n_heads": 8, "index_head_dim": 16, "index_topk": TOPK,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 64,
                     "type": "yarn"},
}
BS, W, SLOTS = 8, 16, 3
SEED = 2**31 + 11


def make_cfg(z, seq=BS * W, dtype=jnp.float32, **over):
    n_dense, rs = z["first_k_dense_replace"], z["rope_scaling"]
    fields = dict(
        vocab_size=z["vocab_size"], d_model=z["hidden_size"], n_layers=z["num_hidden_layers"],
        n_heads=z["num_attention_heads"], head_dim=16, d_ff=z["intermediate_size"],
        max_seq=seq, dtype=dtype, rope_theta=float(z["rope_theta"]),
        layer_types=("dense_mlp",) * n_dense
        + ("expert_mlp",) * (z["num_hidden_layers"] - n_dense),
        n_routed_experts=z["router_width"], experts_held=z["n_routed_experts"],
        rope_yarn_factor=float(rs["factor"]),
        rope_yarn_original_max=rs["original_max_position_embeddings"],
        rope_yarn_beta_fast=rs["beta_fast"], rope_yarn_beta_slow=rs["beta_slow"],
        rope_yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        **{k: z[k] for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "expert_offset", "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts",
            "routed_scaling_factor", "n_group", "topk_group", "index_n_heads", "index_head_dim",
            "index_topk")})
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


def reference_logits(mine, tokens, rows):
    padded = np.zeros(128, np.int32)
    padded[: len(tokens)] = tokens
    at = np.zeros(32, np.int32)
    at[: len(rows)] = rows
    fn = _REFERENCE.setdefault("tiny", jax.jit(lambda p, t, r: ref.logits_at(p, t, r, TINY)))
    return fn(mine, jnp.asarray(padded), jnp.asarray(at))[: len(rows)]


def _pool(cfg):
    return decode.init_block_pool(cfg, 1 + SLOTS * W, BS)


def serve_through_the_programs(cfg, params, tokens, n_prompt, chunks, slot=1):
    """Prefill ``tokens[:n_prompt]`` in ``chunks`` [(start, n, padded)], then decode
    the rest one token a step in ``slot`` beside two inactive lanes.  Returns the
    logits (the last chunk's, then each step's) and the calls' counts summed."""
    pool = _pool(cfg)
    chunk = jax.jit(partial(decode.paged_prefill_chunk, cfg=cfg))
    step = jax.jit(partial(decode.paged_decode_step, cfg=cfg))
    table = np.zeros(W, np.int32)
    table[: -(-len(tokens) // BS)] = 1 + slot * W + np.arange(-(-len(tokens) // BS))
    out, counts = [], 0
    for start, n, padded in chunks:
        buf = np.zeros(padded, np.int32)
        buf[:n] = tokens[start : start + n]
        logits, pool, c = chunk(params, pool, jnp.asarray(table), jnp.asarray(buf),
                                jnp.int32(start), jnp.int32(n))
        counts = counts + np.asarray(c)
    out.append(logits)
    tables = np.zeros((SLOTS, W), np.int32)
    tables[slot] = table
    active = np.arange(SLOTS) == slot
    for i in range(n_prompt, len(tokens)):
        logits, pool, c = step(
            params, pool, jnp.asarray(tables),
            jnp.asarray(np.where(active, tokens[i], 0).astype(np.int32)),
            jnp.asarray(np.where(active, i, 0).astype(np.int32)), jnp.asarray(active))
        counts = counts + np.asarray(c)
        out.append(logits[slot])
    return jnp.stack(out), counts


TOKENS = np.random.default_rng(0).integers(0, 256, 60).astype(np.int32)
N_PROMPT = 40


def test_the_program_draws_the_references_weights(tiny):
    cfg, params, mine = tiny
    blk = params["block"]
    for name in ("index_wq", "index_wk", "index_ww", "wq_a", "wkv_b"):
        np.testing.assert_array_equal(np.asarray(blk[name][1:]), np.asarray(mine["experts"][name]))
    np.testing.assert_array_equal(np.asarray(blk["experts"]["wi"]), np.asarray(mine["experts"]["wi"]))
    np.testing.assert_array_equal(np.asarray(params["unembed"]), np.asarray(mine["unembed"]))
    # the indexer's draws come after every other: a model without one draws what it drew
    plain = init_params(jax.random.PRNGKey(SEED), make_cfg(TINY, index_n_heads=0))
    assert "index_wq" not in plain["block"]
    np.testing.assert_array_equal(np.asarray(plain["embed"]), np.asarray(params["embed"]))
    assert latent_moe.n_params(cfg) == sum(x.size for x in jax.tree.leaves(params))
    assert latent_moe.index_params(cfg) == 48 * 8 * 16 + 64 * 16 + 64 * 8 + 2 * 16


@pytest.mark.parametrize("chunks", [
    [(0, 16, 16), (16, 16, 16), (32, 8, 16)],
    [(0, 40, 64)],
], ids=["three-chunks", "one-chunk"])
def test_prefill_in_chunks_then_decode_agrees_with_the_references_forward(tiny, chunks):
    """Rows 39 to 59, every one past ``index_topk`` = 12: each query attends 12
    of its keys, in the chunk's masked kernel and in the step's gathered rows."""
    cfg, params, mine = tiny
    got, counts = serve_through_the_programs(cfg, params, TOKENS, N_PROMPT, chunks)
    want = reference_logits(mine, TOKENS, np.arange(N_PROMPT - 1, len(TOKENS)))
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL
    assert len(counts) == len(latent_moe.count_names(cfg)) == 5 and counts[4] == 0  # no ties


def test_the_same_program_with_its_selection_off_disagrees_with_the_reference(tiny):
    """``index_topk`` above the table: every key attended, as a model without the
    indexer would; the reference selects 12, and the logits part."""
    cfg, params, mine = tiny
    off = make_cfg(TINY, index_topk=10 * BS * W)
    got, _ = serve_through_the_programs(off, params, TOKENS, N_PROMPT, [(0, 40, 64)])
    want = reference_logits(mine, TOKENS, np.arange(N_PROMPT - 1, len(TOKENS)))
    assert float(jnp.max(jnp.abs(got - want))) > 100 * LOGIT_TOL
    # ... and before position 12 there is nothing to select: the two agree
    short = TOKENS[:11]
    early, _ = serve_through_the_programs(off, params, short, 8, [(0, 8, 16)])
    assert float(jnp.max(jnp.abs(early - reference_logits(mine, short, np.arange(7, 11))))) < LOGIT_TOL


@pytest.mark.parametrize("C,H,d,N", [(32, 4, 32, 256), (64, 8, 128, 512), (8, 64, 128, 128)])
def test_the_indexer_kernel_is_the_plain_rule(C, H, d, N):
    rng = np.random.default_rng(C + N)
    q = jnp.asarray(rng.normal(size=(C, H, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(C, H)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    got = flash.dsa_index(q, w, k, interpret=True)
    want = jnp.einsum("chk,ch->ck", jnp.maximum(jnp.einsum("chd,kd->chk", q, k), 0.0), w)
    assert got.shape == (C, N) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_the_selecting_flash_kernel_is_softmax_over_the_selected_keys():
    rng = np.random.default_rng(4)
    H, C, T, d, dv, k0 = 3, 16, 32, 16, 8, -8  # the rows stand 8 past the tile's first key
    q, k = (jnp.asarray(rng.normal(size=(H, n, d)), jnp.float32) for n in (C, T))
    v = jnp.asarray(rng.normal(size=(H, T, dv)), jnp.float32)
    scores = jnp.asarray(rng.normal(size=(C, T)), jnp.float32)
    thresholds = jnp.asarray(rng.normal(size=(C,)), jnp.float32).at[3].set(jnp.inf)
    o, lse = flash.flash_block_fwd(q, k, v, causal=True, sm_scale=0.3, q_offset=-k0,
                                   select=(scores, thresholds), interpret=True)
    keep = (jnp.arange(C)[:, None] - k0 >= jnp.arange(T)[None]) & (scores >= thresholds[:, None])
    s = jnp.where(keep, jnp.einsum("hqd,hkd->hqk", q, k) * 0.3, -jnp.inf)
    p = jnp.where(keep, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    want = jnp.einsum("hqk,hkd->hqd", p, v) / jnp.maximum(jnp.sum(p, -1), 1e-30)[..., None]
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), rtol=1e-5, atol=1e-5)
    # a row that admits no key (threshold +inf) comes back empty, as the merge wants it
    assert bool(jnp.all(o[:, 3] == 0)) and bool(jnp.all(jnp.isneginf(lse[:, 3])))


def test_the_radix_select_is_the_kth_largest_exactly():
    rng = np.random.default_rng(7)
    R, N, k = 6, 300, 20
    scores = rng.normal(size=(R, N)).astype(np.float32)
    scores[1] = -np.abs(scores[1])
    scores[1, :40] = 0.0  # the threshold falls in a run of ties, signed zeros among them
    scores[1, 40:50] = -0.0
    scores[2, 25:] = -np.inf  # a row that sees 25 keys
    scores[3] = np.abs(scores[3]) * 1e-30  # subnormal-sized, all positive
    qpos = np.array([299, 299, 24, 299, 10, 19])  # the last two see k keys or fewer
    got = np.asarray(latent_moe.select_threshold(jnp.asarray(scores), jnp.asarray(qpos), k))
    want = np.sort(scores, axis=1)[:, -k]
    for r in range(R):
        if qpos[r] + 1 > k:
            assert got[r] == want[r], r
            assert (scores[r] >= got[r]).sum() >= k
        else:
            assert got[r] == -np.inf
    assert latent_moe.selected_keys(make_cfg(TINY), 5, 10) == sum(min(t + 1, TOPK) for t in range(5, 15))
    assert latent_moe.scored_keys(5, 10) == sum(t + 1 for t in range(5, 15))


def _route_by_loop(s, bias, k, n_group, topk_group, scale):
    """The published ``Gate``, one token at a time over its groups."""
    chosen, gates = [], []
    for row, b in zip(s, s + bias):
        size = len(b) // n_group
        groups = [sorted(b[g * size : (g + 1) * size])[-2:] for g in range(n_group)]
        best = sorted(range(n_group), key=lambda g: -sum(groups[g]))[:topk_group]
        allowed = [e for e in range(len(b)) if e // size in best]
        pick = sorted(allowed, key=lambda e: -b[e])[:k]
        chosen.append(sorted(pick))
        total = sum(row[e] for e in pick)
        gates.append({e: scale * row[e] / (total + 1e-20) for e in pick})
    return chosen, gates


def test_the_group_limited_router_is_a_loop_over_groups():
    rng = np.random.default_rng(11)
    N, D, E = 40, 24, 32
    h = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(D, E)) * D**-0.5, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(E,)) * 0.02, jnp.float32)
    chosen, gates = experts.route(h, router, bias, 6, 2.5, 8, 3)
    s = np.asarray(jax.nn.sigmoid(h @ router))
    want_chosen, want_gates = _route_by_loop(s, np.asarray(bias), 6, 8, 3, 2.5)
    for t in range(N):
        assert sorted(np.asarray(chosen[t]).tolist()) == want_chosen[t]
        for e, g in zip(np.asarray(chosen[t]), np.asarray(gates[t])):
            assert g == pytest.approx(want_gates[t][int(e)], rel=1e-5)
    # the limit binds: some token's top 6 over all experts falls outside its groups
    free, _ = experts.route(h, router, bias, 6, 2.5)
    assert any(set(np.asarray(free[t]).tolist()) != set(want_chosen[t]) for t in range(N))


def test_one_group_routes_as_before_bit_for_bit():
    rng = np.random.default_rng(12)
    h = jnp.asarray(rng.normal(size=(30, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(64,)) * 0.02, jnp.float32)
    s = jax.nn.sigmoid(experts._router_logits(h, router))
    _, before = jax.lax.top_k(s + bias, 8)
    want = experts._gates(s, before, 2.5)
    got = experts.route(h, router, bias, 8, 2.5, 1, 1)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """The router's 16 experts in 4 groups over four chips, 4 each: the four
    routed parts plus the shared expert counted once are the reference's uncut
    layer, under the group limit."""
    uncut = {**TINY, "n_routed_experts": 16, "router_width": 16, "expert_offset": 0}
    mine = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), ref.init_params(SEED, uncut))
    lp = jax.tree.map(lambda w: w[0], mine["experts"])
    h = jnp.asarray(np.random.default_rng(5).normal(size=(1, 24, 64)), jnp.float32)
    whole = ref._expert_mlp(h[0], lp, uncut, 2048)
    shared = ref._gated(h[0], lp["shared_wi"], lp["shared_wg"], lp["shared_wd"])
    total = jnp.zeros_like(whole)
    routed = held = 0
    for chip in range(4):
        cfg = make_cfg(uncut, experts_held=4, expert_offset=4 * chip)
        part = {n: lp[n][None, 4 * chip : 4 * chip + 4] for n in ("wi", "wg", "wd")}
        y, counts = latent_moe._expert_mlp(h, lp, jnp.ones((1, 24), bool), cfg, part, 0)
        total = total + (y[0] - shared)  # every chip computes the shared expert alike
        routed, held = int(counts[0]), held + int(counts[1])
    assert held == routed == 24 * 4  # every chosen row fell to exactly one chip
    assert float(jnp.max(jnp.abs(whole))) > 0.5
    assert float(jnp.max(jnp.abs(total + shared - whole))) < 1e-5


def test_yarn_on_latent_attention_is_the_references():
    """At the published rope_scaling: the frequencies, and the score factor
    (0.1 ln 40 + 1)^2 = 1.87385."""
    published = {**TINY, "qk_rope_head_dim": 64, "rope_scaling": {
        **TINY["rope_scaling"], "original_max_position_embeddings": 4096}}
    cfg = make_cfg(published, qk_rope_head_dim=64, rope_yarn_original_max=4096, index_head_dim=64)
    freqs, factor = ref.rotary(published)
    np.testing.assert_array_equal(yarn_inv_freq(cfg, 64), freqs)
    assert factor == pytest.approx(1.87385, abs=1e-5)
    assert latent_moe.score_scale(cfg) == pytest.approx((16 + 64) ** -0.5 * factor, rel=1e-12)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert freqs[0] == pytest.approx(plain[0]) and freqs[-1] == pytest.approx(plain[-1] / 40)
    # without a factor the latent stack's rotation and scale are what they were
    none = make_cfg(TINY, rope_yarn_factor=0.0, rope_yarn_mscale_all_dim=0.0)
    assert latent_moe.score_scale(none) == (16 + 8) ** -0.5


def test_check_config_holds_the_indexer_and_the_groups():
    with pytest.raises(ValueError, match="rotary columns"):
        make_cfg(TINY, index_head_dim=4)
    with pytest.raises(ValueError, match="n_group"):
        make_cfg(TINY, n_group=5)
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        make_cfg(TINY, topk_group=1, n_group=8)  # one group of 2 cannot hold 4 choices


# -- through the engine -----------------------------------------------------------


def _engine(cfg, params, **kw):
    kw = {"slots": SLOTS, "block_size": BS, "num_blocks": 1 + 64, "prefill_chunk": 32,
          "warmup": False, **kw}
    return ServingEngine(params, cfg, **kw).start()


def test_the_engine_serves_the_references_tokens_and_counts_what_it_selected(tiny):
    cfg, params, mine = tiny
    engine = _engine(cfg, params)
    try:
        plan = [(TOKENS[:40].tolist(), 8), (TOKENS[40:45].tolist(), 10)]
        for prompt, new in plan:
            served = engine.generate(prompt, new, timeout=300)
            seq = prompt + list(served)
            logits = reference_logits(mine, seq[:-1], np.arange(len(prompt) - 1, len(seq) - 1))
            gap = jnp.max(logits, axis=-1) - logits[jnp.arange(new), jnp.asarray(served)]
            assert float(jnp.max(gap)) < LOGIT_TOL
        stats = engine.stats()
    finally:
        engine.stop()
    L = cfg.n_layers
    # a decode step feeds the token at positions len(prompt) .. len(prompt) + new - 2
    steps = [p for prompt, new in plan for p in range(len(prompt), len(prompt) + new - 1)]
    assert stats["decode_keys_attended"] == sum(min(p + 1, TOPK) for p in steps) == 7 * 12 + 87
    chunks = [(0, 32), (32, 8), (0, 5)]
    rows = [t for a, n in chunks for t in range(a, a + n)] + steps
    assert stats["dsa_selected_pairs"] == L * sum(min(t + 1, TOPK) for t in rows)
    assert stats["dsa_index_pairs"] == L * sum(t + 1 for t in rows)
    shapes = stats["dsa_index_call_shapes"]
    # the kernel runs once a layer a key tile of a chunk's walk, as the trace
    # counts it: every tile up to the one that holds the chunk's live end
    tile = decode._tile_blocks(engine._table_width, BS) * BS
    assert sum(s["calls"] for s in shapes.values()) == L * sum(
        -(-(a + n) // tile) for a, n in chunks)
    assert sum(s["keys"] for s in shapes.values()) == L * tile * sum(
        -(-(a + n) // tile) for a, n in chunks)
    assert sum(s["pairs"] for s in shapes.values()) == L * sum(
        t + 1 for a, n in chunks for t in range(a, a + n))
    assert stats["dsa_tie_keys"] == 0
    assert stats["kv_row_bytes"] == L * (128 + 16) * 4  # c at whole lane tiles, ik beside it
    assert set(engine._pool) == {"c", "ik"}


def test_a_walk_of_several_tiles_is_counted_a_kernel_call_a_tile(tiny, monkeypatch):
    """Key tiles of 32 (four a table): the indexer's kernel runs once a layer a
    tile a chunk walks, and ``dsa_index_call_shapes`` counts those calls, the
    unit in which the trace counts them; the logits stay the reference's."""
    cfg, params, mine = tiny
    monkeypatch.setattr(decode, "TILE_KEYS", 32)
    engine = _engine(cfg, params)
    try:
        prompt, new = TOKENS[:40].tolist(), 6
        served = engine.generate(prompt, new, timeout=300)
        stats = engine.stats()
    finally:
        engine.stop()
    seq = prompt + list(served)
    logits = reference_logits(mine, seq[:-1], np.arange(len(prompt) - 1, len(seq) - 1))
    gap = jnp.max(logits, axis=-1) - logits[jnp.arange(new), jnp.asarray(served)]
    assert float(jnp.max(gap)) < LOGIT_TOL
    L = cfg.n_layers
    # chunks (0, 32) and (32, 8): the first walks one tile, the second two
    shapes = stats["dsa_index_call_shapes"].values()
    assert sum(s["calls"] for s in shapes) == L * 3
    assert sum(s["keys"] for s in shapes) == L * 32 * 3
    assert sum(s["pairs"] for s in shapes) == L * sum(t + 1 for t in range(40))


def test_an_int8_pool_is_refused_by_name(tiny):
    cfg, params, _ = tiny
    with pytest.raises(LatentStackError) as err:
        ServingEngine(params, cfg, slots=2, block_size=BS, kv_quantize="int8", warmup=False)
    assert err.value.option == "kv_quantize" and "ik" in str(err.value)


def _int8_pairs(w, axes):
    """``decode.quantize_weights``'s pair for ``w``, as shapes."""
    scale = tuple(1 if i in axes else d for i, d in enumerate(w.shape))
    return jax.ShapeDtypeStruct(w.shape, jnp.int8), jax.ShapeDtypeStruct(scale, jnp.float32)


@pytest.mark.parametrize("program", ["chunk", "step", "step-int8"])
@pytest.mark.parametrize("model", ["tiny", "joyai-toy"])
def test_every_stacked_block_weight_enters_the_layer_loop_whole(model, program):
    """Each scan of the layer loop closes over the block's stacks as they are
    and reads its layer inside; what it scans over is the layer indices alone.
    A stack cut to a run of the pattern before the loop is a copy of it written
    in every call (``wo`` of DeepSeek-V3.2's four expert layers is 939.5 MB).  Both models run a dense layer, then a run of two expert layers."""
    cfg = make_cfg(TINY) if model == "tiny" else JOYAI[0]
    assert latent_moe.runs(cfg) == [("dense_mlp", 1), ("expert_mlp", 2)]
    i32, sds = jnp.int32, jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    qweights = latent_moe.quantize_weights(params, _int8_pairs) if program == "step-int8" else None
    pool = jax.eval_shape(lambda: decode.init_block_pool(cfg, 1 + SLOTS * W, BS))
    if program == "chunk":
        def fn(params, pool, table, tokens, start, length, qweights):
            return decode.paged_prefill_chunk(params, pool, table, tokens, start, length, cfg)

        args = (sds((W,), i32), sds((16,), i32), sds((), i32), sds((), i32))
    else:
        def fn(params, pool, tables, tokens, pos, active, qweights):
            return decode.paged_decode_step(params, pool, tables, tokens, pos, active, cfg,
                                            qweights=qweights)

        lanes = sds((SLOTS,), i32)
        args = (sds((SLOTS, W), i32), lanes, lanes, sds((SLOTS,), jnp.bool_))
    inputs = (params, pool, *args, qweights)
    closed = jax.make_jaxpr(fn)(*inputs)
    paths = [path for path, _ in jax.tree_util.tree_flatten_with_path(inputs)[0]]
    block = {id(v) for v, path in zip(closed.jaxpr.invars, paths)
             if path[0].idx in (0, len(inputs) - 1) and path[1].key == "block"}
    assert len(block) == len(jax.tree.leaves(params["block"])) + len(
        jax.tree.leaves(qweights["block"]) if qweights else ())
    scans = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 2
    for eqn in scans:  # the layer indices, global and within the kind
        xs = eqn.invars[eqn.params["num_consts"] + eqn.params["num_carry"]:]
        assert [(v.aval.ndim, v.aval.dtype) for v in xs] == [(1, jnp.int32)] * 2, xs
    for eqn in closed.jaxpr.eqns:
        read = [v for v in eqn.invars if id(v) in block]
        if read:
            assert eqn.primitive.name == "scan", (eqn.primitive, [v.aval for v in read])
            assert {id(v) for v in read} <= {id(v) for v in eqn.invars[: eqn.params["num_consts"]]}
