"""A model of window and full attention layers with routed experts through the
paged programs and the engine: logits against the plain reference through pool,
rings and snapshots, the rotary forms, the softmax router, the chip's share
against the uncut layer, the counters, and the options refused.

Sizes are tiny and compute is float32 on seeded random weights, so the program
and the reference (``benchmark/reference/window_moe_decoder.py``: float32,
matmuls at ``highest``, whole sequences under explicit masks, no ring, no
cache) differ by float32 summation order only.  ``LOGIT_TOL`` = 5e-5 absolute
on logits of magnitude 3: over ten times what the two read apart here (2e-6 to
4e-6), and far under what one position too many in a window reads (asserted
below).
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import window_moe_decoder as ref
from polyaxon_tpu.models import TransformerConfig, decode, init_params, latent_moe, window_moe
from polyaxon_tpu.models.transformer import forward, rotate
from polyaxon_tpu.models.window_moe import FULL, WINDOW, WindowStackError
from polyaxon_tpu.parallel import experts
from polyaxon_tpu.serving import ServingEngine

LOGIT_TOL = 5e-5
WIN = 16
#: The reference's keys.  ``num_experts`` counts the experts HELD (8 of the
#: router's 16, from the fifth on): the cut the benchmark's configuration makes.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128, "rms_norm_eps": 1e-6,
    "layer_types": [FULL, WINDOW, WINDOW, WINDOW, FULL],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4], "gating": "per-head",
    "sliding_window": WIN,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 512, "beta_slow": 1, "beta_fast": 32,
            "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
    },
    "num_experts": 8, "router_width": 16, "expert_offset": 4, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "moe_routed_scaling_factor": 2.5,
}
BS, W, SLOTS = 8, 16, 3
SEED = 2**31 + 7


def make_cfg(z, seq=BS * W, dtype=jnp.float32, **over):
    full, win = (z["rope_parameters"][k] for k in (FULL, WINDOW))
    heads = dict(zip(z["layer_types"], z["num_attention_heads_per_layer"]))
    fields = dict(
        vocab_size=z["vocab_size"], d_model=z["hidden_size"], n_layers=z["num_hidden_layers"],
        n_heads=heads[FULL], sliding_n_heads=heads.get(WINDOW, 0),
        n_kv_heads=z["num_key_value_heads"], head_dim=z["head_dim"],
        d_ff=z["intermediate_size"], max_seq=seq, dtype=dtype,
        layer_types=tuple(z["layer_types"]), mlp_layer_types=tuple(z["mlp_layer_types"]),
        sliding_window=z["sliding_window"], head_gate=True,
        rope_theta=float(full["rope_theta"]), partial_rotary_factor=full["partial_rotary_factor"],
        rope_yarn_factor=float(full["factor"]),
        rope_yarn_original_max=full["original_max_position_embeddings"],
        rope_yarn_beta_fast=full["beta_fast"], rope_yarn_beta_slow=full["beta_slow"],
        rope_attention_factor=full["attention_factor"], sliding_rope_theta=float(win["rope_theta"]),
        n_routed_experts=z["router_width"], experts_held=z["num_experts"],
        expert_offset=z["expert_offset"], num_experts_per_tok=z["num_experts_per_tok"],
        moe_intermediate_size=z["moe_intermediate_size"], n_shared_experts=1,
        routed_scaling_factor=z["moe_routed_scaling_factor"])
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


def reference_logits(mine, tokens, rows, cfg=TINY):
    padded = np.zeros(256, np.int32)
    padded[: len(tokens)] = tokens
    at = np.zeros(64, np.int32)
    at[: len(rows)] = rows
    fn = _REFERENCE.setdefault(cfg["sliding_window"], jax.jit(
        lambda p, t, r: ref.logits_at(p, t, r, cfg)))
    return fn(mine, jnp.asarray(padded), jnp.asarray(at))[: len(rows)]


def _pool(cfg, kv_dtype=None, stale=True):
    """Blocks and rings; the rings full of another sequence's rows, as a slot's are."""
    pool = decode.init_block_pool(cfg, 1 + SLOTS * W, BS, kv_dtype=kv_dtype)
    rings = window_moe.init_rec_state(cfg, SLOTS, kv_dtype)
    if stale:
        rng = np.random.default_rng(9)
        rings = {n: jnp.asarray(
            rng.integers(-127, 128, x.shape) if x.dtype == jnp.int8 else rng.normal(size=x.shape),
            x.dtype) for n, x in rings.items()}
    return {**pool, **rings}


def _serve_through_the_programs(cfg, params, tokens, n_prompt, chunks, pool, slot=1):
    """Prefill ``tokens[:n_prompt]`` in ``chunks`` [(start, n, padded)], then decode
    the rest one token a step in ``slot`` beside two inactive lanes.  Returns the
    logits, the pool and what the calls' expert layers routed in all."""
    chunk, step = _PROGRAMS.setdefault(cfg, (
        jax.jit(lambda p, pool, t, tk, s, n, slot: decode.paged_prefill_chunk(
            p, pool, t, tk, s, n, cfg, slot=slot)),
        jax.jit(partial(decode.paged_decode_step, cfg=cfg))))
    table = np.zeros(W, np.int32)
    table[: -(-len(tokens) // BS)] = 1 + slot * W + np.arange(-(-len(tokens) // BS))
    out, routed = [], np.zeros(4, np.int64)
    for start, n, padded in chunks:
        buf = np.zeros(padded, np.int32)
        buf[:n] = tokens[start : start + n]
        logits, pool, counts = chunk(params, pool, jnp.asarray(table), jnp.asarray(buf),
                                     jnp.int32(start), jnp.int32(n), jnp.int32(slot))
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
    assert cfg.stack == "window" and cfg.n_kv_layers == 2 and cfg.pool_kv_heads == 8
    assert window_moe.runs(cfg) == [
        (FULL, "dense", 1), (WINDOW, "sparse", 3), (FULL, "sparse", 1)]


#: 77 prompt tokens (nearly five windows) and 6 decoded, however the prompt is cut.
CHUNK_PLANS = {
    "whole": [(0, 77, 128)],
    "window-multiples": [(0, 32, 32), (32, 32, 32), (64, 13, 16)],
    "off-every-edge": [(0, 27, 32), (27, 32, 32), (59, 18, 32)],  # no multiple of 16 or of a block
    "short-after-a-hit": [(0, 64, 64), (64, 8, 8), (72, 5, 8)],   # rows fewer than the window
    "first-chunk-under-a-window": [(0, 11, 16), (11, 66, 128)],   # the ring not yet filled
}


@pytest.mark.parametrize("plan", list(CHUNK_PLANS))
def test_prefill_in_chunks_then_decode_agrees_with_the_references_forward(tiny, plan):
    """Through the pool (full layers), the rings (window layers, full of another
    sequence's rows before) and six decode steps that wrap the rings."""
    cfg, params, mine = tiny
    tokens = np.random.default_rng(0).integers(0, 256, 77 + 6)
    want = reference_logits(mine, tokens, np.arange(76, 83))
    got, pool, (routed, held, busiest, hit) = _serve_through_the_programs(
        cfg, params, tokens, 77, CHUNK_PLANS[plan], _pool(cfg))
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL
    # pad rows and idle lanes routed nowhere: 83 tokens x 4 choices x 4 expert layers
    assert routed == 83 * 4 * 4 and 0 < busiest <= held < routed and hit > 0
    # only the two full layers keep blocks; a ring row holds position p at p mod 16
    assert pool["k"].shape == (2, 1 + SLOTS * W, BS, 8, 16)
    assert pool["win_k"].shape == (3, SLOTS, WIN, 2, 16)


def test_a_window_one_position_wider_is_outside_the_tolerance(tiny):
    cfg, params, _ = tiny
    wider = {**TINY, "sliding_window": WIN + 1}
    tokens = np.random.default_rng(0).integers(0, 256, 83)
    want = reference_logits(ref.init_params(SEED, wider), tokens, np.arange(76, 83), wider)
    got, *_ = _serve_through_the_programs(
        cfg, params, tokens, 77, CHUNK_PLANS["window-multiples"], _pool(cfg))
    assert float(jnp.max(jnp.abs(got - want))) > 20 * LOGIT_TOL


def test_decoding_far_past_the_window_wraps_the_rings(tiny):
    cfg, params, mine = tiny
    tokens = np.random.default_rng(4).integers(0, 256, 20 + 50)
    want = reference_logits(mine, tokens, np.arange(19, 70))
    got, *_ = _serve_through_the_programs(cfg, params, tokens, 20, [(0, 20, 32)], _pool(cfg))
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL


def test_the_int8_pool_and_rings_stay_close_and_quantise_once(tiny):
    cfg, params, mine = tiny
    tokens = np.random.default_rng(0).integers(0, 256, 83)
    want = reference_logits(mine, tokens, np.arange(76, 83))
    got, pool, _ = _serve_through_the_programs(
        cfg, params, tokens, 77, CHUNK_PLANS["off-every-edge"], _pool(cfg, "int8"))
    gap = float(jnp.max(jnp.abs(got - want)))
    assert LOGIT_TOL < gap < 0.1
    assert pool["win_k_q"].dtype == jnp.int8 and pool["win_k_scale"].shape == (3, SLOTS, WIN, 2)
    assert window_moe.rec_row_bytes(cfg, "int8") == 2 * 3 * WIN * 2 * (16 + 4)
    assert window_moe.rec_row_bytes(cfg) == 2 * 3 * WIN * 2 * 16 * 4


def test_a_chunk_of_length_zero_and_an_idle_lane_leave_rings_and_blocks_alone(tiny):
    cfg, params, _ = tiny
    pool = _pool(cfg)
    chunk = jax.jit(lambda pool: decode.paged_prefill_chunk(
        params, pool, jnp.zeros(W, jnp.int32), jnp.zeros(16, jnp.int32), jnp.int32(0),
        jnp.int32(0), cfg, slot=jnp.int32(0)))
    step = jax.jit(lambda pool: decode.paged_decode_step(
        params, pool, jnp.zeros((SLOTS, W), jnp.int32), jnp.zeros(SLOTS, jnp.int32),
        jnp.zeros(SLOTS, jnp.int32), jnp.zeros(SLOTS, bool), cfg))
    for fn in (chunk, step):
        logits, after, counts = fn(pool)
        assert bool(jnp.all(jnp.isfinite(logits))) and not np.asarray(counts).any()
        for name in ("win_k", "win_v"):
            assert bool(jnp.all(after[name] == pool[name]))
        assert bool(jnp.all(after["k"][:, 1:] == pool["k"][:, 1:]))  # block 0 is the trash lane


# -- rotary, router, share --------------------------------------------------------

PUBLISHED_ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
           "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
           "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
    WINDOW: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
}


@pytest.mark.parametrize("size", ["published", "tiny"])
def test_yarn_frequencies_and_the_partial_rotation_are_the_references(size):
    groups, d = (PUBLISHED_ROPE, 128) if size == "published" else (TINY["rope_parameters"], 16)
    z = {**TINY, "rope_parameters": groups, "head_dim": d}
    cfg = make_cfg(z)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(40, 3, d)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 16384, 40), jnp.int32)
    for kind in (FULL, WINDOW):
        inv, factor = window_moe.rope_table(cfg, kind)
        want_inv, want_factor = ref.rotary_frequencies(groups[kind], d)
        assert inv.shape == (int(d * groups[kind]["partial_rotary_factor"]) // 2,)
        np.testing.assert_allclose(inv, want_inv, rtol=1e-6)
        assert factor == want_factor
        got = rotate(x[None], pos[None], (inv, factor))[0]
        want = ref._rope(x, pos, want_inv, want_factor)
        # angles of up to 16,384 radians in float32: 1e-3 of rounding between the two tables
        assert float(jnp.max(jnp.abs(got - want))) < 5e-3
        rot = 2 * inv.shape[0]
        assert bool(jnp.all(got[..., rot:] == x[..., rot:]))  # the rest passes
    inv, factor = window_moe.rope_table(cfg, FULL)
    plain = 500000.0 ** (-np.arange(0, 2 * len(inv), 2) / (2 * len(inv)))
    # the fastest dimension turns as it did, the slowest 128 times slower, the ramp between
    assert inv[0] == pytest.approx(plain[0]) and inv[-1] == pytest.approx(plain[-1] / 128)
    assert ((inv <= plain * (1 + 1e-6)) & (inv >= plain / 128 * (1 - 1e-6))).all()
    assert factor == 1.4852030263919618
    if size == "published":  # correction dims 8 and 19 of 32: transformers' own, truncated
        assert (inv[:9] == plain[:9].astype(np.float32)).all() and inv[19] == pytest.approx(plain[19] / 128)
        assert plain[9] / 128 < inv[9] < plain[9]


def test_the_softmax_router_is_the_references_on_near_ties():
    """Columns that differ by a few float32 roundings: the program's choice and
    gates are the reference's (both float32 at ``highest``)."""
    rng = np.random.default_rng(3)
    N, D, E, k = 64, 64, 16, 3  # pairs of twin columns: the third choice splits a pair
    h = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    base = rng.normal(size=(D, E // 2)) * D**-0.5
    router = jnp.asarray(np.concatenate(
        [base, base * (1 + 1e-7 * rng.normal(size=base.shape))], axis=1), jnp.float32)
    chosen, gates = experts.route_softmax(h, router, k, 2.5)
    z = {**TINY, "router_width": E, "num_experts": E, "expert_offset": 0, "num_experts_per_tok": k}
    want = ref.gates(h, {"router": router}, z)
    s = jax.nn.softmax(h @ router, axis=-1)
    near = jnp.sort(s, axis=-1)
    assert float(jnp.min(near[:, -k] - near[:, -k - 1])) < 1e-6  # there ARE near-ties at the edge
    got = jnp.zeros((N, E)).at[jnp.arange(N)[:, None], chosen].set(gates)
    assert bool(jnp.all((got > 0) == (want > 0)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    assert float(jnp.max(jnp.abs(jnp.sum(gates, axis=-1) - 2.5))) < 1e-5


@pytest.mark.parametrize("n", [2, 4, 8])
def test_the_shares_of_n_chips_add_up_to_the_uncut_layer(n):
    """The router's 16 experts over ``n`` chips: the routed parts plus the
    shared expert counted once are the reference's uncut layer."""
    uncut = {**TINY, "num_experts": 16, "router_width": 16, "expert_offset": 0}
    mine = ref.init_params(SEED, uncut)
    ep = jax.tree.map(lambda w: w[0], mine["block"]["experts"])
    h = jnp.asarray(np.random.default_rng(5).normal(size=(1, 24, 64)), jnp.float32)
    whole = ref._expert_mlp(h[0], ep, uncut, 2048)
    shared = ref._gated(h[0], ep["shared_wi"], ep["shared_wg"], ep["shared_wd"])
    total = jnp.zeros_like(whole)
    per, held = 16 // n, 0
    for chip in range(n):
        cfg = make_cfg(uncut, experts_held=per, expert_offset=per * chip)
        part = {w: ep[w][None, per * chip : per * (chip + 1)] for w in ("wi", "wg", "wd")}
        y, counts = latent_moe._expert_mlp(h, ep, jnp.ones((1, 24), bool), cfg, part, 0)
        total = total + (y[0] - shared)  # every chip computes the shared expert alike
        held += int(counts[1])
    assert held == 24 * 4  # every chosen row fell to exactly one chip
    assert float(jnp.max(jnp.abs(whole))) > 0.5
    assert float(jnp.max(jnp.abs(total + shared - whole))) < 1e-5


def test_the_window_pairs_count_is_the_masks():
    cfg = make_cfg(TINY)
    for start, length in ((0, 5), (0, 16), (0, 40), (7, 9), (7, 30), (15, 1), (16, 32), (100, 8), (3, 0)):
        want = sum(min(i + 1, WIN) for i in range(start, start + length))
        assert window_moe.chunk_window_pairs(cfg, start, length) == want
    assert decode.chunk_keys_attended(cfg, 40, W, BS) == W * BS  # a table narrower than a tile
    assert decode.chunk_keys_attended(cfg, 1030, 512, BS) == 2 * decode.TILE_KEYS


def test_the_chunk_program_forms_no_scores_and_compiles_once(tiny, monkeypatch):
    """Lowered for the TPU the two kernels are named device operations and no
    ``heads x C x keys`` array is in the text (window: 6 x C x (16 + C); full:
    4 x C x a tile); the trip count and the ring's turn are data: one
    compilation serves every ``start``."""
    from jax import export

    from polyaxon_tpu.parallel import flash

    cfg, params, _ = tiny
    C = 32
    def program(p, pool, t, tk, s, n, slot):
        return decode.paged_prefill_chunk(p, pool, t, tk, s, n, cfg, slot=slot)

    fn = jax.jit(program)
    wide = 24  # blocks: 192 keys, a number no other axis has (the kernel's lse rides 128 lanes)
    pool = {**decode.init_block_pool(cfg, 1 + wide, BS), **window_moe.init_rec_state(cfg, SLOTS)}
    args = (params, pool, jnp.asarray(1 + np.arange(wide), jnp.int32), jnp.zeros(C, jnp.int32))
    out = [fn(*args, jnp.int32(start), jnp.int32(C), jnp.int32(2))[0] for start in (0, 40)]
    assert fn._cache_size() == 1 and float(jnp.max(jnp.abs(out[0] - out[1]))) > 0
    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)
    # a function of its own: jit would hand back the trace made under the interpreter
    text = export.export(jax.jit(lambda *a: program(*a)), platforms=["tpu"])(
        *args, jnp.int32(0), jnp.int32(C), jnp.int32(2)).mlir_module()
    for name in (f"window_chunk_{C}", "full_chunk_tile"):
        assert f'kernel_name = "{name}"' in text
    found = set(re.findall(r"tensor<([0-9x]+)x[a-z]", text))
    for scores in (f"6x{C}x{WIN + C}", f"4x{C}x{wide * BS}", f"2x3x{C}x{WIN + C}",
                   f"2x2x{C}x{wide * BS}"):
        assert scores not in found, scores


def test_the_step_program_forms_nothing_of_the_tables_width_and_compiles_once(tiny, monkeypatch):
    """The full layers' step through the kernel: see
    ``test_latent_moe_serving._step_program_checks``."""
    from tests.test_serving.test_latent_moe_serving import _step_program_checks

    cfg, params, _ = tiny

    def pool_of(kv_dtype, wide):
        return {**decode.init_block_pool(cfg, 1 + wide, BS, kv_dtype),
                **window_moe.init_rec_state(cfg, SLOTS, kv_dtype)}

    _step_program_checks(cfg, params, pool_of, monkeypatch)


# -- refusals ---------------------------------------------------------------------


@pytest.mark.parametrize("option,kw", [
    ("spec_decode", {"spec_decode": True}),
    ("kv_offload", {"kv_offload": True}),
    ("kv_persist_dir", {"kv_persist_dir": "/nonexistent/kv", "kv_persist_sig": "x"}),
])
def test_what_the_stack_cannot_follow_is_refused_by_name_where_the_engine_is_built(tiny, option, kw):
    cfg, params, _ = tiny
    with pytest.raises(WindowStackError) as err:
        ServingEngine(params, cfg, slots=2, block_size=BS, num_blocks=17, **kw)
    assert err.value.option == option and ".py" in str(err.value)  # names the module to change


def test_the_training_forward_the_verify_step_and_a_bad_config_are_refused(tiny):
    cfg, params, _ = tiny
    with pytest.raises(WindowStackError) as err:
        forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    assert err.value.option == "forward"
    with pytest.raises(WindowStackError) as err:
        decode.paged_verify_step(params, {}, jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 2), jnp.int32),
                                 jnp.zeros(1, jnp.int32), jnp.ones(1, jnp.int32), jnp.ones(1, bool), cfg)
    assert err.value.option == "spec_decode"
    assert set(window_moe.REFUSED) == {"spec_decode", "kv_offload", "kv_persist_dir", "mesh", "forward"}
    for bad, match in (
        ({"sliding_window": 12}, "multiple of 8"),
        ({"mlp_layer_types": ("dense",) * 4}, "names its 5 layers"),
        ({"layer_types": ("linear_attention",) * 5}, "unknown layer_types"),
        ({"sliding_n_heads": 5}, "divisible"),
        ({"experts_held": 14}, "not among the router"),
        ({"partial_rotary_factor": 0.2}, "even number"),
    ):
        with pytest.raises(ValueError, match=match):
            make_cfg(TINY, **bad)


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
    warm = _engine(make_cfg(TINY, seq=256), params, warmup=True)
    rng = np.random.default_rng(1)
    doc = rng.integers(0, 256, 100).tolist()
    first = doc + rng.integers(0, 256, 6).tolist()
    assert _gap(mine, first, warm.generate(first, 8, timeout=300)) < LOGIT_TOL
    yield warm, doc, rng, mine
    warm.stop()


def test_a_prefix_hit_restored_from_a_snapshot_gives_what_a_cold_prefill_gives(served, tiny):
    warm, doc, rng, mine = served
    cfg, params, _ = tiny
    prompt = doc + rng.integers(0, 256, 7).tolist()
    before = warm.stats()
    tokens = warm.generate(prompt, 8, timeout=300)
    after = warm.stats()
    # 96 of 100 shared tokens: twelve blocks, and the rings as they stood after 96
    assert after["prefix_cache_hits"] - before["prefix_cache_hits"] == 12
    assert after["state_restores"] - before["state_restores"] == 1
    assert _gap(mine, prompt, tokens) < LOGIT_TOL
    cold = _engine(make_cfg(TINY, seq=256), params, prefix_cache=False)
    try:
        assert cold.generate(prompt, 8, timeout=300) == tokens
    finally:
        cold.stop()
    # only the 11 tokens past the hit and the 7 decoded were routed again, and only the
    # 11 went through the window kernel: 3 layers x 11 queries x 16 keys
    assert after["moe_rows_routed"] - before["moe_rows_routed"] == (11 + 7) * 4 * 4
    assert after["window_pairs"] - before["window_pairs"] == 3 * 11 * WIN
    assert after["window_chunk_calls"] - before["window_chunk_calls"] == 3
    # ... in a chunk of 16 rows' shape, the shape the kernel's name ends in
    grown = {k: after["window_call_shapes"]["16"][k] - before["window_call_shapes"]["16"][k]
             for k in ("calls", "pairs")}
    assert grown == {"calls": 3, "pairs": 3 * 11 * WIN}
    assert sum(v["pairs"] for v in after["window_call_shapes"].values()) == after["window_pairs"]


def test_the_stats_carry_the_window_stacks_counters(served):
    warm, *_ = served
    s = warm.stats()
    assert s["kv_row_bytes"] == 2 * 2 * 8 * 16 * 4  # the two full layers only, 8 heads a row
    assert s["state_snapshots"] >= 3 and s["state_snapshot_bytes"] == s["state_store_used"] * (
        2 * 3 * WIN * 2 * 16 * 4)
    assert s["prefill_keys_attended"] == s["prefill_keys_table"] > 0  # one tile spans the table
    assert s["steady_state_compiles"] == 0
    for name in experts.COUNT_NAMES:
        assert s[name] > 0
    assert s["moe_call_shapes"] and s["window_pairs"] > 0
    assert s["loop_state_snapshot_n"] > 0 and s["loop_state_restore_n"] > 0


def test_a_hit_with_no_snapshot_on_its_chain_gives_its_tokens_back(tiny):
    """PR 29's rule, unchanged: the KV match is cut back to the newest boundary
    with a snapshot; without one the prompt starts from 0 and ``prefix_floor_tokens``
    counts what was given back."""
    cfg, params, mine = tiny
    eng = _engine(make_cfg(TINY, seq=256), params, state_snapshot_every=64)
    try:
        rng = np.random.default_rng(2)
        doc = rng.integers(0, 256, 60).tolist()  # no boundary of 64 inside: no snapshot
        eng.generate(doc + [1, 2, 3], 4, timeout=300)
        before = eng.stats()
        prompt = doc + [4, 5, 6, 7]
        tokens = eng.generate(prompt, 6, timeout=300)
        after = eng.stats()
        assert after["prefix_floor_tokens"] - before["prefix_floor_tokens"] == 56
        assert after["prefix_cache_hits"] == before["prefix_cache_hits"]
        assert after["state_restores"] == before["state_restores"]
        assert _gap(mine, prompt, tokens) < LOGIT_TOL
    finally:
        eng.stop()


def test_an_int8_engine_serves_with_int8_rings(tiny):
    cfg, params, mine = tiny
    eng = _engine(make_cfg(TINY, seq=256), params, kv_quantize="int8")
    try:
        rng = np.random.default_rng(6)
        doc = rng.integers(0, 256, 70).tolist()
        for tail in ([9, 8, 7], [6, 5, 4, 3]):
            prompt = doc + tail
            assert _gap(mine, prompt, eng.generate(prompt, 6, timeout=300)) < 0.5
        s = eng.stats()
        assert s["kv_dtype"] == "int8" and s["state_restores"] == 1
        assert s["kv_row_bytes"] == 2 * 2 * 8 * (16 + 4)
        assert sorted(n for n in eng._pool if n.startswith("win_")) == sorted(decode.WIN_LEAVES_INT8)
    finally:
        eng.stop()
