"""A model with linear-attention layers through the paged programs and the
engine: logits against the plain reference, prefix hits served from state
snapshots, the snapshot store's bookkeeping, and the options refused.

Sizes are tiny and compute is float32 on seeded random weights, so the program
and the reference (``benchmark/reference/hybrid_decoder.py``: float32, matmuls
at ``highest``, the recurrence token by token) differ by float32 summation
order only.  ``LOGIT_TOL`` = 5e-5 absolute on logits of magnitude 4: five to
ten times what the two read apart here (4e-6 to 1e-5), and a twentieth of
what a recurrent state kept in bfloat16 reads (over 1e-3, asserted below).
"""

import json
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import hybrid_decoder as ref
from polyaxon_tpu.models import TransformerConfig, decode, hybrid, init_params
from polyaxon_tpu.models.hybrid import RecurrentStateError
from polyaxon_tpu.serving import ServingEngine
from polyaxon_tpu.serving.paging import BlockAllocator, PrefixCache, StateSnapshots

LOGIT_TOL = 5e-5
PATTERN = ["linear_attention"] * 3 + ["full_attention"]
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128, "rms_norm_eps": 1e-6,
    "rope_theta": None, "layer_types": PATTERN, "linear_num_key_heads": 2,
    "linear_num_value_heads": 2, "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
}
#: Two periods, grouped KV heads and rotary positions in the full layers: the
#: period scan's second iteration and the switches the published model leaves off.
TWO_PERIODS = {**TINY, "num_hidden_layers": 8, "layer_types": PATTERN * 2,
               "num_key_value_heads": 2, "rope_theta": 10000.0}
BS, W, SLOTS = 8, 16, 3
SEED = 2**31 + 5


def reference_logits(mine, sizes, tokens, rows):
    """The reference's full forward pass at ``rows``; jitted at one padded
    shape (padding lies after every row read, and the model is causal)."""
    padded = np.zeros(128, np.int32)
    padded[: len(tokens)] = tokens
    at = np.zeros(16, np.int32)
    at[: len(rows)] = rows
    fn = _REFERENCE.setdefault(id(sizes), jax.jit(
        lambda p, t, r: ref.logits_at(p, t, r, sizes)))
    return fn(mine, jnp.asarray(padded), jnp.asarray(at))[: len(rows)]


_REFERENCE = {}
_PROGRAMS = {}


def make_cfg(z, seq=BS * W, dtype=jnp.float32):
    return TransformerConfig(
        vocab_size=z["vocab_size"], d_model=z["hidden_size"], n_layers=z["num_hidden_layers"],
        n_heads=z["num_attention_heads"], head_dim=z["head_dim"], d_ff=z["intermediate_size"],
        n_kv_heads=z["num_key_value_heads"], max_seq=seq, dtype=dtype,
        rope_theta=z["rope_theta"], layer_types=tuple(z["layer_types"]),
        **{k: z[k] for k in z if k.startswith("linear_")})


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def tiny():
    cfg = make_cfg(TINY)
    return cfg, init_params(jax.random.PRNGKey(SEED), cfg), ref.init_params(SEED, TINY)


def _pool(cfg, state_dtype=None):
    rec = hybrid.init_rec_state(cfg, SLOTS)
    if state_dtype is not None:
        rec = {k: v.astype(state_dtype) for k, v in rec.items()}
    return {**decode.init_block_pool(cfg, 1 + SLOTS * W, BS), **rec}


def _serve_through_the_programs(cfg, params, tokens, n_prompt, chunks, pool, slot=1):
    """Prefill ``tokens[:n_prompt]`` in ``chunks`` [(start, n, padded)], then
    decode the rest one token a step in ``slot`` beside two inactive lanes.
    Returns the logits after the prompt's last token and after each decoded."""
    chunk, step = _PROGRAMS.setdefault(cfg, (
        jax.jit(partial(decode.paged_prefill_chunk, cfg=cfg)),
        jax.jit(partial(decode.paged_decode_step, cfg=cfg))))
    table = np.zeros(W, np.int32)
    table[: -(-len(tokens) // BS)] = 1 + slot * W + np.arange(-(-len(tokens) // BS))
    out = []
    for start, n, padded in chunks:
        buf = np.zeros(padded, np.int32)
        buf[:n] = tokens[start : start + n]
        logits, pool = chunk(params, pool, jnp.asarray(table), jnp.asarray(buf),
                             jnp.int32(start), jnp.int32(n), slot=jnp.int32(slot))
    out.append(logits)
    tables = np.zeros((SLOTS, W), np.int32)
    tables[slot] = table
    active = np.arange(SLOTS) == slot
    for i in range(n_prompt, len(tokens)):
        logits, pool = step(params, pool, jnp.asarray(tables),
                            jnp.asarray(np.where(active, tokens[i], 0).astype(np.int32)),
                            jnp.asarray(np.where(active, i, 0).astype(np.int32)), jnp.asarray(active))
        out.append(logits[slot])
    return jnp.stack(out), pool


def test_program_draws_the_references_weights(tiny):
    cfg, params, mine = tiny
    ours, theirs = jax.tree.leaves(params), jax.tree.leaves(mine)
    assert len(ours) == len(theirs) and cfg.n_params == sum(x.size for x in ours)
    for a, b in zip(ours, theirs):
        assert a.shape == b.shape and bool(jnp.all(a == b))


CHUNKS = [(0, 32, 32), (32, 32, 32), (64, 13, 16)]  # the last in a bucket of 16


def test_whole_prefill_chunked_prefill_and_decode_agree_with_the_references_forward(tiny):
    cfg, params, mine = tiny
    tokens = np.random.default_rng(0).integers(0, 256, 77 + 6)
    want = reference_logits(mine, TINY, tokens, np.arange(76, 83))
    whole, pool_a = _serve_through_the_programs(
        cfg, params, tokens, 77, [(0, 77, 128)], _pool(cfg))
    chunked, pool_b = _serve_through_the_programs(cfg, params, tokens, 77, CHUNKS, _pool(cfg))
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert float(jnp.max(jnp.abs(whole - want))) < LOGIT_TOL
    assert float(jnp.max(jnp.abs(chunked - want))) < LOGIT_TOL
    # state and tail handed from chunk to chunk are the one chunk's
    assert float(jnp.max(jnp.abs(pool_a["rec_s"] - pool_b["rec_s"]))) < LOGIT_TOL
    assert float(jnp.max(jnp.abs(pool_a["rec_c"] - pool_b["rec_c"]))) < LOGIT_TOL
    # the lanes that were never active kept their (zero) rows
    assert float(jnp.max(jnp.abs(pool_b["rec_s"][:, [0, 2]]))) == 0.0
    assert float(jnp.max(jnp.abs(pool_b["rec_c"][:, [0, 2]]))) == 0.0


def test_two_periods_grouped_kv_heads_and_rotary_full_layers_agree_with_the_reference():
    cfg = make_cfg(TWO_PERIODS)
    params, mine = init_params(jax.random.PRNGKey(SEED), cfg), ref.init_params(SEED, TWO_PERIODS)
    tokens = np.random.default_rng(0).integers(0, 256, 77 + 3)
    want = reference_logits(mine, TWO_PERIODS, tokens, np.arange(76, 80))
    got, _ = _serve_through_the_programs(cfg, params, tokens, 77, CHUNKS, _pool(cfg))
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL


def test_a_recurrent_state_kept_in_bfloat16_is_outside_the_tolerance(tiny):
    """Everything float32 but the pool's recurrent leaves: the same programs,
    the same tokens, twenty times the tolerance away."""
    cfg, params, mine = tiny
    tokens = np.random.default_rng(0).integers(0, 256, 77 + 6)
    want = reference_logits(mine, TINY, tokens, np.arange(76, 83))
    got, _ = _serve_through_the_programs(
        cfg, params, tokens, 77, CHUNKS, _pool(cfg, jnp.bfloat16))
    assert float(jnp.max(jnp.abs(got - want))) > 20 * LOGIT_TOL


# -- through the engine -----------------------------------------------------------


def _engine(cfg, params, **kw):
    kw = {"slots": SLOTS, "block_size": BS, "num_blocks": 1 + 64, "prefill_chunk": 32,
          "state_snapshot_every": 32, "state_snapshots": 8, "warmup": False, **kw}
    return ServingEngine(params, cfg, **kw).start()


def _gap(mine, prompt, served):
    """How far each served token's logit lies below the reference's best."""
    seq = list(prompt) + list(served)
    logits = reference_logits(mine, TINY, seq[:-1], np.arange(len(prompt) - 1, len(seq) - 1))
    return float(jnp.max(jnp.max(logits, axis=-1) - logits[jnp.arange(len(served)),
                                                            jnp.asarray(served)]))


@pytest.fixture(scope="module")
def served(tiny):
    """An engine with snapshots every 32 tokens and a document of 100 tokens,
    first asked about cold.  "What it gives cold" is the reference's full
    forward pass over the whole prompt: no cache, no state handed on."""
    cfg, params, mine = tiny
    warm = _engine(cfg, params)
    rng = np.random.default_rng(1)
    doc = rng.integers(0, 256, 100).tolist()
    first = doc + rng.integers(0, 256, 6).tolist()
    assert _gap(mine, first, warm.generate(first, 8, timeout=300)) < LOGIT_TOL
    yield warm, doc, rng, mine
    warm.stop()


def _delta(engine, before):
    after = engine.stats()
    return {k: after[k] - before[k] for k in (
        "prefix_cache_hits", "prefix_floor_tokens", "state_restores", "state_snapshots")}


# A document of 100 tokens leaves KV blocks for 96 and snapshots at 32, 64, 96.
HITS = {
    # the KV match ends ON the newest snapshot: all 12 blocks, nothing given back
    "on_a_snapshot": (lambda doc, q: doc + q, 12, 0, 1),
    # 80 tokens of the document match (10 blocks), the newest snapshot at or below is 64
    "past_a_snapshot": (lambda doc, q: doc[:80] + q, 8, 16, 1),
    # 24 tokens match (3 blocks), no snapshot that early: a miss, from position 0
    "before_any_snapshot": (lambda doc, q: doc[:24] + q, 0, 24, 0),
    # every block of the prompt hits and a snapshot stands at its very end: the last
    # token's logits need the state BEFORE it, so the hit ends one snapshot earlier
    "every_block_hits": (lambda doc, q: doc[:96], 8, 32, 1),
}


@pytest.mark.parametrize("case", list(HITS))
def test_a_prefix_served_from_a_snapshot_gives_what_it_gives_cold(served, case):
    warm, doc, rng, mine = served
    build, blocks, floor, restores = HITS[case]
    prompt = build(doc, rng.integers(0, 256, 7).tolist())
    before = warm.stats()
    tokens = warm.generate(prompt, 8, timeout=300)
    moved = _delta(warm, before)
    assert moved["prefix_cache_hits"] == blocks  # only what is not prefilled again
    assert moved["prefix_floor_tokens"] == floor and moved["state_restores"] == restores
    assert _gap(mine, prompt, tokens) < LOGIT_TOL


def test_stats_carry_the_state_counters_and_the_phases_still_sum(served):
    warm = served[0]
    stats = warm.stats()
    assert stats["state_snapshots"] >= 3 and stats["state_restores"] >= 1
    assert stats["state_store_used"] <= 8
    assert stats["state_snapshot_bytes"] == stats["state_store_used"] * hybrid.rec_row_bytes(warm.cfg)
    assert stats["loop_state_snapshot_n"] == stats["state_snapshots"]
    assert stats["loop_state_restore_n"] == stats["state_restores"]
    phases = sum(v for k, v in stats.items()
                 if k.startswith("loop_") and k.endswith("_s") and k != "loop_wall_s")
    assert phases == pytest.approx(stats["loop_wall_s"], abs=1e-4)


def test_the_state_copies_count_as_dispatched_programs_on_the_clock(served):
    stats = served[0].stats()
    for phase in ("state_snapshot", "state_restore"):
        assert 0.0 <= stats[f"uncovered_{phase}_s"] <= stats[f"loop_{phase}_s"] + 1e-6
    parts = [v for k, v in stats.items() if k.startswith("uncovered_") and k != "uncovered_s"]
    assert len(parts) == 12 and stats["uncovered_s"] == pytest.approx(sum(parts), abs=1e-9)
    busy = stats["loop_wall_s"] - stats["loop_idle_s"]
    assert 0.0 < stats["uncovered_s"] <= busy + 2e-5
    assert stats["device_reads"] == stats["loop_device_wait_n"] > 0


def test_without_a_prefix_cache_there_is_no_store_and_a_warm_engine_compiles_nothing_later(tiny):
    cfg, params, mine = tiny
    engine = _engine(cfg, params, prefix_cache=False, warmup=True, slots=2)
    try:
        assert engine.wait_ready(300), engine.start_error
        assert engine._snap_store is None and engine._snaps is None
        prompt = np.random.default_rng(4).integers(0, 256, 50).tolist()
        assert _gap(mine, prompt, engine.generate(prompt, 4, timeout=300)) < LOGIT_TOL
        stats = engine.stats()
        assert stats["state_snapshots"] == 0 and stats["steady_state_compiles"] == 0
    finally:
        engine.stop()


def test_a_full_store_loses_its_oldest_and_a_lost_snapshot_is_a_shorter_hit(tiny):
    """Two places for documents that each leave two snapshots: the second
    document takes the first's places; asking about the first again finds KV
    for all of it and no state, prefills it again and answers the same."""
    cfg, params, mine = tiny
    warm = _engine(cfg, params, state_snapshots=2)
    try:
        rng = np.random.default_rng(2)
        docs = [rng.integers(0, 256, 70).tolist() for _ in range(2)]
        for d in docs:
            warm.generate(d + [1, 2, 3], 4, timeout=300)
        stats = warm.stats()
        assert stats["state_snapshots"] == 4 and stats["state_snapshot_evictions"] == 2
        assert stats["state_store_used"] == 2
        prompt = docs[0] + [4, 5, 6]
        before = warm.stats()
        tokens = warm.generate(prompt, 6, timeout=300)
        moved = _delta(warm, before)
        assert moved["prefix_cache_hits"] == 0 and moved["prefix_floor_tokens"] == 64
        assert moved["state_restores"] == 0
        assert _gap(mine, prompt, tokens) < LOGIT_TOL
    finally:
        warm.stop()


# -- the bookkeeping alone ----------------------------------------------------------


def _cache(places=4, blocks=40):
    alloc = BlockAllocator(blocks)
    snaps = StateSnapshots(places)
    return alloc, snaps, PrefixCache(alloc, 4, snaps)


def _publish(alloc, snaps, pc, prompt, at):
    """Offer ``prompt``'s full blocks with snapshots at the positions ``at``."""
    blocks = [alloc.alloc() for _ in range(len(prompt) // 4)]
    pending = {pos: snaps.alloc() for pos in at}
    pc.offer(prompt, blocks, pending)
    for b in blocks:
        alloc.decref(b)  # the request retires; the cache keeps its own reference
    return pending


def test_evicting_a_chain_drops_its_snapshots_and_frees_their_places():
    alloc, snaps, pc = _cache()
    prompt = list(range(16))
    _publish(alloc, snaps, pc, prompt, at=(8, 16))
    assert snaps.used == 2
    blocks, place = pc.match_with_state(prompt + [99])
    assert len(blocks) == 4 and place is not None
    for b in blocks:
        alloc.decref(b)
    assert pc.evict(4) == 4  # the whole chain, oldest first
    assert snaps.used == 0 and snaps.evictions == 2
    assert pc.match_with_state(prompt + [99]) == ([], None)


def test_a_full_store_evicts_its_least_recently_used_and_never_a_pending_place():
    alloc, snaps, pc = _cache(places=3)
    a, b = list(range(8)), list(range(100, 108))
    place_a = _publish(alloc, snaps, pc, a, at=(8,))[8]
    place_b = _publish(alloc, snaps, pc, b, at=(8,))[8]
    pending = snaps.alloc()  # a prefill in flight holds the third place
    blocks, got = pc.match_with_state(a + [1])  # touches a: b is now the oldest
    assert got == place_a
    taken = snaps.alloc()
    assert taken == place_b and snaps.evictions == 1
    assert pc.match_with_state(b + [1]) == ([], None)  # KV there, state gone: a miss
    assert pc.floor_tokens == 8
    snaps.release(taken)
    assert snaps.alloc() == taken and snaps.alloc() == place_a  # then a; never ``pending``
    assert snaps.alloc() is None and pending not in (taken, place_a)


def test_no_snapshot_is_found_under_other_tokens():
    """A snapshot is keyed by the chain of every token before it: a prompt that
    shares the first block and differs in the second gets the first's KV at
    most, never the state that stood after the second."""
    alloc, snaps, pc = _cache()
    prompt = list(range(12))
    _publish(alloc, snaps, pc, prompt, at=(8,))
    other = prompt[:4] + [77] + prompt[5:]
    assert pc.match_with_state(other + [1]) == ([], None)
    assert pc.hits == 0 and pc.floor_tokens == 4
    # the same prefix, asked again by another request, finds it; the first writer's
    # snapshot stays and the duplicate's place goes back
    again = _publish(alloc, snaps, pc, prompt, at=(8,))
    assert snaps.used == 1 and snaps.lookup(pc._keys_for(prompt)[1][0]) != again[8]


# -- refused, by name ----------------------------------------------------------------


@pytest.mark.parametrize("option,kwargs", [
    ("spec_decode", {"spec_decode": True, "spec_k": 2, "spec_min_ngram": 2}),
    ("kv_offload", {"kv_offload": True}),
    ("kv_persist_dir", {"kv_persist_dir": "/nonexistent/kv", "kv_persist_sig": "x"}),
    ("mesh", {"mesh": object()}),
])
def test_options_that_would_have_to_move_recurrent_state_are_refused_by_name(tiny, option, kwargs):
    cfg, params, _ = tiny
    with pytest.raises(RecurrentStateError) as err:
        ServingEngine(params, cfg, slots=2, block_size=BS, warmup=False, **kwargs)
    assert err.value.option == option and option in str(err.value)


def test_the_verify_program_refuses_a_layer_pattern_and_kv_quantize_is_taken(tiny):
    cfg, params, _ = tiny
    with pytest.raises(RecurrentStateError) as err:
        decode.paged_verify_step(params, _pool(cfg), None, None, None, None, None, cfg)
    assert err.value.option == "spec_decode"
    with pytest.raises(ValueError, match="multiple of block_size"):
        ServingEngine(params, cfg, slots=2, block_size=BS, state_snapshot_every=20, warmup=False)
    # an int8 KV pool only changes how the full layer's blocks are stored
    engine = ServingEngine(params, cfg, slots=2, block_size=BS, kv_quantize="int8", warmup=False)
    assert "k_q" in engine._pool and engine._pool["rec_s"].dtype == jnp.float32


def test_int8_weights_cover_both_kinds_of_layer(tiny):
    cfg, params, _ = tiny
    q = decode.quantize_weights(params)
    assert set(q["block"]["linear"]) == {"wq", "wk", "wv", "wg", "wo"}
    assert set(q["block"]["full"]) == {"wq", "wk", "wv", "wo"}
    assert all(q["block"][n][0].dtype == jnp.int8 for n in ("wi", "wg", "wd"))
    tokens = np.random.default_rng(0).integers(0, 256, 40)
    step = jax.jit(partial(decode.paged_decode_step, cfg=cfg))
    tables = jnp.asarray(np.arange(1, 1 + SLOTS * W).reshape(SLOTS, W), jnp.int32)
    args = (tables, jnp.asarray(tokens[:SLOTS], jnp.int32), jnp.zeros(SLOTS, jnp.int32),
            jnp.ones(SLOTS, bool))
    full, _ = step(params, _pool(cfg), *args)
    int8, _ = step(params, _pool(cfg), *args, qweights=q)
    err = float(jnp.max(jnp.abs(full - int8)))
    assert LOGIT_TOL < err < 0.5  # not the same numbers, and not another model's
