"""The engine rounds its weights to the compute dtype once, and every program
still sees the operand bits it saw (PR 30).

``decode.serving_params`` casts the embeddings and the matmul weights to
``cfg.dtype`` with the ``astype`` the paged programs themselves apply to a
float32 leaf, so handing the programs the cast tree must change NOTHING they
compute: at bfloat16 compute on the CPU, logits, greedy tokens and pool leaves
are compared with ``np.array_equal``, never a tolerance.  Every leaf is drawn
at random first (norm scales, ``A_log``, ``dt_bias`` and the convolutions
too): a scale of one is exact in bfloat16 and would hide a leaf that was
rounded by mistake.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import TransformerConfig, decode, init_params
from polyaxon_tpu.models.transformer import stack_module
from polyaxon_tpu.serving import ServingEngine

DENSE = dict(vocab_size=256, d_model=64, n_layers=3, n_heads=4, head_dim=16, d_ff=128,
             n_kv_heads=2)
HYBRID = dict(vocab_size=256, d_model=64, n_layers=4, n_heads=4, head_dim=16, d_ff=128,
              n_kv_heads=4, rope_theta=None,
              layer_types=("linear_attention",) * 3 + ("full_attention",),
              linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=16,
              linear_value_head_dim=32, linear_conv_kernel_dim=4,
              linear_allow_neg_eigval=True)
LATENT = dict(vocab_size=256, d_model=64, n_layers=3, n_heads=4, head_dim=16, d_ff=128,
              rope_theta=32e6, layer_types=("dense_mlp", "expert_mlp", "expert_mlp"),
              kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
              moe_intermediate_size=32, n_shared_experts=1, routed_scaling_factor=2.5,
              experts_held=8, expert_offset=4)
WINDOWED = dict(vocab_size=256, d_model=64, n_layers=5, n_heads=4, head_dim=16, d_ff=128,
                n_kv_heads=2, rope_theta=500000.0,
                layer_types=("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",),
                mlp_layer_types=("dense",) + ("sparse",) * 4, sliding_window=16,
                sliding_n_heads=6, head_gate=True, partial_rotary_factor=0.5,
                rope_yarn_factor=128.0, rope_yarn_original_max=512, rope_attention_factor=1.485,
                n_routed_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
                n_shared_experts=1, routed_scaling_factor=2.5, experts_held=8, expert_offset=4)
SIZES = {"dense": DENSE, "hybrid": HYBRID, "latent": LATENT, "window": WINDOWED}
BS, W, SLOTS, CHUNK = 8, 16, 3, 32
KINDS = ["dense", "hybrid", "latent", "window"]
#: The stacks that keep per-slot rows beside the blocks and snapshot them.
PER_SLOT = ("hybrid", "window")

#: What ``serving_params`` must leave in ``param_dtype``: the norm scales, and
#: what the hybrid programs read in float32.
KEPT = {
    "dense": {"final_norm", "block.attn_norm", "block.mlp_norm"},
    "hybrid": {"final_norm", "block.mixer_norm", "block.mlp_norm",
               "block.full.q_norm", "block.full.k_norm",
               "block.linear.A_log", "block.linear.dt_bias", "block.linear.o_norm",
               "block.linear.conv_q", "block.linear.conv_k", "block.linear.conv_v"},
    # the latent stack's norms, and the router, which chooses in float32
    "latent": {"final_norm", "block.attn_norm", "block.mlp_norm", "block.q_norm",
               "block.kv_norm", "block.experts.router", "block.experts.router_bias"},
    # the window stack's norms and its router
    "window": {"final_norm", "block.attn_norm", "block.mlp_norm", "block.experts.router"},
}


def _leaves(tree):
    return {".".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _model(kind, dtype=jnp.bfloat16):
    """The toy configuration and a float32 tree with EVERY leaf random."""
    cfg = TransformerConfig(max_seq=BS * W, dtype=dtype, **SIZES[kind])
    params = init_params(jax.random.PRNGKey(7), cfg)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(11), len(leaves))
    leaves = [w * (1.0 + 0.1 * jax.random.normal(k, w.shape, w.dtype))
              for w, k in zip(leaves, keys)]
    assert all(w.dtype == jnp.float32 for w in leaves)
    # no leaf is exact in bfloat16: rounding any of them would show
    assert not any(bool(jnp.all(w.astype(jnp.bfloat16).astype(jnp.float32) == w)) for w in leaves)
    return cfg, jax.tree.unflatten(treedef, leaves)


@pytest.fixture(scope="module", params=KINDS)
def model(request):
    return (request.param, *_model(request.param))


def _pool(cfg):
    pool = decode.init_block_pool(cfg, 1 + SLOTS * W, BS)
    if cfg.stack in PER_SLOT:
        pool.update(stack_module(cfg).init_rec_state(cfg, SLOTS))
    return pool


def _through_the_programs(cfg, params, tokens, n_prompt, slot=1):
    """Prefill ``tokens[:n_prompt]`` in chunks of ``CHUNK`` (the last one
    padded), decode the rest a token a step beside two inactive lanes, then
    (dense only) verify three rows.  Returns every logits array and the pool."""
    recurrent = cfg.stack in PER_SLOT
    chunk = jax.jit(partial(decode.paged_prefill_chunk, cfg=cfg))
    step = jax.jit(partial(decode.paged_decode_step, cfg=cfg))
    pool = _pool(cfg)
    table = np.zeros(W, np.int32)
    n_blocks = -(-(len(tokens) + 4) // BS)
    table[:n_blocks] = 1 + slot * W + np.arange(n_blocks)
    out = []
    for start in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - start)
        buf = np.zeros(CHUNK, np.int32)
        buf[:n] = tokens[start:start + n]
        kw = {"slot": jnp.int32(slot)} if recurrent else {}
        logits, pool, *_ = chunk(params, pool, jnp.asarray(table), jnp.asarray(buf),
                             jnp.int32(start), jnp.int32(n), **kw)
        out.append(logits)
    tables = np.zeros((SLOTS, W), np.int32)
    tables[slot] = table
    active = np.arange(SLOTS) == slot
    lane = lambda v: jnp.asarray(np.where(active, v, 0).astype(np.int32))  # noqa: E731
    for i in range(n_prompt, len(tokens)):
        logits, pool, *_ = step(params, pool, jnp.asarray(tables), lane(tokens[i]), lane(i),
                            jnp.asarray(active))
        out.append(logits)
    if cfg.stack == "uniform":
        verify = jax.jit(partial(decode.paged_verify_step, cfg=cfg))
        rows = np.zeros((SLOTS, 3), np.int32)
        rows[slot] = tokens[:3]
        logits, pool = verify(params, pool, jnp.asarray(tables), jnp.asarray(rows),
                              lane(len(tokens)), lane(3), jnp.asarray(active))
        out.append(logits)
    return out, pool


def test_the_three_programs_compute_the_same_bits_on_the_cast_tree(model):
    kind, cfg, params = model
    tokens = np.random.default_rng(0).integers(0, 256, 77 + 5)
    want, pool_want = _through_the_programs(cfg, params, tokens, 77)
    got, pool_got = _through_the_programs(cfg, decode.serving_params(params, cfg), tokens, 77)
    assert len(want) == len(got) == 3 + 5 + (kind == "dense")
    for a, b in zip(want, got):
        assert float(jnp.max(jnp.abs(a))) > 0.5  # logits, not zeros
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.argmax(a, -1), np.argmax(b, -1))
    assert set(pool_want) == set(pool_got)
    for name in pool_want:
        assert np.array_equal(np.asarray(pool_want[name]), np.asarray(pool_got[name])), name


def _serve(engine, rng_seed):
    """A document of 100 tokens asked about twice (the second a prefix hit;
    for the hybrid, from a state snapshot) and an unrelated prompt: prefills
    of several chunks, decode steps, a copy-on-write or a restore."""
    rng = np.random.default_rng(rng_seed)
    doc = rng.integers(0, 256, 100).tolist()
    prompts = [doc + rng.integers(0, 256, 6).tolist(), doc + rng.integers(0, 256, 9).tolist(),
               rng.integers(0, 256, 41).tolist()]
    return [engine.generate(p, 8, timeout=300) for p in prompts]


def test_an_engine_serves_the_tokens_and_leaves_the_pool_the_float32_tree_gave(model):
    """Two engines from the same float32 tree; the second is then handed that
    tree back in place of its cast one, which is the engine of before PR 30."""
    kind, cfg, params = model
    kw = dict(slots=SLOTS, block_size=BS, num_blocks=1 + 64, prefill_chunk=CHUNK, warmup=False)
    if kind in PER_SLOT:
        kw.update(state_snapshot_every=32, state_snapshots=8)
    ours, before = ServingEngine(params, cfg, **kw), ServingEngine(params, cfg, **kw)
    assert ours.weight_dtype == "bfloat16"
    assert _leaves(ours._params)["unembed"].dtype == jnp.bfloat16
    before._params = params
    ours.start(), before.start()
    try:
        got, want = _serve(ours, 1), _serve(before, 1)
        assert got == want and all(len(t) == 8 for t in got)
        a, b = ours.stats(), before.stats()
        for key in ("prefix_cache_hits", "state_restores", "state_snapshots", "prefix_cache_misses"):
            assert a[key] == b[key]
        assert a["prefix_cache_hits"] > 0
        assert (a["state_restores"] >= 1) == (kind in PER_SLOT)
    finally:
        ours.stop(), before.stop()
    assert set(ours._pool) == set(before._pool)
    for name in ours._pool:
        assert np.array_equal(np.asarray(ours._pool[name]), np.asarray(before._pool[name])), name


def test_serving_params_is_idempotent_and_the_identity_where_nothing_is_to_do(model):
    kind, cfg, params = model
    once = decode.serving_params(params, cfg)
    twice = decode.serving_params(once, cfg)
    assert jax.tree.structure(once) == jax.tree.structure(params) == jax.tree.structure(twice)
    assert all(a is b for a, b in zip(jax.tree.leaves(once), jax.tree.leaves(twice)))
    # a model at float32 compute: every leaf comes back itself
    cfg32, params32 = _model(kind, jnp.float32)
    same = decode.serving_params(params32, cfg32)
    assert all(a is b for a, b in zip(jax.tree.leaves(same), jax.tree.leaves(params32)))


@pytest.mark.parametrize("kind,name", [(k, n) for k in KINDS for n in sorted(KEPT[k])])
def test_a_leaf_the_programs_read_in_float32_keeps_its_dtype(kind, name):
    cfg, params = _model(kind)
    assert _leaves(decode.serving_params(params, cfg))[name] is _leaves(params)[name]


@pytest.mark.parametrize("kind", KINDS)
def test_every_other_leaf_is_the_compute_dtype_rounded_as_the_programs_round(kind):
    cfg, params = _model(kind)
    cast, handed = _leaves(decode.serving_params(params, cfg)), _leaves(params)
    assert {n for n, w in cast.items() if w.dtype != jnp.bfloat16} == KEPT[kind]
    for name in set(cast) - KEPT[kind]:
        assert cast[name].shape == handed[name].shape
        assert np.array_equal(np.asarray(cast[name]), np.asarray(handed[name].astype(cfg.dtype)))


@pytest.mark.parametrize("kind", KINDS)
def test_the_int8_tree_is_made_from_the_float32_weights_as_before(kind):
    """``serving_params`` does not touch the tree it is handed, so
    ``quantize_weights`` reads what it read; quantizing the CAST tree instead
    would give other int8 values, which is why ``lm_server`` keeps the order."""
    cfg, params = _model(kind)
    want = decode.quantize_weights(params)
    kept = jax.tree.leaves(params)
    cast = decode.serving_params(params, cfg)
    assert all(a is b and b.dtype == jnp.float32 for a, b in zip(jax.tree.leaves(params), kept))
    got = decode.quantize_weights(params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
    other = decode.quantize_weights(cast)
    assert not all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree.leaves(other), jax.tree.leaves(want)))


@pytest.mark.parametrize("kind", KINDS)
def test_the_int8_engine_reads_the_same_int8_pairs_and_the_cast_tree(kind):
    cfg, params = _model(kind)
    qweights = decode.quantize_weights(params)
    engine = ServingEngine(params, cfg, slots=SLOTS, block_size=BS, num_blocks=1 + 64,
                           prefill_chunk=CHUNK, qweights=qweights, warmup=False)
    assert all(a is b for a, b in zip(jax.tree.leaves(engine._qweights), jax.tree.leaves(qweights)))
    assert engine.weight_dtype == "bfloat16"
    assert engine.weight_bytes == sum(w.nbytes for w in jax.tree.leaves(engine._params))
    assert engine.weight_bytes < 0.55 * sum(w.nbytes for w in jax.tree.leaves(params))


def test_a_cast_leaf_keeps_the_sharding_it_was_placed_with():
    from polyaxon_tpu.parallel import template_for
    from polyaxon_tpu.runtime.mesh import build_mesh

    cfg, params = _model("dense")
    mesh_axes = {"data": jax.local_device_count() // 2, "tensor": 2}
    mesh = build_mesh(mesh_axes)
    shardings = decode.decode_param_shardings(
        cfg, mesh, template_for("tp", mesh_axes), params=params)
    engine = ServingEngine(params, cfg, slots=SLOTS, block_size=BS, num_blocks=1 + 64,
                           mesh=mesh, param_shardings=shardings, warmup=False)
    held, want = _leaves(engine._params), _leaves(shardings)
    assert not want["block.wq"].is_fully_replicated  # heads over the tensor axis
    for name, leaf in held.items():
        assert leaf.sharding.is_equivalent_to(want[name], leaf.ndim), name
