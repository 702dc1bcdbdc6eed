"""How the engine's loop hands a program its host arguments.

The loop passes host values (numpy arrays and scalars) straight to each jitted
program, whose dispatch transfers them, a decode step's packed into one int32
buffer with its key, which is drawn on the host: after warm-up the loop makes no
device array of its own and runs no program but the engine's.  The warm-up
passed the same kinds of argument, so the jit caches do not grow.  A step's key
still splits per slot inside the program: a sampled lane does not depend on its
neighbours, and a seed fixes the tokens.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import TransformerConfig, init_params
from polyaxon_tpu.serving import ServingEngine

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8, d_ff=64,
    max_seq=64, dtype=jnp.float32,
)
HYBRID = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=4, n_heads=4, head_dim=8, d_ff=64,
    max_seq=64, dtype=jnp.float32, rope_theta=None,
    layer_types=("linear_attention",) * 3 + ("full_attention",),
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
)
SEED = 2**31 + 39
SAMPLED = 0.8

#: The engine kinds whose loop paths differ: a dense model (chunks, steps, a
#: prefix hit's copy-on-write), the same with the verify step, and a recurrent
#: model (state snapshots and restores).
ENGINES = {
    "dense": (CFG, {}),
    "verify": (CFG, {"spec_decode": True, "spec_k": 4, "spec_min_ngram": 2}),
    "recurrent": (HYBRID, {"state_snapshot_every": 16, "state_snapshots": 8}),
}


@pytest.fixture(scope="module")
def params():
    return {
        cfg: init_params(jax.random.PRNGKey(3), cfg) for cfg in (CFG, HYBRID)
    }


def _engine(params, cfg=CFG, **kw):
    kw = {"slots": 2, "block_size": 4, "num_blocks": 1 + 48, "prefill_chunk": 16,
          "seed": SEED, "warmup": False, **kw}
    return ServingEngine(params[cfg], cfg, **kw)


def _counting(monkeypatch):
    """Count every call of the ways a host value becomes a device array, and of
    the eager key split; ``jnp.int32(x)`` goes through its scalar type's own
    ``asarray``."""
    calls = {}

    def count(owner, name, label):
        real = getattr(owner, name)

        def counted(*args, **kwargs):
            calls[label] = calls.get(label, 0) + 1
            return real(*args, **kwargs)

        monkeypatch.setattr(owner, name, counted)

    count(jnp, "asarray", "jnp.asarray")
    count(sys.modules[type(jnp.int32).__module__], "asarray", "jnp.int32")
    count(jax, "device_put", "jax.device_put")
    count(jax.random, "split", "jax.random.split")
    return calls


@pytest.mark.parametrize("kind", list(ENGINES))
def test_the_loop_makes_no_device_array_and_no_key_program(params, kind, monkeypatch):
    cfg, kw = ENGINES[kind]
    engine = _engine(params, cfg, warmup=True, **kw).start()
    try:
        assert engine.wait_ready(timeout=300), engine.start_error
        baseline = engine._compiled_count()
        calls = _counting(monkeypatch)
        rng = np.random.default_rng(0)
        doc = list(rng.integers(0, 64, 40))
        prompts = [doc + [1, 2], doc + [3], [3, 7] * 4, list(rng.integers(0, 64, 7)), doc]
        reqs = [engine.submit(p, 16, temperature=SAMPLED * (i % 2)) for i, p in enumerate(prompts)]
        for req in reqs:
            assert len(req.wait(timeout=300)) == 16
        stats = engine.stats()
    finally:
        engine.stop()
    assert calls == {}
    assert engine._compiled_count() == baseline
    assert stats["steady_state_compiles"] == 0
    # chunks and steps both ran, with a prefix hit among the chunks
    assert stats["loop_prefill_host_n"] > len(prompts) and stats["decode_steps"] > 0
    assert stats["prefix_cache_hits"] > 0
    if kind == "verify":
        assert stats["spec_steps"] > 0
    if kind == "recurrent":
        assert stats["loop_state_snapshot_n"] > 0 and stats["loop_state_restore_n"] > 0


def _sampled_tokens(params, prompts, seed=SEED):
    """Serve ``prompts`` (a prompt and its temperature each), all queued before
    the loop starts, and return each request's tokens."""
    engine = _engine(params, seed=seed)
    reqs = [engine.submit(p, 12, temperature=t) for p, t in prompts]
    engine.start()
    try:
        return [req.wait(timeout=300) for req in reqs]
    finally:
        engine.stop()


def test_one_seed_gives_the_same_sampled_tokens(params):
    rng = np.random.default_rng(1)
    prompts = [(list(rng.integers(0, 64, n)), SAMPLED) for n in (5, 21, 9)]
    first = _sampled_tokens(params, prompts)
    assert _sampled_tokens(params, prompts) == first
    # the seed draws the keys: another seed samples other tokens, and neither
    # is the greedy reply
    assert _sampled_tokens(params, prompts, seed=SEED + 1) != first
    assert _sampled_tokens(params, [(p, 0.0) for p, _ in prompts]) != first


def test_a_sampled_lane_does_not_depend_on_its_neighbour(params):
    """The lane's prompt is the shorter, so it is in before any step runs; the
    neighbour, sampled too, draws its first token after it."""
    rng = np.random.default_rng(2)
    lane = (list(rng.integers(0, 64, 6)), SAMPLED)
    neighbour = (list(rng.integers(0, 64, 30)), SAMPLED)
    [alone] = _sampled_tokens(params, [lane])
    beside, other = _sampled_tokens(params, [lane, neighbour])
    assert beside == alone
    assert other != alone
