"""Start()-time warmup: the readiness gate and its zero-compile promise.

Contract (serving/engine.py): ``start()`` pre-executes the decode step,
every prefill chunk bucket, and the COW copy fn in the scheduler thread;
``stats()["state"]`` is ``"warming"`` until that finishes and
``"ready"`` after, and the FIRST request served after ``ready`` performs
no compilation at all.  The tree-wide conftest turns warmup off for the
other serving tests — everything here opts back in with ``warmup=True``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import TransformerConfig, decode, init_params
from polyaxon_tpu.serving import ServingEngine

CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    n_layers=2,
    n_heads=4,
    head_dim=8,
    d_ff=64,
    max_seq=48,
    dtype=jnp.float32,
)
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def params():
    return init_params(KEY, CFG)


@pytest.fixture(scope="module")
def warm_engine(params):
    # Module-scoped: the warmup costs seconds, and the tests below only
    # ever ASSERT nothing compiles after it — safe to share.
    eng = ServingEngine(params, CFG, slots=2, max_len=48, warmup=True).start()
    assert eng.wait_ready(timeout=300), "warmup never finished"
    yield eng
    eng.stop()


def test_env_knob_resolves_default(params, monkeypatch):
    """The conftest env opt-out reaches the ctor default; an explicit
    warmup= argument always wins over the env."""
    assert ServingEngine(params, CFG, slots=2, max_len=48)._warmup is False
    monkeypatch.setenv("POLYAXON_TPU_SERVING_WARMUP", "1")
    assert ServingEngine(params, CFG, slots=2, max_len=48)._warmup is True
    monkeypatch.setenv("POLYAXON_TPU_SERVING_WARMUP", "0")
    assert (
        ServingEngine(params, CFG, slots=2, max_len=48, warmup=True)._warmup
        is True
    )


def test_warming_until_warmup_completes(params):
    eng = ServingEngine(params, CFG, slots=2, max_len=48, warmup=True)
    # Not started: the gate is closed and stats say so.
    assert eng.stats()["state"] == "warming"
    assert eng.wait_ready(timeout=0.05) is False
    eng.start()
    try:
        assert eng.wait_ready(timeout=300)
        st = eng.stats()
        assert st["state"] == "ready"
        assert st["warmup"]["total"] > 0
        assert st["warmup"]["done"] == st["warmup"]["total"]
        assert st["warmup"]["ready_s"] > 0
    finally:
        eng.stop()


def test_first_request_after_ready_compiles_nothing(params, warm_engine):
    """The acceptance bar: ready means READY — the first real request
    adds zero entries to any jit cache and the steady-state compile
    counter stays at zero."""
    baseline = warm_engine._compiled_count()
    assert baseline > 0  # warmup actually compiled the family
    rng = np.random.default_rng(7)
    prompt = list(rng.integers(0, CFG.vocab_size, 9))
    out = warm_engine.submit(prompt, 6).wait(timeout=120)
    ref = decode.generate(
        params, jnp.asarray([prompt]), CFG, max_new_tokens=6
    )
    assert out == np.asarray(ref)[0].tolist()
    assert warm_engine._compiled_count() == baseline
    assert warm_engine.stats()["steady_state_compiles"] == 0


def test_mixed_lengths_after_ready_compile_nothing(params, warm_engine):
    """Every chunk bucket was warmed, so prompts landing in different
    pad buckets still add no compiles."""
    baseline = warm_engine._compiled_count()
    rng = np.random.default_rng(8)
    reqs = [
        warm_engine.submit(list(rng.integers(0, CFG.vocab_size, t)), mn)
        for t, mn in [(3, 4), (17, 2), (30, 3)]
    ]
    [r.wait(timeout=120) for r in reqs]
    assert warm_engine._compiled_count() == baseline
    assert warm_engine.stats()["steady_state_compiles"] == 0


def test_quantized_pool_warmup_compiles_nothing_after_ready(params):
    """With ``kv_quantize="int8"`` the warmup executes the QUANTIZED
    bucket family (the pool pytree structure is part of every compiled
    signature), so mixed-length traffic after ready still adds zero
    compiles and ``steady_state_compiles`` stays 0."""
    eng = ServingEngine(
        params, CFG, slots=2, max_len=48, kv_quantize="int8", warmup=True
    ).start()
    try:
        assert eng.wait_ready(timeout=300), "warmup never finished"
        baseline = eng._compiled_count()
        assert baseline > 0
        rng = np.random.default_rng(9)
        reqs = [
            eng.submit(list(rng.integers(0, CFG.vocab_size, t)), mn)
            for t, mn in [(3, 4), (9, 6), (17, 2), (30, 3)]
        ]
        for r in reqs:
            out = r.wait(timeout=120)
            assert out and all(0 <= t < CFG.vocab_size for t in out)
        assert eng._compiled_count() == baseline
        assert eng.stats()["steady_state_compiles"] == 0
    finally:
        eng.stop()


def test_no_warmup_counts_lazy_compiles(params):
    """warmup=False keeps the old lazy behavior but MONITORS it: the
    gate opens immediately and the first request's compiles land on the
    steady-state counter (the alert signal warmup exists to keep at 0)."""
    eng = ServingEngine(params, CFG, slots=2, max_len=48, warmup=False).start()
    try:
        assert eng.wait_ready(timeout=30)
        st = eng.stats()
        assert st["state"] == "ready"
        assert st["warmup"]["total"] == 0
        eng.submit([1, 2, 3], 4).wait(timeout=120)
        assert eng.stats()["steady_state_compiles"] > 0
    finally:
        eng.stop()


def test_failed_warmup_is_a_failed_start(params):
    """A warmup that raises (a compile the backend refuses, a step that
    died after donating the pool) must NOT open the readiness gate: the
    engine reports ``failed`` with the error, refuses new requests with
    it, and fails whoever queued while it was warming."""
    eng = ServingEngine(params, CFG, slots=2, max_len=48, warmup=True)

    def boom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM (simulated)")

    eng._step_fn = boom
    early = eng.submit([1, 2, 3], max_new_tokens=4)  # queued while warming
    eng.start()
    try:
        assert eng.wait_ready(timeout=60) is False
        st = eng.stats()
        assert st["state"] == "failed"
        assert "RESOURCE_EXHAUSTED" in st["start_error"]
        assert not eng._ready.is_set()
        with pytest.raises(RuntimeError, match="failed to start"):
            early.wait(timeout=5)
        with pytest.raises(RuntimeError, match="failed to start"):
            eng.submit([1, 2, 3], max_new_tokens=4)
    finally:
        eng.stop()


def test_serve_engine_exits_nonzero_on_failed_start(params):
    """``lm_server``/``replica`` serve through ``serve_engine``: a failed
    start shuts the HTTP server down and raises, so the process exits
    non-zero instead of sitting in ``warming``; /healthz said 503
    ``failed`` meanwhile."""
    from http.server import ThreadingHTTPServer

    from polyaxon_tpu.builtins.services import _make_lm_handler, serve_engine

    eng = ServingEngine(params, CFG, slots=2, max_len=48, warmup=True)
    eng._step_fn = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("Mosaic refused the tile (simulated)")
    )
    server = ThreadingHTTPServer(
        ("127.0.0.1", 0), _make_lm_handler(eng, CFG, {})
    )
    eng.start()
    with pytest.raises(RuntimeError, match="failed to start.*Mosaic refused"):
        serve_engine(server, eng)
    server.server_close()
