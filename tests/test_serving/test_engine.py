"""Continuous-batching engine: slot bookkeeping + decode-step parity.

The invariant that makes the engine trustworthy: GREEDY outputs through
the shared slot cache are token-identical to sequential ``generate()``
calls, for any mix of prompt lengths and generation budgets, while the
step function compiles exactly once (zero steady-state recompilation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import TransformerConfig, decode, init_params
from polyaxon_tpu.serving import ServingEngine, SlotAllocator

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


@pytest.fixture()
def engine(params):
    eng = ServingEngine(params, CFG, slots=2, max_len=48).start()
    yield eng
    eng.stop()


def _ref(params, prompt, max_new):
    out = decode.generate(
        params, jnp.asarray([prompt]), CFG, max_new_tokens=max_new
    )
    return np.asarray(out)[0].tolist()


class TestSlotAllocator:
    def test_admit_evict_reuse_ordering(self):
        """Slots hand out in index order; freed slots are reused in the
        order they were RELEASED (FIFO), not stack order."""
        a = SlotAllocator(3)
        assert [a.alloc() for _ in range(3)] == [0, 1, 2]
        assert a.alloc() is None  # exhausted
        a.free(1)
        a.free(0)
        # Reuse order = release order: 1 was freed first.
        assert a.alloc() == 1
        assert a.alloc() == 0
        assert a.alloc() is None
        assert a.n_active == 3 and a.n_free == 0

    def test_double_free_is_loud(self):
        a = SlotAllocator(2)
        s = a.alloc()
        a.free(s)
        with pytest.raises(ValueError, match="not allocated"):
            a.free(s)
        with pytest.raises(ValueError, match="not allocated"):
            a.free(1)  # never allocated

    def test_zero_slots_rejected(self):
        with pytest.raises(ValueError):
            SlotAllocator(0)


class TestEngineParity:
    def test_mixed_length_greedy_identical_to_sequential(self, params, engine):
        """The acceptance bar: N > slots mixed-length requests through 2
        shared slots, every output token-identical to its own sequential
        ``generate()`` call."""
        rng = np.random.default_rng(1)
        shapes = [(3, 10), (7, 4), (12, 8), (5, 1), (9, 14), (4, 6)]
        prompts = [list(rng.integers(0, CFG.vocab_size, t)) for t, _ in shapes]
        reqs = [
            engine.submit(p, mn) for p, (_, mn) in zip(prompts, shapes)
        ]
        outs = [r.wait(timeout=120) for r in reqs]
        for i, (p, (_, mn)) in enumerate(zip(prompts, shapes)):
            assert outs[i] == _ref(params, p, mn), f"request {i}"

    def test_zero_steadystate_recompilation(self, params, engine):
        """One compiled step serves every mix: after a first warm-up wave,
        a second wave with different lengths/budgets must not add a step
        compilation (slot index, positions, and the active mask are data)."""
        rng = np.random.default_rng(2)
        wave1 = [engine.submit(list(rng.integers(0, 64, t)), mn)
                 for t, mn in [(3, 6), (7, 3)]]
        [r.wait(timeout=120) for r in wave1]
        n_compiles = engine._step_fn._cache_size()
        assert n_compiles == 1
        wave2 = [engine.submit(list(rng.integers(0, 64, t)), mn)
                 for t, mn in [(5, 9), (6, 2), (4, 11)]]
        [r.wait(timeout=120) for r in wave2]
        assert engine._step_fn._cache_size() == n_compiles

    def test_slots_refill_mid_flight(self, params, engine):
        """Continuous batching's defining property: with 2 slots, one long
        and four short requests finish in FEWER decode steps than the
        sequential sum — short requests ride alongside the long one,
        taking over each other's freed slot without waiting for it."""
        rng = np.random.default_rng(3)
        long_req = engine.submit(list(rng.integers(0, 64, 4)), 20)
        shorts = [
            engine.submit(list(rng.integers(0, 64, 3)), 4) for _ in range(4)
        ]
        long_req.wait(timeout=120)
        [r.wait(timeout=120) for r in shorts]
        steps = engine.stats()["decode_steps"]
        sequential = (20 - 1) + 4 * (4 - 1)  # 31 steps one-at-a-time
        assert steps < sequential, steps
        assert steps >= 20 - 1  # the long request alone needs 19

    def test_streaming_tokens_arrive_incrementally(self, params, engine):
        rng = np.random.default_rng(4)
        prompt = list(rng.integers(0, 64, 5))
        req = engine.submit(prompt, 6)
        streamed = []
        while True:
            tok = req.stream.get(timeout=60)
            if tok is None:
                break
            streamed.append(tok)
        assert streamed == req.tokens == _ref(params, prompt, 6)

    def test_sampling_path_runs_and_stays_in_vocab(self, params, engine):
        rng = np.random.default_rng(5)
        req = engine.submit(list(rng.integers(0, 64, 6)), 8, temperature=0.9)
        out = req.wait(timeout=120)
        assert len(out) == 8
        assert all(0 <= t < CFG.vocab_size for t in out)

    def test_eos_retires_slot_early(self, params):
        """Set eos_id to the reference generation's 3rd token: the engine
        must stop there instead of spending the full budget."""
        rng = np.random.default_rng(6)
        prompt = list(rng.integers(0, 64, 5))
        ref = _ref(params, prompt, 10)
        eos = ref[2]
        # eos must not appear earlier, or the comparison below shifts.
        if eos in ref[:2]:
            pytest.skip("random model emitted eos early")
        eng = ServingEngine(params, CFG, slots=2, max_len=48, eos_id=eos).start()
        try:
            out = eng.submit(prompt, 10).wait(timeout=120)
        finally:
            eng.stop()
        assert out == ref[:3]

    def test_int8_quantized_engine(self, params):
        qweights = decode.quantize_weights(params)
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48, qweights=qweights
        ).start()
        try:
            rng = np.random.default_rng(7)
            reqs = [
                eng.submit(list(rng.integers(0, 64, t)), mn)
                for t, mn in [(4, 6), (8, 3)]
            ]
            outs = [r.wait(timeout=120) for r in reqs]
        finally:
            eng.stop()
        for out, (t, mn) in zip(outs, [(4, 6), (8, 3)]):
            assert len(out) == mn
            assert all(0 <= tok < CFG.vocab_size for tok in out)

    def test_max_new_one_finishes_without_decode_step(self, params, engine):
        """A 1-token request is satisfied by prefill alone — exactly like
        ``generate()``'s final pick-without-step."""
        rng = np.random.default_rng(8)
        prompt = list(rng.integers(0, 64, 6))
        before = engine.stats()["decode_steps"]
        out = engine.submit(prompt, 1).wait(timeout=60)
        assert out == _ref(params, prompt, 1)
        assert engine.stats()["decode_steps"] == before


class TestEngineValidation:
    def test_submit_rejects_bad_requests(self, engine):
        with pytest.raises(ValueError, match="non-empty"):
            engine.submit([], 4)
        with pytest.raises(ValueError, match="vocabulary"):
            engine.submit([0, CFG.vocab_size], 4)
        with pytest.raises(ValueError, match="positive"):
            engine.submit([1, 2], 0)
        with pytest.raises(ValueError, match="max_len"):
            engine.submit([1] * 40, 20)

    def test_max_len_cannot_exceed_model(self, params):
        with pytest.raises(ValueError, match="max_seq"):
            ServingEngine(params, CFG, slots=2, max_len=CFG.max_seq + 1)

    def test_stop_unblocks_queued_waiters(self, params):
        eng = ServingEngine(params, CFG, slots=1, max_len=48)
        # Not started: submissions just queue.
        req = eng.submit([1, 2, 3], 4)
        eng.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            req.wait(timeout=5)
        with pytest.raises(RuntimeError, match="stopped"):
            eng.submit([1, 2, 3], 4)

    def test_stats_shape(self, params, engine):
        engine.submit([1, 2, 3], 2).wait(timeout=60)
        s = engine.stats()
        assert s["slots"] == 2
        assert s["requests_finished"] >= 1
        assert s["tokens_generated"] >= 2
        assert {"queue_depth", "slots_active", "tokens_per_s", "max_len"} <= set(s)


@pytest.mark.slow
class TestShardedEngine:
    def test_tp_sharded_engine_matches_single_device(self, params):
        """The sharded + continuous-batching paths COMPOSE: params placed
        per the tp template, GSPMD propagates head-sharding through
        prefill and the slot step, tokens identical to the unsharded
        engine (and therefore to sequential generate())."""
        from polyaxon_tpu.models.decode import decode_param_shardings
        from polyaxon_tpu.parallel import template_for
        from polyaxon_tpu.runtime.mesh import build_mesh

        mesh_axes = {"tensor": jax.local_device_count()}
        mesh = build_mesh(mesh_axes)
        template = template_for("tp", mesh_axes)
        shardings = decode_param_shardings(CFG, mesh, template, params=params)
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48,
            mesh=mesh, param_shardings=shardings,
        ).start()
        try:
            rng = np.random.default_rng(9)
            shapes = [(5, 8), (9, 4), (3, 12)]
            prompts = [list(rng.integers(0, 64, t)) for t, _ in shapes]
            reqs = [eng.submit(p, mn) for p, (_, mn) in zip(prompts, shapes)]
            outs = [r.wait(timeout=300) for r in reqs]
        finally:
            eng.stop()
        for p, (_, mn), out in zip(prompts, shapes, outs):
            assert out == _ref(params, p, mn)


class TestEngineUtilization:
    def test_stats_carry_decode_utilization(self, params, engine):
        engine.submit([1, 2, 3], 4).wait(timeout=60)
        s = engine.stats()
        assert {"decode_busy_frac", "slot_occupancy", "decode_utilization"} <= set(s)
        # Real decode work happened, so the busy fraction is a genuine
        # fraction — not zero, and bounded by wall clock.
        assert 0.0 < s["decode_busy_frac"] <= 1.0
        assert 0.0 < s["slot_occupancy"] <= 1.0
        assert s["decode_utilization"] == pytest.approx(
            s["decode_busy_frac"] * s["slot_occupancy"], abs=1e-5
        )

    def test_engine_ships_final_ledger_row_on_stop(self, params):
        from polyaxon_tpu.serving import ServingEngine
        from polyaxon_tpu.tracking.ledger import get_ledger

        rows = []
        get_ledger().configure(sink=rows.append)
        try:
            eng = ServingEngine(params, CFG, slots=2, max_len=48).start()
            eng.submit([1, 2, 3], 4).wait(timeout=60)
            eng.stop()
        finally:
            get_ledger().configure(sink=None)
        final = [r for r in rows if r["final"]]
        assert final, "engine.stop() must flush a final ledger row"
        row = final[-1]
        assert row["source"] == "serving"
        assert row["tokens"] >= 4
        # Decode busy time is accounted as step-compute directly.
        assert row["buckets"]["step_compute_s"] > 0
        assert 0.0 < row["goodput"] <= 1.0
        assert 0.0 < row["extra"]["decode_busy_frac"] <= 1.0
        # Pool accounting rides the same row: /goodput HBM math needs
        # the true pool bytes (and sees them shrink under kv_quantize).
        assert row["extra"]["kv_pool_bytes"] > 0
        assert row["extra"]["kv_dtype"] == "float32"
        # and the weights the programs read, beside the pool
        assert row["extra"]["weight_bytes"] == eng.weight_bytes > 0
        assert row["extra"]["weight_dtype"] == "float32"

    def test_final_ledger_row_reports_quantized_pool(self, params):
        from polyaxon_tpu.serving import ServingEngine
        from polyaxon_tpu.tracking.ledger import get_ledger

        rows = []
        get_ledger().configure(sink=rows.append)
        try:
            eng = ServingEngine(
                params, CFG, slots=2, max_len=48, kv_quantize="int8"
            ).start()
            eng.submit([1, 2, 3], 4).wait(timeout=60)
            eng.stop()
        finally:
            get_ledger().configure(sink=None)
        row = [r for r in rows if r["final"]][-1]
        assert row["extra"]["kv_dtype"] == "int8"
        assert row["extra"]["kv_pool_bytes"] > 0
