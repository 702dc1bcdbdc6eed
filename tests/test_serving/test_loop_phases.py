"""The engine loop accounts for itself: the phase clock's whole-run counters
in ``stats()``, and the same phases as annotations while a capture is on.

One toy engine serves a few shared-prefix requests once (module fixture);
every case below reads what that run left.  No timing is asserted: only
that the keys are there, never shrink, add up, and count the calls made.
"""

import threading
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.analysis.rules import _SPAN_NAMES
from polyaxon_tpu.models import TransformerConfig, init_params
from polyaxon_tpu.serving import ServingEngine
from polyaxon_tpu.serving.engine import (
    LOOP_PHASES, PH_DECODE_HOST, PH_IDLE, STEP_LAPS, _phase_key, _stats_key,
)
from polyaxon_tpu.tracking.trace import get_tracer

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8, d_ff=64,
    max_seq=48, dtype=jnp.float32,
)
PHASE_KEYS = [_stats_key(p) for p in LOOP_PHASES]
LOOP_KEYS = ["loop_wall_s"] + [k + s for k in PHASE_KEYS for s in ("_s", "_n")]
#: What rides on the clock beside the phases: parts of their seconds.
UNCOVERED_KEYS = [f"uncovered_{_phase_key(p)}_s" for p in LOOP_PHASES if p != PH_IDLE]
LAP_KEYS = [f"decode_host_{lap}_s" for lap in STEP_LAPS]
INSTRUMENT_KEYS = (["uncovered_s"] + UNCOVERED_KEYS + LAP_KEYS
                   + ["host_cpu_s", "host_off_cpu_s", "device_reads", "device_reads_ready"])
READS = ("before", "first", "second", "final")


def _counting(calls, name, fn):
    def wrapper(*args, **kwargs):
        calls[name] += 1
        return fn(*args, **kwargs)

    return wrapper


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` during the second wave."""

    names = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Annotation.names.append((threading.current_thread().name, self.name))

    def __exit__(self, *exc):
        return False


@pytest.fixture(scope="module")
def served():
    params = init_params(jax.random.PRNGKey(0), CFG)
    # A pool of 11 usable blocks under prompts of 2-3: the second wave evicts.
    eng = ServingEngine(params, CFG, slots=2, max_len=48, block_size=8,
                        num_blocks=12, prefill_chunk=5, prefix_cache=True)
    calls = {"match": 0, "offer": 0, "alloc": 0, "release": 0}
    eng.prefix_cache.match = _counting(calls, "match", eng.prefix_cache.match)
    eng.prefix_cache.offer = _counting(calls, "offer", eng.prefix_cache.offer)
    eng._alloc_block = _counting(calls, "alloc", eng._alloc_block)
    eng._release_slot_blocks = _counting(calls, "release", eng._release_slot_blocks)
    before = eng.stats()  # the loop has not started: everything reads 0
    eng.start()
    rng = np.random.default_rng(5)
    shared = list(rng.integers(0, 64, 16))  # two full blocks
    dup = list(rng.integers(0, 64, 16))  # block-aligned: copy-on-write
    tracer = get_tracer()
    _Annotation.names = []
    try:
        wave1 = [(shared + list(rng.integers(0, 64, 7)), 6), (dup, 5), (dup, 5),
                 (shared + list(rng.integers(0, 64, 3)), 4)]
        for prompt, new in wave1:
            eng.submit(prompt, new).wait(timeout=120)
        first = eng.stats()
        tracer.profiler_hook = _Annotation
        wave2 = [(list(rng.integers(0, 64, 20)), 5) for _ in range(4)]
        wave2 += [(shared + list(rng.integers(0, 64, 9)), 7)]
        reqs = [eng.submit(p, n) for p, n in wave2]
        for r in reqs:
            r.wait(timeout=120)
        tracer.profiler_hook = None
        second = eng.stats()
    finally:
        tracer.profiler_hook = None
        eng.stop()
    yield SimpleNamespace(before=before, first=first, second=second,
                          final=eng.stats(), calls=calls, n_requests=9,
                          annotated=list(_Annotation.names))


@pytest.mark.parametrize("key", LOOP_KEYS)
def test_stats_carry_every_loop_key_and_none_shrinks(served, key):
    values = [s[key] for s in (served.before, served.first, served.second, served.final)]
    assert values[0] == 0
    assert values == sorted(values)
    assert isinstance(values[-1], int if key.endswith("_n") else float)


@pytest.mark.parametrize("key", INSTRUMENT_KEYS)
def test_stats_carry_every_instrument_key_and_none_shrinks(served, key):
    values = [getattr(served, name)[key] for name in READS]
    assert values[0] == 0
    # The two CPU figures leave out what the waits burn by an estimate (one
    # visit in 64 is read), and the CPU backend computes on the thread that
    # waits for it: over a few dozen reads they may shrink.
    assert values == sorted(values) or key in ("host_cpu_s", "host_off_cpu_s")
    assert isinstance(values[-1], int if key.startswith("device_reads") else float)
    assert not key.startswith("loop_")  # the loop_*_s keys alone sum to the wall


@pytest.mark.parametrize("between", [("before", "first"), ("first", "second"),
                                     ("before", "final")])
def test_phase_seconds_sum_to_the_loop_wall_between_two_reads(served, between):
    a, b = (getattr(served, name) for name in between)
    wall = b["loop_wall_s"] - a["loop_wall_s"]
    phases = sum(b[k + "_s"] - a[k + "_s"] for k in PHASE_KEYS)
    assert wall > 0
    # Each key is rounded to the microsecond on its own.
    assert phases == pytest.approx(wall, rel=0.01, abs=2e-5)
    # ... and every key that starts with loop_ and ends with _s is one of them.
    named = {k for k in b if k.startswith("loop_") and k.endswith("_s")}
    assert named == {"loop_wall_s"} | {k + "_s" for k in PHASE_KEYS}


@pytest.mark.parametrize("read", READS[1:])
def test_uncovered_seconds_are_a_part_of_each_phase_and_of_the_busy_time(served, read):
    stats = getattr(served, read)
    for phase in LOOP_PHASES:
        if phase != PH_IDLE:
            part = stats[f"uncovered_{_phase_key(phase)}_s"]
            assert 0.0 <= part <= stats[_stats_key(phase) + "_s"] + 1e-6
    assert "uncovered_idle_s" not in stats  # idling is no work
    assert stats["uncovered_s"] == pytest.approx(
        sum(stats[k] for k in UNCOVERED_KEYS), abs=1e-9)
    busy = stats["loop_wall_s"] - stats["loop_idle_s"]
    assert 0.0 < stats["uncovered_s"] <= busy + 2e-5
    # A chunk's and a step's device time lie under the blocking reads.
    assert stats["uncovered_device_wait_s"] < 0.5 * stats["loop_device_wait_s"]


@pytest.mark.parametrize("read", READS[1:])
def test_the_laps_are_a_part_of_the_decode_steps_host_side(served, read):
    stats = getattr(served, read)
    laps = [stats[k] for k in LAP_KEYS]
    assert all(v > 0.0 for v in laps)
    assert sum(laps) <= stats[_stats_key(PH_DECODE_HOST) + "_s"] + 1e-5


def test_every_blocking_read_is_counted_and_cpu_time_is_host_time(served):
    final = served.final
    assert final["device_reads"] == final["loop_device_wait_n"]
    assert 0 <= final["device_reads_ready"] <= final["device_reads"]
    host = final["loop_wall_s"] - final["loop_idle_s"] - final["loop_device_wait_s"]
    assert 0.0 < final["host_cpu_s"] <= final["loop_wall_s"]
    assert final["host_cpu_s"] + final["host_off_cpu_s"] == pytest.approx(host, abs=1e-5)


@pytest.mark.parametrize("key, made", [
    ("loop_paging_match_n", lambda calls, stats: calls["match"]),
    ("loop_paging_offer_n", lambda calls, stats: calls["offer"]),
    # _alloc_block, the release at every retire, the decref of each copy-on-write
    ("loop_paging_alloc_n",
     lambda calls, stats: calls["alloc"] + calls["release"] + stats["cow_copies"]),
    ("loop_decode_host_n", lambda calls, stats: stats["decode_steps"]),
])
def test_phase_counts_are_the_calls_made(served, key, made):
    final, calls = served.final, served.calls
    assert calls["match"] == calls["offer"] == calls["release"] == served.n_requests
    assert final["cow_copies"] >= 1 and final["prefix_cache_evictions"] >= 1
    assert final[key] == made(calls, final) > 0


def test_device_wait_is_one_read_a_step_and_one_a_prompt(served):
    final = served.final
    assert final["loop_device_wait_n"] == final["decode_steps"] + served.n_requests


def test_busy_fraction_comes_from_the_same_clock(served):
    final = served.final
    busy = final["loop_wall_s"] - final["loop_idle_s"]
    assert final["decode_busy_frac"] == pytest.approx(busy / final["loop_wall_s"], abs=1e-3)
    assert 0.0 < final["decode_busy_frac"] <= 1.0 and 0.0 < final["slot_occupancy"] <= 1.0


def test_annotations_during_a_capture_are_the_catalog_on_the_engine_thread(served):
    threads = {t for t, _ in served.annotated}
    names = {n for _, n in served.annotated}
    laps = {f"{PH_DECODE_HOST}.{lap}" for lap in STEP_LAPS}
    assert threads == {"serving-engine"}
    assert names <= set(LOOP_PHASES) | laps <= _SPAN_NAMES
    # Everything a served request passes through showed up under its own name.
    assert names >= (set(LOOP_PHASES) | laps) - {"serving.loop.idle"}
