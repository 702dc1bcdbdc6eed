"""The readers of the engine's phase-clock counters, on a recorded pair of
``/v1/stats`` snapshots: each new metric's file is read as the harness reads
it, and a program without the counters gives ``None``, not an error."""

import importlib
import json
from pathlib import Path

import pytest

from benchmark.readers import stats_share

ROOT = Path(__file__).resolve().parents[2]

#: The loop_* keys and the eviction counter of the two ``/v1/stats`` snapshots of
#: one doc-QA window on the chip (PR 27, seed 2147483777, from the git archive).
OPEN = {
    "loop_admit_s": 0.002824, "loop_bookkeeping_s": 0.051387, "loop_decode_host_s": 0.399371,
    "loop_device_wait_s": 9.277719, "loop_emit_s": 0.016091, "loop_idle_s": 0.015502,
    "loop_other_s": 0.004028, "loop_paging_alloc_s": 0.009735, "loop_paging_match_s": 0.025727,
    "loop_paging_offer_s": 0.408996, "loop_prefill_host_s": 0.178437, "loop_wall_s": 10.389817,
    "prefix_cache_evictions": 0,
}
CLOSE = {
    "loop_admit_s": 0.026444, "loop_bookkeeping_s": 0.268284, "loop_decode_host_s": 2.847441,
    "loop_device_wait_s": 53.333371, "loop_emit_s": 0.115578, "loop_idle_s": 0.015502,
    "loop_other_s": 0.025082, "loop_paging_alloc_s": 2.086915, "loop_paging_match_s": 0.123967,
    "loop_paging_offer_s": 1.669752, "loop_prefill_host_s": 0.87621, "loop_wall_s": 61.388544,
    "prefix_cache_evictions": 7299,
}
BUSY = (61.388544 - 10.389817) - (0.015502 - 0.015502)  # 50.998727 s, none of it idle


def _run(first=OPEN, last=CLOSE):
    return {"serve": {"stats_open": first, "stats_close": last, "window_s": 51.0}}


def _read(metric, run):
    spec = json.loads((ROOT / "benchmark/layer_metrics" / f"{metric}.json").read_text())
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(run, spec.get("args") or {})


@pytest.mark.parametrize("metric, seconds, reads", [
    ("serve.loop_paging_share", 0.09824 + 1.260756 + 2.07718, 6.7378),
    ("serve.loop_device_wait_share", 44.055652, 86.3858),
    ("serve.loop_step_host_share", 0.697773 + 2.44807 + 0.099487, 6.3636),
    ("serve.loop_bookkeeping_share", 0.216897, 0.4253),
])
def test_share_of_the_busy_loop_seconds(metric, seconds, reads):
    value = _read(metric, _run())
    assert value == pytest.approx(100.0 * seconds / BUSY)
    assert value == pytest.approx(reads, abs=1e-4)  # what that run's result line printed


def test_the_recorded_shares_leave_the_rest_to_admit_and_other():
    named = sum(_read(m, _run()) for m in (
        "serve.loop_paging_share", "serve.loop_device_wait_share",
        "serve.loop_step_host_share", "serve.loop_bookkeeping_share"))
    rest = (0.026444 - 0.002824) + (0.025082 - 0.004028)
    assert named == pytest.approx(100.0 * (1 - rest / BUSY), abs=1e-4)


def test_evictions_per_second_of_the_window():
    assert _read("serve.evictions_per_s", _run()) == pytest.approx(7299 / 51.0)


@pytest.mark.parametrize("run", [
    {},
    {"serve": None},
    _run({"prefix_cache_hits": 1}, {"prefix_cache_hits": 9}),
    _run(OPEN, {k: v for k, v in CLOSE.items() if k != "loop_paging_offer_s"}),
    _run({k: v for k, v in OPEN.items() if k != "loop_idle_s"}, CLOSE),
    _run(OPEN, dict(CLOSE, loop_wall_s=10.389817)),
], ids=["train_cell", "no_serve", "program_without_the_clock", "one_key_missing_at_close",
        "idle_missing_at_open", "loop_never_busy"])
def test_nothing_to_read_is_none(run):
    args = {"keys": ["loop_paging_match_s", "loop_paging_offer_s"], "scale": 100}
    assert stats_share.read(run, args) is None
