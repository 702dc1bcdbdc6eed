"""The seven metrics that read what rides on the engine's phase clock beside
the phases (uncovered seconds, the laps of a decode step's host side, the
thread's CPU seconds): each metric's file is read as the harness reads it, on
two recorded ``/v1/stats`` snapshots, and a program from before the instruments
gives ``None``, not a zero and not an error."""

import importlib
import json
from pathlib import Path

import pytest

from benchmark.harness.manifest import Manifest
from benchmark.readers import stats_per

ROOT = Path(__file__).resolve().parents[2]
SERVED = ["serve-mistral7b-docqa", "serve-olmohybrid7b-docqa",
          "serve-joyaiflash-docqa", "serve-lagunas21-docqa"]
LAPS = ["inputs", "key", "upload", "dispatch"]
METRICS = (["serve.loop_uncovered_share", "serve.loop_uncovered_paging_share",
            "serve.loop_host_off_cpu_share"] + [f"serve.step_{lap}_us" for lap in LAPS])

#: The clock's keys of two ``/v1/stats`` reads of one engine, the numbers round
#: ones: 50 busy seconds between them, 2,000 decode steps.
OPEN = {
    "loop_wall_s": 10.0, "loop_idle_s": 1.0, "loop_decode_host_s": 0.5, "loop_decode_host_n": 100,
    "loop_paging_match_s": 0.1, "loop_paging_offer_s": 0.1, "loop_paging_alloc_s": 1.0,
    "uncovered_s": 2.0, "uncovered_paging_match_s": 0.1, "uncovered_paging_offer_s": 0.1,
    "uncovered_paging_alloc_s": 0.5, "uncovered_decode_host_s": 0.5,
    "decode_host_inputs_s": 0.1, "decode_host_key_s": 0.05, "decode_host_upload_s": 0.2,
    "decode_host_dispatch_s": 0.1, "host_cpu_s": 2.5, "host_off_cpu_s": 0.5,
    "device_reads": 110, "device_reads_ready": 3,
}
CLOSE = {
    "loop_wall_s": 61.0, "loop_idle_s": 2.0, "loop_decode_host_s": 6.5, "loop_decode_host_n": 2100,
    "loop_paging_match_s": 0.3, "loop_paging_offer_s": 0.6, "loop_paging_alloc_s": 11.0,
    "uncovered_s": 22.0, "uncovered_paging_match_s": 0.3, "uncovered_paging_offer_s": 0.6,
    "uncovered_paging_alloc_s": 9.5, "uncovered_decode_host_s": 6.0,
    "decode_host_inputs_s": 1.1, "decode_host_key_s": 0.45, "decode_host_upload_s": 3.2,
    "decode_host_dispatch_s": 1.3, "host_cpu_s": 20.5, "host_off_cpu_s": 3.0,
    "device_reads": 2400, "device_reads_ready": 40,
}
BUSY, STEPS = (61.0 - 10.0) - (2.0 - 1.0), 2100 - 100
#: What a program from before the instruments reports: the phases alone.
PARENT_OPEN = {k: v for k, v in OPEN.items() if k.startswith("loop_")}
PARENT_CLOSE = {k: v for k, v in CLOSE.items() if k.startswith("loop_")}


def _run(first=OPEN, last=CLOSE):
    return {"serve": {"stats_open": first, "stats_close": last, "window_s": 51.0}}


def _spec(metric):
    return json.loads((ROOT / "benchmark/layer_metrics" / f"{metric}.json").read_text())


def _read(metric, run):
    spec = _spec(metric)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(run, spec.get("args") or {})


@pytest.mark.parametrize("metric", METRICS)
def test_the_file_agrees_with_the_manifest_and_lists_the_served_cells(metric):
    manifest = Manifest(ROOT)
    manifest.check()
    spec = _spec(metric)
    entry = next(m for m in manifest.data["per_layer"] if m["name"] == metric)
    assert {k: spec[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    # SERVED are listed; a cell added later may be too, where it reports the rate.
    assert set(SERVED) <= set(entry["workloads"]) and entry["moves"] == "serve_tokens_per_s"
    for cell in entry["workloads"]:
        assert "serve_tokens_per_s" in {m["name"] for m in manifest.cell(cell).end_to_end}, cell
    assert entry["better"] == "lower"
    assert (ROOT / "benchmark/readers" / f"{spec['reader']}.py").exists()
    assert set(METRICS) <= {m["name"] for m in manifest.data["per_layer"]}
    for cell in SERVED:
        assert metric in {m["name"] for m in manifest.cell(cell).per_layer}
    assert metric not in {m["name"] for m in manifest.cell("train-mistral7b-8k").per_layer}


@pytest.mark.parametrize("metric, reads", [
    ("serve.loop_uncovered_share", 100.0 * (22.0 - 2.0) / BUSY),
    ("serve.loop_uncovered_paging_share", 100.0 * (0.2 + 0.5 + 9.0) / BUSY),
    ("serve.loop_host_off_cpu_share", 100.0 * 2.5 / BUSY),
    ("serve.step_inputs_us", 1e6 * 1.0 / STEPS),
    ("serve.step_key_us", 1e6 * 0.4 / STEPS),
    ("serve.step_upload_us", 1e6 * 3.0 / STEPS),
    ("serve.step_dispatch_us", 1e6 * 1.2 / STEPS),
])
def test_each_metric_on_the_recorded_pair(metric, reads):
    assert _read(metric, _run()) == pytest.approx(reads)


def test_the_laps_a_step_are_a_part_of_the_steps_host_seconds():
    laps = sum(_read(f"serve.step_{lap}_us", _run()) for lap in LAPS)
    whole = 1e6 * (CLOSE["loop_decode_host_s"] - OPEN["loop_decode_host_s"]) / STEPS
    assert laps == pytest.approx(2800.0) and laps <= whole


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("run", [
    {}, {"serve": None}, _run(PARENT_OPEN, PARENT_CLOSE), _run(OPEN, PARENT_CLOSE),
    _run(PARENT_OPEN, CLOSE),
], ids=["train_cell", "no_serve", "parent", "key_missing_at_close", "key_missing_at_open"])
def test_nothing_to_read_is_none(metric, run):
    assert _read(metric, run) is None


@pytest.mark.parametrize("args, run, reads", [
    ({"keys": ["a_s", "b_s"], "per": "n"}, _run({"a_s": 1.0, "b_s": 2.0, "n": 4},
                                                {"a_s": 2.0, "b_s": 5.0, "n": 12}), 0.5),
    ({"keys": ["a_s"], "per": "n", "scale": 1000}, _run({"a_s": 1.0, "n": 4},
                                                        {"a_s": 1.5, "n": 5}), 500.0),
    ({"keys": ["a_s"], "per": "n"}, _run({"a_s": 1.0, "n": 4}, {"a_s": 1.5, "n": 4}), None),
    ({"keys": ["a_s"], "per": "n"}, _run({"a_s": 1.0}, {"a_s": 1.5, "n": 4}), None),
    ({"keys": ["a_s", "b_s"], "per": "n"}, _run({"a_s": 1.0, "n": 4},
                                                {"a_s": 1.5, "b_s": 1.0, "n": 8}), None),
], ids=["sum_of_keys", "scaled", "per_did_not_grow", "per_missing", "a_key_missing"])
def test_stats_per_is_growth_over_growth(args, run, reads):
    value = stats_per.read(run, args)
    assert value is None if reads is None else value == pytest.approx(reads)
