"""The reduction from trace events to numbers, on a small recorded trace, and
the rooflines' operation and byte counts against hand counts."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark.readers import step_mfu, trace_idle, trace_kernel_roofline
from benchmark.roofline import flash, model_flops
from benchmark.trace import reduce as tr

ROOT = Path(__file__).resolve().parents[2]
PEAK = json.loads((ROOT / "benchmark/peaks.json").read_text())["TPU v5 lite"]
CONFIGS = {p.stem: json.loads(p.read_text()) for p in (ROOT / "benchmark/configs").glob("*.json")}

# Two devices, ns x 1000.  Device 0: ops at [100,300) [250,400) [600,700) inside a
# while over [100,400): union 400, gaps [0,100) [400,600).  Device 1: [0,500):
# union 500.  The window is the device ops' span, [0,700).
EVENTS = {
    "devices": {
        "/device:TPU:0": {
            "ops": [["while.3", 100_000, 300_000],
                    ["fusion.1", 100_000, 200_000], ["flash_fwd", 250_000, 150_000],
                    ["fusion.1", 600_000, 100_000]],
            "modules": [["jit_step(1)", 100_000, 300_000], ["jit_step(1)", 600_000, 100_000]],
        },
        "/device:TPU:1": {"ops": [["fusion.1", 0, 500_000]], "modules": []},
    },
    "host": [
        ["python3", "$engine.py:1 _loop", 0, 1_000_000],
        ["python3", "$engine.py:2 _step_once", 380_000, 240_000],
        ["python3", "$paging.py:3 offer", 450_000, 100_000],
        ["http", "$server.py:9 handle", 0, 60_000],
    ],
}


def test_busy_union_idle_share_and_per_op_sums_equal_hand_counts():
    r = tr.reduce(EVENTS)
    assert r["window_s"] == pytest.approx(700e-6)
    assert r["busy_by_device"]["/device:TPU:0"] == pytest.approx(400e-6)
    assert r["busy_by_device"]["/device:TPU:1"] == pytest.approx(500e-6)
    assert r["busy_s"] == pytest.approx(450e-6)
    assert r["idle_share"] == pytest.approx(1 - 450 / 700)
    assert r["idle_share_worst"] == pytest.approx(1 - 400 / 700)
    assert "while.3" not in {name for name, _, _ in r["ops"]}  # holds other ops only
    ops = {name: (s, n) for name, s, n in r["ops"]}
    assert ops["fusion.1"] == (pytest.approx(400e-6), 1.5)
    assert ops["flash_fwd"] == (pytest.approx(75e-6), 0.5)
    assert dict((n, s) for n, s, _ in r["modules"])["jit_step(1)"] == pytest.approx(200e-6)
    assert trace_idle.read({"trace": r}, {"which": "mean"}) == pytest.approx(100 * (1 - 450 / 700))
    assert trace_idle.read({"trace": None}, {}) is None


def test_gaps_go_to_the_innermost_host_span_at_their_middle():
    gaps = dict(tr.reduce(EVENTS)["idle_gaps"])
    # [400,600): middle 500 lies in offer, inside _step_once, inside _loop.
    assert gaps["python3:$paging.py:3 offer"] == pytest.approx(200e-6)
    # [0,100): middle 50: _loop and handle both cover it and both start at 0.
    assert sum(gaps.values()) == pytest.approx(300e-6)
    assert tr.short_name("%convert.18 = bf16[24,8192,2048]{2,1,0:T(8,128)} convert(f32[24,8192,2048] %p)") \
        == "convert.18_bf16_24_8192_2048"
    assert tr.short_name("%while.3 = (s32[]{:T(128)}, bf16[56,1,2048]) while(...)") == "while.3"
    b = tr.breakdown(tr.reduce(EVENTS))
    assert len(b["device_ops"]) <= 10 and b["device_ops"][0][0] == "fusion.1"


def test_union_of_nested_and_touching_intervals():
    s = np.array([0, 10, 10, 40, 45], np.int64)
    e = np.array([30, 20, 35, 50, 48], np.int64)
    us, ue = tr.union_intervals(s, e)
    assert list(us) == [0, 40] and list(ue) == [35, 50]


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "host": EVENTS["host"]})


def test_model_flops_against_hand_counts():
    m = CONFIGS["mistral-7b-v0.3-train"]
    layer = 4096 * 128 * (2 * 32 + 2 * 8) + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert model_flops.matmul_params(m) == 2 * layer + 4096 * 32768
    attn = 4 * 2 * 32 * 128  # QK^T and PV, both layers, one query against one key
    assert model_flops.train_flops_per_token(m, 8192) == 6 * (2 * layer + 4096 * 32768) + 3 * attn * 4096
    v = CONFIGS["mistral-7b-v0.3-serve"]
    n = 4 * layer + 4096 * 32768
    assert model_flops.matmul_params(v) == n
    assert model_flops.decode_flops(v, 30, 30 * 350) == 2 * n * 30 + 4 * 4 * 32 * 128 * 30 * 350
    assert model_flops.prefill_flops(v, 100, 50) == 2 * n * 100 + 4 * 4 * 32 * 128 * (100 * 50 + 5000)


def test_rooflines_against_hand_counts_at_the_cells_shapes():
    f = flash.needs(3, 8192, 32, 8, 128)
    unit = 3 * 32 * 8192 * 8192 * 128 / 2
    assert f["fwd_flops"] == 4 * unit and f["bwd_flops"] == 10 * unit
    least = flash.least_seconds(3, 8192, 32, 8, 128, PEAK)
    assert least["bound"] == "compute" and least["seconds"] == pytest.approx(14 * unit / 197e12)


def test_every_share_reads_under_100_at_the_ledgers_measured_times():
    # doc-QA cell: 88 requests of about 9,000 prompt tokens, 74 % of them cached, and
    # 64 answer tokens each in a 51 s window (my chip run, PR 26).
    v = CONFIGS["mistral-7b-v0.3-serve"]
    serve = {"window_s": 51.0, "hit_share": 0.745, "measured": [
        {"ok": True, "prompt_tokens": 9000, "output_tokens": 64} for _ in range(88)]}
    assert 2 < step_mfu.read({"config": v, "peak": PEAK, "chips": 1, "serve": serve},
                             {"kind": "closed"}) < 100
    # train cell: 26,225 tokens/s at T = 8192 on one chip (ledger, PR 23).
    m = CONFIGS["mistral-7b-v0.3-train"]
    run = {"config": m, "peak": PEAK, "chips": 1, "train": {"tokens_per_s": 26225.4, "seq": 8192}}
    assert 30 < step_mfu.read(run, {"kind": "train"}) < 100
    # a reader with nothing to read returns nothing, never 0
    assert trace_kernel_roofline.read({"trace": {"ops": [], "modules": []}, "config": m, "peak": PEAK},
                                      {"roofline": "flash", "match": ["flash_fwd"]}) is None


def test_recorded_trace_reduces_to_the_counted_values():
    """45 real device ops of the doc-QA cell (``benchmark/trace/recorded_small.json``):
    the union is counted again here by walking the sorted intervals."""
    events = json.loads((ROOT / "benchmark/trace/recorded_small.json").read_text())
    ops = events["devices"]["/device:TPU:0"]["ops"]
    busy, reach = 0, 0
    for _, start, dur in sorted(ops, key=lambda o: o[1]):
        busy += max(0, start + dur - max(start, reach))
        reach = max(reach, start + dur)
    r = tr.reduce(events)
    assert r["busy_s"] == pytest.approx(busy / 1e9) and busy == 10_953_805
    assert r["window_s"] == pytest.approx(0.010960256)
    assert 0 < r["idle_share"] < 0.001
    by_name = {n: s for n, s, _ in r["ops"]}
    assert by_name["copy.60_bf16_4_8193_16_8_128"] == pytest.approx(0.003242896)
    assert sum(by_name.values()) == pytest.approx(sum(o[2] for o in ops) / 1e9)
    assert all(name.startswith("jit_") for name, _, _ in r["modules"])
