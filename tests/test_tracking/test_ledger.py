"""Utilization-ledger accounting + the worker→watcher→registry flow.

Unit-level: bucket decomposition (sum == wall), goodput/MFU math, the
compile-hook fallback, analytic FLOPs helpers.  Pipeline-level: a real
Reporter writes ``ledger`` lines, GangWatcher ingests them, and
``goodput_status`` aggregates the gang — no subprocesses.
"""

import json
import time
from types import SimpleNamespace

import pytest

from polyaxon_tpu.db.registry import RunRegistry
from polyaxon_tpu.monitor.watcher import GangWatcher, goodput_status
from polyaxon_tpu.stores.layout import RunPaths
from polyaxon_tpu.tracking import ledger as ledger_mod
from polyaxon_tpu.tracking.ledger import (
    BUCKETS,
    UtilizationLedger,
    conv_classifier_flops_per_image,
    transformer_flops_per_token,
)
from polyaxon_tpu.tracking.reporter import Reporter

SPEC = {
    "kind": "experiment",
    "run": {"entrypoint": "polyaxon_tpu.builtins.trainers:noop"},
}


class TestLedgerAccounting:
    def test_buckets_sum_to_wall(self):
        led = UtilizationLedger(interval_s=1e9)
        led.start()
        led.account("data_wait_s", 0.002)
        led.step(0.01, tokens=100)
        led.step(0.01, tokens=100)
        time.sleep(0.03)
        row = led.snapshot()
        assert set(row["buckets"]) == set(BUCKETS)
        assert sum(row["buckets"].values()) == pytest.approx(
            row["wall_s"], rel=1e-6
        )
        # Idle absorbs the sleep the steps didn't cover.
        assert row["buckets"]["idle_s"] > 0
        assert row["steps"] == 2
        assert row["tokens"] == 200

    def test_step_compute_derived_from_step_wall_minus_waits(self):
        led = UtilizationLedger(interval_s=1e9)
        led.start()
        led.mark_loop_start()
        led.account("data_wait_s", 0.4)
        led.account("ckpt_block_s", 0.1)
        led.step(1.0)
        row = led.snapshot()
        assert row["buckets"]["step_compute_s"] == pytest.approx(0.5)

    def test_explicit_step_compute_wins_over_derivation(self):
        # The serving engine accounts device-busy time directly; the
        # derivation must not double-count on top of it.
        led = UtilizationLedger(interval_s=1e9)
        led.start(source="serving")
        led.account("step_compute_s", 0.25)
        led.step(tokens=4)
        row = led.snapshot()
        assert row["source"] == "serving"
        assert row["buckets"]["step_compute_s"] == pytest.approx(0.25)

    def test_goodput_clamped_to_one(self):
        led = UtilizationLedger(interval_s=1e9)
        led.start()
        led.account("step_compute_s", 99.0)  # absurd vs ~0 wall
        led.step()
        assert led.snapshot()["goodput"] == 1.0

    def test_flops_per_step_accumulates_and_mfu_needs_peak(self):
        led = UtilizationLedger(interval_s=1e9)
        led.start()
        led.set_flops_per_step(1e6)
        led.step(0.01)
        led.step(0.01, flops=5e5)  # explicit override for one step
        row = led.snapshot()
        assert row["flops"] == pytest.approx(1.5e6)
        # No known peak (CPU) → MFU honestly 0, not a made-up ratio.
        assert row["mfu"] == 0.0

    def test_flush_emits_seq_numbered_rows_through_sink(self):
        rows = []
        led = UtilizationLedger(sink=rows.append, process_id=3, interval_s=1e9)
        led.start()
        led.step(0.01, tokens=10)
        led.flush()
        led.step(0.01, tokens=10)
        led.flush(final=True)
        assert [r["seq"] for r in rows] == [1, 2]
        assert [r["final"] for r in rows] == [False, True]
        assert rows[1]["tokens"] == 20  # cumulative, not per-interval
        assert rows[0]["process_id"] == 3

    def test_sink_errors_never_propagate(self):
        def bad_sink(row):
            raise RuntimeError("sink down")

        led = UtilizationLedger(sink=bad_sink, interval_s=1e9)
        led.start()
        led.step(0.01)
        assert led.flush() is not None  # survives; telemetry can't kill

    def test_maybe_flush_throttles(self):
        rows = []
        led = UtilizationLedger(sink=rows.append, interval_s=60.0)
        led.start()
        for _ in range(5):
            led.step(0.001)
            led.maybe_flush()
        assert rows == []  # inside the interval: nothing emitted
        led.interval_s = 0.0
        led.step(0.001)
        assert led.maybe_flush() is True
        assert len(rows) == 1

    def test_unarmed_ledger_is_inert(self):
        rows = []
        led = UtilizationLedger(sink=rows.append)
        led.step(1.0)
        led.account("data_wait_s", 1.0)
        assert led.flush(final=True) is None
        assert rows == []


class TestCompileTelemetry:
    def test_install_hooks_and_measure_a_compile(self):
        import jax
        import jax.numpy as jnp

        assert ledger_mod.install_compile_hooks() is True
        s0, e0 = ledger_mod.compile_telemetry()

        @jax.jit
        def f(x):
            return (x * 2.0).sum()

        f(jnp.arange(8.0)).block_until_ready()
        s1, e1 = ledger_mod.compile_telemetry()
        assert s1 > s0  # backend_compile duration observed
        assert e1 > e0  # compile request counted

    def test_peak_table_unknown_tpu_kind_is_an_error(self):
        # The CPU has no peak (no MFU is claimed there); the v5e chip
        # reports "TPU v5 lite"; a TPU kind nobody recorded a sourced
        # peak for is an error, never a default or a silent 0.
        assert ledger_mod.peak_flops_per_chip("cpu", "cpu") == 0.0
        assert ledger_mod.peak_flops_per_chip("tpu", "TPU v5 lite") == 197e12
        with pytest.raises(KeyError, match="TPU v9"):
            ledger_mod.peak_flops_per_chip("tpu", "TPU v9")

    def test_start_snapshots_compile_baseline(self):
        import jax
        import jax.numpy as jnp

        ledger_mod.install_compile_hooks()

        @jax.jit
        def g(x):
            return x + 1

        g(jnp.ones(4)).block_until_ready()  # compile BEFORE start()
        led = UtilizationLedger(interval_s=1e9)
        led.start()
        row = led.snapshot()
        assert row["compile_s"] == pytest.approx(0.0, abs=1e-9)


class TestAnalyticFlops:
    def test_transformer_matches_bench_accounting(self):
        # 6N + 12·L·H·hd·T — same formula bench.py uses for headline MFU.
        assert transformer_flops_per_token(1000, 2, 4, 16, 64) == (
            6 * 1000 + 12 * 2 * 4 * 16 * 64
        )

    def test_conv_classifier_counts_macs_at_each_resolution(self):
        # One 3x3 SAME conv at 8x8 (3→4 ch) + dense head, ×3 for train.
        flops = conv_classifier_flops_per_image(8, 3, (4,), 16, 10)
        conv = 2 * 8 * 8 * 9 * 3 * 4
        flat = 4 * 4 * 4
        dense = 2 * flat * 16 + 2 * 16 * 10
        assert flops == pytest.approx(3 * (conv + dense))


@pytest.fixture()
def rig(tmp_path):
    registry = RunRegistry(tmp_path / "registry.sqlite")
    run = registry.create_run(SPEC, name="ledgered")
    paths = RunPaths(tmp_path / "run").ensure()
    handle = SimpleNamespace(
        run_id=run.id,
        run_uuid=run.uuid,
        plan=SimpleNamespace(num_hosts=2),
        paths=paths,
        report_offsets={},
    )
    yield registry, GangWatcher(registry), handle
    registry.close()


def _ledger_event(pid, seq, wall, step_compute, *, final=False, **over):
    buckets = {
        "xla_compile_s": 0.5,
        "data_wait_s": 0.2,
        "step_compute_s": step_compute,
        "ckpt_block_s": 0.1,
        "metric_drain_s": 0.0,
        "idle_s": max(0.0, wall - 0.8 - step_compute),
    }
    event = {
        "type": "ledger",
        "ts": 100.0 + seq,
        "source": "train",
        "process_id": pid,
        "seq": seq,
        "wall_s": wall,
        "buckets": buckets,
        "steps": seq * 10,
        "tokens": seq * 1000,
        "flops": seq * 1e9,
        "goodput": step_compute / wall,
        "mfu": 0.01 * seq,
        "tokens_per_device_s": 100.0,
        "compile_s": 0.5,
        "compile_events": 2,
        "hbm_peak_bytes": 1e9,
        "devices": 4,
        "device_kind": "TPU v4",
        "peak_flops_per_s": 4 * 275e12,
        "final": final,
    }
    event.update(over)
    return event


def _append(paths, process_id, events):
    with open(paths.report_file(process_id), "a", encoding="utf-8") as fh:
        for event in events:
            fh.write(json.dumps(event) + "\n")


class TestLedgerPipeline:
    def test_reporter_to_registry_roundtrip(self, rig):
        registry, watcher, handle = rig
        reporter = Reporter(handle.paths.report_file(0), process_id=0)
        reporter.ledger(_ledger_event(0, 1, 10.0, 8.0))
        reporter.close()
        watcher.ingest(handle)
        (row,) = registry.get_utilization(handle.run_id)
        assert row["wall_s"] == 10.0
        assert row["buckets"]["step_compute_s"] == 8.0
        assert row["process_id"] == 0
        assert row["device_kind"] == "TPU v4"

    def test_goodput_status_aggregates_latest_row_per_process(self, rig):
        registry, watcher, handle = rig
        _append(handle.paths, 0, [
            _ledger_event(0, 1, 5.0, 4.0),
            _ledger_event(0, 2, 10.0, 8.0, final=True),
        ])
        _append(handle.paths, 1, [
            _ledger_event(1, 1, 12.0, 6.0, final=True),
        ])
        watcher.ingest(handle)
        g = goodput_status(registry, handle.run_id)
        assert g["rows"] == 3
        assert g["processes"] == 2
        # Latest per process: (wall 10, sc 8) + (wall 12, sc 6).
        assert g["wall_s"] == 12.0
        assert g["buckets"]["step_compute_s"]["sum"] == pytest.approx(14.0)
        assert g["buckets"]["step_compute_s"]["min"] == 6.0
        assert g["buckets"]["step_compute_s"]["max"] == 8.0
        assert g["goodput_ratio"] == pytest.approx(14.0 / 22.0)
        # MFU recomputed from summed flops over max wall × summed peak.
        assert g["flops"] == pytest.approx(2e9 + 1e9)
        assert g["mfu"] == pytest.approx(3e9 / (12.0 * 8 * 275e12))
        assert g["final"] is True
        assert len(g["timeline"]) == 3
        assert g["timeline"][0]["mfu"] == 0.01

    def test_goodput_status_sums_kv_pool_bytes_from_extras(self, rig):
        """Serving engines ship their KV pool bytes under the row's
        free-form extras; /goodput surfaces the gang-wide sum so HBM
        accounting sees an int8 pool shrink."""
        registry, watcher, handle = rig
        _append(handle.paths, 0, [
            _ledger_event(0, 1, 5.0, 4.0, extra={"kv_pool_bytes": 1024}),
            _ledger_event(0, 2, 10.0, 8.0, final=True,
                          extra={"kv_pool_bytes": 384, "kv_dtype": "int8"}),
        ])
        _append(handle.paths, 1, [
            _ledger_event(1, 1, 12.0, 6.0, final=True,
                          extra={"kv_pool_bytes": 384, "kv_dtype": "int8"}),
        ])
        watcher.ingest(handle)
        g = goodput_status(registry, handle.run_id)
        # Latest row per process wins — 384 + 384, not the stale 1024.
        assert g["kv_pool_bytes"] == 768.0

    def test_goodput_status_aggregates_spec_counters_from_extras(self, rig):
        """Speculative-decoding engines ship proposed/accepted draft
        counts under extras; /goodput recomputes the gang-wide accept
        rate from the SUMS (never averages per-proc rates)."""
        registry, watcher, handle = rig
        _append(handle.paths, 0, [
            _ledger_event(0, 1, 10.0, 8.0, final=True, extra={
                "spec_proposed_total": 80, "spec_accepted_total": 60,
            }),
        ])
        _append(handle.paths, 1, [
            _ledger_event(1, 1, 10.0, 8.0, final=True, extra={
                "spec_proposed_total": 20, "spec_accepted_total": 5,
            }),
        ])
        watcher.ingest(handle)
        g = goodput_status(registry, handle.run_id)
        assert g["spec_accept_rate"] == pytest.approx(65 / 100)

    def test_goodput_status_spec_rate_zero_without_proposals(self, rig):
        registry, watcher, handle = rig
        _append(handle.paths, 0, [_ledger_event(0, 1, 10.0, 8.0, final=True)])
        watcher.ingest(handle)
        assert goodput_status(registry, handle.run_id)["spec_accept_rate"] == 0.0

    def test_goodput_status_empty_until_rows_land(self, rig):
        registry, _, handle = rig
        g = goodput_status(registry, handle.run_id)
        assert g["rows"] == 0
        assert g["buckets"] == {}
        assert g["goodput_ratio"] == 0.0

    def test_gauges_refresh_while_running_and_freeze_at_terminal(self, rig):
        registry, _, handle = rig

        class FakeStats:
            def __init__(self):
                self.gauges = {}
                self.sets = []

            def gauge(self, name, value):
                self.gauges[name] = value
                self.sets.append(name)

        stats = FakeStats()
        watcher = GangWatcher(registry, stats)
        # No rows yet: must not publish synthetic zeros.
        watcher._refresh_goodput_gauges(handle)
        assert "run_goodput_ratio" not in stats.gauges
        _append(handle.paths, 0, [_ledger_event(0, 1, 10.0, 8.0)])
        watcher.ingest(handle)
        watcher._refresh_goodput_gauges(handle)
        assert stats.gauges["run_goodput_ratio"] == pytest.approx(0.8)
        # MFU recomputed from flops/(wall × peak), not echoed per-row.
        assert stats.gauges["run_mfu"] == pytest.approx(
            1e9 / (10.0 * 4 * 275e12)
        )
        assert stats.gauges["run_compile_s_total"] == 0.5
        assert stats.gauges["run_hbm_peak_bytes"] == 1e9

        # Terminal: observe() does one final refresh, then freezes.
        handle.poll = lambda: {0: 0, 1: 0}
        registry.upsert_process(handle.run_id, 0, status="succeeded")
        registry.upsert_process(handle.run_id, 1, status="succeeded")
        n_before = len(stats.sets)
        watcher.observe(handle)
        assert stats.gauges["run_goodput_ratio"] == pytest.approx(0.8)
        assert getattr(handle, "goodput_frozen") is True
        n_frozen = len(stats.sets)
        assert n_frozen > n_before
        watcher.observe(handle)  # second terminal poll: no more sets
        assert len(stats.sets) == n_frozen
