"""Per-run goodput & utilization ledger.

The reference platform reports *that* a run finished; it never answers
the two questions a TPU platform exists to answer — what fraction of
wall-clock was useful training (Google's ML-productivity "goodput"
metric) and what fraction of peak FLOPs the run sustained (PaLM-style
MFU).  Until now MFU lived only in ``bench.py``, out-of-band.

:class:`UtilizationLedger` is the worker-side accountant that makes both
first-class: it decomposes a run's wall clock into named buckets
(xla-compile, data-wait, step-compute, checkpoint-block, metric-drain,
idle), tracks model FLOPs per step (XLA cost analysis when available,
analytic estimates otherwise), HBM high-water marks, and XLA compile
telemetry from ``jax.monitoring`` record hooks.
Rows flow as typed ``ledger`` report lines through the Reporter → the
GangWatcher ingests them into the registry's ``utilization`` table → the
API aggregates them gang-wide as ``GET /api/v1/runs/<id>/goodput``.

Process-wide singleton, same contract as ``trace.get_tracer()``:
workloads call :func:`get_ledger` and feed it; only the worker
entrypoint calls :func:`configure` to wire the report sink.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from polyaxon_tpu.conf.knobs import knob_float

__all__ = [
    "UtilizationLedger",
    "get_ledger",
    "configure",
    "install_compile_hooks",
    "compile_telemetry",
    "compile_cache_telemetry",
    "executable_flops",
    "transformer_flops_per_token",
    "conv_classifier_flops_per_image",
    "BUCKETS",
    "PEAK_FLOPS",
    "peak_flops_per_chip",
]

#: The wall-clock decomposition vocabulary.  Every ledger row's
#: ``buckets`` dict has exactly these keys; their sum equals the row's
#: ``wall_s`` (``idle_s`` is derived as the remainder, clamped at 0).
BUCKETS = (
    "xla_compile_s",
    "data_wait_s",
    "step_compute_s",
    "ckpt_block_s",
    "metric_drain_s",
    "idle_s",
)

#: bf16 peak FLOP/s per chip, keyed by the PJRT ``device_kind`` the
#: runtime reports.  Shared with ``bench.py`` and ``chip_smoke.py`` so the
#: platform's MFU and the benchmark's can never disagree about the
#: denominator.  Only kinds seen on hardware, each with its source:
#:
#: - ``"TPU v5 lite"`` — what a v5e chip reports; 197 TFLOP/s bf16
#:   (Google Cloud documentation, "TPU v5e").
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_flops_per_chip(platform: str, device_kind: str) -> float:
    """The MFU denominator for one device.  The CPU has no peak (0.0: no
    MFU is claimed there); a TPU kind missing from :data:`PEAK_FLOPS` is
    an error, not a default."""
    if platform == "cpu":
        return 0.0
    if device_kind not in PEAK_FLOPS:
        raise KeyError(
            f"no peak FLOP/s recorded for {platform} device kind "
            f"{device_kind!r}: add it to tracking/ledger.py:PEAK_FLOPS "
            f"with its source"
        )
    return PEAK_FLOPS[device_kind]

_UNSET = object()


# -- XLA compile telemetry (jax.monitoring record hooks) -----------------------

_compile_lock = threading.Lock()
_compile_seconds = 0.0
_compile_events = 0
_cache_hits = 0
_cache_misses = 0
_hooks_installed = False


def install_compile_hooks() -> bool:
    """Register ``jax.monitoring`` listeners for compile telemetry.

    Duration events under ``/jax/core/compile/`` (jaxpr trace, MLIR
    lowering, backend compile) accumulate into compile seconds; each
    ``compile_requests``/``cache_miss`` event counts one jit-cache miss.
    Idempotent.  Never imports jax itself (returns False until some
    workload has): callers arm the ledger from workloads that already did.
    """
    global _hooks_installed
    if _hooks_installed:
        return True
    if "jax" not in sys.modules:
        return False  # a later start() after the jax import retries
    from jax import monitoring

    def _on_duration(event: str, duration: float, **kw: Any) -> None:
        if "compile" in event:
            global _compile_seconds
            with _compile_lock:
                _compile_seconds += float(duration)

    def _on_event(event: str, **kw: Any) -> None:
        # With the persistent cache armed (runtime/compilecache.py) a
        # cold compile fires BOTH compile_requests and cache_miss, so
        # requests carry compile_events and hit/miss feed their own
        # counters.
        global _compile_events, _cache_hits, _cache_misses
        if "cache_hit" in event:
            with _compile_lock:
                _cache_hits += 1
        elif "cache_miss" in event:
            with _compile_lock:
                _cache_misses += 1
        elif "compile_requests" in event:
            with _compile_lock:
                _compile_events += 1

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _hooks_installed = True
    return True


def compile_telemetry() -> Tuple[float, int]:
    """(cumulative compile seconds, cumulative compile requests) so far."""
    with _compile_lock:
        return _compile_seconds, _compile_events


def compile_cache_telemetry() -> Tuple[int, int]:
    """(persistent-cache hits, misses) so far — both stay 0 while the
    cache is disabled."""
    with _compile_lock:
        return _cache_hits, _cache_misses


# -- FLOPs accounting ----------------------------------------------------------

def executable_flops(compiled: Any) -> Optional[float]:
    """Total FLOPs from an ALREADY-COMPILED executable's cost analysis.

    The free probe: callers that AOT-compiled their step anyway
    (``runtime/compilecache.aot_compile``) get the number without paying
    a second compile.  Returns None where the backend's analysis holds
    no FLOP count (callers fall back to the analytic estimates below)."""
    analysis = compiled.cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    flops = (analysis or {}).get("flops")
    if flops is not None and float(flops) > 0:
        return float(flops)
    return None


def transformer_flops_per_token(
    n_params: int, n_layers: int, n_heads: int, head_dim: int, seq: int
) -> float:
    """Train-step FLOPs per token: 6·N (fwd+bwd matmuls) + attention
    scores 12·L·H·hd·T (fwd+bwd, causal halves then doubles back) — the
    same accounting ``bench.py`` uses for its headline MFU."""
    return 6.0 * n_params + 12.0 * n_layers * n_heads * head_dim * seq


def conv_classifier_flops_per_image(
    image_size: int,
    in_channels: int,
    channels: Tuple[int, ...],
    dense_dim: int,
    n_classes: int,
) -> float:
    """Analytic train-step FLOPs per image for the builtin conv net
    (3x3 SAME convs + 2x2 maxpool per stage + dense head): 2 FLOPs per
    MAC forward, x3 for forward+backward."""
    flops = 0.0
    h = image_size
    cin = in_channels
    for cout in channels:
        flops += 2.0 * h * h * 9.0 * cin * cout
        h //= 2
        cin = cout
    flat = h * h * cin
    flops += 2.0 * flat * dense_dim + 2.0 * dense_dim * n_classes
    return 3.0 * flops


# -- the accountant ------------------------------------------------------------

class UtilizationLedger:
    """Wall-clock decomposition + live MFU accountant for one workload.

    Feeding is cheap (a lock + float adds): trainers call
    :meth:`step`/:meth:`account` per step and :meth:`maybe_flush` to
    emit a cumulative row at most every ``interval_s``; a final row with
    ``final=True`` goes out at workload exit.  Rows are cumulative
    (monotone totals, ``seq``-numbered) so the at-least-once report
    channel needs no dedup — consumers take the latest row per process.
    """

    def __init__(
        self,
        *,
        sink: Optional[Callable[[Dict[str, Any]], None]] = None,
        process_id: int = 0,
        interval_s: Optional[float] = None,
    ) -> None:
        self.sink = sink
        self.process_id = process_id
        if interval_s is None:
            interval_s = knob_float("POLYAXON_TPU_LEDGER_INTERVAL_S")
        self.interval_s = interval_s
        self._lock = threading.Lock()
        self._reset_locked()

    def _reset_locked(self) -> None:
        self.armed = False
        self.source = "train"
        self._t0_wall = 0.0
        self._p0 = 0.0
        self._acc: Dict[str, float] = {}
        self._step_wall_s = 0.0
        self.steps = 0
        self.tokens = 0
        self.flops = 0.0
        self._flops_per_step: Optional[float] = None
        self.devices = 0
        self.device_kind = ""
        self.peak_flops_per_s = 0.0
        self._hbm_peak_bytes = 0.0
        self._extra: Dict[str, Any] = {}
        self._seq = 0
        self._last_flush = 0.0
        self._compile0: Tuple[float, int] = (0.0, 0)
        self._cache0: Tuple[int, int] = (0, 0)
        self._compile_preloop: Optional[float] = None

    def reset(self) -> None:
        with self._lock:
            self._reset_locked()

    def configure(
        self,
        *,
        sink: Any = _UNSET,
        process_id: Any = _UNSET,
        interval_s: Any = _UNSET,
    ) -> "UtilizationLedger":
        """In-place update (the worker entrypoint is the only caller) —
        workloads holding a :func:`get_ledger` reference see the sink."""
        with self._lock:
            if sink is not _UNSET:
                self.sink = sink
            if process_id is not _UNSET:
                self.process_id = process_id
            if interval_s is not _UNSET:
                self.interval_s = interval_s
        return self

    # -- arming ----------------------------------------------------------------

    def start(self, *, source: str = "train") -> "UtilizationLedger":
        """Arm at workload entry: reset totals, snapshot the compile
        counters (so back-to-back workloads in one process don't inherit
        each other's compile time), probe local devices for the peak-FLOPs
        denominator.  Installs the compile hooks if jax is importable."""
        install_compile_hooks()
        with self._lock:
            sink, process_id, interval = self.sink, self.process_id, self.interval_s
            self._reset_locked()
            self.sink, self.process_id, self.interval_s = sink, process_id, interval
            self.armed = True
            self.source = source
            self._t0_wall = time.time()
            self._p0 = time.perf_counter()
            self._last_flush = self._p0
            self._compile0 = compile_telemetry()
            self._cache0 = compile_cache_telemetry()
        if "jax" in sys.modules:
            import jax

            devices = jax.local_devices()
            per_chip = peak_flops_per_chip(
                devices[0].platform, devices[0].device_kind
            )
            with self._lock:
                self.devices = len(devices)
                self.device_kind = devices[0].device_kind
                self.peak_flops_per_s = per_chip * len(devices)
        return self

    # -- feeding ---------------------------------------------------------------

    def set_flops_per_step(self, flops: Optional[float]) -> None:
        with self._lock:
            self._flops_per_step = float(flops) if flops else None

    def mark_loop_start(self) -> None:
        """Everything compiled from here on happened *inside* the hot loop
        — and therefore inside measured step wall — so the snapshot
        subtracts it from step-compute (first-step jit, in-loop FLOPs
        probes).  Falls back to the first :meth:`step` call when never
        invoked, which mis-files the first step's own compile as
        step-compute; call this right before the loop."""
        compile_s, _ = compile_telemetry()
        with self._lock:
            if self._compile_preloop is None:
                self._compile_preloop = compile_s - self._compile0[0]

    def merge_extra(self, **extra: Any) -> None:
        """Workload-specific fields for the row's attrs (e.g. the serving
        engine's slot occupancy)."""
        with self._lock:
            self._extra.update(extra)

    def account(self, bucket: str, seconds: float) -> None:
        """Fold externally measured seconds into a named bucket."""
        if seconds and seconds > 0:
            with self._lock:
                self._acc[bucket] = self._acc.get(bucket, 0.0) + float(seconds)

    def step(
        self,
        dt: Optional[float] = None,
        *,
        tokens: int = 0,
        flops: Optional[float] = None,
    ) -> None:
        """One training/decode step: ``dt`` is its wall seconds (omit when
        the workload accounts ``step_compute_s`` explicitly), ``tokens``
        the examples/tokens it advanced."""
        compile_s, _ = compile_telemetry()
        with self._lock:
            if self._compile_preloop is None:
                # Compile seconds before the first step (jit_init, cost
                # probes) must not be subtracted from step wall below.
                self._compile_preloop = compile_s - self._compile0[0]
            self.steps += 1
            self.tokens += int(tokens)
            if dt is not None and dt > 0:
                self._step_wall_s += float(dt)
            if flops is not None:
                self.flops += float(flops)
            elif self._flops_per_step is not None:
                self.flops += self._flops_per_step

    def sample_hbm(self) -> float:
        """Refresh the HBM high-water mark from ``memory_stats()`` (the
        CPU backend reports none: 0 there)."""
        total = 0.0
        if "jax" in sys.modules:
            import jax

            for d in jax.local_devices():
                stats = d.memory_stats() or {}
                peak = stats.get("peak_bytes_in_use")
                if peak is None:
                    peak = stats.get("bytes_in_use")
                if peak:
                    total += float(peak)
        with self._lock:
            if total > self._hbm_peak_bytes:
                self._hbm_peak_bytes = total
            return self._hbm_peak_bytes

    # -- reading / emitting ----------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """Cumulative totals as one row: the bucket decomposition (summing
        to ``wall_s``), goodput ratio, MFU, throughput, compile and HBM
        telemetry."""
        compile_now, events_now = compile_telemetry()
        hits_now, misses_now = compile_cache_telemetry()
        with self._lock:
            wall = time.perf_counter() - self._p0 if self.armed else 0.0
            hooks_compile = max(0.0, compile_now - self._compile0[0])
            compile_s = hooks_compile + self._acc.get("xla_compile_s", 0.0)
            compile_events = max(0, events_now - self._compile0[1])
            data = self._acc.get("data_wait_s", 0.0)
            ckpt = self._acc.get("ckpt_block_s", 0.0)
            drain = self._acc.get("metric_drain_s", 0.0)
            step_compute = self._acc.get("step_compute_s", 0.0)
            if step_compute <= 0.0 and self._step_wall_s > 0.0:
                # Derive useful compute from step wall: subtract the waits
                # measured inside the loop and any compile that happened
                # after the first step (the first step's jit).
                in_loop_compile = max(
                    0.0, hooks_compile - (self._compile_preloop or 0.0)
                )
                step_compute = max(
                    0.0, self._step_wall_s - data - ckpt - in_loop_compile
                )
            idle = max(
                0.0, wall - (compile_s + data + step_compute + ckpt + drain)
            )
            # Clamped: sub-resolution timing jitter must not report >100%.
            goodput = min(1.0, step_compute / wall) if wall > 0 else 0.0
            mfu = (
                self.flops / (wall * self.peak_flops_per_s)
                if wall > 0 and self.peak_flops_per_s > 0
                else 0.0
            )
            tpds = (
                self.tokens / (wall * self.devices)
                if wall > 0 and self.devices > 0
                else 0.0
            )
            row: Dict[str, Any] = {
                "source": self.source,
                "process_id": self.process_id,
                "wall_s": wall,
                "buckets": {
                    "xla_compile_s": compile_s,
                    "data_wait_s": data,
                    "step_compute_s": step_compute,
                    "ckpt_block_s": ckpt,
                    "metric_drain_s": drain,
                    "idle_s": idle,
                },
                "steps": self.steps,
                "tokens": self.tokens,
                "flops": self.flops,
                "goodput": goodput,
                "mfu": mfu,
                "tokens_per_device_s": tpds,
                "compile_s": compile_s,
                "compile_events": compile_events,
                # Persistent-cache efficacy: how much of compile_s was a
                # disk read vs a cold XLA compile (registry folds these
                # into row attrs — no schema change).
                "compile_cache_hits": max(0, hits_now - self._cache0[0]),
                "compile_cache_misses": max(0, misses_now - self._cache0[1]),
                "hbm_peak_bytes": self._hbm_peak_bytes,
                "devices": self.devices,
                "device_kind": self.device_kind,
                "peak_flops_per_s": self.peak_flops_per_s,
            }
            if self._extra:
                row["extra"] = dict(self._extra)
            return row

    def maybe_flush(self) -> bool:
        """Throttled emit — call freely from hot loops."""
        if not self.armed or self.sink is None:
            return False
        now = time.perf_counter()
        with self._lock:
            if now - self._last_flush < self.interval_s:
                return False
        self.flush()
        return True

    def flush(self, final: bool = False) -> Optional[Dict[str, Any]]:
        """Emit one cumulative row through the sink (best-effort — the
        ledger must never be what kills a trainer)."""
        if not self.armed:
            return None
        self.sample_hbm()
        row = self.snapshot()
        with self._lock:
            self._seq += 1
            row["seq"] = self._seq
            self._last_flush = time.perf_counter()
        row["final"] = bool(final)
        if self.sink is not None:
            try:
                self.sink(row)
            except Exception:
                pass
        return row


_ledger = UtilizationLedger()


def get_ledger() -> UtilizationLedger:
    """The process-wide ledger (unconfigured: accounting only, no sink)."""
    return _ledger


def configure(**kwargs: Any) -> UtilizationLedger:
    """Configure the process-wide ledger (see :meth:`UtilizationLedger.configure`)."""
    return _ledger.configure(**kwargs)
