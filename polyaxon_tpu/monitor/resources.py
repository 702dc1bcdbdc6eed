"""In-worker resource telemetry sampler.

Parity: reference ``monitor_resources/`` — the per-node DaemonSet reading
docker stats + ``polyaxon_gpustat.query()`` (NVML) and publishing to Redis
for the streams layer (``monitor_resources/monitor.py:30-120``).
TPU-native: each gang process samples itself (psutil process stats) and its
local accelerator (``device.memory_stats()`` from the PJRT client — the
libtpu telemetry path), reporting through the same reports channel as
metrics; rows land in the registry prefixed ``sys/`` so the WS metric tail
streams them live.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional


# psutil computes cpu_percent(interval=None) against the PREVIOUS call on
# the same Process object — a fresh object's first call always returns
# 0.0.  Keep one Process per sampled pid so every call after the first
# measures a real interval; the priming call reports no cpu row at all
# instead of a fabricated zero.
_proc_cache: Dict[int, Any] = {}
_proc_cache_lock = threading.Lock()


def sample_process(pid: Optional[int] = None) -> Dict[str, float]:
    """CPU / memory of the given (default: calling) process."""
    out: Dict[str, float] = {}
    key = -1 if pid is None else pid
    try:
        import psutil

        with _proc_cache_lock:
            p = _proc_cache.get(key)
            primed = p is not None
            if p is None:
                p = psutil.Process(pid)
                _proc_cache[key] = p
        try:
            with p.oneshot():
                cpu = p.cpu_percent(interval=None)
                if primed:
                    out["sys/cpu_percent"] = cpu
                out["sys/rss_mb"] = p.memory_info().rss / 1e6
                out["sys/threads"] = float(p.num_threads())
        except Exception:
            # Target gone (or pid reused): drop the cached handle so a
            # later process with the same pid re-primes cleanly.
            with _proc_cache_lock:
                _proc_cache.pop(key, None)
            raise
    except Exception:
        if pid is not None:
            return out  # target process gone; report nothing rather than self
        try:
            import resource

            out["sys/rss_mb"] = (
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e3
            )
        except Exception:
            pass
    return out


# Probe-once gate for the accelerator sampler: CPU and older PJRT backends
# have no memory_stats() — the first sample that yields no memory telemetry
# disables the device sampler for the process lifetime instead of paying a
# device walk (and swallowing an exception) on every tick.
_device_probe_ok: Optional[bool] = None
_device_probe_lock = threading.Lock()
_hbm_peak_mb = 0.0


def _reset_device_probe() -> None:
    """Re-arm the probe (tests; a process never needs this)."""
    global _device_probe_ok, _hbm_peak_mb
    with _device_probe_lock:
        _device_probe_ok = None
        _hbm_peak_mb = 0.0


def sample_devices() -> Dict[str, float]:
    """Per-local-device HBM usage from the PJRT client, if initialized.

    Degrades gracefully: the first sample without memory telemetry turns
    the sampler off (``_device_probe_ok = False``) rather than raising —
    or even probing — on every tick.  Emits per-device current and peak
    usage plus an aggregate ``sys/hbm_peak_mb`` high-water mark.
    """
    global _device_probe_ok, _hbm_peak_mb
    out: Dict[str, float] = {}
    import sys

    if "jax" not in sys.modules:
        # No jax in this process yet → no PJRT client to sample, and the
        # telemetry thread must not be the thing that pays the jax import
        # (non-jax gang workloads boot ~2s faster without it).  Leaves the
        # probe unanswered: jax may still be imported later.
        return out
    with _device_probe_lock:
        if _device_probe_ok is False:
            return out
    total_peak_mb = 0.0
    got_any = False
    try:
        import jax

        for d in jax.local_devices():
            try:
                stats = d.memory_stats() or {}
            except Exception:
                stats = {}
            in_use = stats.get("bytes_in_use")
            limit = stats.get("bytes_limit")
            peak = stats.get("peak_bytes_in_use")
            if in_use is not None:
                got_any = True
                out[f"sys/hbm{d.id}_mb"] = in_use / 1e6
            if in_use is not None and limit:
                out[f"sys/hbm{d.id}_frac"] = in_use / limit
            if peak is not None:
                out[f"sys/hbm{d.id}_peak_mb"] = peak / 1e6
                total_peak_mb += peak / 1e6
            elif in_use is not None:
                total_peak_mb += in_use / 1e6
    except Exception:
        pass
    with _device_probe_lock:
        if _device_probe_ok is None:
            _device_probe_ok = got_any
        if got_any:
            _hbm_peak_mb = max(_hbm_peak_mb, total_peak_mb)
            out["sys/hbm_peak_mb"] = _hbm_peak_mb
    return out


def sample_tpu_utilization() -> Dict[str, float]:
    """TensorCore duty cycle per chip via the ``tpu_info`` library (the
    gpustat analogue — reference ``monitor_resources/monitor.py:30-34``
    polled NVML; on TPU-VMs the equivalent is libtpu's metrics endpoint,
    which ``tpu-info`` wraps).  Gated: returns {} wherever the library or
    the endpoint is absent (CPU test boxes), so
    the sampler composes it unconditionally."""
    out: Dict[str, float] = {}
    try:
        from tpu_info import device as tpu_device
        from tpu_info import metrics as tpu_metrics

        chip_type, count = tpu_device.get_local_chips()
        if not chip_type or not count:
            return out
        for i, usage in enumerate(tpu_metrics.get_chip_usage(chip_type)):
            duty = getattr(usage, "duty_cycle_pct", None)
            if duty is not None:
                out[f"sys/tpu{i}_duty_pct"] = float(duty)
            used = getattr(usage, "memory_usage", None)
            total = getattr(usage, "total_memory", None)
            if used is not None:
                out[f"sys/tpu{i}_mem_mb"] = float(used) / 1e6
            if used is not None and total:
                out[f"sys/tpu{i}_mem_frac"] = float(used) / float(total)
    except Exception:
        pass
    return out


class ResourceSampler:
    """Background thread reporting resource samples at an interval."""

    def __init__(self, reporter, interval: float = 10.0) -> None:
        self.reporter = reporter
        self.interval = interval
        #: When set, sample this pid instead of the calling process — the
        #: shell-command path points this at the user's subprocess, so
        #: telemetry reflects the workload, not the idle wrapper.
        self.pid: Optional[int] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def sample_once(self) -> Dict[str, Any]:
        values = sample_process(self.pid)
        values.update(sample_devices())
        values.update(sample_tpu_utilization())
        return values

    def start(self) -> None:
        if self._thread is not None or self.interval <= 0:
            return
        # Prime the per-process cpu_percent window now (unreported), so
        # the first row the loop emits measures a real interval.
        sample_process(self.pid)

        def loop() -> None:
            while not self._stop.wait(self.interval):
                values = self.sample_once()
                if values:
                    self.reporter.resources(values)

        self._thread = threading.Thread(target=loop, name="resources", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None
