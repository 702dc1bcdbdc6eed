"""Driving one gang through the program's ``Orchestrator``, as ``polyaxon-tpu
run`` does: spec -> compiler -> spawner -> ``runtime.worker`` -> entrypoint.

This process never imports jax: a chip belongs to one process at a time.
Every wait is bounded, and ``close()`` stops whatever is still running.
"""

from __future__ import annotations

import json
import shutil
import tempfile
import time
import urllib.error
import urllib.request
from typing import Any, Dict, Optional


class GangFailed(RuntimeError):
    pass


class Gang:
    def __init__(self, *, toy: bool, chips: int) -> None:
        from polyaxon_tpu.orchestrator import Orchestrator

        self.toy = toy
        self.chips = chips
        self.base_dir = tempfile.mkdtemp(prefix="bench_run_")
        self.orch = Orchestrator(
            self.base_dir, monitor_interval=0.5, heartbeat_interval=1.0,
            heartbeat_ttl=900.0,
        )
        self.orch.register_device("bench-host", "cpu" if toy else f"v5e-{chips}", chips)
        self.run = None

    # -- lifecycle ---------------------------------------------------------------
    def submit(self, spec: Dict[str, Any], name: str) -> None:
        self.run = self.orch.submit(spec, name=name)

    def pump(self, wait: float = 0.1) -> None:
        self.orch.pump(max_wait=wait)

    def current(self):
        return self.orch.get_run(self.run.id)

    def logs_tail(self, chars: int = 3000) -> str:
        try:
            text = "\n".join(r["line"] for r in self.orch.registry.get_logs(self.run.id))
            for f in sorted(self.orch.layout.run_paths(self.run.uuid).logs.glob("proc*.log")):
                text += f"\n--- {f.name}\n" + f.read_text(errors="replace")[-chars:]
            return text[-2 * chars:]
        except Exception as e:  # noqa: BLE001 - diagnostics only
            return f"(no logs: {e})"

    def check_alive(self, what: str) -> None:
        cur = self.current()
        if cur.is_done:
            raise GangFailed(f"gang ended {cur.status!r} {what}: {self.logs_tail()}")

    def wait_done(self, timeout: float):
        deadline = time.time() + timeout
        while time.time() < deadline:
            self.pump(0.2)
            cur = self.current()
            if cur.is_done:
                return cur
        raise GangFailed(f"gang still running after {timeout:.0f}s: {self.logs_tail()}")

    def stop(self, timeout: float = 60.0) -> str:
        if self.run is None or self.current().is_done:
            return self.current().status if self.run is not None else "none"
        self.orch.stop_run(self.run.id)
        deadline = time.time() + timeout
        while time.time() < deadline:
            self.pump(0.2)
            if self.current().is_done:
                break
        return self.current().status

    def close(self) -> None:
        try:
            if self.run is not None:
                self.stop()
        finally:
            self.orch.stop()
            shutil.rmtree(self.base_dir, ignore_errors=True)

    # -- what the program reports ----------------------------------------------------
    def run_paths(self):
        return self.orch.layout.run_paths(self.run.uuid)

    def last_ledger_row(self) -> Dict[str, Any]:
        rows = self.orch.registry.get_utilization(self.run.id)
        return rows[-1] if rows else {}

    def hbm_peak_bytes(self) -> Optional[int]:
        """The largest per-device ``peak_bytes_in_use`` the worker's resource
        sampler reported (``monitor/resources.py``, MB as float)."""
        peak = 0.0
        for m in self.orch.registry.get_metrics(self.run.id):
            for k, v in (m.get("values") or {}).items():
                if k.startswith("sys/hbm") and k.endswith("_peak_mb") and k != "sys/hbm_peak_mb":
                    peak = max(peak, float(v))
        return int(peak * 1e6) if peak > 0 else None

    # -- a service gang ---------------------------------------------------------------
    def wait_ready(self, timeout: float) -> str:
        """Pump until ``/healthz`` says ready; returns the service URL."""
        deadline = time.time() + timeout
        health = None
        while time.time() < deadline:
            self.pump(0.2)
            self.check_alive("before it was ready")
            url = self.current().service_url
            if not url:
                continue
            try:
                with urllib.request.urlopen(f"{url}/healthz", timeout=2) as r:
                    health = json.load(r)
            except urllib.error.HTTPError as e:
                health = json.load(e)
            except OSError:
                continue
            if health.get("state") == "failed":
                raise GangFailed(f"engine failed to start: {health.get('start_error')}")
            if health.get("state") == "ready":
                return url
        raise GangFailed(f"server not ready after {timeout:.0f}s (last: {health})")

    def profile(self, num_steps: int, duration_s: float) -> str:
        """Ask the worker for an xplane capture over its next steps."""
        cmd = self.orch.request_profile(
            self.run.id, num_steps=num_steps, duration_s=duration_s)
        return cmd["capture_id"]

    def capture_dir(self, capture_id: str):
        return self.run_paths().profiles / capture_id / "proc0"


def get_json(url: str, timeout: float = 10.0) -> Dict[str, Any]:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.load(r)
