"""Gang spawner: N host processes for one accelerator slice.

Parity: reference ``polypod/experiment.py`` — ``ExperimentSpawner`` builds
pods+services per replica, injects rendezvous env, and starts/stops the
experiment (``start_experiment`` :350-357, pod creation :160-244).
TPU-native: a *gang* is N host processes for one accelerator slice; the
spawner launches ``runtime.worker`` once per host through a
:class:`~polyaxon_tpu.spawner.transport.Transport` (local subprocesses for
dev/test, ssh for real TPU-VM slices), injecting the coordinator/process-id/
mesh env contract that replaces TF_CONFIG.  Each process's stdout/stderr
stream to per-process log files; the reporting channel is the run's
``reports/`` dir on the shared store layout.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import time
from pathlib import Path
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from polyaxon_tpu.compiler import GangPlan
from polyaxon_tpu.db.registry import Run
from polyaxon_tpu.exceptions import SpawnerError
from polyaxon_tpu.runtime.env import gang_env, visible_chips_env
from polyaxon_tpu.spawner.transport import (
    LocalExecTransport,
    ProcessRef,
    Transport,
    terminate_refs,
)
from polyaxon_tpu.stores.layout import RunPaths, StoreLayout
from polyaxon_tpu.stores.snapshots import materialize_snapshot

LOOPBACK_HOSTS = ("127.0.0.1", "localhost", "::1")


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclass
class GangHandle:
    """A live (or finished) gang: the spawner's unit of control."""

    run_id: int
    run_uuid: str
    plan: GangPlan
    paths: RunPaths
    processes: Dict[int, ProcessRef] = field(default_factory=dict)
    #: Byte offsets into each process's report file (watcher tail cursor).
    report_offsets: Dict[int, int] = field(default_factory=dict)
    started_at: float = field(default_factory=time.time)
    #: Consecutive monitor-poll failures (scheduler bookkeeping).
    monitor_failures: int = 0
    #: When the gang's roll-up first went terminal while members were still
    #: alive (scheduler grace-window bookkeeping).
    terminal_since: Optional[float] = None
    #: Escalation bookkeeping: each signal stage fires once per attempt
    #: (re-signalling every monitor tick would hammer ssh hosts).
    term_sent: bool = False
    kill_sent: bool = False
    #: Edge-trigger marks for the watcher's stall/straggler detector (one
    #: anomaly row per episode, not per monitor tick).
    anomaly_marks: Dict[str, bool] = field(default_factory=dict)

    def poll(self) -> Dict[int, Optional[int]]:
        """process_id -> exit code (None while running)."""
        return {pid: ref.poll() for pid, ref in self.processes.items()}

    @property
    def all_exited(self) -> bool:
        return all(code is not None for code in self.poll().values())


class GangSpawner:
    """Launches gangs of ``runtime.worker`` processes through a transport.

    ``hosts`` is the worker host pool: process ``i`` lands on
    ``hosts[i % len(hosts)]`` (one worker per TPU-VM host in the standard
    slice layout).  The coordinator address is ``hosts[0]`` — routable by
    every gang member, which is what ``jax.distributed.initialize`` needs.
    """

    def __init__(
        self,
        layout: StoreLayout,
        *,
        transport: Optional[Transport] = None,
        hosts: Optional[List[str]] = None,
        heartbeat_interval: float = 5.0,
        python: Optional[str] = None,
        coordinator_port_base: int = 8476,
    ) -> None:
        self.layout = layout
        self.transport = transport or LocalExecTransport()
        self.hosts = hosts or ["127.0.0.1"]
        self.heartbeat_interval = heartbeat_interval
        self.python = python or sys.executable
        self.coordinator_port_base = coordinator_port_base

    # -- host / coordinator assignment ---------------------------------------
    def host_for(self, process_id: int) -> str:
        return self.hosts[process_id % len(self.hosts)]

    def _pick_port(self, run: Run, offset: int) -> int:
        """A port on the head host: loopback pools probe a genuinely free
        one; remote heads get a derived port (base + offset block + run id)
        — the control plane can't probe a remote host's ports cheaply, and
        the run-id spread keeps concurrent gangs on a shared pool apart."""
        if self.host_for(0) in LOOPBACK_HOSTS:
            return _free_port()
        return self.coordinator_port_base + offset + run.id % 512

    def _coordinator(self, run: Run, plan: GangPlan) -> Optional[str]:
        if plan.num_hosts <= 1:
            return None
        return f"{self.host_for(0)}:{self._pick_port(run, 0)}"

    def allocate_service_port(self, run: Run) -> int:
        """The serving port for a service gang (block above the coordinator
        range so the two never collide)."""
        return self._pick_port(run, 512)

    # -- env contract ---------------------------------------------------------
    def _process_env(
        self,
        run: Run,
        plan: GangPlan,
        paths: RunPaths,
        process_id: int,
        coordinator: Optional[str],
    ) -> Dict[str, Optional[str]]:
        """Env overrides for one gang process (None = unset on the host)."""
        env: Dict[str, Optional[str]] = {}
        if not plan.accelerator.startswith("cpu") and plan.num_hosts == 1:
            # One process for each chip set: a single-host TPU gang sees
            # exactly its ``devices_per_host`` chips, so a v5e-1 run works
            # on a four-chip host.  Multi-host gangs own whole hosts and
            # keep libtpu's own slice discovery.  (The platform itself —
            # cpu or tpu — is requested by the worker from the plan's
            # accelerator: ``runtime/worker.py:_configure_jax_env``.)
            env.update(visible_chips_env(plan.devices_per_host))
        env.update(plan.env_vars)
        # The worker runs with cwd=run_dir; make sure it can import this
        # package even when it isn't pip-installed (dev/test checkouts) by
        # prepending the package parent to PYTHONPATH — after the spec's
        # env_vars so a user PYTHONPATH augments rather than clobbers it.
        pkg_parent = str(Path(__file__).resolve().parents[2])
        inherited_pp = env.get("PYTHONPATH") or os.environ.get("PYTHONPATH")
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (pkg_parent, inherited_pp) if p
        )
        env.update(
            gang_env(
                run_id=run.id,
                run_uuid=run.uuid,
                run_dir=str(paths.root),
                spec_path=str(paths.spec_path),
                process_id=process_id,
                num_processes=plan.num_hosts,
                coordinator=coordinator,
                devices_per_host=plan.devices_per_host,
                accelerator=plan.accelerator,
                mesh_axes=plan.mesh_axes,
                strategy=plan.strategy,
                dcn_axes=plan.dcn_axes,
                strategy_options=plan.strategy_options,
                heartbeat_interval=self.heartbeat_interval,
                seed=run.spec.environment.seed,
                data_dir=str(self.layout.data_dir),
            )
        )
        return env

    # -- lifecycle ------------------------------------------------------------
    def start(self, run: Run, plan: GangPlan) -> GangHandle:
        """Create the run dir, write the spec, launch all gang processes."""
        paths = self.layout.run_paths(run.uuid).ensure()
        # Per-process command mailboxes (the control-plane→worker bus):
        # provisioned before launch so a command can never race a worker
        # that hasn't created its own dir yet.
        for process_id in range(plan.num_hosts):
            paths.command_dir(process_id).mkdir(parents=True, exist_ok=True)
        paths.spec_path.write_text(json.dumps(run.spec_data))
        if run.code_ref:
            materialize_snapshot(run.code_ref, self.layout.snapshots_dir, paths.code)

        coordinator = self._coordinator(run, plan)
        handle = GangHandle(
            run_id=run.id, run_uuid=run.uuid, plan=plan, paths=paths
        )
        try:
            for process_id in range(plan.num_hosts):
                env = self._process_env(run, plan, paths, process_id, coordinator)
                log_path = paths.log_file(process_id)
                rc_path = log_path.with_suffix(".rc")
                ref = self.transport.launch(
                    self.host_for(process_id),
                    [self.python, "-m", "polyaxon_tpu.runtime.worker"],
                    env,
                    cwd=str(paths.root),
                    log_path=log_path,
                    rc_path=rc_path,
                )
                handle.processes[process_id] = ref
        except Exception as e:
            self.stop(handle)
            raise SpawnerError(f"Failed to launch gang for run {run.id}: {e}") from e
        return handle

    def reattach(
        self, run: Run, plan: GangPlan, processes: List[Dict]
    ) -> Optional[GangHandle]:
        """Rebuild the handle for a gang a previous control plane launched.

        ``processes`` are the registry's process rows (pid + durable report
        offset). Returns None when the gang is not reattachable — run dir
        gone or pids unrecorded — in which case the caller re-dispatches.
        The reference gets this for free from k8s (pods outlive the API
        server); here the shared run dir + pid bookkeeping play that role.
        """
        paths = self.layout.run_paths(run.uuid)
        if not paths.root.exists():
            return None
        by_id = {p["process_id"]: p for p in processes}
        if any(
            process_id not in by_id or not by_id[process_id].get("pid")
            for process_id in range(plan.num_hosts)
        ):
            return None
        handle = GangHandle(
            run_id=run.id, run_uuid=run.uuid, plan=plan, paths=paths
        )
        for process_id in range(plan.num_hosts):
            row = by_id[process_id]
            rc_path = paths.log_file(process_id).with_suffix(".rc")
            handle.processes[process_id] = self.transport.reattach(
                self.host_for(process_id), int(row["pid"]), rc_path
            )
            handle.report_offsets[process_id] = int(row.get("report_offset") or 0)
        return handle

    def signal_gang(self, handle: GangHandle, sig: int) -> None:
        """Signal every live process group without waiting — the monitor's
        kill-escalation path, which must never block the task-bus thread."""
        for ref in handle.processes.values():
            if ref.poll() is None:
                ref.signal(sig)

    def stop(self, handle: GangHandle, grace: float = 15.0) -> None:
        """Terminate the gang (whole process groups): SIGTERM, wait
        ``grace``, then SIGKILL.  The grace covers a worker unwinding on
        SIGTERM and the TPU runtime's own shutdown: an ``lm_server`` on a
        v5e took 3.4-5.7 s to exit (PR 21 chip runs), and a worker killed
        while it still holds the chip can leave the next gang waiting."""
        terminate_refs(handle.processes, grace=grace)


class LocalGangSpawner(GangSpawner):
    """The dev/test backend: gangs as local subprocesses (loopback pool)."""

    def __init__(
        self,
        layout: StoreLayout,
        *,
        heartbeat_interval: float = 5.0,
        python: Optional[str] = None,
    ) -> None:
        super().__init__(
            layout,
            transport=LocalExecTransport(),
            hosts=["127.0.0.1"],
            heartbeat_interval=heartbeat_interval,
            python=python,
        )
