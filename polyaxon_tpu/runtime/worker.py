"""Gang-process entrypoint: ``python -m polyaxon_tpu.runtime.worker``.

This is what runs inside every gang member — the TPU-native fusion of the
reference's user container + sidecar + init container
(``polypod/experiment.py:160-244`` pod anatomy): it bootstraps the
distributed world (``jax.distributed.initialize`` — replacing TF_CONFIG /
MASTER_ADDR rendezvous), builds the device mesh, runs the spec's command or
python entrypoint with a tracking :class:`Context`, heartbeats, and reports
statuses/metrics/logs through the run-dir reporting channel.

Env knobs are set *before* importing jax: for the ``cpu`` accelerator the
worker forces ``JAX_PLATFORMS=cpu`` and a virtual device count, which is how
tests and the driver's multichip dry-run exercise real sharding without TPU
hardware; for a TPU accelerator it requests ``JAX_PLATFORMS=tpu`` and then
verifies the chips it got before the entrypoint runs.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
from pathlib import Path

# stdlib-only import — safe before the deferred jax imports below
from polyaxon_tpu.conf.knobs import knob_float, knob_str


#: PJRT ``device_kind`` each TPU accelerator family reports, as seen on the
#: hardware (a v5e chip reports "TPU v5 lite").  Families absent here are
#: checked for platform and chip count only.
_FAMILY_DEVICE_KIND = {"v5e": "TPU v5 lite"}


def _configure_jax_env(info) -> None:
    """Request the jax platform the plan's accelerator names.

    Env-var only — jax itself is NOT imported here.  The jax import is
    the dominant cost of a gang member's boot (~2s of CPU), and plenty of
    gang workloads (metric probes, shell services, notebooks) never touch
    it; deferring it to first real use is what makes hpsearch waves
    orchestration-bound instead of import-bound.

    ``cpu*`` accelerators get the CPU backend with the plan's virtual
    device count; every other accelerator is a TPU slice and gets
    ``JAX_PLATFORMS=tpu`` — jax then fails at backend start-up when there
    is no chip instead of quietly initialising the CPU backend
    (:func:`_verify_devices` checks what it got).
    """
    if info.accelerator.startswith("cpu"):
        os.environ["JAX_PLATFORMS"] = "cpu"
        # The plan's device count wins over any inherited flag value.
        flags = [
            f
            for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={info.devices_per_host}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
        if info.num_processes > 1:
            # Cross-process CPU collectives need an explicit backend; gloo
            # plays the role ICI/DCN transports play on real slices.
            os.environ.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
    else:
        os.environ["JAX_PLATFORMS"] = "tpu"
    # Deterministic partitionable PRNG across meshes (same key → same stream
    # regardless of sharding).
    os.environ.setdefault("JAX_THREEFRY_PARTITIONABLE", "1")


def _init_distributed(info) -> bool:
    """Join the jax.distributed world. Returns True if initialized."""
    if info.num_processes <= 1 or not info.coordinator:
        return False
    import jax

    jax.distributed.initialize(
        coordinator_address=info.coordinator,
        num_processes=info.num_processes,
        process_id=info.process_id,
    )
    return True


def _verify_devices(info) -> None:
    """A TPU plan runs on the TPU chips it names, or not at all.

    Checked before the entrypoint: backend platform, local chip count
    against ``devices_per_host`` (the spawner limits the process to its
    chips — see ``runtime/env.py:visible_chips_env``), and a device kind
    consistent with the accelerator family.  Any mismatch raises with
    what was missing, and the run ends ``failed``.
    """
    if info.accelerator.startswith("cpu"):
        return
    import jax

    from polyaxon_tpu.exceptions import RuntimeLayerError

    try:
        devices = jax.local_devices()
    except RuntimeError as e:
        raise RuntimeLayerError(
            f"accelerator {info.accelerator!r} needs "
            f"{info.devices_per_host} TPU chip(s) on this host and the TPU "
            f"backend did not start: {e}"
        ) from e
    platform = devices[0].platform
    kind = devices[0].device_kind
    if platform != "tpu":
        raise RuntimeLayerError(
            f"accelerator {info.accelerator!r} needs the TPU platform, "
            f"jax initialised {platform!r} ({kind})"
        )
    if len(devices) != info.devices_per_host:
        raise RuntimeLayerError(
            f"accelerator {info.accelerator!r} needs {info.devices_per_host} "
            f"chip(s) in this process, jax sees {len(devices)} ({kind})"
        )
    want = _FAMILY_DEVICE_KIND.get(info.accelerator.split("-", 1)[0])
    if want is not None and kind != want:
        raise RuntimeLayerError(
            f"accelerator {info.accelerator!r} needs device kind {want!r}, "
            f"the chip reports {kind!r}"
        )


class _Terminated(SystemExit):
    """SIGTERM, raised in the main thread so the ``finally`` blocks run."""


def _on_sigterm(signum, frame) -> None:
    raise _Terminated(128 + signum)


def _run_cmd(cmd: str, env: dict, cwd: str, sampler=None) -> int:
    proc = subprocess.Popen(cmd, shell=True, env=env, cwd=cwd)
    if sampler is not None:
        # Telemetry must describe the workload, not this idle wrapper.
        sampler.pid = proc.pid
        sampler.start()
    return proc.wait()


def main() -> int:
    from polyaxon_tpu.runtime.env import GangInfo
    from polyaxon_tpu.stores.layout import RunPaths
    from polyaxon_tpu.tracking import Context, Reporter

    # The spawner stops a gang with SIGTERM first (``terminate_refs``).
    # Unwinding instead of dying in place lets the workload release what it
    # holds — the engine's final ledger row and prefix snapshot, the
    # reporter's tail, and the TPU client (a process killed while holding
    # the chip can leave the next one waiting for it).  No status is
    # reported: the exit code speaks, exactly as when the signal killed us.
    signal.signal(signal.SIGTERM, _on_sigterm)
    info = GangInfo.from_env()
    paths = RunPaths(Path(info.run_dir)).ensure()
    reporter = Reporter(paths.report_file(info.process_id), info.process_id)
    # Route this process's tracer spans through the report channel: the
    # watcher ingests them and the control plane assembles the
    # cross-process timeline (GET /api/v1/runs/<id>/timeline).
    from polyaxon_tpu.tracking import trace

    tracer = trace.configure(
        sink=reporter.span,
        process_id=info.process_id,
        trace_id=info.run_uuid or None,
    )
    # Same wiring for the utilization ledger: workloads that feed it
    # (trainers, serving engine) get their goodput/MFU rows shipped as
    # typed ``ledger`` report lines.  Imports no jax.
    from polyaxon_tpu.tracking import ledger as ledger_mod

    ledger_mod.configure(sink=reporter.ledger, process_id=info.process_id)
    # Command-bus receiver: the control plane drops command files into this
    # process's mailbox; the agent's poll rides the heartbeat thread (no
    # extra thread, near-zero idle cost) and on-demand profile captures
    # hook the workload step loops via get_capture_agent().on_step.
    from polyaxon_tpu.tracking import capture as capture_mod

    mailbox = paths.command_dir(info.process_id)
    mailbox.mkdir(parents=True, exist_ok=True)
    capture_agent = capture_mod.configure(
        reporter=reporter,
        mailbox=mailbox,
        profiles_root=paths.profiles,
        process_id=info.process_id,
    )
    reporter.add_beat_hook(capture_agent.poll)
    reporter.status("starting")
    reporter.start_heartbeat(info.heartbeat_interval)
    from polyaxon_tpu.tracking.flightrec import FlightRecorder, get_progress

    # Stall watchdog + crash forensics: trainers/serving beat the shared
    # progress beacon; no beat within the adaptive deadline → forensic
    # dump to reports/flightrec-<proc>-<n>.json + typed anomaly line.
    recorder = FlightRecorder(
        get_progress(),
        reporter=reporter,
        out_dir=paths.reports,
        process_id=info.process_id,
    )
    recorder.start()
    from polyaxon_tpu.monitor.resources import ResourceSampler

    # NOT started yet: the sampler thread touches jax.local_devices(),
    # which would initialize the backend and race jax.distributed below.
    sampler = ResourceSampler(
        reporter,
        interval=knob_float("POLYAXON_TPU_RESOURCE_INTERVAL"),
    )

    try:
        _configure_jax_env(info)
        # Persistent XLA compile cache: env-armed here (before any jax
        # import) so gang members and warm restarts share executables.
        from polyaxon_tpu.runtime.compilecache import enable_compile_cache

        enable_compile_cache()

        spec_data = json.loads(Path(info.spec_path).read_text())
        from polyaxon_tpu.schemas.specifications import specification_for_kind

        spec = specification_for_kind(spec_data["kind"]).model_validate(spec_data)
        service_port = knob_str("POLYAXON_TPU_SERVICE_PORT") or None
        if service_port is not None:
            # The dispatch-time port allocation reaches the workload both as
            # a template variable ({{service_port}} in cmd/kwargs) and as a
            # Context param for python entrypoints.
            spec.declarations.setdefault("service_port", int(service_port))
        run_cfg = spec.resolved_run() if hasattr(spec, "resolved_run") else spec.run

        # Code snapshot (if the build step materialized one) takes import
        # precedence — the init-container equivalent.
        code_dir = paths.code
        if code_dir.exists():
            sys.path.insert(0, str(code_dir))

        if run_cfg.cmd is not None:
            # Shell command path: the distributed bootstrap belongs to the
            # command itself (it can read the same env contract).
            reporter.status("running")
            with tracer.span("worker.cmd"):
                rc = _run_cmd(
                    run_cfg.cmd,
                    env=dict(os.environ),
                    cwd=str(code_dir if code_dir.exists() else paths.root),
                    sampler=sampler,
                )
            if rc == 0:
                reporter.status("succeeded")
                return 0
            reporter.status("failed", message=f"command exited {rc}")
            return 1

        # Python entrypoint path: managed distributed world + mesh.
        with tracer.span("worker.distributed_init", hosts=info.num_processes):
            distributed = _init_distributed(info)
        _verify_devices(info)
        sampler.start()

        # The mesh is a THUNK: entrypoints that never read ctx.mesh (metric
        # probes, services) never pay the jax import it pulls in.
        mesh = None
        if info.mesh_axes:
            def mesh(axes=info.mesh_axes, dcn=info.dcn_axes):
                from polyaxon_tpu.runtime.mesh import build_mesh

                return build_mesh(axes, dcn_axes=dcn)

        params = dict(spec.declarations)
        params.update(run_cfg.kwargs)
        ctx = Context(
            params=params,
            process_id=info.process_id,
            num_processes=info.num_processes,
            mesh=mesh,
            strategy=info.strategy,
            strategy_options=info.strategy_options,
            outputs_path=str(paths.outputs),
            checkpoints_path=str(paths.checkpoints),
            # Spawner-resolved (layout knowledge stays in StoreLayout);
            # parent-walk only as a fallback for hand-launched workers.
            data_path=info.data_dir or str(paths.root.parent.parent / "data"),
            runs_root=str(paths.root.parent),
            reporter=reporter,
            seed=info.seed,
            run_uuid=info.run_uuid,
        )

        module_name, fn_name = run_cfg.entrypoint.split(":")
        import importlib

        module = importlib.import_module(module_name)
        fn = getattr(module, fn_name)

        reporter.status("running")
        with tracer.span("worker.entrypoint", entrypoint=run_cfg.entrypoint):
            fn(ctx)

        if distributed:
            import jax

            jax.distributed.shutdown()
        reporter.status("succeeded")
        return 0
    except _Terminated as e:
        return int(e.code)
    except BaseException as e:  # noqa: BLE001 — report, then die loudly
        # Postmortem first (thread stacks, span tail, HBM stats) so every
        # FAILED run leaves a flight-recorder dump next to its reports.
        recorder.crash_dump(e)
        reporter.error(e)
        raise
    finally:
        recorder.stop()
        sampler.stop()
        # A capture the gang is mid-way through must resolve (failed) —
        # an exiting worker must not leave its command hanging ACKED.
        try:
            capture_agent.close()
        except Exception:
            pass
        # Final ledger row (no-op if the workload never armed it): the
        # run's last cumulative truth, flagged final for consumers.
        try:
            ledger_mod.get_ledger().flush(final=True)
        except Exception:
            pass
        reporter.close()


if __name__ == "__main__":
    sys.exit(main())
