"""No hidden CPU: a TPU plan runs on the chips it names, or fails.

``runtime/worker.py`` requests the TPU platform explicitly for every
non-``cpu`` accelerator and verifies what jax initialised — platform,
chip count against ``devices_per_host``, device kind against the
accelerator family — before the entrypoint runs.  The platform check is
faked here (the suite has no chip); ``chip_smoke.py`` is the real one.
"""

import os
import sys
import types

import pytest

from polyaxon_tpu.exceptions import RuntimeLayerError
from polyaxon_tpu.runtime import worker
from polyaxon_tpu.runtime.env import GangInfo, gang_env, visible_chips_env


def _info(accelerator: str, devices_per_host: int = 1) -> GangInfo:
    return GangInfo.from_env(
        gang_env(
            run_id=1, run_uuid="u", run_dir="/r", spec_path="/r/spec.json",
            process_id=0, num_processes=1, coordinator=None,
            devices_per_host=devices_per_host, accelerator=accelerator,
            mesh_axes={"data": devices_per_host}, strategy="ddp",
            strategy_options={},
        )
    )


def _fake_devices(monkeypatch, platform, kind, n):
    import jax

    devs = [types.SimpleNamespace(platform=platform, device_kind=kind, id=i)
            for i in range(n)]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)


class TestConfigureEnv:
    def test_tpu_accelerator_requests_the_tpu_platform(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        worker._configure_jax_env(_info("v5e-1"))
        # jax then fails at backend start-up without a chip instead of
        # quietly initialising the CPU backend.
        assert os.environ["JAX_PLATFORMS"] == "tpu"

    def test_cpu_accelerator_pins_cpu_and_device_count(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        worker._configure_jax_env(_info("cpu", devices_per_host=2))
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert os.environ["XLA_FLAGS"].split() == [
            "--xla_force_host_platform_device_count=2"
        ]


class TestVerifyDevices:
    def test_cpu_plan_is_not_checked(self):
        worker._verify_devices(_info("cpu-1"))

    def test_tpu_plan_on_the_cpu_backend_fails(self, monkeypatch):
        # What happened before PR 21: libtpu failed to initialise, jax fell
        # back to the CPU and the run finished `succeeded`.
        _fake_devices(monkeypatch, "cpu", "cpu", 1)
        with pytest.raises(RuntimeLayerError, match="needs the TPU platform.*'cpu'"):
            worker._verify_devices(_info("v5e-1"))

    def test_backend_that_did_not_start_names_the_missing_chips(self, monkeypatch):
        import jax

        def boom():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "local_devices", boom)
        with pytest.raises(RuntimeLayerError, match="v5e-4.*4 TPU chip"):
            worker._verify_devices(_info("v5e-4", devices_per_host=4))

    def test_wrong_chip_count_fails(self, monkeypatch):
        _fake_devices(monkeypatch, "tpu", "TPU v5 lite", 4)
        with pytest.raises(RuntimeLayerError, match="needs 1 chip.*sees 4"):
            worker._verify_devices(_info("v5e-1"))

    def test_wrong_family_fails(self, monkeypatch):
        _fake_devices(monkeypatch, "tpu", "TPU v4", 1)
        with pytest.raises(RuntimeLayerError, match="TPU v5 lite.*TPU v4"):
            worker._verify_devices(_info("v5e-1"))

    def test_matching_chips_pass(self, monkeypatch):
        _fake_devices(monkeypatch, "tpu", "TPU v5 lite", 4)
        worker._verify_devices(_info("v5e-4", devices_per_host=4))


_WORKER_CHILD = """
import sys, types
import jax
# The fake: whatever platform the worker requested, jax "initialised" the
# CPU backend (what libtpu's init failure used to fall back to).
from polyaxon_tpu.runtime import worker
worker._configure_jax_env = lambda info: None
mod = types.ModuleType("_smoke_entry")
mod.main = lambda ctx: open(sys.argv[1], "w").write("ran")
sys.modules["_smoke_entry"] = mod
sys.exit(worker.main())
"""


def test_tpu_plan_without_a_chip_ends_failed_not_succeeded(tmp_path):
    """Worker-level: ``worker.main()`` for a ``v5e-1`` plan whose jax
    came up on the CPU reports ``failed`` naming what was missing, exits
    non-zero, and the entrypoint never runs.  (Own process: the worker
    configures process-wide singletons.)"""
    import json
    import subprocess

    from polyaxon_tpu.stores.layout import RunPaths

    paths = RunPaths(tmp_path / "runs" / "u1").ensure()
    ran = tmp_path / "entrypoint_ran"
    paths.spec_path.write_text(json.dumps({
        "kind": "experiment",
        "run": {"entrypoint": "_smoke_entry:main"},
        "environment": {"topology": {"accelerator": "v5e-1"}},
    }))
    env = dict(os.environ)
    env.update(gang_env(
        run_id=1, run_uuid="u1", run_dir=str(paths.root),
        spec_path=str(paths.spec_path), process_id=0, num_processes=1,
        coordinator=None, devices_per_host=1, accelerator="v5e-1",
        mesh_axes={"data": 1}, strategy="ddp", strategy_options={},
        heartbeat_interval=60.0,
    ))
    env.update(
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
        PYTHONPATH=os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.dirname(__file__)))]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
        ),
    )
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER_CHILD, str(ran)],
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode != 0
    assert "needs the TPU platform" in proc.stderr
    assert not ran.exists()
    lines = [json.loads(l) for l in paths.report_file(0).read_text().splitlines()]
    statuses = [l["status"] for l in lines if l.get("type") == "status"]
    assert statuses[-1] == "failed" and "succeeded" not in statuses
    failed = [l for l in lines if l.get("status") == "failed"][-1]
    assert "TPU platform" in json.dumps(failed)


class TestOneProcessPerChip:
    def test_visible_chips_env(self):
        assert visible_chips_env(1) == {
            "TPU_VISIBLE_CHIPS": "0",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        }
        four = visible_chips_env(4)
        assert four["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
        assert four["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"
        assert visible_chips_env(1, first_chip=2)["TPU_VISIBLE_CHIPS"] == "2"

    def test_spawner_limits_a_tpu_gang_to_its_chips(self, tmp_path):
        from polyaxon_tpu.compiler import compile_gang_plan, compile_spec
        from polyaxon_tpu.spawner.local import LocalGangSpawner
        from polyaxon_tpu.stores.layout import StoreLayout

        layout = StoreLayout(tmp_path / "plat")
        spawner = LocalGangSpawner(layout)

        def env_for(accelerator):
            spec = compile_spec({
                "kind": "experiment",
                "run": {"entrypoint": "polyaxon_tpu.builtins.trainers:noop"},
                "environment": {"topology": {"accelerator": accelerator}},
            })
            run = types.SimpleNamespace(id=7, uuid="u7", spec=spec)
            return spawner._process_env(
                run, compile_gang_plan(spec), layout.run_paths("u7"), 0, None
            )

        one = env_for("v5e-1")
        assert one["TPU_VISIBLE_CHIPS"] == "0"
        assert one["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env_for("v5e-4")["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
        cpu = env_for("cpu-1")
        assert not [k for k in cpu if k.startswith("TPU_")]
        # The compile cache is placed from outside; the spawner adds none.
        assert not [k for k in one if "COMPILATION_CACHE" in k or "COMPILE_CACHE" in k]

    def test_local_fleet_gives_each_tpu_replica_its_own_chip(self, tmp_path, monkeypatch):
        from polyaxon_tpu.serving.fleet import LocalServingFleet

        fleet = LocalServingFleet(tmp_path, {"vocab_size": 8}, replicas=0,
                                  env={"JAX_PLATFORMS": "tpu"})
        a = fleet._platform_env("r0")
        b = fleet._platform_env("r1")
        assert a["TPU_VISIBLE_CHIPS"] == "0" and b["TPU_VISIBLE_CHIPS"] == "1"
        assert a["JAX_PLATFORMS"] == "tpu"
        fleet._chips.pop("r0")  # r0 retired: its chip is free again
        assert fleet._platform_env("r2")["TPU_VISIBLE_CHIPS"] == "0"
        # The CPU fleet (the test suite's) pins nothing.
        cpu = LocalServingFleet(tmp_path, {"vocab_size": 8}, replicas=0,
                                env={"JAX_PLATFORMS": "cpu"})
        assert cpu._platform_env("r0") == {}
