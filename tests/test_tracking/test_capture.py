"""CaptureAgent: mailbox dispatch, windowed capture, deadline reap.

Drives the worker side of the run command bus with a fake reporter and a
stub jax profiler — no devices, no real traces, but the full lifecycle:
command file → ack → step window → artifacts → capture/command report
lines.
"""

import json
import sys
import threading
import time
from types import SimpleNamespace

import pytest

from polyaxon_tpu.analysis.rules import _SPAN_NAMES
from polyaxon_tpu.tracking.capture import (
    DEFAULT_NUM_STEPS,
    CaptureAgent,
    configure,
    get_capture_agent,
)
from polyaxon_tpu.tracking.trace import get_tracer


class _Reporter:
    def __init__(self):
        self.captures = []
        self.commands = []

    def capture(self, record):
        self.captures.append(dict(record))

    def command_event(self, uuid, state, message=None, **attrs):
        self.commands.append({"uuid": uuid, "state": state, "message": message})


class _StubProfiler:
    """start_trace remembers the dir; stop_trace materializes an xplane
    file there (the shape of a real jax trace dump).  Both note what the
    process tracer's hook was when they were called, and every annotation
    entered through the hook leaves its name in ``annotated``."""

    def __init__(self, fail_start=False):
        self.fail_start = fail_start
        self.trace_dir = None
        self.options = None
        self.hook_at = {}
        self.annotated = []
        self.stop_entered = threading.Event()
        self.stop_may_return = threading.Event()
        self.stop_may_return.set()
        stub = self

        class TraceAnnotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                stub.annotated.append(self.name)

            def __exit__(self, *exc):
                return False

        self.TraceAnnotation = TraceAnnotation

    @staticmethod
    def ProfileOptions():
        return SimpleNamespace(python_tracer_level=1, host_tracer_level=2)

    def start_trace(self, path, profiler_options=None):
        self.hook_at["start"] = get_tracer().profiler_hook
        if self.fail_start:
            raise RuntimeError("trace already active")
        self.trace_dir = path
        self.options = profiler_options

    def stop_trace(self):
        self.hook_at["stop"] = get_tracer().profiler_hook
        self.stop_entered.set()
        assert self.stop_may_return.wait(10)
        if self.trace_dir:
            from pathlib import Path

            d = Path(self.trace_dir) / "plugins" / "profile" / "run1"
            d.mkdir(parents=True, exist_ok=True)
            (d / "host.xplane.pb").write_bytes(b"xplane")
        self.trace_dir = None

    def device_memory_profile(self):
        return b"memory-profile-proto"


@pytest.fixture()
def rig(tmp_path, monkeypatch):
    prof = _StubProfiler()
    monkeypatch.setitem(sys.modules, "jax", SimpleNamespace(profiler=prof))
    monkeypatch.setattr(get_tracer(), "profiler_hook", None)
    reporter = _Reporter()
    mailbox = tmp_path / "commands" / "proc0"
    mailbox.mkdir(parents=True)
    agent = CaptureAgent().configure(
        reporter=reporter,
        mailbox=mailbox,
        profiles_root=tmp_path / "profiles",
        process_id=0,
    )
    yield SimpleNamespace(
        agent=agent,
        reporter=reporter,
        mailbox=mailbox,
        profiler=prof,
        run_root=tmp_path,
    )
    # The writer thread imports the stubbed jax: it has to end in here.
    prof.stop_may_return.set()
    agent.close()
    assert agent.wait_written(10)


def _drop(rig, uuid="cmd1", kind="profile", payload=None):
    body = {"uuid": uuid, "kind": kind, "payload": payload or {}}
    (rig.mailbox / f"{uuid}.json").write_text(json.dumps(body))


class TestMailbox:
    def test_idle_poll_is_noop(self, rig):
        rig.agent.poll()
        assert rig.reporter.commands == [] and rig.reporter.captures == []

    def test_unconfigured_agent_poll_is_noop(self):
        CaptureAgent().poll()  # no mailbox — must not raise

    def test_garbage_command_file_dropped(self, rig):
        (rig.mailbox / "bad.json").write_text("{not json")
        rig.agent.poll()
        assert list(rig.mailbox.iterdir()) == []

    def test_unknown_kind_fails_typed(self, rig):
        _drop(rig, uuid="u1", kind="quantum_teleport")
        rig.agent.poll()
        assert list(rig.mailbox.iterdir()) == []
        (evt,) = rig.reporter.commands
        assert evt["state"] == "failed" and "quantum_teleport" in evt["message"]

    def test_register_handler_extends_the_bus(self, rig):
        seen = []
        rig.agent.register_handler("checkpoint-now", seen.append)
        _drop(rig, uuid="u2", kind="checkpoint-now")
        rig.agent.poll()
        assert seen and seen[0]["uuid"] == "u2"
        states = [e["state"] for e in rig.reporter.commands]
        assert states == ["acked"]


class TestProfileCapture:
    def test_full_window_capture(self, rig):
        _drop(rig, uuid="cap1", payload={"num_steps": 2})
        rig.agent.poll()
        # acked + capture started
        assert rig.reporter.commands[0] == {
            "uuid": "cap1",
            "state": "acked",
            "message": None,
        }
        assert rig.reporter.captures[0]["status"] == "started"
        # a registered AOT executable contributes its HLO text
        rig.agent.register_executable(
            "train_step", SimpleNamespace(as_text=lambda: "HloModule m")
        )
        rig.agent.on_step(10)
        assert rig.profiler.trace_dir is not None  # tracing
        rig.agent.on_step(11)  # window filled -> the writer finalizes
        assert rig.agent.wait_written(10)
        record = rig.reporter.captures[-1]
        assert record["status"] == "complete"
        assert record["start_step"] == 10
        assert record["num_steps"] == 2
        assert record["attrs"]["xplane"] is True
        out = rig.run_root / "profiles" / "cap1" / "proc0"
        assert (out / "memory.prof").read_bytes() == b"memory-profile-proto"
        assert "HloModule m" in (out / "hlo.txt").read_text()
        assert json.loads((out / "manifest.json").read_text())["capture_id"] == "cap1"
        # artifact keys are run-root relative and include the xplane dump
        assert all(a.startswith("profiles/cap1/proc0/") for a in record["artifacts"])
        assert any(a.endswith("host.xplane.pb") for a in record["artifacts"])
        assert rig.reporter.commands[-1]["state"] == "complete"
        # agent is free for the next capture
        _drop(rig, uuid="cap2", payload={"num_steps": 1})
        rig.agent.poll()
        rig.agent.on_step(12)
        assert rig.agent.wait_written(10)
        assert rig.reporter.captures[-1]["capture_id"] == "cap2"

    def test_default_window_length(self, rig):
        _drop(rig, uuid="cap3")
        rig.agent.poll()
        for i in range(DEFAULT_NUM_STEPS):
            rig.agent.on_step(i)
        assert rig.agent.wait_written(10)
        assert rig.reporter.captures[-1]["status"] == "complete"

    def test_xplane_failure_degrades_not_fails(self, rig):
        rig.profiler.fail_start = True
        _drop(rig, uuid="cap4", payload={"num_steps": 1})
        rig.agent.poll()
        rig.agent.on_step(0)
        assert rig.agent.wait_written(10)
        record = rig.reporter.captures[-1]
        assert record["status"] == "complete"
        assert record["attrs"]["xplane"] is False
        assert "xplane_error" in record["attrs"]
        # memory snapshot still collected
        assert any(a.endswith("memory.prof") for a in record["artifacts"])

    def test_second_command_while_in_flight_fails_typed(self, rig):
        _drop(rig, uuid="cap5", payload={"num_steps": 10})
        rig.agent.poll()
        rig.agent.on_step(0)
        _drop(rig, uuid="cap6")
        rig.agent.poll()
        failed = [e for e in rig.reporter.commands if e["uuid"] == "cap6"]
        assert failed[-1]["state"] == "failed"
        assert "in flight" in failed[-1]["message"]

    def test_deadline_reap_without_steps(self, rig):
        """A capture on a workload that never steps resolves at its
        deadline instead of hanging the command forever."""
        _drop(rig, uuid="cap7", payload={"duration_s": 1.0})
        rig.agent.poll()
        rig.agent._job["deadline"] = time.time() - 1  # fast-forward
        rig.agent.poll()
        assert rig.agent.wait_written(10)
        record = rig.reporter.captures[-1]
        assert record["status"] == "complete"
        assert record["attrs"]["no_step_window"] is True
        assert rig.reporter.commands[-1] == {
            "uuid": "cap7",
            "state": "complete",
            "message": None,
        }

    def test_deadline_reap_mid_window_truncates(self, rig):
        _drop(rig, uuid="cap8", payload={"num_steps": 100, "duration_s": 1.0})
        rig.agent.poll()
        rig.agent.on_step(0)
        rig.agent._job["deadline"] = time.time() - 1
        rig.agent.poll()
        assert rig.agent.wait_written(10)
        record = rig.reporter.captures[-1]
        assert record["status"] == "complete"
        assert record["attrs"]["window_truncated"] is True
        assert record["num_steps"] == 1

    def test_close_mid_capture_reports_failed(self, rig):
        _drop(rig, uuid="cap9", payload={"num_steps": 100})
        rig.agent.poll()
        rig.agent.on_step(0)
        rig.agent.close()
        record = rig.reporter.captures[-1]
        assert record["status"] == "failed"
        assert "exited" in record["message"]
        assert rig.reporter.commands[-1]["state"] == "failed"
        # closed agents ignore further mailbox traffic
        _drop(rig, uuid="cap10")
        rig.agent.poll()
        assert rig.reporter.commands[-1]["uuid"] == "cap9"

    def test_on_step_fast_path_without_job(self, rig):
        rig.agent.on_step(0)  # no capture armed — must be free of effects
        assert rig.reporter.captures == []


class TestProfilerClock:
    """The program's spans and phases on the device trace's clock, and the
    write-out off the workload's thread."""

    def _window(self, rig, num_steps=2):
        _drop(rig, uuid="capx", payload={"num_steps": num_steps})
        rig.agent.poll()
        rig.agent.on_step(0)

    @pytest.mark.parametrize("end", ["window_filled", "deadline", "close"])
    def test_hook_is_set_only_between_start_and_stop(self, rig, end):
        tracer = get_tracer()
        assert tracer.profiler_hook is None
        self._window(rig)
        assert rig.profiler.hook_at["start"] is None
        assert tracer.profiler_hook is rig.profiler.TraceAnnotation
        if end == "window_filled":
            rig.agent.on_step(1)
        elif end == "deadline":
            rig.agent._job["deadline"] = time.time() - 1
            rig.agent.poll()
        else:
            rig.agent.close()
        # Cleared by whoever ends the window, before the profiler stops.
        assert tracer.profiler_hook is None
        assert rig.agent.wait_written(10)
        assert rig.profiler.hook_at["stop"] is None
        assert tracer.profiler_hook is None

    def test_python_tracer_is_off(self, rig):
        self._window(rig)
        assert rig.profiler.options.python_tracer_level == 0
        assert rig.profiler.options.host_tracer_level == 2

    def test_failed_start_leaves_the_hook_unset(self, rig):
        rig.profiler.fail_start = True
        self._window(rig)
        assert get_tracer().profiler_hook is None

    @pytest.mark.parametrize("sample", [1.0, 0.0])
    def test_spans_and_phases_are_annotated_under_catalog_names(self, rig, sample):
        tracer = get_tracer()
        clock = tracer.phase_clock(
            ["serving.loop.other", "serving.paging.alloc"], "serving.loop.other"
        )
        clock.start()
        with tracer.span("engine.compile", sample=sample):
            pass
        assert rig.profiler.annotated == []  # no capture: nothing annotated
        self._window(rig)
        with clock.phase("serving.paging.alloc"):
            # Sampling is ignored while the trace is on.
            with tracer.span("engine.compile", sample=sample):
                pass
        rig.agent.on_step(1)
        with clock.phase("serving.paging.alloc"):
            pass
        clock.stop()
        assert rig.profiler.annotated == [
            "serving.paging.alloc", "engine.compile", "serving.loop.other"
        ]
        assert set(rig.profiler.annotated) <= _SPAN_NAMES

    def test_on_step_returns_before_the_write_out_finishes(self, rig):
        rig.profiler.stop_may_return.clear()
        self._window(rig)
        rig.agent.on_step(1)  # fills the window; returns at once
        assert rig.profiler.stop_entered.wait(10)
        out = rig.run_root / "profiles" / "capx" / "proc0"
        assert not (out / "manifest.json").exists()
        assert rig.reporter.captures[-1]["status"] == "started"
        rig.agent.on_step(2)  # the workload goes on stepping meanwhile
        assert not rig.agent.wait_written(0.05)
        rig.profiler.stop_may_return.set()
        assert rig.agent.wait_written(10)
        record = rig.reporter.captures[-1]
        assert record["status"] == "complete" and record["num_steps"] == 2
        assert (out / "manifest.json").exists()
        assert {"start_trace_s", "stop_trace_s"} <= set(record["attrs"])

    def test_close_waits_for_a_write_out_in_flight(self, rig):
        rig.profiler.stop_may_return.clear()
        self._window(rig, num_steps=1)
        assert rig.profiler.stop_entered.wait(10)
        threading.Timer(0.05, rig.profiler.stop_may_return.set).start()
        rig.agent.close()
        assert rig.reporter.captures[-1]["status"] == "complete"


class TestModuleSingleton:
    def test_configure_returns_shared_agent(self, tmp_path):
        agent = configure(
            reporter=None,
            mailbox=tmp_path,
            profiles_root=tmp_path / "profiles",
            process_id=3,
        )
        try:
            assert agent is get_capture_agent()
            assert agent.process_id == 3
        finally:
            configure(
                reporter=None, mailbox=None, profiles_root=None, process_id=0
            )
