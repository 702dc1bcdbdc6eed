"""Transport seam tests: command builders (pure), the remote launch script
under a real shell, and the full remote spawner path through a stub ssh.

Mirrors the reference's spawner tests (``tests/test_spawner/``): what the
spawner hands the infrastructure is asserted without needing the real
infrastructure (there: a fake k8s client; here: sh standing in for sshd).
"""

import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from polyaxon_tpu.spawner.transport import (
    LocalExecTransport,
    SSHTransport,
    build_remote_script,
    build_ssh_argv,
)


class TestBuildSshArgv:
    def test_defaults(self):
        argv = build_ssh_argv("10.0.0.5", "echo hi")
        assert argv[0] == "ssh"
        assert "BatchMode=yes" in argv
        assert argv[-2:] == ["10.0.0.5", "echo hi"]

    def test_user_port_identity(self):
        argv = build_ssh_argv(
            "tpu-w0", "true", user="ml", port=2222, identity_file="/k/id"
        )
        assert "ml@tpu-w0" in argv
        assert argv[argv.index("-p") + 1] == "2222"
        assert argv[argv.index("-i") + 1] == "/k/id"

    def test_extra_opts_precede_target(self):
        argv = build_ssh_argv("h", "x", extra_opts=["-J", "bastion"])
        assert argv.index("-J") < argv.index("h")


class TestBuildRemoteScript:
    def test_env_quoting_and_unset(self):
        script = build_remote_script(
            ["python3", "-m", "w"],
            {"A": "has space", "GONE": None},
            cwd="/runs/x",
            log_path="/runs/x/l.log",
            rc_path="/runs/x/l.rc",
            pid_path="/runs/x/l.pid",
        )
        assert "export A='has space'" in script
        assert "unset GONE" in script
        assert "cd /runs/x" in script
        assert "setsid" in script

    def test_script_runs_and_reports_rc(self, tmp_path):
        """The generated script must work under a real sh: background the
        command, print the session pid, write rc atomically."""
        log, rc, pid = tmp_path / "p.log", tmp_path / "p.rc", tmp_path / "p.pid"
        script = build_remote_script(
            [sys.executable, "-c", "import os; print('out', os.environ['MARK'])"],
            {"MARK": "m42"},
            cwd=str(tmp_path),
            log_path=str(log),
            rc_path=str(rc),
            pid_path=str(pid),
        )
        out = subprocess.run(
            ["sh", "-c", script], capture_output=True, text=True, timeout=30
        )
        assert out.returncode == 0, out.stderr
        launched_pid = int(out.stdout.strip())
        assert launched_pid > 0
        for _ in range(100):
            if rc.exists():
                break
            time.sleep(0.1)
        assert rc.read_text().strip() == "0"
        assert "out m42" in log.read_text()

    def test_script_session_is_signalable(self, tmp_path):
        log, rc, pid = tmp_path / "s.log", tmp_path / "s.rc", tmp_path / "s.pid"
        script = build_remote_script(
            [sys.executable, "-c", "import time; time.sleep(60)"],
            {},
            cwd=str(tmp_path),
            log_path=str(log),
            rc_path=str(rc),
            pid_path=str(pid),
        )
        out = subprocess.run(
            ["sh", "-c", script], capture_output=True, text=True, timeout=30
        )
        sid = int(out.stdout.strip())
        os.killpg(sid, signal.SIGTERM)
        for _ in range(100):
            if rc.exists():
                break
            time.sleep(0.1)
        # Killed by TERM → sh reports 128+15.
        assert rc.read_text().strip() == str(128 + signal.SIGTERM)


@pytest.fixture()
def stub_ssh(tmp_path, monkeypatch):
    """An ``ssh`` on PATH that runs the payload locally — sshd stand-in.

    Mimics the real contract: last argv element is the remote script,
    everything before it is options+target, execution happens under sh.
    """
    bin_dir = tmp_path / "stub-bin"
    bin_dir.mkdir()
    stub = bin_dir / "ssh"
    stub.write_text('#!/bin/sh\nfor last; do :; done\nexec sh -c "$last"\n')
    stub.chmod(0o755)
    monkeypatch.setenv("PATH", f"{bin_dir}{os.pathsep}{os.environ['PATH']}")
    return stub


class TestSSHTransportViaStub:
    def test_sigkill_targets_worker_and_wrapper_records_rc(self, tmp_path, stub_ssh):
        """KILL can't be trapped: it must hit the worker (published child
        pid), leaving the wrapper alive to write 137 to the rc channel."""
        t = SSHTransport()
        ref = t.launch(
            "fake-host",
            [
                sys.executable,
                "-c",
                # A worker that ignores TERM — the case that forces KILL.
                "import pathlib, signal, time; signal.signal(signal.SIGTERM, "
                "signal.SIG_IGN); pathlib.Path('ready').touch(); time.sleep(60)",
            ],
            {},
            cwd=str(tmp_path),
            log_path=tmp_path / "k.log",
            rc_path=tmp_path / "k.rc",
        )
        for _ in range(100):
            if (tmp_path / "ready").exists():
                break
            time.sleep(0.1)
        assert (tmp_path / "ready").exists()
        assert ref.poll() is None
        ref.signal(signal.SIGTERM)
        assert ref.wait(2.0) is None  # survived TERM
        ref.signal(signal.SIGKILL)
        assert ref.wait(10.0) == 128 + signal.SIGKILL

    def test_signal_to_unreachable_host_does_not_raise(self, tmp_path, monkeypatch):
        bad_bin = tmp_path / "bad-bin"
        bad_bin.mkdir()
        bad = bad_bin / "ssh"
        bad.write_text("#!/bin/sh\necho 'connect refused' >&2\nexit 255\n")
        bad.chmod(0o755)
        monkeypatch.setenv("PATH", f"{bad_bin}{os.pathsep}{os.environ['PATH']}")
        from polyaxon_tpu.spawner.transport import _RemoteProcessRef

        ref = _RemoteProcessRef(SSHTransport(), "dead-host", 1234, tmp_path / "x.rc")
        ref.signal(signal.SIGTERM)  # must swallow, not raise

    def test_launch_poll_signal(self, tmp_path, stub_ssh):
        t = SSHTransport()
        log = tmp_path / "w.log"
        ref = t.launch(
            "fake-host",
            [sys.executable, "-c", "import time; time.sleep(60)"],
            {},
            cwd=str(tmp_path),
            log_path=log,
            rc_path=tmp_path / "w.rc",
        )
        assert ref.poll() is None
        ref.signal(signal.SIGTERM)
        assert ref.wait(10.0) == 128 + signal.SIGTERM

    def test_exit_code_roundtrip(self, tmp_path, stub_ssh):
        t = SSHTransport()
        ref = t.launch(
            "fake-host",
            [sys.executable, "-c", "raise SystemExit(7)"],
            {},
            cwd=str(tmp_path),
            log_path=tmp_path / "e.log",
            rc_path=tmp_path / "e.rc",
        )
        assert ref.wait(15.0) == 7


class TestLocalExecTransport:
    def test_env_overrides_and_unsets(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DROP_ME", "1")
        t = LocalExecTransport()
        ref = t.launch(
            "127.0.0.1",
            [
                sys.executable,
                "-c",
                "import os,sys; sys.exit(0 if 'DROP_ME' not in os.environ "
                "and os.environ['KEEP']=='k' else 3)",
            ],
            {"DROP_ME": None, "KEEP": "k"},
            cwd=str(tmp_path),
            log_path=tmp_path / "t.log",
            rc_path=tmp_path / "t.rc",
        )
        assert ref.wait(15.0) == 0


class TestReattach:
    def test_local_reattach_reads_rc_file(self, tmp_path):
        from polyaxon_tpu.spawner.transport import LocalExecTransport

        rc = tmp_path / "p.rc"
        ref = LocalExecTransport().reattach("127.0.0.1", 999999999, rc)
        # Dead pid, no rc file: synthesized failure code.
        assert ref.poll() == 1
        # With an rc file the real exit code wins.
        rc2 = tmp_path / "q.rc"
        rc2.write_text("0\n")
        ref2 = LocalExecTransport().reattach("127.0.0.1", 999999999, rc2)
        assert ref2.poll() == 0

    def test_local_reattach_live_process(self, tmp_path):
        import subprocess

        from polyaxon_tpu.spawner.transport import LocalExecTransport

        proc = subprocess.Popen(["sleep", "5"], start_new_session=True)
        try:
            ref = LocalExecTransport().reattach(
                "127.0.0.1", proc.pid, tmp_path / "none.rc"
            )
            assert ref.poll() is None  # genuinely alive
            import signal

            ref.signal(signal.SIGKILL)
            assert ref.wait(5.0) is not None
        finally:
            proc.kill()
            proc.wait()

    def test_remote_reattach_polls_rc_from_shared_dir(self, tmp_path):
        from polyaxon_tpu.spawner.transport import SSHTransport

        t = SSHTransport()
        rc = tmp_path / "proc0.rc"
        ref = t.reattach("worker-host", 4242, rc)
        assert ref.poll() is None  # no rc yet: still running
        rc.write_text("7\n")
        assert ref.poll() == 7  # exit code rides the shared run dir
        assert ref.pid == 4242 and ref.host == "worker-host"
