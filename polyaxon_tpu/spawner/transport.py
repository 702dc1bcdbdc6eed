"""Spawner transports: how a gang process reaches its host.

Parity: the reference's spawner drives *remote* infrastructure through the
k8s API (``polypod/experiment.py:160-244`` builds pods, ``:350-357``
starts/stops them).  TPU-native equivalent: a transport seam —
``launch(host, argv, env) / poll / signal`` — with two backends:

- :class:`LocalExecTransport` — subprocesses on this machine (dev/test; the
  whole e2e suite runs through it), and
- :class:`SSHTransport` — TPU-VM hosts over ssh, the way real multi-host
  slices are driven (``gcloud compute tpus tpu-vm ssh`` is a thin wrapper
  over exactly this).

The contract both sides share: the run directory lives on a filesystem
visible to the control plane AND every worker host at the same path (on
TPU-VM pods: an NFS or gcsfuse mount) — reports, logs, exit codes, and
code snapshots all ride it, so the control plane never needs a persistent
connection to a worker.
"""

from __future__ import annotations

import logging
import os
import shlex
import signal as signal_mod
import subprocess
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

logger = logging.getLogger(__name__)


class ProcessRef:
    """A launched gang process as seen by the control plane."""

    #: Host-local pid (also the process-group id: transports launch every
    #: process as a session leader so signals take down the whole tree).
    pid: int

    def poll(self) -> Optional[int]:  # pragma: no cover - interface
        """Exit code, or None while running."""
        raise NotImplementedError

    def signal(self, sig: int) -> None:  # pragma: no cover - interface
        """Deliver ``sig`` to the process group (non-blocking)."""
        raise NotImplementedError

    def wait(self, timeout: float) -> Optional[int]:  # pragma: no cover
        """Block up to ``timeout`` for exit; return the code or None."""
        raise NotImplementedError


class Transport:
    """Launches gang processes on a host. One instance serves many gangs."""

    def launch(
        self,
        host: str,
        argv: Sequence[str],
        env: Dict[str, str],
        *,
        cwd: str,
        log_path: Path,
        rc_path: Path,
    ) -> ProcessRef:  # pragma: no cover - interface
        """Start ``argv`` on ``host`` with ``env`` exported (None values =
        unset), stdout+stderr appended to ``log_path``, exit code written to
        ``rc_path``."""
        raise NotImplementedError

    def reattach(
        self, host: str, pid: int, rc_path: Path
    ) -> ProcessRef:  # pragma: no cover - interface
        """Rebuild a ref for a process launched by a PREVIOUS control plane
        (restart recovery).  The ref must poll correctly whether the process
        is still running or already exited."""
        raise NotImplementedError


# -- local exec ---------------------------------------------------------------


class _LocalProcessRef(ProcessRef):
    def __init__(self, proc: subprocess.Popen) -> None:
        self._proc = proc
        self.pid = proc.pid

    def poll(self) -> Optional[int]:
        return self._proc.poll()

    def signal(self, sig: int) -> None:
        try:
            os.killpg(self.pid, sig)  # pgid == pid (start_new_session)
        except (ProcessLookupError, PermissionError, OSError):
            try:
                self._proc.send_signal(sig)
            except (ProcessLookupError, OSError):
                pass

    def wait(self, timeout: float) -> Optional[int]:
        try:
            return self._proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None


class LocalExecTransport(Transport):
    """Subprocesses on the control-plane machine (ignores ``host``).

    Inherits the control plane's os.environ under the overrides — local
    workers need the same interpreter setup (PATH, venv) the service has.
    """

    def launch(
        self,
        host: str,
        argv: Sequence[str],
        env: Dict[str, str],
        *,
        cwd: str,
        log_path: Path,
        rc_path: Path,
    ) -> ProcessRef:
        full_env = dict(os.environ)
        # The gang contract may DELETE inherited vars: None means "unset".
        for key, value in env.items():
            if value is None:
                full_env.pop(key, None)
            else:
                full_env[key] = value
        log_path.parent.mkdir(parents=True, exist_ok=True)
        log_fh = open(log_path, "ab")
        proc = subprocess.Popen(
            list(argv),
            env={k: v for k, v in full_env.items() if v is not None},
            stdout=log_fh,
            stderr=subprocess.STDOUT,
            cwd=cwd,
            start_new_session=True,
        )
        log_fh.close()  # child holds the fd
        return _LocalProcessRef(proc)

    def reattach(self, host: str, pid: int, rc_path: Path) -> ProcessRef:
        return _ReattachedLocalRef(pid, rc_path)


class _ReattachedLocalRef(ProcessRef):
    """A local gang process inherited from a dead control plane.

    We are not its parent, so ``waitpid`` is unavailable: liveness comes
    from signal-0 to the process group (pgid == pid — launches are session
    leaders), and the exit code from the rc file when one exists.  A local
    launch records no rc file, so a process found dead reads as exit 1
    (status-wise the worker's own final report line, ingested from the run
    dir, still wins when it got written)."""

    def __init__(self, pid: int, rc_path: Path) -> None:
        self.pid = pid
        self._rc_path = rc_path
        self._exit_code: Optional[int] = None

    def poll(self) -> Optional[int]:
        if self._exit_code is not None:
            return self._exit_code
        try:
            raw = self._rc_path.read_text().strip()
        except OSError:
            raw = ""
        if raw:
            self._exit_code = int(raw)
            return self._exit_code
        try:
            os.killpg(self.pid, 0)
        except ProcessLookupError:
            self._exit_code = 1  # died before this control plane attached
            return self._exit_code
        except (PermissionError, OSError):
            # Exists but not signalable by us — treat as alive; the
            # heartbeat cron is the backstop if it's a reused pid.
            return None
        # Signal-0 counts zombies as alive: a worker whose (dead or
        # unrelated) parent never reaped it would read as running forever.
        try:
            with open(f"/proc/{self.pid}/stat") as fh:
                # Field 3, after the parenthesized comm (which may itself
                # contain spaces/parens — split after the LAST ')').
                state = fh.read().rsplit(")", 1)[1].split()[0]
            if state == "Z":
                self._exit_code = 1
                return self._exit_code
        except (OSError, IndexError):
            pass  # no procfs — fall back to signal-0 semantics
        return None

    def signal(self, sig: int) -> None:
        try:
            os.killpg(self.pid, sig)
        except (ProcessLookupError, PermissionError, OSError):
            pass

    def wait(self, timeout: float) -> Optional[int]:
        deadline = time.time() + timeout
        while True:
            code = self.poll()
            if code is not None or time.time() >= deadline:
                return code
            time.sleep(min(0.2, max(0.0, deadline - time.time())))


# -- ssh ----------------------------------------------------------------------


def build_remote_script(
    argv: Sequence[str],
    env: Dict[str, str],
    *,
    cwd: str,
    log_path: str,
    rc_path: str,
    pid_path: str,
) -> str:
    """The shell script SSHTransport runs on the worker host.

    Pure function (unit-tested without ssh): backgrounds the worker in its
    own session, appends stdout+stderr to ``log_path``, records the session
    pid in ``pid_path`` and the worker's own pid in ``pid_path``+``.child``
    (signalling targets), and the exit code in ``rc_path`` (the poll
    channel) — all on the shared run dir, so polling never needs an ssh
    round-trip.
    """
    pre = [f"cd {shlex.quote(cwd)}"]
    for key, value in sorted(env.items()):
        if value is None:
            pre.append(f"unset {key}")
        else:
            pre.append(f"export {key}={shlex.quote(str(value))}")
    inner = " && ".join(pre)
    cmd = " ".join(shlex.quote(a) for a in argv)
    rc_q, rc_tmp_q = shlex.quote(rc_path), shlex.quote(rc_path + ".tmp")
    pid_q, pid_tmp_q = shlex.quote(pid_path), shlex.quote(pid_path + ".tmp")
    child_q = shlex.quote(pid_path + ".child")
    child_tmp_q = shlex.quote(pid_path + ".child.tmp")
    # The tmp+mv dance makes the rc/pid files appear atomically (the control
    # plane polls them over the shared mount). setsid → the whole remote
    # tree is one signalable session; $! after a backgrounded setsid is the
    # session leader's pid.  The wrapper must SURVIVE a group TERM (or the
    # exit code is never recorded): it forwards the signal to the worker and
    # re-waits for the real status.  SIGKILL can't be trapped, which is why
    # the worker's own pid is published: KILL goes to the worker, the
    # wrapper lives to record 137.
    wrapped = (
        "child=; "
        "trap 'kill -TERM \"$child\" 2>/dev/null' TERM INT; "
        f"{cmd} & child=$!; "
        f"echo $child > {child_tmp_q} && mv {child_tmp_q} {child_q}; "
        'rc=127; while :; do wait "$child"; rc=$?; '
        'kill -0 "$child" 2>/dev/null || break; done; '
        f"echo $rc > {rc_tmp_q} && mv {rc_tmp_q} {rc_q}"
    )
    return (
        f"{inner} && "
        f"setsid sh -c {shlex.quote(wrapped)} >> {shlex.quote(log_path)} 2>&1 & "
        f"echo $! > {pid_tmp_q} && mv {pid_tmp_q} {pid_q} && cat {pid_q}"
    )


def build_ssh_argv(
    host: str,
    script: str,
    *,
    user: Optional[str] = None,
    port: Optional[int] = None,
    identity_file: Optional[str] = None,
    extra_opts: Sequence[str] = (),
) -> List[str]:
    """The ssh command line (pure function, unit-tested)."""
    argv = ["ssh", "-o", "BatchMode=yes", "-o", "StrictHostKeyChecking=accept-new"]
    if port is not None:
        argv += ["-p", str(port)]
    if identity_file:
        argv += ["-i", identity_file]
    argv += list(extra_opts)
    target = f"{user}@{host}" if user else host
    argv += [target, script]
    return argv


class _RemoteProcessRef(ProcessRef):
    """A process on a worker host, observed via the shared run dir.

    Liveness: the rc file appearing means exited (its content is the code);
    no rc file means running — a host that dies without writing one is
    caught by the zombie-heartbeat cron, the same backstop local gangs have.
    """

    #: How long after a group SIGKILL (rc writer dead too) before the exit
    #: code is synthesized.
    KILL_SETTLE = 5.0

    def __init__(
        self, transport: "SSHTransport", host: str, pid: int, rc_path: Path
    ) -> None:
        self._transport = transport
        self.host = host
        self.pid = pid
        self._rc_path = rc_path
        self._child_pid_path = rc_path.with_suffix(".pid.child")
        self._exit_code: Optional[int] = None
        self._group_killed_at: Optional[float] = None

    def poll(self) -> Optional[int]:
        if self._exit_code is not None:
            return self._exit_code
        try:
            raw = self._rc_path.read_text().strip()
        except (FileNotFoundError, OSError):
            raw = ""
        if raw:
            self._exit_code = int(raw)
            return self._exit_code
        if (
            self._group_killed_at is not None
            and time.time() - self._group_killed_at > self.KILL_SETTLE
        ):
            # The whole session (rc writer included) took the KILL; nothing
            # will ever write the rc file — synthesize the code so the gang
            # reads as exited and the run can finalize.
            self._exit_code = 128 + int(signal_mod.SIGKILL)
            return self._exit_code
        return None

    def signal(self, sig: int) -> None:
        """Best-effort: an unreachable host (the usual reason to signal a
        zombie) must not crash the monitor/cron tasks doing the signalling."""
        # The ``-s N --`` spelling is the one dash's kill builtin accepts
        # for group targets (``kill -15 -- -pid`` it rejects).
        target = f"-- -{self.pid}"  # negative pid == whole remote session
        if sig == signal_mod.SIGKILL:
            # KILL can't be trapped: aim it at the worker itself (published
            # by the launch wrapper) so the wrapper survives to record the
            # exit code; fall back to the group if the file never appeared.
            try:
                child = self._child_pid_path.read_text().strip()
            except (FileNotFoundError, OSError):
                child = ""
            if child:
                target = child
            else:
                self._group_killed_at = self._group_killed_at or time.time()
        try:
            self._transport.run_on(
                self.host, f"kill -s {int(sig)} {target} 2>/dev/null || true"
            )
        except Exception as e:
            logger.warning("Signal %s to %s on %s failed: %s", sig, self.pid, self.host, e)

    def wait(self, timeout: float) -> Optional[int]:
        deadline = time.time() + timeout
        while True:
            code = self.poll()
            if code is not None or time.time() >= deadline:
                return code
            time.sleep(min(0.2, max(0.0, deadline - time.time())))


class SSHTransport(Transport):
    """Drive TPU-VM (or any ssh-reachable) hosts.

    Assumes: passwordless ssh (agent or ``identity_file``), the worker image
    has the same python env at ``remote_python``, and the store layout's
    base dir is mounted at the same path on every host.
    """

    def __init__(
        self,
        *,
        user: Optional[str] = None,
        port: Optional[int] = None,
        identity_file: Optional[str] = None,
        extra_opts: Sequence[str] = (),
        connect_timeout: float = 10.0,
    ) -> None:
        self.user = user
        self.port = port
        self.identity_file = identity_file
        self.extra_opts = list(extra_opts)
        self.connect_timeout = connect_timeout

    def run_on(self, host: str, script: str) -> str:
        """Run a short script on ``host``; returns stdout. Raises on failure."""
        argv = build_ssh_argv(
            host,
            script,
            user=self.user,
            port=self.port,
            identity_file=self.identity_file,
            extra_opts=self.extra_opts,
        )
        out = subprocess.run(
            argv,
            capture_output=True,
            text=True,
            timeout=self.connect_timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"ssh to {host} failed (rc={out.returncode}): {out.stderr.strip()[:500]}"
            )
        return out.stdout

    def launch(
        self,
        host: str,
        argv: Sequence[str],
        env: Dict[str, str],
        *,
        cwd: str,
        log_path: Path,
        rc_path: Path,
    ) -> ProcessRef:
        pid_path = rc_path.with_suffix(".pid")
        script = build_remote_script(
            argv,
            env,
            cwd=cwd,
            log_path=str(log_path),
            rc_path=str(rc_path),
            pid_path=str(pid_path),
        )
        out = self.run_on(host, script)
        pid = int(out.strip().splitlines()[-1])
        return _RemoteProcessRef(self, host, pid, rc_path)

    def reattach(self, host: str, pid: int, rc_path: Path) -> ProcessRef:
        # The remote ref is already reconstructable from disk alone: the rc
        # file (shared run dir) is the poll channel and pid the signal target.
        return _RemoteProcessRef(self, host, pid, rc_path)


def terminate_refs(
    refs: Dict[int, ProcessRef], grace: float = 5.0
) -> None:
    """TERM every live ref, wait up to ``grace``, then KILL stragglers."""
    for ref in refs.values():
        if ref.poll() is None:
            ref.signal(signal_mod.SIGTERM)
    deadline = time.time() + grace
    for ref in refs.values():
        remaining = max(0.0, deadline - time.time())
        if ref.wait(remaining) is None:
            ref.signal(signal_mod.SIGKILL)
            ref.wait(5.0)
