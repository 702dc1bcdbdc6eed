"""Managed storage layout for runs.

Parity: reference ``stores/managers/base.py:11-40`` and friends —
``get_experiment_outputs_path`` / logs path / data path resolution over
NFS/S3/GCS volumes.  TPU-native: one base directory (local disk or a
mounted GCS fuse path) with a fixed per-run layout; the reports/ directory
is the worker→control-plane reporting channel (the sidecar/publisher
replacement), and checkpoints/ is first-class (the reference only manages
outputs dirs; see SURVEY §5 checkpoint/resume).
"""

from __future__ import annotations

import shutil
from dataclasses import dataclass
from pathlib import Path
from typing import Union


@dataclass(frozen=True)
class RunPaths:
    root: Path

    @property
    def spec_path(self) -> Path:
        return self.root / "spec.json"

    @property
    def outputs(self) -> Path:
        return self.root / "outputs"

    @property
    def logs(self) -> Path:
        return self.root / "logs"

    @property
    def reports(self) -> Path:
        return self.root / "reports"

    @property
    def checkpoints(self) -> Path:
        return self.root / "checkpoints"

    @property
    def commands(self) -> Path:
        """Control-plane→worker command bus root: the inverse of reports/.
        The control plane drops ``<uuid>.json`` files into per-process
        mailboxes; each worker's heartbeat thread polls its own."""
        return self.root / "commands"

    @property
    def profiles(self) -> Path:
        """On-demand capture artifacts: ``profiles/<capture_id>/proc<N>/``."""
        return self.root / "profiles"

    @property
    def code(self) -> Path:
        return self.root / "code"

    def report_file(self, process_id: int) -> Path:
        return self.reports / f"proc{process_id}.jsonl"

    def log_file(self, process_id: int) -> Path:
        return self.logs / f"proc{process_id}.log"

    def command_dir(self, process_id: int) -> Path:
        return self.commands / f"proc{process_id}"

    def ensure(self) -> "RunPaths":
        for p in (self.root, self.outputs, self.logs, self.reports,
                  self.checkpoints, self.commands):
            p.mkdir(parents=True, exist_ok=True)
        return self


class StoreLayout:
    """Resolves per-run and shared paths under one base directory."""

    def __init__(self, base_dir: Union[str, Path]) -> None:
        self.base_dir = Path(base_dir)
        self.base_dir.mkdir(parents=True, exist_ok=True)

    @property
    def runs_dir(self) -> Path:
        return self.base_dir / "runs"

    @property
    def snapshots_dir(self) -> Path:
        return self.base_dir / "snapshots"

    @property
    def data_dir(self) -> Path:
        return self.base_dir / "data"

    @property
    def kv_cache_dir(self) -> Path:
        """Shared persistent prefix-KV store (``serving/kvstore.py``):
        serving replicas snapshot their hot prefix blocks here, and
        replacement/scale-up replicas preload them during warmup, so a
        new replica boots prefix-warm as well as compile-warm."""
        return self.base_dir / "kv_cache"

    def run_paths(self, run_uuid: str) -> RunPaths:
        return RunPaths(self.runs_dir / run_uuid)

    def copy_outputs(self, from_uuid: str, to_uuid: str) -> None:
        """COPY cloning strategy: duplicate a run's outputs+checkpoints.

        Parity: reference ``scheduler/tasks/experiments.py:27-56``
        (``copy_experiment`` via stores).
        """
        src = self.run_paths(from_uuid)
        dst = self.run_paths(to_uuid).ensure()
        for sub in ("outputs", "checkpoints"):
            s, d = src.root / sub, dst.root / sub
            if s.exists():
                shutil.copytree(s, d, dirs_exist_ok=True)
