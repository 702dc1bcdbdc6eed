"""The gang rendezvous env contract: the TF_CONFIG equivalent.

Parity: the reference injects framework-specific rendezvous env into every
pod — ``TF_CONFIG`` (``polypod/tensorflow.py:193-203``), ``MASTER_ADDR/RANK``
(``polypod/pytorch.py:139-157``), DMLC vars (``polypod/mxnet.py:19-35``).
TPU-native: one dialect for every strategy — coordinator address +
process id + mesh shape — consumed by ``jax.distributed.initialize`` and the
mesh builder.  The spawner writes these; the worker reads them.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Dict, Optional


class EnvVars:
    RUN_ID = "POLYAXON_TPU_RUN_ID"
    RUN_UUID = "POLYAXON_TPU_RUN_UUID"
    RUN_DIR = "POLYAXON_TPU_RUN_DIR"
    SPEC_PATH = "POLYAXON_TPU_SPEC_PATH"
    PROCESS_ID = "POLYAXON_TPU_PROCESS_ID"
    NUM_PROCESSES = "POLYAXON_TPU_NUM_PROCESSES"
    COORDINATOR = "POLYAXON_TPU_COORDINATOR"
    DEVICES_PER_HOST = "POLYAXON_TPU_DEVICES_PER_HOST"
    ACCELERATOR = "POLYAXON_TPU_ACCELERATOR"
    MESH = "POLYAXON_TPU_MESH"
    MESH_DCN = "POLYAXON_TPU_MESH_DCN"
    STRATEGY = "POLYAXON_TPU_STRATEGY"
    STRATEGY_OPTIONS = "POLYAXON_TPU_STRATEGY_OPTIONS"
    HEARTBEAT_INTERVAL = "POLYAXON_TPU_HEARTBEAT_INTERVAL"
    SEED = "POLYAXON_TPU_SEED"
    DATA_DIR = "POLYAXON_TPU_DATA_DIR"


@dataclass
class GangInfo:
    """Decoded worker-side view of the rendezvous contract."""

    run_id: int
    run_uuid: str
    run_dir: str
    spec_path: str
    process_id: int
    num_processes: int
    coordinator: Optional[str]
    devices_per_host: int
    accelerator: str
    mesh_axes: Dict[str, int]
    #: subset of mesh_axes spanning slices (DCN); empty for single-slice
    dcn_axes: Dict[str, int]
    strategy: str
    strategy_options: Dict[str, Any]
    heartbeat_interval: float
    seed: Optional[int]
    #: The store layout's shared data/ dir (registered datasets); the
    #: spawner resolves it so workers never re-derive layout structure.
    data_dir: Optional[str] = None

    @classmethod
    def from_env(cls, env: Optional[Dict[str, str]] = None) -> "GangInfo":
        e = env if env is not None else os.environ
        seed = e.get(EnvVars.SEED)
        return cls(
            run_id=int(e[EnvVars.RUN_ID]),
            run_uuid=e[EnvVars.RUN_UUID],
            run_dir=e[EnvVars.RUN_DIR],
            spec_path=e[EnvVars.SPEC_PATH],
            process_id=int(e[EnvVars.PROCESS_ID]),
            num_processes=int(e[EnvVars.NUM_PROCESSES]),
            coordinator=e.get(EnvVars.COORDINATOR) or None,
            devices_per_host=int(e.get(EnvVars.DEVICES_PER_HOST, "1")),
            accelerator=e.get(EnvVars.ACCELERATOR, "cpu"),
            mesh_axes=json.loads(e.get(EnvVars.MESH, "{}")),
            dcn_axes=json.loads(e.get(EnvVars.MESH_DCN, "{}")),
            strategy=e.get(EnvVars.STRATEGY, "ddp"),
            strategy_options=json.loads(e.get(EnvVars.STRATEGY_OPTIONS, "{}")),
            heartbeat_interval=float(e.get(EnvVars.HEARTBEAT_INTERVAL, "5.0")),
            seed=int(seed) if seed not in (None, "") else None,
            data_dir=e.get(EnvVars.DATA_DIR) or None,
        )


def gang_env(
    *,
    run_id: int,
    run_uuid: str,
    run_dir: str,
    spec_path: str,
    process_id: int,
    num_processes: int,
    coordinator: Optional[str],
    devices_per_host: int,
    accelerator: str,
    mesh_axes: Dict[str, int],
    strategy: str,
    dcn_axes: Optional[Dict[str, int]] = None,
    strategy_options: Dict[str, Any],
    heartbeat_interval: float = 5.0,
    seed: Optional[int] = None,
    data_dir: Optional[str] = None,
) -> Dict[str, str]:
    """Spawner-side encoder (inverse of ``GangInfo.from_env``)."""
    env = {
        EnvVars.RUN_ID: str(run_id),
        EnvVars.RUN_UUID: run_uuid,
        EnvVars.RUN_DIR: run_dir,
        EnvVars.SPEC_PATH: spec_path,
        EnvVars.PROCESS_ID: str(process_id),
        EnvVars.NUM_PROCESSES: str(num_processes),
        EnvVars.DEVICES_PER_HOST: str(devices_per_host),
        EnvVars.ACCELERATOR: accelerator,
        EnvVars.MESH: json.dumps(mesh_axes),
        EnvVars.MESH_DCN: json.dumps(dcn_axes or {}),
        EnvVars.STRATEGY: strategy,
        EnvVars.STRATEGY_OPTIONS: json.dumps(strategy_options),
        EnvVars.HEARTBEAT_INTERVAL: str(heartbeat_interval),
    }
    if coordinator:
        env[EnvVars.COORDINATOR] = coordinator
    if seed is not None:
        env[EnvVars.SEED] = str(seed)
    if data_dir:
        env[EnvVars.DATA_DIR] = data_dir
    return env


#: libtpu's per-process chip grid for the chip counts one host can hold
#: (a v5e host is a 2x2 or 2x4 tray).
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def visible_chips_env(n_chips: int, first_chip: int = 0) -> Dict[str, str]:
    """The environment that limits one process to ``n_chips`` TPU chips
    of its host, starting at ``first_chip``.

    A chip belongs to one process at a time and libtpu claims every chip
    it can see, so a process that should use fewer than the host holds
    must be told which: this is what lets a ``v5e-1`` gang run on a
    four-chip host and gives each replica of a local fleet its own chip.
    (The platform itself is requested separately: ``JAX_PLATFORMS=tpu``,
    by the worker from its plan, by the fleet for its replicas.)
    """
    env = {
        "TPU_VISIBLE_CHIPS": ",".join(
            str(first_chip + i) for i in range(n_chips)
        ),
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
    bounds = _CHIP_BOUNDS.get(n_chips)
    if bounds is not None:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
    return env
