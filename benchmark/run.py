#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell by name from ``BENCHMARK.json`` (configuration, traffic mix,
per-layer metrics: files of their own under ``benchmark/``), lets the
program's ``Orchestrator`` spawn the gang on the cell's chips, drives it,
stops it, checks what the timed path produced against the plain reference, and
prints as its last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), with the numbers compared beside their limits under
``compared``, last.  This process never imports jax.

No TPU of a kind in ``benchmark/peaks.json``, or fewer chips than the cell
asks for: a non-zero exit and no result.  ``--cpu-toy`` rehearses the same
path at toy sizes on the CPU; its numbers go under ``cpu_toy.*`` names, never
a device metric's, and ``correct`` is false.  ``--control`` puts the
configuration's lower-precision control in the program's place and ``--fault``
breaks the timed path underneath: both are held to the same limits, and
``correct`` has to come out false (the tests and the limits' readings only).
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-toy", action="store_true",
                    help="rehearse at toy sizes on the CPU (never a fallback)")
    ap.add_argument("--control", action="store_true",
                    help="the lower-precision control in the program's place")
    ap.add_argument("--fault", default="",
                    help="plant a fault under the timed path (tests and limits only)")
    return ap.parse_args(argv)


def fail(message: str, code: int = 1) -> "NoReturn":  # noqa: F821
    print(f"benchmark: {message}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def run_cell(args, t_start: float = T_START):
    """One run of one cell: the result's last line."""
    if not (ROOT / "polyaxon_tpu" / "__init__.py").exists():
        fail(f"no polyaxon_tpu package in {ROOT}: run from the root of a checkout", 2)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".compile_cache"))
    os.environ["POLYAXON_TPU_RESOURCE_INTERVAL"] = "2"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.cpu_toy:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        os.environ.pop("JAX_PLATFORMS", None)

    import importlib

    from benchmark.harness import overrides
    from benchmark.harness.gang import GangFailed
    from benchmark.harness.manifest import Manifest, ManifestError, read_json

    try:
        manifest = Manifest(ROOT)
        manifest.check()
        cell = manifest.cell(args.workload)
    except ManifestError as e:
        fail(str(e), 2)
    if args.cpu_toy:
        cell.config = overrides.apply_toy(cell.config)
        cell.traffic = overrides.apply_toy(cell.traffic)
    if args.control:
        cell.config = overrides.apply_control(cell.config)
    generator = importlib.import_module(f"benchmark.generators.{cell.traffic['kind']}")
    driver = importlib.import_module(f"benchmark.harness.drive_{cell.config['entry']}")
    peaks = read_json(manifest.bench / "peaks.json")
    try:
        raw = driver.run(cell, args, ROOT, generator, t_start)
        return driver.finish(cell, args, raw, manifest, peaks, ROOT)
    except GangFailed as e:
        fail(str(e))


def main(argv=None) -> int:
    line = run_cell(parse_args(argv))
    for name, c in line["compared"].items():
        print(f"compared {name} = {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
