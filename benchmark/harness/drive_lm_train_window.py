"""A training cell: a ``kind: experiment`` gang spawned by the ``Orchestrator``
whose worker runs ``benchmark/entries/lm_train_window.py`` (the program's
train step under a window).  This process never imports jax; it waits, with a
bound, for the worker's result file.
"""

from __future__ import annotations

import json
import tempfile
import time
from pathlib import Path
from typing import Any, Dict

from .drive_lm_server import model_declarations
from .gang import Gang, GangFailed

ENTRY = "benchmark.entries.lm_train_window:main"


def run(cell, args, root: Path, generator, t_start: float) -> Dict[str, Any]:
    config, traffic = cell.config, cell.traffic
    toy = bool(args.cpu_toy)
    model_seed = int(args.seed) % 2147483647
    stream = generator.schedule(traffic, int(args.seed), float(args.seconds),
                                int(config["vocab_size"]))[0]
    work = Path(tempfile.mkdtemp(prefix="bench_train_"))
    job = {
        "seconds": float(args.seconds),
        "warm_steps": stream["warm_steps"],
        "reference_steps": int(config.get("correct", {}).get("reference_steps", stream["warm_steps"])),
        "trace_steps": stream["trace_steps"],
        "trace_dir": str(work / "trace") if int(args.trace) else None,
        "reference_file": str(root / config["reference"]),
        "config": {k: v for k, v in config.items() if not isinstance(v, dict)},
        "result_file": str(work / "result.json"),
        "control_mode": (config.get("control") or {}).get("reference_mode") if args.control else None,
        "fault": args.fault or None,
    }
    (work / "job.json").write_text(json.dumps(job))
    topology = dict(config["topology"])
    if toy:
        topology = {"accelerator": "cpu-1"} if cell.chips == 1 else {
            "accelerator": "cpu", "num_devices": cell.chips, "num_hosts": 1,
            **{k: v for k, v in topology.items() if k != "accelerator"}}
    spec = {
        "kind": "experiment",
        "run": {"entrypoint": ENTRY},
        "declarations": {
            **model_declarations(config), **config["trainer"],
            "batch": stream["batch"], "seq": stream["seq"],
            "bench_job": str(work / "job.json"),
        },
        "environment": {"seed": model_seed, "topology": topology},
    }
    gang = Gang(toy=toy, chips=cell.chips)
    try:
        gang.submit(spec, cell.name)
        done = gang.wait_done(1100.0)
        result_file = Path(job["result_file"])
        if done.status != "succeeded" or not result_file.exists():
            raise GangFailed(f"train gang ended {done.status!r}: {gang.logs_tail()}")
        result = json.loads(result_file.read_text())
        result["work_dir"] = str(work)
        result["t_start"] = t_start
        result["t_done"] = time.time()
        return result
    finally:
        gang.close()


def finish(cell, args, raw: Dict[str, Any], manifest, peaks, root):
    import shutil

    from . import finish as fin

    config = cell.config
    toy = bool(args.cpu_toy)
    shutil.rmtree(raw["work_dir"], ignore_errors=True)
    device = dict(raw["device"])
    peak = fin.peak_for(device, peaks, toy, cell.chips)
    ends = raw["step_ends"]
    if not ends:
        raise GangFailed("no training step completed inside the window")
    span = ends[-1] - raw["t_open"]
    rate = len(ends) * raw["tokens_per_step"] / span
    end_to_end = {"train_tokens_per_s": rate, "setup_s": raw["t_open"] - raw["t_start"]}
    trace = raw.get("trace")
    # The profiler's stop costs the host about a second between two steps: the
    # traced run's own rate (for the per-layer readings) is taken after it.
    traced_until = (raw.get("trace_span") or [None, None])[1]
    after = [t for t in ends if traced_until and t > traced_until]
    rate_untraced = ((len(after) - 1) * raw["tokens_per_step"] / (after[-1] - after[0])
                     if len(after) > 2 else rate)
    run_ctx = {
        "config": config, "traffic": cell.traffic, "peak": peak, "chips": cell.chips,
        "setup": {
            "boot_to_chip_s": raw["t_chip"] - raw["t_start"],
            "compile_cache_misses": raw["compile_cache_misses_setup"],
        },
        "train": {
            "t_open": raw["t_open"], "step_ends": ends, "tokens_per_s": rate_untraced,
            "batch": raw["batch"], "seq": raw["seq"],
            "steps_traced": int(cell.traffic.get("trace_steps", 3)) if trace else None,
        },
        "trace": trace,
    }
    got = raw.get("compared") or {}
    compared = {name: {"value": got.get(name), "limit": fin.limit_of(config, name)}
                for name in config["correct"]["limits"]}
    sound = raw["compile_cache_misses_window"] == 0
    return fin.compose(
        cell, args, manifest, device=device, attempted=len(ends), failed=0,
        end_to_end=end_to_end, run_ctx=run_ctx, compared=compared, trace=trace, sound=sound)
