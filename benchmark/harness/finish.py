"""From what a run collected to the result's last line."""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Any, Dict, Optional

from .manifest import read_json

TOY_PREFIX = "cpu_toy."


def peak_for(device: Dict[str, Any], peaks: Dict[str, Any], toy: bool, chips: int) -> Dict[str, Any]:
    """The chip's peaks by ``device_kind``.  An unknown kind or fewer chips than
    the cell asks for ends the run with no result, never a default."""
    if toy:
        return next(iter(peaks.values()))
    if (device.get("platform") != "tpu" or device.get("kind") not in peaks
            or device.get("count", 0) < chips):
        print(f"benchmark: JAX found {device}: the cell needs {chips} TPU chip(s) of a "
              "kind in peaks.json", file=sys.stderr, flush=True)
        raise SystemExit(1)
    return peaks[device["kind"]]


def read_layer_metrics(cell, manifest, run_ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        spec = read_json(manifest.metric_file(m["name"]))
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(run_ctx, spec.get("args") or {})
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def compose(cell, args, manifest, *, device: Dict[str, Any], attempted: int, failed: int,
            end_to_end: Dict[str, float], run_ctx: Dict[str, Any],
            compared: Dict[str, Dict[str, float]], trace: Optional[Dict[str, Any]],
            sound: bool = True) -> Dict[str, Any]:
    toy = bool(args.cpu_toy)
    if int(args.trace):
        metrics = read_layer_metrics(cell, manifest, run_ctx)
    else:
        metrics = {m["name"]: {"value": float(end_to_end[m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end}
    agree = sound and bool(compared) and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in compared.values())
    line: Dict[str, Any] = {
        "correct": bool(agree and not toy),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": ({TOY_PREFIX + k: v for k, v in metrics.items()} if toy else metrics),
        "device": dict(device),
    }
    if int(args.trace) and trace:
        from benchmark.trace.reduce import breakdown

        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["window_s"]
        line["breakdown"] = breakdown(trace)
    if toy:
        line["cpu_toy"] = True
        line["toy_compare_ok"] = bool(agree)
    line["compared"] = compared
    return line


def run_post(root: Path, job: Dict[str, Any], toy: bool, timeout: float = 900.0) -> Dict[str, Any]:
    """The reference and the trace's reduction, in a child on the freed chip."""
    work = Path(job["work_dir"])
    job["result_file"] = str(work / "post_result.json")
    (work / "post_job.json").write_text(json.dumps(job))
    env = dict(os.environ)
    if toy:
        env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark" / "harness" / "post.py"),
         str(work / "post_job.json")],
        env=env, capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0 or not Path(job["result_file"]).exists():
        print(proc.stderr[-3000:], file=sys.stderr)
        print(f"benchmark: the reference child exited {proc.returncode}",
              file=sys.stderr, flush=True)
        raise SystemExit(1)
    return json.loads(Path(job["result_file"]).read_text())


def limit_of(config: Dict[str, Any], name: str) -> float:
    return float(config["correct"]["limits"][name])
