"""The child process that runs once the gang has gone and the chip is free:
the plain reference over the sampled requests, and the reduction of the trace.
Reads a job file, writes a result file; prints nothing the parent parses.

    python benchmark/harness/post.py <job.json>
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    job = json.loads(Path(sys.argv[1]).read_text())
    sys.path.insert(0, str(ROOT))
    import jax

    from benchmark.harness.manifest import load_module
    from benchmark.trace import reduce as trace_reduce

    devices = jax.devices()
    result = {"device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind, "count": len(devices)}}
    if job.get("groups"):
        from benchmark.reference import served_gap

        ref = load_module(ROOT / job["reference_file"])
        t0 = time.time()
        params = ref.init_params(int(job["model_seed"]), job["config"])
        per_token = served_gap.token_gaps(
            ref, params, job["config"], job["groups"],
            int(job["pad_tokens_to"]), int(job["pad_rows_to"]))
        result["served"] = served_gap.summarize(**per_token)
        result["served"]["requests_compared"] = sum(len(g["requests"]) for g in job["groups"])
        result["reference_s"] = time.time() - t0
        del params
    if job.get("capture_dir") and devices[0].platform != "cpu":  # a CPU run has no device trace
        events = trace_reduce.load_xplane(trace_reduce.find_xplane(job["capture_dir"]))
        result["trace"] = trace_reduce.reduce(events)
    Path(job["result_file"]).write_text(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
