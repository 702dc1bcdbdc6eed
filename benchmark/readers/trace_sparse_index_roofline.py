"""The lightning indexer's kernel's share of its roofline: the least time the
chip could take for the kernel's calls in the traced window
(``roofline/sparse_index.py``) over the device time the trace shows for them,
in percent.

The time is that of every device operation whose name holds one of ``match``
(the program names the kernel ``dsa_index_<rows of the chunk's shape>``) and
the trace says how often each ran.  A call's shape holds padding, so what a
call of a shape really scored is COUNTED by the program, shape by shape:
``dsa_index_call_shapes`` in ``/v1/stats`` gives, by the rows of a chunk's
shape, the kernel's calls (one a layer a key tile of a chunk's walk, as the
trace counts them), the pairs scored and the keys read in them (from each
chunk's start and length); the reader takes their
growth between the window's two snapshots and gives a traced call of a shape
that shape's mean pairs and keys, as ``trace_window_attention_roofline.py``
does.  The indexer's heads and head size are the configuration's.  A shape the
window never ran is left out, time and all.  No such operation in the trace, a
configuration without an indexer, or stats without the counts: nothing to
read."""

import re

from benchmark.roofline import sparse_index

_ROWS = re.compile(r"dsa_index_(\d+)")


def read(run, args):
    trace, cfg, serve = run.get("trace"), run["config"], run.get("serve")
    if not trace or not serve or not cfg.get("index_n_heads"):
        return None
    first, last = (serve[k].get("dsa_index_call_shapes") for k in ("stats_open", "stats_close"))
    if first is None or last is None:
        return None
    seconds = least = 0.0
    for name, secs, calls in trace[args.get("line", "ops")]:
        if not any(m in name for m in args["match"]):
            continue
        rows = _ROWS.search(name)
        if not rows:
            return None
        now, then = last.get(rows.group(1)), first.get(rows.group(1), {})
        ran = now["calls"] - then.get("calls", 0) if now else 0
        if ran <= 0:
            continue
        pairs = (now["pairs"] - then.get("pairs", 0)) / ran
        keys = (now["keys"] - then.get("keys", 0)) / ran
        seconds += secs
        least += calls * sparse_index.least_seconds(
            int(rows.group(1)), pairs, keys, cfg["index_n_heads"], cfg["index_head_dim"],
            run["peak"])["seconds"]
    if seconds <= 0:
        return None
    return 100.0 * least / seconds
