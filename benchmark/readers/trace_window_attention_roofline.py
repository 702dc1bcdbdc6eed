"""The window kernel's share of its roofline: the least time the chip could take
for the kernel's calls in the traced window (``roofline/window_attention.py``)
over the device time the trace shows for them, in percent.

The time is that of every device operation whose name holds one of ``match``
(the program names the kernel ``window_chunk_<rows of the chunk's shape>``) and
the trace says how often each ran.  A call's shape holds padding, so what a
call of a shape really admitted is COUNTED by the program, shape by shape:
``window_call_shapes`` in ``/v1/stats`` gives, by the rows of a chunk's shape,
the kernel's calls and the (query, key) pairs admitted in them (from each
chunk's start and length); the reader takes their growth between the window's
two snapshots and gives a traced call of a shape that shape's mean pairs.  (The
trace is taken after the window: the mix of shapes is the trace's own, what a
call of one shape admits is the window's.)  Query heads of the window layers,
KV heads and the window are the configuration's.  A shape the window never ran
is left out, time and all.  No such operation in the trace, a configuration
without window layers, or stats without the counts: nothing to read."""

import re

from benchmark.roofline import window_attention

_ROWS = re.compile(r"window_chunk_(\d+)")


def read(run, args):
    trace, cfg, serve = run.get("trace"), run["config"], run.get("serve")
    if not trace or not serve or not cfg.get("sliding_window"):
        return None
    first, last = (serve[k].get("window_call_shapes") for k in ("stats_open", "stats_close"))
    if first is None or last is None:
        return None
    kinds = list(cfg["layer_types"])[: cfg["num_hidden_layers"]]
    heads = {h for k, h in zip(kinds, cfg["num_attention_heads_per_layer"])
             if k == "sliding_attention"}
    if len(heads) != 1:
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
        seconds += secs
        least += calls * window_attention.least_seconds(
            pairs, next(iter(heads)), cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], run["peak"])["seconds"]
    if seconds <= 0:
        return None
    return 100.0 * least / seconds
