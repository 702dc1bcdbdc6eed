"""The routed experts' grouped product's share of its roofline: the least time
the chip could take for the product's calls in the traced window
(``roofline/grouped_matmul.py``) over the device time the trace shows for them,
in percent.

The time is that of every device operation whose name holds one of ``match``
(XLA runs ``lax.ragged_dot`` as ``ragged-dot-*``: three products a call, and
the operation that lays out their groups).  A product's name ends in its
result's shape, ``[rows of the call's shape, columns]``, and the trace says how
often each ran.  The calls' shapes hold padding, idle lanes and the rows of
absent experts, so what a call of a shape really multiplied is COUNTED by the
program, shape by shape: ``moe_call_shapes`` in ``/v1/stats`` gives, by the rows
of a call's shape, the product's calls with the rows that fell to experts held
here and the experts that had a row; the reader takes their growth between the
window's two snapshots and gives a traced call of a shape that shape's mean
rows and mean experts hit.  (The trace is taken after the window, and which
shapes it catches differs from run to run: the mix of shapes is the trace's
own, only what a call of one shape holds is the window's.  The least time is a
larger-of-two of the means, which is at or under the mean of the calls' least
times.)  A call's three products are one pass of the gated MLP over those rows.
A product whose shape the window never ran is left out, time and all.  No such
operation in the trace, or stats without the counts: nothing to read."""

import re

from benchmark.roofline import grouped_matmul

_SHAPE = re.compile(r"_(\d+)_(\d+)$")


def read(run, args):
    trace, cfg, serve = run.get("trace"), run["config"], run.get("serve")
    if not trace or not serve or not cfg.get("moe_intermediate_size"):
        return None
    first, last = (serve[k].get("moe_call_shapes") for k in ("stats_open", "stats_close"))
    if first is None or last is None:
        return None
    hidden, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    seconds = least = layout = 0.0
    for name, secs, calls in trace[args.get("line", "ops")]:
        if not any(m in name for m in args["match"]):
            continue
        shape = _SHAPE.search(name)
        if not shape:
            layout += secs  # the operation that lays out the groups: time, no product
            continue
        now, then = last.get(shape.group(1)), first.get(shape.group(1), {})
        ran = now["calls"] - then.get("calls", 0) if now else 0
        if ran <= 0:
            continue
        rows = (now["rows_held"] - then.get("rows_held", 0)) / ran
        hit = (now["experts_hit"] - then.get("experts_hit", 0)) / ran
        seconds += secs
        # one of a call's three products: a third of the call's least time
        least += calls * grouped_matmul.least_seconds(rows, hit, hidden, width, run["peak"])["seconds"] / 3.0
    if seconds <= 0:
        return None
    return 100.0 * least / (seconds + layout)
