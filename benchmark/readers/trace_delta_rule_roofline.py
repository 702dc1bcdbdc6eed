"""The gated-delta-rule kernel's share of its roofline: the least time the chip
could take for the kernel's calls in the traced window
(``roofline/gated_delta_rule.py``, from the shapes alone) over the device time
the trace shows for them, in percent.

A call's tokens are read off its name in the trace: the kernel's one result is
``[heads, sub-chunks + E, c, dv]`` (``E = ceil(dk / c)`` row blocks of state
after the sub-chunks' outputs), and the trace names an op by its result's
shape.  No such op in the trace, or a name without a shape: nothing to read."""

import re

from benchmark.roofline import gated_delta_rule

_SHAPE = re.compile(r"_(\d+)_(\d+)_(\d+)_(\d+)$")


def read(run, args):
    trace, cfg = run.get("trace"), run["config"]
    if not trace or not cfg.get("layer_types"):
        return None
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    seconds = least = 0.0
    for name, secs, calls in trace[args.get("line", "ops")]:
        if not any(m in name for m in args["match"]):
            continue
        shape = _SHAPE.search(name)
        if not shape:
            return None
        heads, blocks, c, value_dim = (int(g) for g in shape.groups())
        if value_dim != dv:
            return None
        tokens = (blocks - -(-dk // c)) * c
        seconds += secs
        least += calls * gated_delta_rule.least_seconds(tokens, heads, dk, dv, run["peak"])["seconds"]
    if seconds <= 0:
        return None
    return 100.0 * least / seconds
