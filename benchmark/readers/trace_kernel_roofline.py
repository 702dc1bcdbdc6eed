"""A kernel's share of its roofline: the least time the chip could take for
the calls in the traced window, by the benchmark's own count of operations and
bytes (``roofline/<name>.py``), over the device time the trace shows for them,
in percent.  Nothing to read returns nothing.

- ``flash``: the three flash kernels summed; calls needed = layers x the train
  steps traced, forward and backward once each.
"""

from benchmark.roofline import flash


def _time_and_calls(trace, line, match):
    rows = [r for r in trace[line] if any(m in r[0] for m in match)]
    return sum(r[1] for r in rows), sum(r[2] for r in rows)


def read(run, args):
    trace = run.get("trace")
    if not trace:
        return None
    cfg, peak = run["config"], run["peak"]
    seconds, calls = _time_and_calls(trace, args.get("line", "ops"), args["match"])
    if seconds <= 0 or calls <= 0:
        return None
    if args["roofline"] == "flash":
        train = run.get("train")
        steps = train and train.get("steps_traced")
        if not steps:
            return None
        h = cfg["num_attention_heads"]
        one = flash.least_seconds(
            train["batch"], train["seq"], h, cfg["num_key_value_heads"],
            cfg.get("head_dim") or cfg["hidden_size"] // h, peak)
        least = one["seconds"] * cfg["num_hidden_layers"] * steps
    else:
        raise ValueError(f"unknown roofline {args['roofline']!r}")
    return 100.0 * least / seconds
