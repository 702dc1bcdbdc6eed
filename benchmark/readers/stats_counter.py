"""A ``/v1/stats`` counter's growth over the window, times a factor the stats
name (``times_key``), over a quantity the harness counted (``over``), scaled.

``prefix_cache_hits`` x ``block_size`` over the window's prompt tokens is the
share of prompt tokens served from cached blocks."""


def read(run, args):
    serve = run.get("serve")
    if not serve:
        return None
    key = args["counter"]
    grown = serve["stats_close"].get(key, 0) - serve["stats_open"].get(key, 0)
    if args.get("times_key"):
        grown *= serve["stats_close"][args["times_key"]]
    over = serve.get(args["over"])
    if not over:
        return None
    return grown / over * float(args.get("scale", 1.0))
