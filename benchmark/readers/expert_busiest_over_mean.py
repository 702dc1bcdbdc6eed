"""The straggler among the experts held here: the busiest expert's rows over the
mean rows an expert, both summed over the window's calls and expert layers.
``moe_rows_busiest`` (the sum over calls and layers of the largest single
expert's rows) over ``moe_rows_held`` / the experts held (the sum over calls and
layers of the mean), each its growth between the window's two ``/v1/stats``
snapshots.  1 is an even load; a call's time is its busiest expert's.  Stats
without the counters (a program from before them, a model without experts):
nothing to read."""


def read(run, args):
    serve, cfg = run.get("serve"), run["config"]
    if not serve or not cfg.get("n_routed_experts"):
        return None
    first, last = serve["stats_open"], serve["stats_close"]
    if any(k not in s for s in (first, last) for k in ("moe_rows_held", "moe_rows_busiest")):
        return None
    held_rows = last["moe_rows_held"] - first["moe_rows_held"]
    if held_rows <= 0:
        return None
    busiest = last["moe_rows_busiest"] - first["moe_rows_busiest"]
    return busiest * float(cfg["n_routed_experts"]) / held_rows
