"""A share of the engine loop's busy time: the growth, between the window's two
``/v1/stats`` snapshots, of a sum of the engine's phase-clock counters
(``keys``: ``loop_<phase>_s``) over the growth of ``loop_wall_s`` less
``loop_idle_s``, scaled.  A program whose stats lack the counters (one from
before the phase clock) has nothing to read."""


def read(run, args):
    serve = run.get("serve")
    if not serve:
        return None
    first, last = serve["stats_open"], serve["stats_close"]
    keys = list(args["keys"])
    if any(k not in first or k not in last for k in keys + ["loop_wall_s", "loop_idle_s"]):
        return None

    def grown(key):
        return last[key] - first[key]

    busy = grown("loop_wall_s") - grown("loop_idle_s")
    if busy <= 0:
        return None
    return sum(grown(k) for k in keys) / busy * float(args.get("scale", 1.0))
