"""Seconds of one thing for each of another: the growth, between the window's
two ``/v1/stats`` snapshots, of a sum of the engine's counters (``keys``) over
the growth of one more (``per``), scaled.  Host seconds a decode step do not
fall when the device gets faster, which a share of the loop's time does.  A
program whose stats lack a counter has nothing to read, nor has a window in
which ``per`` did not grow."""


def read(run, args):
    serve = run.get("serve")
    if not serve:
        return None
    first, last = serve["stats_open"], serve["stats_close"]
    keys = list(args["keys"])
    if any(k not in first or k not in last for k in keys + [args["per"]]):
        return None

    def grown(key):
        return last[key] - first[key]

    per = grown(args["per"])
    if per <= 0:
        return None
    return sum(grown(k) for k in keys) / per * float(args.get("scale", 1.0))
