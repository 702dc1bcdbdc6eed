"""The device's idle share of the traced window: 1 - busy union / window, in
percent; ``which`` = ``mean`` over the devices or the ``worst`` (idlest) one."""


def read(run, args):
    trace = run.get("trace")
    if not trace:
        return None
    key = "idle_share_worst" if args.get("which") == "worst" else "idle_share"
    return 100.0 * trace[key]
