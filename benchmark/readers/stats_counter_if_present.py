"""``stats_counter`` for a counter that a program from before it does not have:
its growth over the window over a quantity the harness counted, scaled; nothing
where the window's closing ``/v1/stats`` lacks the counter."""

from benchmark.readers import stats_counter


def read(run, args):
    serve = run.get("serve")
    if not serve or args["counter"] not in serve["stats_close"]:
        return None
    return stats_counter.read(run, args)
