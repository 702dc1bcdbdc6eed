"""A percentile of what the benchmark's clients saw, over all requests of the
window; a failed request ranks above every success."""

from benchmark.harness.stats import percentile


def read(run, args):
    serve = run.get("serve")
    if not serve or not serve["measured"]:
        return None
    value = percentile([r.get(args["field"]) for r in serve["measured"]], float(args["q"]))
    return value if value != float("inf") else None
