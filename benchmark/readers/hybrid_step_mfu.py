"""The whole step's share of the chip's peak for a hybrid decoder: the
benchmark's own count of model FLOPs by layer type (``roofline/hybrid_flops.py``)
for the tokens the engine computed in the window (prompt tokens not served from
the cache, prefilled after the cached part, plus the decoded tokens) over
window x chips x peak, in percent.  The cached share is the one the engine
reports AFTER a hit was cut back to a state snapshot.  A configuration without
a layer pattern has nothing to read here."""

from benchmark.roofline import hybrid_flops


def read(run, args):
    cfg, peak, serve = run["config"], run["peak"], run.get("serve")
    if not serve or not cfg.get("layer_types"):
        return None
    done = [r for r in serve["measured"] if r["ok"]]
    if not done:
        return None
    hit = serve.get("hit_share", 0.0)
    flops = 0.0
    for r in done:
        cached = hit * r["prompt_tokens"]
        flops += hybrid_flops.prefill_flops(cfg, r["prompt_tokens"] - cached, cached)
        flops += hybrid_flops.decode_flops(
            cfg, r["output_tokens"],
            r["output_tokens"] * (r["prompt_tokens"] + r["output_tokens"] / 2.0))
    return 100.0 * flops / (serve["window_s"] * run["chips"] * peak["flops_bf16"])
