"""The whole step's share of the chip's peak: model FLOPs (the benchmark's own
count, recomputation excluded) over time x chips x peak, in percent.

- ``train``: FLOPs per token x the window's tokens per second.
- ``closed``: the tokens the engine computed in the window (prompt tokens not
  served from the cache, prefilled after the cached part, plus the decoded
  tokens) over the window.
"""

from benchmark.roofline import model_flops


def read(run, args):
    cfg, peak = run["config"], run["peak"]
    denom = run["chips"] * peak["flops_bf16"]
    kind = args["kind"]
    if kind == "train":
        train = run.get("train")
        if not train or not train.get("tokens_per_s"):
            return None
        flops = model_flops.train_flops_per_token(cfg, train["seq"])
        return 100.0 * flops * train["tokens_per_s"] / denom
    serve = run.get("serve")
    if not serve:
        return None
    if kind == "closed":
        done = [r for r in serve["measured"] if r["ok"]]
        if not done:
            return None
        hit = serve.get("hit_share", 0.0)
        flops = 0.0
        for r in done:
            cached = hit * r["prompt_tokens"]
            flops += model_flops.prefill_flops(cfg, r["prompt_tokens"] - cached, cached)
            flops += model_flops.decode_flops(
                cfg, r["output_tokens"],
                r["output_tokens"] * (r["prompt_tokens"] + r["output_tokens"] / 2.0))
        return 100.0 * flops / (serve["window_s"] * denom)
    raise ValueError(f"unknown kind {kind!r}")
