"""The whole step's share of the chip's peak for a latent-attention decoder with
a lightning indexer and routed experts: the benchmark's own count of model
FLOPs (``roofline/sparse_latent_flops.py``: the indexer's weights and its
scores over every pair a query may see, attention over the selected pairs
only) for the tokens the engine computed in the window (prompt tokens not
served from the cache, prefilled after the cached part, plus the decoded
tokens), the routed experts' part from the rows the program counted
(``moe_rows_held``'s growth between the window's two ``/v1/stats``
snapshots), over window x chips x peak, in percent.  A configuration without
an indexer, or a program whose stats lack the counter, has nothing to read
here."""

from benchmark.roofline import sparse_latent_flops


def read(run, args):
    cfg, peak, serve = run["config"], run["peak"], run.get("serve")
    if not serve or not cfg.get("kv_lora_rank") or not cfg.get("index_n_heads"):
        return None
    first, last = serve["stats_open"], serve["stats_close"]
    if "moe_rows_held" not in first or "moe_rows_held" not in last:
        return None
    done = [r for r in serve["measured"] if r["ok"]]
    if not done:
        return None
    hit = serve.get("hit_share", 0.0)
    flops = sparse_latent_flops.routed_flops(cfg, last["moe_rows_held"] - first["moe_rows_held"])
    for r in done:
        cached = int(hit * r["prompt_tokens"])
        flops += sparse_latent_flops.prefill_flops(cfg, r["prompt_tokens"] - cached, cached)
        flops += sparse_latent_flops.decode_flops(cfg, r["output_tokens"], r["prompt_tokens"])
    return 100.0 * flops / (serve["window_s"] * run["chips"] * peak["flops_bf16"])
