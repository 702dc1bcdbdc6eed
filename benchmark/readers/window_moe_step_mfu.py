"""The whole step's share of the chip's peak for a decoder of window and full
attention layers with routed experts: the benchmark's own count of model FLOPs
(``roofline/window_moe_flops.py``) for the tokens the engine computed in the
window (prompt tokens not served from the cache, prefilled after the cached
part, plus the decoded tokens), the routed experts' part from the rows the
program counted (``moe_rows_held``'s growth between the window's two
``/v1/stats`` snapshots), over window x chips x peak, in percent.  A
configuration without window layers, or a program whose stats lack the
counter, has nothing to read here."""

from benchmark.roofline import window_moe_flops


def read(run, args):
    cfg, peak, serve = run["config"], run["peak"], run.get("serve")
    if not serve or not cfg.get("sliding_window") or not cfg.get("mlp_layer_types"):
        return None
    first, last = serve["stats_open"], serve["stats_close"]
    if "moe_rows_held" not in first or "moe_rows_held" not in last:
        return None
    done = [r for r in serve["measured"] if r["ok"]]
    if not done:
        return None
    hit = serve.get("hit_share", 0.0)
    flops = window_moe_flops.routed_flops(cfg, last["moe_rows_held"] - first["moe_rows_held"])
    for r in done:
        cached = hit * r["prompt_tokens"]
        flops += window_moe_flops.prefill_flops(cfg, r["prompt_tokens"] - cached, cached)
        flops += window_moe_flops.decode_flops(
            cfg, r["output_tokens"],
            r["output_tokens"] * (r["prompt_tokens"] + r["output_tokens"] / 2.0),
            r["prompt_tokens"])
    return 100.0 * flops / (serve["window_s"] * run["chips"] * peak["flops_bf16"])
