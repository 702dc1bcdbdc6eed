"""The configuration ``deepseek-v3.2-serve``, its plain reference, its cell and
the readers and rooflines that came with it.  Pinned by membership only
(``benchmark/README.md``): its own entries, files and metrics.

Nothing here describes a TPU topology or starts a gang.
"""

import json
from pathlib import Path

import pytest

from benchmark.harness.manifest import Manifest, load_module
from benchmark.readers import sparse_latent_step_mfu, trace_sparse_index_roofline
from benchmark.roofline import latent_moe_flops, sparse_index, sparse_latent_flops

ROOT = Path(__file__).resolve().parents[2]
NAME, CELL, TRAFFIC = "deepseek-v3.2-serve", "serve-deepseekv32-docqa", "docqa-closed8-1024req"
CONFIG = json.loads((ROOT / f"benchmark/configs/{NAME}.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAK = {"flops_bf16": 197e12, "hbm_bytes_s": 819e9}
#: ``deepseek-ai/DeepSeek-V3.2``'s ``config.json`` as the catalog beside the
#: ``model-configs`` guide holds it.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7168, "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
    "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 8, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}
REDUCED = {"num_hidden_layers": (61, 5), "first_k_dense_replace": (3, 1),
           "n_routed_experts": (256, 8), "vocab_size": (129280, 16160)}


def test_every_published_key_stands_in_the_file_and_only_the_reduced_ones_differ():
    assert CONFIG["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert CONFIG[key] == value, key
    for key, (published, here) in REDUCED.items():
        assert (CONFIG["published"][key], CONFIG[key]) == (published, here), key
    assert CONFIG["router_width"] == 256 and CONFIG["expert_offset"] == 0
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"]  # an eighth of the vocabulary
    assert {"layer_forms", "yarn", "indexer_hadamard", "indexer_precision", "rotary_layout",
            "num_nextn_predict_layers", "router_width"} <= set(CONFIG["assumed"])
    assert "32 chips share each layer" in CONFIG["deployment"]
    assert "3,226,232,064" in CONFIG["memory"]
    assert CONFIG["control"]["overrides"]["engine"] == {"quantize": "int8"}


def test_the_engine_block_repeats_what_the_top_level_keys_say():
    engine, rs = CONFIG["engine"], CONFIG["rope_scaling"]
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "index_n_heads", "index_head_dim", "index_topk", "n_group",
                "topk_group", "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts",
                "routed_scaling_factor", "rope_theta", "expert_offset"):
        assert engine[key] == CONFIG[key], key
    assert (engine["n_routed_experts"], engine["experts_held"]) == (
        CONFIG["router_width"], CONFIG["n_routed_experts"])
    assert engine["layer_types"] == ["dense_mlp"] + ["expert_mlp"] * 4
    assert (engine["rope_yarn_factor"], engine["rope_yarn_original_max"],
            engine["rope_yarn_beta_fast"], engine["rope_yarn_beta_slow"],
            engine["rope_yarn_mscale_all_dim"]) == (
        rs["factor"], rs["original_max_position_embeddings"], rs["beta_fast"], rs["beta_slow"],
        rs["mscale_all_dim"])
    assert (engine["seq"], engine["slots"], engine["kv_blocks"], engine["block_size"],
            engine["prefill_chunk"], engine["prefix_cache"]) == (16384, 8, 8193, 16, 1024, 1)


def _program_config():
    from polyaxon_tpu.models import TransformerConfig

    engine = CONFIG["engine"]
    fields = {k: v for k, v in engine.items() if k not in (
        "seq", "slots", "kv_blocks", "block_size", "prefill_chunk", "prefix_cache",
        "request_timeout_s")}
    fields["layer_types"] = tuple(fields["layer_types"])
    return TransformerConfig(
        vocab_size=CONFIG["vocab_size"], d_model=CONFIG["hidden_size"],
        n_layers=CONFIG["num_hidden_layers"], n_heads=CONFIG["num_attention_heads"],
        head_dim=CONFIG["head_dim"], d_ff=CONFIG["intermediate_size"],
        n_kv_heads=CONFIG["num_key_value_heads"], max_seq=engine["seq"], **fields)


def test_the_cut_counts_what_the_memory_reckoning_says():
    from polyaxon_tpu.models import decode, latent_moe

    cfg = _program_config()
    assert cfg.n_params == 3_226_232_064
    assert latent_moe.index_params(cfg) == 1536 * 64 * 128 + 7168 * 128 + 7168 * 64 + 2 * 128
    # 5 layers of 640 + 128 values a token at 2 bytes
    assert decode.kv_block_bytes(cfg, 16) // 16 == 5 * 768 * 2 == 7680
    # the count the FLOPs are taken over leaves out the norms, the router's bias and
    # the embedding table, and counts the 8 experts held a layer apart
    flops_params = (sparse_latent_flops.fixed_params(CONFIG)
                    + 4 * 8 * latent_moe_flops.expert_params(CONFIG) + 7168 * 16160)
    norms = 5 * (2 * 7168 + 1536 + 512 + 2 * 128) + 7168
    assert cfg.n_params - flops_params == norms + 4 * 256


def test_the_manifest_holds_the_configuration_and_the_cell_by_membership():
    Manifest(ROOT).check()
    conf = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert conf["file"] == f"benchmark/configs/{NAME}.json" and conf["reduced"] == list(REDUCED)
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, TRAFFIC, 1)
    serve = next(m for m in MANIFEST["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in serve["workloads"]
    reported = {m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [])}
    assert {"serve.sparse_latent_step_mfu", "serve.sparse_index_roofline",
            "serve.expert_matmul_roofline", "serve.expert_busiest_over_mean",
            "serve.closed_device_idle_share", "serve.prefix_hit_share",
            "setup.boot_to_chip_s"} <= reported
    assert "serve.latent_moe_step_mfu" not in reported  # it counts dense attention
    for name in ("serve.sparse_latent_step_mfu", "serve.sparse_index_roofline"):
        spec = json.loads((ROOT / f"benchmark/layer_metrics/{name}.json").read_text())
        assert (ROOT / f"benchmark/readers/{spec['reader']}.py").exists()
    ref = load_module(ROOT / CONFIG["reference"])
    assert ref._whole({"rope_theta": 10000})["rope_scaling"] == PUBLISHED["rope_scaling"]


def test_the_flops_count_and_the_indexers_roofline_on_hand_counted_shapes():
    k = CONFIG["index_topk"]
    for first, count in ((0, 10), (2000, 100), (5000, 1024), (0, 3000)):
        assert sparse_latent_flops.scored(first, count) == sum(t + 1 for t in range(first, first + count))
        assert sparse_latent_flops.selected(first, count, k) == sum(
            min(t + 1, k) for t in range(first, first + count))
    unit = 2.0 * 5 * 128 * (128 + 64 + 128)
    index_unit = 2.0 * 5 * 64 * 129
    fixed = sparse_latent_flops.fixed_params(CONFIG)
    assert fixed == latent_moe_flops.fixed_params(CONFIG) + 5 * (1536 * 64 * 128 + 7168 * 192)
    want = 2.0 * fixed * 1024 + index_unit * sparse_latent_flops.scored(4096, 1024) + unit * 2048 * 1024
    assert sparse_latent_flops.prefill_flops(CONFIG, 1024, 4096) == pytest.approx(want)
    assert sparse_latent_flops.decode_flops(CONFIG, 1, 100) == pytest.approx(
        2.0 * fixed + index_unit * 101 + unit * 101)
    n = sparse_index.needs(1024, 5_000_000, 9000, 64, 128)
    assert n["flops"] == 5_000_000 * (2 * 64 * 128 + 2 * 64)
    assert n["bytes"] == 1024 * 64 * (256 + 4) + 9000 * 256 + 4 * 5_000_000
    assert sparse_index.least_seconds(1024, 5e6, 9000, 64, 128, PEAK)["bound"] == "compute"


def _stats(pairs_a, pairs_b, held_a=1000, held_b=5000):
    shapes = lambda pairs: {"1024": {"calls": 50, "pairs": pairs, "keys": 50 * 8192}}  # noqa: E731
    return ({"moe_rows_held": held_a, "dsa_index_call_shapes": shapes(pairs_a)},
            {"moe_rows_held": held_b, "dsa_index_call_shapes": {
                "1024": {"calls": 150, "pairs": pairs_b, "keys": 150 * 8192}}})


def test_the_new_readers_read_what_is_there_and_nothing_where_nothing_is():
    first, last = _stats(10**9, 10**9 + 100 * 8 * 10**6)
    serve = {"stats_open": first, "stats_close": last, "window_s": 51.0, "hit_share": 0.7,
             "measured": [{"ok": True, "prompt_tokens": 9000, "output_tokens": 64}] * 10}
    run = {"config": CONFIG, "peak": PEAK, "chips": 1, "serve": serve,
           "trace": {"ops": [["dsa_index_1024", 0.5, 100], ["fusion.3", 1.0, 10]]}}
    mfu = sparse_latent_step_mfu.read(run, {})
    assert 0 < mfu < 100
    share = trace_sparse_index_roofline.read(run, {"line": "ops", "match": ["dsa_index"]})
    least = 100 * sparse_index.least_seconds(1024, 8e6, 8192, 64, 128, PEAK)["seconds"]
    assert share == pytest.approx(100 * least / 0.5)
    assert 0 < share < 100
    # a parent without the counters, a model without an indexer: nothing to read
    bare = {**run, "serve": {**serve, "stats_open": {"moe_rows_held": 1},
                             "stats_close": {"moe_rows_held": 2}}}
    assert trace_sparse_index_roofline.read(bare, {"line": "ops", "match": ["dsa_index"]}) is None
    joyai = json.loads((ROOT / "benchmark/configs/joyai-llm-flash-serve.json").read_text())
    assert sparse_latent_step_mfu.read({**run, "config": joyai}, {}) is None
    assert trace_sparse_index_roofline.read(
        {**run, "trace": {"ops": [["fusion.3", 1.0, 10]]}}, {"line": "ops", "match": ["dsa_index"]}) is None
