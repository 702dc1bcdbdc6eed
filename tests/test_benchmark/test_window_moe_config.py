"""The configuration ``laguna-s-2.1-serve``, its plain reference, its cell and the
readers and rooflines that came with it.

Nothing here describes a TPU topology or starts a gang, except the ``--cpu-toy``
rehearsals at the end, which are marked slow as the others are.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import overrides
from benchmark.harness.manifest import Manifest, load_module
from benchmark.readers import trace_window_attention_roofline, window_moe_step_mfu
from benchmark.reference import served_gap
from benchmark.roofline import window_attention, window_moe_flops

ROOT = Path(__file__).resolve().parents[2]
NAME, CELL, TRAFFIC = "laguna-s-2.1-serve", "serve-lagunas21-docqa", "docqa-closed8-1024req"
CONFIG = json.loads((ROOT / f"benchmark/configs/{NAME}.json").read_text())
REFERENCE = ROOT / CONFIG["reference"]
PEAK = {"flops_bf16": 197e12, "hbm_bytes_s": 819e9}
FULL, WINDOW = "full_attention", "sliding_attention"
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types", "gating_types",
           "num_attention_heads_per_layer", "num_experts"]
#: ``poolside/Laguna-S-2.1``'s ``config.json`` as the catalog beside the
#: ``model-configs`` guide holds it; the four per-layer lists by their period.
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072, "intermediate_size": 12288,
    "num_hidden_layers": 48, "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False, "rms_norm_eps": 1e-06,
    "num_experts": 256, "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
    "shared_expert_intermediate_size": 1024, "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
               "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
               "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
        WINDOW: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
    },
    "layer_types": [FULL, WINDOW, WINDOW, WINDOW] * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0,
}
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128, "rms_norm_eps": 1e-6,
    "layer_types": [FULL, WINDOW, WINDOW, WINDOW, FULL],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4], "gating": "per-head", "sliding_window": 16,
    "rope_parameters": PUBLISHED["rope_parameters"],
    "num_experts": 8, "router_width": 16, "expert_offset": 4, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "moe_routed_scaling_factor": 2.5,
}


def test_every_published_key_stands_in_the_file_and_only_the_reduced_ones_differ():
    assert CONFIG["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            continue
        assert CONFIG[key] == value, key
    # the cut: layers 0-4 (the dense layer and one whole period), 32 of 256 experts held;
    # the four per-layer lists are the published ones' first five entries
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"]) == (5, 32)
    for key in REDUCED[1:5]:
        assert CONFIG[key] == PUBLISHED[key][:5], key
    assert CONFIG["published"]["num_hidden_layers"] == 48 and CONFIG["published"]["num_experts"] == 256
    # no width, window, head count or experts-per-token differs; the router keeps its width
    assert CONFIG["router_width"] == 256 and CONFIG["num_experts_per_tok"] == 10
    assert CONFIG["expert_offset"] + CONFIG["num_experts"] <= CONFIG["router_width"]
    assert {"router_scoring", "shared_expert", "gating", "qk_norm", "rotary_layout", "weights",
            "published_keys", "router_width"} <= set(CONFIG["assumed"])
    assert "eight chips share each layer" in CONFIG["deployment"] and CONFIG["memory"]
    assert (CONFIG["param_dtype"], CONFIG["compute_dtype"], CONFIG["router_dtype"]) == (
        "float32", "bfloat16", "float32")
    assert CONFIG["control"]["overrides"]["engine"] == {"quantize": "int8", "kv_quantize": "int8"}
    assert set(CONFIG["correct"]["limits"]) == {"served_gap_mean_square", "served_gap_mean"}
    assert all(v > 0 for v in CONFIG["correct"]["limits"].values())


@pytest.mark.parametrize("toy", [False, True], ids=["real", "toy"])
def test_the_engine_block_repeats_what_the_top_level_keys_say(toy):
    """``drive_lm_server`` hands ``lm_server`` seven dense keys from the top level
    and everything else from ``engine``, verbatim; the reference reads the top
    level.  So what both need is written twice, and has to agree."""
    config = overrides.apply_toy(CONFIG) if toy else CONFIG
    engine = config["engine"]
    for key in ("layer_types", "mlp_layer_types", "sliding_window", "num_experts_per_tok",
                "moe_intermediate_size", "expert_offset"):
        assert engine[key] == config[key], key
    heads = dict(zip(config["layer_types"], config["num_attention_heads_per_layer"]))
    assert heads[FULL] == config["num_attention_heads"] and heads[WINDOW] == engine["sliding_n_heads"]
    # the file's num_experts counts the experts HELD; the engine's n_routed_experts is the router's width
    assert engine["n_routed_experts"] == config["router_width"]
    assert engine["experts_held"] == config["num_experts"]
    assert engine["n_shared_experts"] * config["moe_intermediate_size"] == config[
        "shared_expert_intermediate_size"]
    assert engine["routed_scaling_factor"] == config["moe_routed_scaling_factor"]
    full, window = (config["rope_parameters"][k] for k in (FULL, WINDOW))
    assert (engine["rope_theta"], engine["partial_rotary_factor"], engine["rope_yarn_factor"],
            engine["rope_yarn_original_max"], engine["rope_yarn_beta_fast"],
            engine["rope_yarn_beta_slow"], engine["rope_attention_factor"]) == (
        full["rope_theta"], full["partial_rotary_factor"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"], full["beta_slow"],
        full["attention_factor"])
    assert engine["sliding_rope_theta"] == window["rope_theta"] and engine["head_gate"] == 1
    assert len(config["layer_types"]) == config["num_hidden_layers"] == 5
    assert engine["seq"] % engine["block_size"] == 0
    assert engine["prefill_chunk"] % engine["block_size"] == 0  # a snapshot a chunk, on a block edge


def _program_config(config):
    from benchmark.harness.drive_lm_server import model_declarations
    from polyaxon_tpu.models import TransformerConfig

    d = {**model_declarations(config), **config["engine"]}
    return TransformerConfig(
        vocab_size=d["vocab_size"], d_model=d["d_model"], n_layers=d["n_layers"],
        n_heads=d["n_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"], n_kv_heads=d["n_kv_heads"],
        max_seq=d["seq"], layer_types=tuple(d["layer_types"]),
        mlp_layer_types=tuple(d["mlp_layer_types"]), head_gate=bool(d["head_gate"]),
        **{k: float(d[k]) for k in (
            "rope_theta", "partial_rotary_factor", "rope_yarn_factor", "rope_yarn_beta_fast",
            "rope_yarn_beta_slow", "rope_attention_factor", "sliding_rope_theta",
            "routed_scaling_factor")},
        **{k: int(d[k]) for k in (
            "sliding_window", "sliding_n_heads", "rope_yarn_original_max", "n_routed_experts",
            "experts_held", "expert_offset", "num_experts_per_tok", "moe_intermediate_size",
            "n_shared_experts")})


def test_lm_server_builds_the_model_the_declarations_name():
    """The declarations as the harness sends them make the configuration the
    reference computes, and the issue's reckoning of its parameters and memory."""
    from polyaxon_tpu.models import decode, window_moe

    cfg = _program_config(CONFIG)
    assert cfg.stack == "window" and cfg.n_kv_layers == 2 and cfg.pool_kv_heads == 8
    assert window_moe.runs(cfg) == [(FULL, "dense", 1), (WINDOW, "sparse", 3), (FULL, "sparse", 1)]
    assert (window_moe.heads(cfg, FULL), window_moe.heads(cfg, WINDOW)) == (48, 72)
    # the issue's table: attention 44.19 M (full) and 63.13 M (window), the dense MLP 113.25 M,
    # an expert 9.437 M, a router 0.786 M, embedding and head 616.6 M; outside the routed
    # experts 1.049 B; with 32 experts held a layer 2.257 B (the norms are 0.03 M more)
    full, window = (3072 * 128 * (2 * h + 16) + 3072 * h for h in (48, 72))
    assert (full, window) == (44_187_648, 63_135_744)
    expert, router, dense, vocab = 3 * 3072 * 1024, 3072 * 256, 3 * 3072 * 12288, 2 * 100352 * 3072
    outside = 2 * full + 3 * window + dense + 4 * (expert + router) + vocab
    assert outside == pytest.approx(1.049e9, rel=1e-3)
    norms = 5 * 2 * 3072 + 3072
    assert cfg.n_params == outside + 4 * 32 * expert + norms == 2_256_479_232
    assert cfg.n_params == pytest.approx(2.257e9, rel=1e-3)
    # only the two full layers keep blocks: 8,192 B a token; 8,193 blocks = 1.07 GB
    assert decode.kv_block_bytes(cfg, 16) // 16 == 2 * 8 * 128 * 2 * 2 == 8192
    assert 8193 * decode.kv_block_bytes(cfg, 16) == pytest.approx(1.07e9, rel=5e-3)
    # the three window layers keep 6.29 MB a sequence, whatever its length
    assert window_moe.rec_row_bytes(cfg) == 3 * 512 * 8 * 128 * 2 * 2 == 6_291_456
    assert CONFIG["engine"]["state_snapshots"] * window_moe.rec_row_bytes(cfg) == pytest.approx(
        1.01e9, rel=5e-3)
    mistral = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.3-serve.json").read_text())
    assert CONFIG["engine"]["kv_blocks"] == mistral["engine"]["kv_blocks"] == 8193


def test_lm_server_takes_the_window_stacks_declarations():
    import inspect

    from polyaxon_tpu.builtins import services

    source = inspect.getsource(services.lm_server)
    for name in (k for k in CONFIG["engine"] if k not in (
            "seq", "slots", "kv_blocks", "block_size", "prefill_chunk", "prefix_cache",
            "request_timeout_s", "state_snapshots")):
        assert f'"{name}"' in source, name
    assert '"state_snapshots"' in source


def test_the_new_entries_load_and_the_references_name_escapes_the_dense_glob():
    manifest = Manifest(ROOT)
    manifest.check()
    assert CELL in {w["name"] for w in manifest.data["workloads"]}
    assert NAME in {c["name"] for c in manifest.data["configs"]}
    cell = manifest.cell(CELL)
    assert (cell.chips, cell.traffic["name"], cell.config["name"]) == (1, TRAFFIC, NAME)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s", "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert {"serve.window_moe_step_mfu", "serve.window_attention_roofline",
            "serve.state_floor_share", "serve.loop_state_share", "serve.prefix_hit_share",
            "serve.evictions_per_s", "serve.loop_paging_share", "setup.boot_to_chip_s"} <= reported
    assert not {"serve.closed_step_mfu", "serve.hybrid_step_mfu", "serve.latent_moe_step_mfu",
                "serve.delta_rule_roofline"} & reported  # other decoders' counts
    for name in ("serve.window_moe_step_mfu", "serve.window_attention_roofline"):
        entry = next(m for m in manifest.data["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == "serve_tokens_per_s"
    # the expert product's roofline is the one metric of every cell with routed experts
    assert "serve.expert_matmul_roofline" in reported
    entry = next(c for c in manifest.data["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    # test_rehearsal.py holds every configs/*_reference.py to the DENSE program
    assert REFERENCE.exists() and REFERENCE not in set((ROOT / "benchmark/configs").glob("*_reference.py"))
    ref = load_module(REFERENCE)
    assert all(callable(getattr(ref, n)) for n in ("init_params", "logits_at", "loss_row", "hidden"))
    source = (ROOT / "benchmark/reference/window_moe_decoder.py").read_text()
    body = source.split('"""')[2]
    assert "polyaxon_tpu" not in body and "HIGHEST" in body
    assert "argsort" not in body and "ragged" not in body and "pallas" not in body


def test_the_reference_draws_the_programs_weights():
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import init_params

    toy = overrides.apply_toy(CONFIG)
    cfg = _program_config(toy).scaled(dtype=jnp.float32)
    ref = load_module(REFERENCE)
    sizes = {k: v for k, v in toy.items() if not isinstance(v, dict)}  # what post.py hands over
    assert "rope_parameters" not in sizes  # a group: the reference's own file reads it back
    params = init_params(jax.random.PRNGKey(2**31 + 3), cfg)
    mine = ref.init_params(2**31 + 3, sizes)
    ours, theirs = jax.tree.leaves(params), jax.tree.leaves(mine)
    assert len(ours) == len(theirs) and cfg.n_params == sum(x.size for x in ours)
    for a, b in zip(ours, theirs):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 2048, 40))
    with_group = ref.logits_at(mine, tokens, jnp.arange(40), {**sizes, "rope_parameters": CONFIG["rope_parameters"]})
    assert bool(jnp.all(ref.logits_at(mine, tokens, jnp.arange(40), sizes) == with_group))


def test_requests_of_one_document_go_through_the_window_reference_together_as_alone():
    """Segment 0 once, each request's own tokens after it, seeing the prefix and
    themselves at the positions they had when served, the window measured in
    positions: the same logits as one pass per request."""
    ref = load_module(REFERENCE)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 256, 40).tolist()  # two windows and a half
    requests = [{"prompt": shared + rng.integers(0, 256, 8).tolist(),
                 "tokens": rng.integers(0, 256, 24).tolist()} for _ in range(3)]
    params = ref.init_params(5, TINY)
    together = served_gap.token_gaps(
        ref, params, TINY, [{"shared": shared, "requests": requests}], 32, 16)
    alone = served_gap.token_gaps(
        ref, params, TINY, [{"shared": [], "requests": [r]} for r in requests], 32, 16)
    assert together["gap"].shape == (72,) and float(together["gap"].max()) > 0.1
    assert np.allclose(together["gap"], alone["gap"], atol=2e-5)
    assert np.allclose(together["margin"], alone["margin"], atol=2e-5)


def test_the_references_loss_is_the_cross_entropy_of_its_logits():
    import jax
    import jax.numpy as jnp

    ref = load_module(REFERENCE)
    params = ref.init_params(7, TINY)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, 33))
    logits = jax.jit(lambda p, t: ref.logits_at(p, t, jnp.arange(32), TINY))(params, tokens[:-1])
    want = -float(jnp.mean(jax.nn.log_softmax(logits, axis=-1)[jnp.arange(32), tokens[1:]]))
    got = jax.jit(lambda p, t, y: ref.loss_row(p, t, y, TINY))(params, tokens[:-1], tokens[1:])
    assert abs(want - float(got)) < 1e-4
    assert jax.eval_shape(lambda p, t: ref.hidden(p, t, TINY), params, tokens).shape == (33, 64)
    # the gates: 4 chosen of the router's 16, summing to the scaling factor
    h = jnp.asarray(np.random.default_rng(1).normal(size=(9, 64)), jnp.float32)
    g = ref.gates(h, jax.tree.map(lambda w: w[0], params["block"]["experts"]), TINY)
    assert g.shape == (9, 16) and bool(jnp.all(jnp.sum(g > 0, axis=-1) == 4))
    assert np.allclose(np.asarray(jnp.sum(g, axis=-1)), 2.5, atol=1e-5)


def test_the_flops_count_and_the_window_kernels_roofline_on_hand_counted_shapes():
    full, window = (3072 * 128 * (2 * h + 16) + 3072 * h for h in (48, 72))
    assert window_moe_flops.attention_params(CONFIG, 48) == full
    assert window_moe_flops.attention_params(CONFIG, 72) == window
    expert = 3 * 3072 * 1024
    assert window_moe_flops.expert_params(CONFIG) == expert
    fixed = 2 * full + 3 * window + 3 * 3072 * 12288 + 4 * (3072 * 256 + expert) + 3072 * 100352
    assert window_moe_flops.fixed_params(CONFIG) == fixed
    assert window_moe_flops.routed_flops(CONFIG, 1280) == 6 * 1280 * 3072 * 1024
    # a (query, key) pair: 4 x heads x 128 a layer; two full layers of 48, three window layers of 72
    unit_full, unit_window = 4 * 2 * 48 * 128, 4 * 3 * 72 * 128
    # pairs of ONE window layer: min(i + 1, 512) a position
    for start, n in ((0, 100), (0, 512), (0, 2000), (300, 400), (511, 1), (512, 1024), (9000, 64)):
        want = sum(min(i + 1, 512) for i in range(start, start + n))
        assert window_moe_flops.window_pairs(CONFIG, n, start) == pytest.approx(want)
    # one token decoded at position 1,000 against 1,001 keys
    assert window_moe_flops.decode_flops(CONFIG, 1, 1001, 1000) == pytest.approx(
        2.0 * fixed + unit_full * 1001 + unit_window * 512)
    assert window_moe_flops.prefill_flops(CONFIG, 10, 100) == pytest.approx(
        10 * 2.0 * fixed + unit_full * (10 * 100 + 50) + unit_window * sum(range(101, 111)))
    # a full chunk's call: 1,024 queries of 512 keys each, 72 heads: 19.3 GFLOP, compute-bound
    need = window_attention.needs(1024 * 512, 72, 8, 128, 512)
    assert need["flops"] == 4 * 1024 * 512 * 72 * 128
    assert need["bytes"] == 2 * 1024 * 72 * 128 * 2 + 2 * (1024 + 512) * 8 * 128 * 2
    least = window_attention.least_seconds(1024 * 512, 72, 8, 128, 512, PEAK)
    assert least["bound"] == "compute" and least["seconds"] == pytest.approx(need["flops"] / 197e12)


def test_the_new_readers_read_what_is_there_and_nothing_where_nothing_is():
    run = {"config": CONFIG, "peak": PEAK, "chips": 1}
    stats = ({"moe_rows_held": 1000, "window_call_shapes": {
                 "1024": {"calls": 30, "pairs": 30 * 500_000}}},
             {"moe_rows_held": 21000, "window_call_shapes": {
                 "1024": {"calls": 330, "pairs": 30 * 500_000 + 300 * 520_000},
                 "64": {"calls": 90, "pairs": 90 * 20_000}}})
    serve = {"stats_open": stats[0], "stats_close": stats[1], "window_s": 1.0, "hit_share": 0.5,
             "measured": [{"ok": True, "prompt_tokens": 1000, "output_tokens": 10}]}
    # the traced stretch: 6 calls of a full chunk's shape, 9 of a short one's, one of a shape
    # the window never ran, and operations that are none of the kernel's
    ops = [["window_chunk_1024.3", 0.0018, 6.0], ["window_chunk_64", 0.00045, 9.0],
           ["window_chunk_256.1", 0.5, 3.0], ["fusion.1", 1.0, 40.0], ["full_chunk_tile", 0.2, 12.0]]
    args = {"line": "ops", "match": ["window_chunk"]}
    got = trace_window_attention_roofline.read({**run, "serve": serve, "trace": {"ops": ops}}, args)
    full = window_attention.least_seconds(520_000, 72, 8, 128, 512, PEAK)["seconds"]
    short = window_attention.least_seconds(20_000, 72, 8, 128, 512, PEAK)["seconds"]
    assert got == pytest.approx(100 * (6 * full + 9 * short) / 0.00225) and 0 < got < 100
    none = {**run, "serve": serve}
    for left in (ops[3:], ops[2:3]):  # no kernel in the trace; a shape the window never ran
        assert trace_window_attention_roofline.read({**none, "trace": {"ops": left}}, args) is None
    assert trace_window_attention_roofline.read({**none, "trace": None}, args) is None
    parent = {**serve, "stats_open": {}, "stats_close": {}}  # a program from before the counters
    assert trace_window_attention_roofline.read(
        {**run, "serve": parent, "trace": {"ops": ops}}, args) is None
    assert window_moe_step_mfu.read({**run, "serve": parent}, {}) is None
    # the whole step: the fixed part and both attentions by tokens, the routed part by the rows counted
    want = (window_moe_flops.prefill_flops(CONFIG, 500, 500)
            + window_moe_flops.decode_flops(CONFIG, 10, 10 * 1005, 1000)
            + window_moe_flops.routed_flops(CONFIG, 20000)) / 197e12 * 100
    assert window_moe_step_mfu.read({**run, "serve": serve}, {}) == pytest.approx(want)
    # a configuration without window layers has nothing to read here
    for other in ("mistral-7b-v0.3-serve", "joyai-llm-flash-serve", "olmo-hybrid-7b-serve"):
        cfg = json.loads((ROOT / f"benchmark/configs/{other}.json").read_text())
        assert window_moe_step_mfu.read({**run, "config": cfg, "serve": serve}, {}) is None
        assert trace_window_attention_roofline.read(
            {**run, "config": cfg, "serve": serve, "trace": {"ops": ops}}, args) is None


def _toy(*extra, seed="11"):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/run.py"), "--workload", CELL, "--seed", seed,
         "--seconds", "8", "--trace", "1", "--cpu-toy", *extra],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    served = [l for l in proc.stderr.splitlines() if l.startswith("served {")][-1]
    return (json.loads(proc.stdout.strip().splitlines()[-1]),
            json.loads(served[len("served "):served.index("}") + 1]))


@pytest.mark.slow
def test_cpu_toy_runs_the_new_cell_end_to_end_and_its_control_reads_worse():
    line, served = _toy()
    assert line["cpu_toy"] and not line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["toy_compare_ok"], line["compared"]
    assert set(line["compared"]) == {"served_gap_mean_square", "served_gap_mean"}
    names = {k[len("cpu_toy."):] for k in line["metrics"]}
    assert {"serve.window_moe_step_mfu", "serve.prefix_hit_share", "serve.state_floor_share",
            "serve.loop_state_share"} <= names
    assert "serve.window_attention_roofline" not in names  # no device trace on a CPU
    assert line["metrics"]["cpu_toy.serve.prefix_hit_share"]["value"] > 20.0  # hits ride on snapshots
    # At toy widths a few near-ties that fall to another expert in bfloat16 are the
    # whole gap (the configuration's file says so), so the limits cannot tell the
    # control; the served tokens that are not the reference's first choice can.
    control, control_served = _toy("--control")
    assert control["failed"] == 0
    assert served["tokens_compared"] == control_served["tokens_compared"] > 500
    assert control_served["tokens_differ"] > 1.2 * served["tokens_differ"]


@pytest.mark.slow
def test_a_planted_fault_comes_out_not_correct():
    bad, _ = _toy("--fault", "altered_token", seed="18")
    assert bad["toy_compare_ok"] is False and bad["failed"] == 0
