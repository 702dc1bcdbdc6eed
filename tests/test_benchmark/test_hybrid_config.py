"""The configuration ``olmo-hybrid-7b-serve``, its plain reference, its cell and
the readers and rooflines that came with it.

Nothing here describes a TPU topology or starts a gang, except the two
``--cpu-toy`` rehearsals at the end, which are marked slow as the others are.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import overrides
from benchmark.harness.manifest import Manifest, load_module
from benchmark.readers import hybrid_step_mfu, stats_counter_if_present, trace_delta_rule_roofline
from benchmark.reference import served_gap
from benchmark.roofline import gated_delta_rule, hybrid_flops

ROOT = Path(__file__).resolve().parents[2]
NAME, CELL = "olmo-hybrid-7b-serve", "serve-olmohybrid7b-docqa"
CONFIG = json.loads((ROOT / f"benchmark/configs/{NAME}.json").read_text())
REFERENCE = ROOT / CONFIG["reference"]
PEAK = {"flops_bf16": 197e12, "hbm_bytes_s": 819e9}
#: ``allenai/Olmo-Hybrid-7B``'s ``config.json`` as the catalog beside the
#: ``model-configs`` guide holds it (the keys that say something about the shape).
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": ["linear_attention", "linear_attention", "linear_attention",
                    "full_attention"] * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128, "rms_norm_eps": 1e-6,
    "rope_theta": None, "layer_types": PUBLISHED["layer_types"][:4], "linear_num_key_heads": 2,
    "linear_num_value_heads": 2, "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
}


def test_every_published_key_stands_in_the_file_and_only_the_reduced_ones_differ():
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            continue
        assert CONFIG[key] == value, key
    # the cut: one whole period, the pattern's first four entries
    assert CONFIG["num_hidden_layers"] == 4 == len(CONFIG["layer_types"])
    assert CONFIG["layer_types"] == PUBLISHED["layer_types"][:4]
    assert CONFIG["published"]["num_hidden_layers"] == PUBLISHED["num_hidden_layers"]
    # derived and copied scalars are said to be so
    assert CONFIG["head_dim"] == CONFIG["hidden_size"] // CONFIG["num_attention_heads"] == 128
    assert CONFIG["rope_theta"] is None and {"head_dim", "rope_theta"} <= set(CONFIG["assumed"])
    assert (CONFIG["param_dtype"], CONFIG["compute_dtype"], CONFIG["state_dtype"]) == (
        "float32", "bfloat16", "float32")


@pytest.mark.parametrize("toy", [False, True], ids=["real", "toy"])
def test_the_engine_block_repeats_what_the_top_level_keys_say(toy):
    """``drive_lm_server`` hands ``lm_server`` seven dense keys from the top level
    and everything else from ``engine``, verbatim; the reference reads the top
    level.  So what both need is written twice, and has to agree."""
    config = overrides.apply_toy(CONFIG) if toy else CONFIG
    engine = config["engine"]
    for key in ("layer_types", "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim"):
        assert engine[key] == config[key], key
    assert bool(engine["linear_allow_neg_eigval"]) == config["linear_allow_neg_eigval"]
    assert bool(engine["rope"]) == (config["rope_theta"] is not None)
    assert engine["state_snapshot_every"] % engine["block_size"] == 0
    assert engine["state_snapshot_every"] % engine["prefill_chunk"] == 0
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    assert config["layer_types"].count("full_attention") == 1  # the toy keeps the pattern


def test_lm_server_builds_the_model_the_declarations_name():
    """The declarations as the harness sends them make the configuration the
    reference computes: same pattern, same sizes, no rotary embedding."""
    from benchmark.harness.drive_lm_server import model_declarations
    from polyaxon_tpu.models import TransformerConfig

    d = {**model_declarations(CONFIG), **CONFIG["engine"]}
    cfg = TransformerConfig(
        vocab_size=d["vocab_size"], d_model=d["d_model"], n_layers=d["n_layers"],
        n_heads=d["n_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"], n_kv_heads=d["n_kv_heads"],
        max_seq=d["seq"], layer_types=tuple(d["layer_types"]),
        rope_theta=10000.0 if d["rope"] else None,
        linear_allow_neg_eigval=bool(d["linear_allow_neg_eigval"]),
        **{k: d[k] for k in d if k.startswith("linear_") and k != "linear_allow_neg_eigval"})
    assert cfg.n_kv_layers == 1 and cfg.pool_kv_heads == 32
    # the issue's reckoning: a period of four 832.5 M, embedding and head 770.7 M
    assert cfg.n_params == pytest.approx(832.5e6 + 770.7e6, rel=2e-4)


def test_the_new_entries_load_and_the_references_name_escapes_the_dense_glob():
    manifest = Manifest(ROOT)
    manifest.check()
    cell = manifest.cell(CELL)
    assert (cell.chips, cell.traffic["name"], cell.config["name"]) == (1, "docqa-closed8", NAME)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s", "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert {"serve.hybrid_step_mfu", "serve.delta_rule_roofline", "serve.state_floor_share",
            "serve.loop_state_share", "serve.prefix_hit_share"} <= reported
    assert "serve.closed_step_mfu" not in reported  # a dense decoder's count
    # test_rehearsal.py holds every configs/*_reference.py to the DENSE program
    assert REFERENCE.exists() and REFERENCE not in set((ROOT / "benchmark/configs").glob("*_reference.py"))
    ref = load_module(REFERENCE)
    assert all(callable(getattr(ref, n)) for n in ("init_params", "logits_at", "loss_row", "hidden"))
    source = (ROOT / "benchmark/reference/hybrid_decoder.py").read_text()
    assert "polyaxon_tpu" not in source.split('"""')[2] and "HIGHEST" in source


def test_requests_of_one_document_go_through_the_hybrid_reference_together_as_alone():
    """Segment 0 once, each request's own tokens after it, the recurrent state and
    the convolution tail restarted from the end of segment 0: the same logits as
    one pass per request (float32, 2e-5 as for the dense reference)."""
    ref = load_module(REFERENCE)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 256, 24).tolist()
    requests = [{"prompt": shared + rng.integers(0, 256, 8).tolist(),
                 "tokens": rng.integers(0, 256, 16).tolist()} for _ in range(3)]
    params = ref.init_params(5, TINY)
    together = served_gap.token_gaps(
        ref, params, TINY, [{"shared": shared, "requests": requests}], 32, 16)
    alone = served_gap.token_gaps(
        ref, params, TINY, [{"shared": [], "requests": [r]} for r in requests], 32, 16)
    assert together["gap"].shape == (48,) and float(together["gap"].max()) > 0.1
    assert np.allclose(together["gap"], alone["gap"], atol=2e-5)
    assert np.allclose(together["margin"], alone["margin"], atol=2e-5)
    # a prompt that is nothing but the shared prefix: its first row is the prefix's last
    one = requests[0]
    edge = served_gap.token_gaps(ref, params, TINY, [{"shared": one["prompt"], "requests": [one]}], 32, 16)
    assert np.allclose(edge["gap"], alone["gap"][:16], atol=2e-5)


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


def test_flops_by_layer_type_and_the_rules_roofline():
    # the issue's reckoning: MLP 126.8 M, full attention 59.0 M, linear attention 88.8 M
    # (46 k of it convolutions, which are no matmul), the head 385.4 M
    weights = 4 * 126.8e6 + 59.0e6 + 3 * (88.8e6 - 46e3) + 385.4e6
    assert hybrid_flops.matmul_params(CONFIG) == pytest.approx(weights, rel=5e-4)
    per_token = 2.0 * hybrid_flops.matmul_params(CONFIG) + 3 * 30 * 6 * 96 * 192
    assert hybrid_flops.decode_flops(CONFIG, 1, 0) == pytest.approx(per_token)
    # causal attention counts for the ONE full layer: 4 x 30 heads x 128 a query-key pair
    assert hybrid_flops.decode_flops(CONFIG, 1, 1000) - per_token == pytest.approx(4 * 30 * 128 * 1000)
    assert hybrid_flops.prefill_flops(CONFIG, 10, 100) == pytest.approx(
        10 * per_token + 4 * 30 * 128 * (10 * 100 + 50))
    need = gated_delta_rule.needs(1024, 30, 96, 192)
    assert need["flops"] == 6 * 1024 * 30 * 96 * 192
    assert need["bytes"] == 1024 * 30 * ((96 + 96 + 192 + 192) * 2 + 8) + 2 * 30 * 96 * 192 * 4
    least = gated_delta_rule.least_seconds(1024, 30, 96, 192, PEAK)
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(need["bytes"] / 819e9)


def test_the_new_readers_read_what_is_there_and_nothing_where_nothing_is():
    run = {"config": CONFIG, "peak": PEAK, "chips": 1}
    # a call's tokens come off its name: [heads, sub-chunks + E, c, dv], E = ceil(96 / 64) = 2
    ops = [["gated_delta_chunk.6_f32_30_18_64_192", 0.02, 40.0], ["fusion.1", 1.0, 40.0],
           ["gated_delta_chunk.9_f32_30_13_8_192", 0.001, 2.0]]  # a bucket of 8: 12 state blocks
    args = {"line": "ops", "match": ["gated_delta_chunk"]}
    got = trace_delta_rule_roofline.read({**run, "trace": {"ops": ops}}, args)
    least = (40 * gated_delta_rule.least_seconds(1024, 30, 96, 192, PEAK)["seconds"]
             + 2 * gated_delta_rule.least_seconds(8, 30, 96, 192, PEAK)["seconds"])
    assert got == pytest.approx(100 * least / 0.021) and 0 < got < 100
    assert trace_delta_rule_roofline.read({**run, "trace": {"ops": ops[1:2]}}, args) is None
    assert trace_delta_rule_roofline.read({**run, "trace": None}, args) is None
    assert trace_delta_rule_roofline.read(
        {**run, "trace": {"ops": [["gated_delta_chunk", 0.02, 40.0]]}}, args) is None
    # a program from before the counter (the parent) has nothing to read; this one reads a share
    floor = {"counter": "prefix_floor_tokens", "over": "prompt_tokens", "scale": 100}
    serve = {"stats_open": {}, "stats_close": {"block_size": 16}, "prompt_tokens": 1000}
    assert stats_counter_if_present.read({"serve": serve}, floor) is None
    serve = {"stats_open": {"prefix_floor_tokens": 100},
             "stats_close": {"prefix_floor_tokens": 150}, "prompt_tokens": 1000}
    assert stats_counter_if_present.read({"serve": serve}, floor) == pytest.approx(5.0)
    # the whole-step share: a dense configuration has nothing to read here
    measured = [{"ok": True, "prompt_tokens": 1000, "output_tokens": 10}]
    serve = {"measured": measured, "window_s": 1.0, "hit_share": 0.5}
    dense = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.3-serve.json").read_text())
    assert hybrid_step_mfu.read({**run, "config": dense, "serve": serve}, {}) is None
    want = (hybrid_flops.prefill_flops(CONFIG, 500, 500)
            + hybrid_flops.decode_flops(CONFIG, 10, 10 * 1005)) / 197e12 * 100
    assert hybrid_step_mfu.read({**run, "serve": serve}, {}) == pytest.approx(want)


def _toy(*extra):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/run.py"), "--workload", CELL, "--seed", "11",
         "--seconds", "8", "--trace", "1", "--cpu-toy", *extra],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_cpu_toy_runs_the_new_cell_end_to_end_and_its_control_reads_worse():
    line = _toy()
    assert line["cpu_toy"] and not line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["toy_compare_ok"], line["compared"]
    names = {k[len("cpu_toy."):] for k in line["metrics"]}
    assert {"serve.hybrid_step_mfu", "serve.state_floor_share", "serve.loop_state_share",
            "serve.prefix_hit_share"} <= names
    assert line["metrics"]["cpu_toy.serve.prefix_hit_share"]["value"] > 20.0  # snapshots serve hits
    # At toy widths the limit cannot tell the control (the configuration's file
    # says so); on one seed the int8 path still reads several times the program.
    control = _toy("--control")
    assert control["failed"] == 0
    gap = lambda l: l["compared"]["served_gap_mean_square"]["value"]  # noqa: E731
    assert gap(control) > 2.0 * gap(line)
