"""The configuration ``joyai-llm-flash-serve``, its plain reference, its traffic,
its cell and the readers and rooflines that came with it.

Nothing here describes a TPU topology or starts a gang, except the ``--cpu-toy``
rehearsal at the end, which is marked slow as the others are.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark.generators import closed_sessions
from benchmark.harness import overrides
from benchmark.harness.manifest import Manifest, load_module
from benchmark.readers import (
    expert_busiest_over_mean,
    latent_moe_step_mfu,
    trace_expert_matmul_roofline,
)
from benchmark.reference import served_gap
from benchmark.roofline import grouped_matmul, latent_moe_flops

ROOT = Path(__file__).resolve().parents[2]
NAME, CELL, TRAFFIC = "joyai-llm-flash-serve", "serve-joyaiflash-docqa", "docqa-closed8-1024req"
CONFIG = json.loads((ROOT / f"benchmark/configs/{NAME}.json").read_text())
REFERENCE = ROOT / CONFIG["reference"]
PEAK = {"flops_bf16": 197e12, "hbm_bytes_s": 819e9}
#: ``jdopensource/JoyAI-LLM-Flash``'s ``config.json`` as the catalog beside the
#: ``model-configs`` guide holds it (the keys that say something about the shape).
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 8, "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
}
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "intermediate_size": 128, "rms_norm_eps": 1e-6, "rope_theta": 32e6,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "first_k_dense_replace": 1, "n_routed_experts": 8, "router_width": 16,
    "expert_offset": 4, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
}


def test_every_published_key_stands_in_the_file_and_only_the_reduced_ones_differ():
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            continue
        assert CONFIG[key] == value, key
    # the cut: the leading dense layer and four expert layers, 64 of 256 experts held
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"]) == (5, 64)
    assert CONFIG["published"] == {"num_hidden_layers": 40, "n_routed_experts": 256}
    # the router keeps its published width and its 8 choices; nothing is a width
    assert CONFIG["router_width"] == 256 and CONFIG["num_experts_per_tok"] == 8
    assert CONFIG["expert_offset"] + CONFIG["n_routed_experts"] <= CONFIG["router_width"]
    assert {"router_width", "rotary_layout", "e_score_correction_bias",
            "num_nextn_predict_layers", "layer_forms"} <= set(CONFIG["assumed"])
    assert "four chips share each layer" in CONFIG["deployment"]
    assert (CONFIG["param_dtype"], CONFIG["compute_dtype"], CONFIG["router_dtype"]) == (
        "float32", "bfloat16", "float32")
    assert CONFIG["control"]["overrides"]["engine"] == {"quantize": "int8", "kv_quantize": "int8"}


@pytest.mark.parametrize("toy", [False, True], ids=["real", "toy"])
def test_the_engine_block_repeats_what_the_top_level_keys_say(toy):
    """``drive_lm_server`` hands ``lm_server`` seven dense keys from the top level
    and everything else from ``engine``, verbatim; the reference reads the top
    level.  So what both need is written twice, and has to agree."""
    config = overrides.apply_toy(CONFIG) if toy else CONFIG
    engine = config["engine"]
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts",
                "routed_scaling_factor", "rope_theta", "expert_offset"):
        assert engine[key] == config[key], key
    # the file's n_routed_experts counts the experts HELD; the engine's is the router's width
    assert engine["n_routed_experts"] == config["router_width"]
    assert engine["experts_held"] == config["n_routed_experts"]
    n_dense = config["first_k_dense_replace"]
    assert engine["layer_types"] == ["dense_mlp"] * n_dense + ["expert_mlp"] * (
        config["num_hidden_layers"] - n_dense)
    assert config["qk_head_dim"] == 192 or toy
    assert engine["seq"] % engine["block_size"] == 0


def _program_config(config):
    from benchmark.harness.drive_lm_server import model_declarations
    from polyaxon_tpu.models import TransformerConfig

    d = {**model_declarations(config), **config["engine"]}
    return TransformerConfig(
        vocab_size=d["vocab_size"], d_model=d["d_model"], n_layers=d["n_layers"],
        n_heads=d["n_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"], n_kv_heads=d["n_kv_heads"],
        max_seq=d["seq"], layer_types=tuple(d["layer_types"]), rope_theta=float(d["rope_theta"]),
        routed_scaling_factor=float(d["routed_scaling_factor"]),
        **{k: int(d[k]) for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_routed_experts", "experts_held", "expert_offset", "num_experts_per_tok",
            "moe_intermediate_size", "n_shared_experts")})


def test_lm_server_builds_the_model_the_declarations_name():
    """The declarations as the harness sends them make the configuration the
    reference computes: the same pattern, sizes, share of the experts and pool row."""
    from polyaxon_tpu.models import decode, latent_moe

    cfg = _program_config(CONFIG)
    assert cfg.stack == "latent" and cfg.n_kv_layers == 5
    assert latent_moe.row_width(cfg) == 576 and latent_moe.pool_row_width(cfg) == 640
    assert latent_moe.experts_held(cfg) == 64 and latent_moe.runs(cfg) == [
        ("dense_mlp", 1), ("expert_mlp", 4)]
    # the issue's reckoning: attention 26.3 M a layer, an expert layer 333.6 M, the dense
    # layer 70.3 M, embedding and head 529.5 M: 1.93 B
    assert cfg.n_params == pytest.approx(70.3e6 + 4 * 333.6e6 + 529.5e6, rel=2e-4)
    assert cfg.n_params == 1_934_263_296
    # one padded row a token a layer: 6,400 B, of which the row itself 5,760 B
    assert decode.kv_block_bytes(cfg, 16) // 16 == 5 * 640 * 2
    # the issue's pool, the other served cells' 8,193 blocks: 131k tokens, 0.839 GB
    mistral = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.3-serve.json").read_text())
    assert CONFIG["engine"]["kv_blocks"] == mistral["engine"]["kv_blocks"] == 8193
    assert 8193 * decode.kv_block_bytes(cfg, 16) == pytest.approx(0.839e9, rel=1e-3)


def test_lm_server_takes_rope_theta_and_the_latent_declarations():
    import inspect

    from polyaxon_tpu.builtins import services

    source = inspect.getsource(services.lm_server)
    for name in ("rope_theta", *(k for k in CONFIG["engine"] if k not in (
            "seq", "slots", "kv_blocks", "block_size", "prefill_chunk", "prefix_cache",
            "request_timeout_s"))):
        assert f'"{name}"' in source, name


def test_the_new_entries_load_and_the_references_name_escapes_the_dense_glob():
    manifest = Manifest(ROOT)
    manifest.check()
    cell = manifest.cell(CELL)
    assert (cell.chips, cell.traffic["name"], cell.config["name"]) == (1, TRAFFIC, NAME)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s", "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert {"serve.latent_moe_step_mfu", "serve.expert_matmul_roofline",
            "serve.expert_busiest_over_mean", "serve.prefix_hit_share",
            "serve.evictions_per_s", "serve.loop_paging_share"} <= reported
    assert not {"serve.closed_step_mfu", "serve.hybrid_step_mfu",
                "serve.delta_rule_roofline"} & reported  # other decoders' counts
    for name in ("serve.latent_moe_step_mfu", "serve.expert_matmul_roofline",
                 "serve.expert_busiest_over_mean"):
        entry = next(m for m in manifest.data["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == "serve_tokens_per_s"
    entry = next(c for c in manifest.data["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    # test_rehearsal.py holds every configs/*_reference.py to the DENSE program
    assert REFERENCE.exists() and REFERENCE not in set((ROOT / "benchmark/configs").glob("*_reference.py"))
    ref = load_module(REFERENCE)
    assert all(callable(getattr(ref, n)) for n in ("init_params", "logits_at", "loss_row", "hidden"))
    source = (ROOT / "benchmark/reference/latent_moe_decoder.py").read_text()
    body = source.split('"""')[2]
    assert "polyaxon_tpu" not in body and "HIGHEST" in body
    assert "argsort" not in body and "ragged" not in body  # no sort, no grouping


def test_the_reference_draws_the_programs_weights():
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import init_params

    toy = overrides.apply_toy(CONFIG)
    cfg = _program_config(toy).scaled(dtype=jnp.float32)
    ref = load_module(REFERENCE)
    sizes = {k: v for k, v in toy.items() if not isinstance(v, dict)}  # what post.py hands over
    params = init_params(jax.random.PRNGKey(2**31 + 3), cfg)
    mine = ref.init_params(2**31 + 3, sizes)
    ours, theirs = jax.tree.leaves(params), jax.tree.leaves(mine)
    assert len(ours) == len(theirs) and cfg.n_params == sum(x.size for x in ours)
    for a, b in zip(ours, theirs):
        assert a.shape == b.shape and bool(jnp.all(a == b))


def test_requests_of_one_document_go_through_the_latent_reference_together_as_alone():
    """Segment 0 once, each request's own tokens after it, seeing the prefix and
    themselves at the positions they had when served: the same logits as one
    pass per request (float32, 2e-5 as for the dense reference)."""
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
    # the gates: 4 chosen of the router's 16, summing to the scaling factor
    h = jnp.asarray(np.random.default_rng(1).normal(size=(9, 64)), jnp.float32)
    g = ref.gates(h, jax.tree.map(lambda w: w[0], params["block"]["experts"]), TINY)
    assert g.shape == (9, 16) and bool(jnp.all(jnp.sum(g > 0, axis=-1) == 4))
    assert np.allclose(np.asarray(jnp.sum(g, axis=-1)), 2.5, atol=1e-5)


def test_the_flops_count_and_the_grouped_products_roofline_on_hand_counted_shapes():
    # attention a layer: 2,048 x 1,536 + 1,536 x 6,144 + 2,048 x 576 + 512 x 8,192 + 4,096 x 2,048
    attention = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert latent_moe_flops.attention_params(CONFIG) == attention == 26_345_472
    expert = 3 * 2048 * 768
    assert latent_moe_flops.expert_params(CONFIG) == expert
    fixed = (5 * attention + 3 * 2048 * 7168 + 4 * (2048 * 256 + expert) + 2048 * 129280)
    assert latent_moe_flops.fixed_params(CONFIG) == fixed
    assert latent_moe_flops.decode_flops(CONFIG, 1, 0) == pytest.approx(2.0 * fixed)
    # attention in the up-projected form: 2 x 5 layers x 32 heads x (192 + 128) a query-key pair
    unit = 2 * 5 * 32 * 320
    assert latent_moe_flops.decode_flops(CONFIG, 1, 1000) - 2.0 * fixed == pytest.approx(unit * 1000)
    assert latent_moe_flops.prefill_flops(CONFIG, 10, 100) == pytest.approx(
        10 * 2.0 * fixed + unit * (10 * 100 + 50))
    assert latent_moe_flops.routed_flops(CONFIG, 2048) == 6 * 2048 * 2048 * 768
    # a chunk's 2,048 held rows over 64 experts: bound by the experts' weights
    need = grouped_matmul.needs(2048, 64, 2048, 768)
    assert need["flops"] == 6 * 2048 * 2048 * 768
    assert need["bytes"] == 3 * 64 * 2048 * 768 * 2 + 2 * 2048 * 2048 * 2
    least = grouped_matmul.least_seconds(2048, 64, 2048, 768, PEAK)
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(need["bytes"] / 819e9)
    assert grouped_matmul.least_seconds(10**6, 64, 2048, 768, PEAK)["bound"] == "compute"
    # a decode step's 16 held rows on 14 experts: the 14 experts' weights, and little else
    step = grouped_matmul.least_seconds(16, 14, 2048, 768, PEAK)
    assert step["bound"] == "memory" and step["bytes"] == 3 * 14 * 2048 * 768 * 2 + 2 * 16 * 2048 * 2


def test_the_new_readers_read_what_is_there_and_nothing_where_nothing_is():
    run = {"config": CONFIG, "peak": PEAK, "chips": 1}
    # the window: 40 calls of the product on a chunk's shape, a quarter of whose rows were held
    # and multiplied, on all 64 experts; 400 on a decode step's, 16 rows on 14 experts each
    stats = ({"moe_rows_routed": 0, "moe_rows_held": 1000, "moe_rows_busiest": 100,
              "moe_call_shapes": {"8192": {"calls": 4, "rows_held": 900, "experts_hit": 200}}},
             {"moe_rows_routed": 80000, "moe_rows_held": 21000, "moe_rows_busiest": 600,
              "moe_call_shapes": {"8192": {"calls": 44, "rows_held": 900 + 40 * 2048,
                                           "experts_hit": 200 + 40 * 64},
                                  "64": {"calls": 400, "rows_held": 6400, "experts_hit": 5600}}})
    serve = {"stats_open": stats[0], "stats_close": stats[1], "window_s": 1.0, "hit_share": 0.5,
             "measured": [{"ok": True, "prompt_tokens": 1000, "output_tokens": 10}]}
    # three products a call by their results' shapes, and the operation that lays out the groups;
    # the traced stretch holds another mix of shapes than the window, and one the window never ran
    ops = [["ragged-dot-none_bf16_8192_768", 0.010, 20.0], ["ragged-dot-none.1_bf16_8192_768", 0.010, 20.0],
           ["ragged-dot-none.2_bf16_8192_2048", 0.012, 20.0], ["ragged-dot-metadata", 0.001, 20.0],
           ["fusion.1", 1.0, 40.0], ["ragged-dot-none.5_bf16_64_2048", 0.004, 12.0],
           ["ragged-dot-none.7_bf16_512_768", 0.5, 3.0]]
    args = {"line": "ops", "match": ["ragged-dot"]}
    got = trace_expert_matmul_roofline.read({**run, "serve": serve, "trace": {"ops": ops}}, args)
    chunk = grouped_matmul.least_seconds(2048, 64, 2048, 768, PEAK)["seconds"]
    step = grouped_matmul.least_seconds(16, 14, 2048, 768, PEAK)["seconds"]
    assert got == pytest.approx(100 * (20 * chunk + 12 * step / 3) / 0.037) and 0 < got < 100
    none = {**run, "serve": serve}
    # no product in the trace, the layout operation alone, a shape the window never ran: nothing
    for left in (ops[4:5], ops[3:5], ops[6:]):
        assert trace_expert_matmul_roofline.read({**none, "trace": {"ops": left}}, args) is None
    assert trace_expert_matmul_roofline.read({**none, "trace": None}, args) is None
    parent = {**serve, "stats_open": {}, "stats_close": {}}  # a program from before the counters
    assert trace_expert_matmul_roofline.read({**run, "serve": parent, "trace": {"ops": ops}}, args) is None
    assert latent_moe_step_mfu.read({**run, "serve": parent}, {}) is None
    assert expert_busiest_over_mean.read({**run, "serve": parent}, {}) is None
    # the straggler: 500 rows for the busiest against 20,000 / 64 an expert on average
    assert expert_busiest_over_mean.read({**run, "serve": serve}, {}) == pytest.approx(500 * 64 / 20000)
    # the whole step: the fixed part by tokens, the routed part by the rows counted
    want = (latent_moe_flops.prefill_flops(CONFIG, 500, 500)
            + latent_moe_flops.decode_flops(CONFIG, 10, 10 * 1005)
            + latent_moe_flops.routed_flops(CONFIG, 20000)) / 197e12 * 100
    assert latent_moe_step_mfu.read({**run, "serve": serve}, {}) == pytest.approx(want)
    # a configuration without latent attention or experts has nothing to read here
    dense = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.3-serve.json").read_text())
    for reader in (latent_moe_step_mfu, expert_busiest_over_mean):
        assert reader.read({**run, "config": dense, "serve": serve}, {}) is None
    assert trace_expert_matmul_roofline.read(
        {**run, "config": dense, "serve": serve, "trace": {"ops": ops}}, args) is None


def test_the_longer_list_starts_with_docqa_closed8s_documents():
    """``docqa-closed8-1024req`` is ``docqa-closed8`` with ``documents`` 256: the
    generator draws the document groups first, so the first 64 keep their lengths
    and their order; the list is four times as long."""
    short = json.loads((ROOT / "benchmark/traffic/docqa-closed8.json").read_text())
    long = json.loads((ROOT / f"benchmark/traffic/{TRAFFIC}.json").read_text())
    differ = {k for k in set(short) | set(long) if short.get(k) != long.get(k)}
    assert differ == {"name", "documents", "why", "who"} and long["documents"] == 256
    toy_short, toy_long = overrides.apply_toy(short), overrides.apply_toy(long)
    assert toy_long["documents"] == toy_short["documents"]  # the rehearsal's list is the toy's

    def document_lengths(traffic):
        first = {}
        for r in closed_sessions.schedule(traffic, 3, 51.0, 1000):
            first.setdefault(r["document"], r["shared_tokens"])
        return [first[d] for d in sorted(first)]

    a, b = document_lengths(short), document_lengths(long)
    assert len(a) == 64 and len(b) == 256 and b[:64] == a
    assert len(closed_sessions.schedule(long, 3, 51.0, 1000)) == 1024
    assert sorted(b[64:72]) == sorted(a[:8])  # every group holds the distribution's quantiles


def _toy(*extra):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/run.py"), "--workload", CELL, "--seed", "11",
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
    names = {k[len("cpu_toy."):] for k in line["metrics"]}
    assert {"serve.latent_moe_step_mfu", "serve.expert_busiest_over_mean",
            "serve.prefix_hit_share"} <= names
    assert line["metrics"]["cpu_toy.serve.prefix_hit_share"]["value"] > 20.0
    assert line["metrics"]["cpu_toy.serve.expert_busiest_over_mean"]["value"] >= 1.0
    # At toy widths a few near-ties that fall to another expert in bfloat16 are the
    # whole squared gap (the configuration's file says so), so the limit cannot tell
    # the control; the served tokens that are not the reference's first choice can:
    # 45-63 of 971 under the int8 path against 29-41 (four seeds, PR 34).
    control, control_served = _toy("--control")
    assert control["failed"] == 0
    assert served["tokens_compared"] == control_served["tokens_compared"] > 500
    assert control_served["tokens_differ"] > 1.2 * served["tokens_differ"]
