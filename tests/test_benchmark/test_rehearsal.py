"""The plain reference against the program at a tiny size, its lower-precision
control, the planted faults, and ``run.py --cpu-toy`` end to end.

The end-to-end rehearsals start real gangs (15-40 s each) and are marked slow:
run them with ``-m slow``.  Nothing here describes a TPU topology, uses a fixed
port or leaves a gang running.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness.manifest import load_module
from benchmark.reference import compare_train, served_gap

ROOT = Path(__file__).resolve().parents[2]
TINY = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6}
REFERENCES = sorted((ROOT / "benchmark/configs").glob("*_reference.py"))
SERVE_TOY = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.3-serve.json").read_text())["toy"]
SERVE_TOY = {**TINY, **{k: v for k, v in SERVE_TOY.items() if not isinstance(v, dict)}}


def _program(dtype, sizes=TINY):
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import TransformerConfig, init_params
    from polyaxon_tpu.models.transformer import forward

    cfg = TransformerConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"], n_heads=sizes["num_attention_heads"],
        head_dim=sizes["head_dim"], d_ff=sizes["intermediate_size"],
        n_kv_heads=sizes["num_key_value_heads"], max_seq=64, dtype=dtype, attention_impl="dense")
    fwd = jax.jit(lambda p, t: forward(p, t[None], cfg)[0])
    return cfg, lambda seed: init_params(jax.random.PRNGKey(seed), cfg), (
        lambda p, t: fwd(p, jnp.asarray(t)))


@pytest.mark.parametrize("path", REFERENCES, ids=lambda p: p.stem)
def test_reference_draws_the_programs_weights_and_agrees_with_its_forward(path):
    import jax
    import jax.numpy as jnp

    ref = load_module(path)
    cfg, init, fwd = _program(jnp.float32)
    params = init(2**31 + 3)
    mine = ref.init_params(2**31 + 3, TINY)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(mine)):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    tokens = np.random.default_rng(0).integers(0, 256, 48)
    with jax.default_matmul_precision("highest"):
        theirs = fwd(params, tokens)
    ours = ref.logits_at(mine, jnp.asarray(tokens), jnp.arange(48), TINY)
    assert float(jnp.max(jnp.abs(theirs - ours))) < 2e-4
    # the loss of one row is the mean cross-entropy of those logits
    logp = jax.nn.log_softmax(ours[:-1], axis=-1)
    want = -float(jnp.mean(logp[jnp.arange(47), jnp.asarray(tokens[1:])]))
    got = float(ref.loss_row(mine, jnp.asarray(tokens[:-1]), jnp.asarray(tokens[1:]), TINY))
    assert abs(want - got) < 1e-4


def _served(seed, int8, sizes=TINY, n_groups=4, per_group=3, new_tokens=16):
    """Groups of requests as the doc-QA cell serves them, decoded greedily by the
    program's own ``models/decode.py:generate`` in bfloat16; ``int8`` switches on
    its int8 weight-only path, which is the serving cells' control."""
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import decode

    cfg, init, _ = _program(jnp.bfloat16, sizes)
    params = init(seed)
    qweights = decode.quantize_weights(params) if int8 else None
    gen = jax.jit(lambda p, t: decode.generate(
        p, t, cfg, max_new_tokens=new_tokens, qweights=qweights))
    rng = np.random.default_rng(seed)
    groups = []
    for _ in range(n_groups):
        shared = rng.integers(0, sizes["vocab_size"], 24).tolist()
        prompts = [shared + rng.integers(0, sizes["vocab_size"], 8).tolist()
                   for _ in range(per_group)]
        tokens = np.asarray(gen(params, jnp.asarray(prompts)))
        groups.append({"shared": shared, "requests": [
            {"prompt": p, "tokens": t.tolist()} for p, t in zip(prompts, tokens)]})
    return groups


def _compare(seed, groups, sizes=TINY):
    ref = load_module(REFERENCES[0])
    per_token = served_gap.token_gaps(ref, ref.init_params(seed, sizes), sizes, groups, 64, 64)
    return served_gap.summarize(**per_token)


def test_served_comparison_passes_the_program_and_fails_its_int8_path_and_an_altered_token():
    """The comparison that decides ``correct`` for a served model, at a size a
    test run can hold, against the limit of the serving configuration's toy
    section: the program's bfloat16 tokens pass it; the tokens of its own int8
    path (the control) and a reply with one token altered do not."""
    limit = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.3-serve.json").read_text())[
        "toy"]["correct"]["limits"]
    shape = dict(sizes=SERVE_TOY, n_groups=8, per_group=4, new_tokens=32)
    for seed in (11, 12):
        sound = _served(seed, int8=False, **shape)
        out = _compare(seed, sound, SERVE_TOY)
        assert out["tokens_compared"] == 8 * 4 * 32
        assert all(out[k] <= v for k, v in limit.items()), (seed, out)
        control = _compare(seed, _served(seed, int8=True, **shape), SERVE_TOY)
        assert any(control[k] > v for k, v in limit.items()), (seed, control)
        one = sound[0]["requests"][1]["tokens"]
        one[6] = (one[6] + 1) % SERVE_TOY["vocab_size"]
        altered = _compare(seed, sound, SERVE_TOY)
        assert any(altered[k] > v for k, v in limit.items()), (seed, altered)


def test_requests_of_one_document_go_through_the_reference_together_as_they_would_alone():
    """The prefix once and each request's own tokens as a segment after it is the
    same arithmetic as one pass per request: rows, positions and gaps agree."""
    import jax.numpy as jnp

    groups = _served(5, int8=False)[:1]
    lay = served_gap.lay_out(groups[0]["shared"], groups[0]["requests"])
    assert len(lay["tokens"]) == 24 + 3 * (8 + 15) and len(lay["rows"]) == 3 * 16
    assert lay["pos"][24:24 + 23].tolist() == list(range(24, 47)) == lay["pos"][47:70].tolist()
    assert lay["seg"][23:26].tolist() == [0, 1, 1] and lay["seg"][-1] == 3
    assert lay["rows"][:3].tolist() == [31, 32, 33] and lay["rows"][16] == 24 + 23 + 7
    ref = load_module(REFERENCES[0])
    params = ref.init_params(5, TINY)
    together = served_gap.token_gaps(ref, params, TINY, groups, 32, 16)
    alone = served_gap.token_gaps(ref, params, TINY, [
        {"shared": [], "requests": [r]} for r in groups[0]["requests"]], 32, 16)
    assert np.allclose(together["gap"], alone["gap"], atol=2e-5)
    assert np.allclose(together["margin"], alone["margin"], atol=2e-5)
    # no shared prefix, and a prompt that is nothing but the shared prefix
    one = groups[0]["requests"][0]
    edge = served_gap.lay_out(one["prompt"], [one])
    assert edge["rows"][0] == len(one["prompt"]) - 1 and edge["rows"][1] == len(one["prompt"])
    got = served_gap.token_gaps(ref, params, TINY, [{"shared": one["prompt"], "requests": [one]}], 32, 16)
    assert np.allclose(got["gap"], alone["gap"][:16], atol=2e-5)
    assert jnp.asarray(got["margin"]).shape == (16,)


def test_training_comparison_reads_one_for_unchanged_state_and_flags_half_a_batch():
    ref_side = {"losses": [6.0, 5.9, 5.8],
                "grad_norms": {"a": 1.0, "b": 0.5, "c": 1e-6},
                "change_norms": {"a": 0.1, "b": 0.1, "c": 0.1}}
    sound = {"losses": [6.0003, 5.9002, 5.8004],
             "grad_norms": {"a": 1.004, "b": 0.499, "c": 2e-6},
             "change_norms": {"a": 0.1003, "b": 0.0998, "c": 0.5}}
    out = compare_train.compare(sound, ref_side)
    assert out["loss_gap"] < 1e-4 and out["grad_norm_gap"] < 5e-3 and out["change_norm_gap"] < 5e-3
    assert out["left_out_of_change"] == ["c"]  # moved by round-off alone under Adam
    unchanged = dict(sound, change_norms={"a": 0.0, "b": 0.0, "c": 0.0},
                     losses=[6.0003, 6.0003, 6.0003])
    out = compare_train.compare(unchanged, ref_side)
    assert out["change_norm_gap"] == pytest.approx(1.0) and out["loss_gap"] > 0.01
    doubled = dict(sound, change_norms={"a": 0.2, "b": 0.1, "c": 0.1})
    assert compare_train.compare(doubled, ref_side)["change_norm_gap"] == pytest.approx(1.0)
    half = dict(sound, grad_norms={"a": 1.3, "b": 0.7, "c": 1e-6}, losses=[6.02, 5.9, 5.8])
    out = compare_train.compare(half, ref_side)
    assert out["grad_norm_gap"] > 0.25 and out["loss_gap"] > 3e-3


def _toy(*extra, timeout=420):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/run.py"), "--cpu-toy", *extra],
        capture_output=True, text=True, timeout=timeout, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def _check_line(line, cell_metrics):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["metrics"] and all(k.startswith("cpu_toy.") for k in line["metrics"])
    assert not set(line["metrics"]) & cell_metrics  # no device metric name carries a CPU number


def _metric_names():
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {m["name"] for m in data["end_to_end"] + data["per_layer"]}


@pytest.mark.slow
def test_rehearsal_serve_cell_end_to_end_and_an_altered_token():
    line, err = _toy("--workload", "serve-mistral7b-docqa", "--seed", str(2**31 + 17),
                     "--seconds", "3", "--trace", "0")
    _check_line(line, _metric_names())
    assert line["toy_compare_ok"] is True and line["failed"] == 0 and line["attempted"] > 5
    assert "compared served_gap_mean_square" in err.splitlines()[-1]
    bad, _ = _toy("--workload", "serve-mistral7b-docqa", "--seed", "18", "--seconds", "3",
                  "--trace", "1", "--fault", "altered_token")
    assert bad["toy_compare_ok"] is False and bad["failed"] == 0
    assert "cpu_toy.serve.prefix_hit_share" in bad["metrics"]
    assert "cpu_toy.serve.closed_device_idle_share" not in bad["metrics"]  # no device trace on a CPU


@pytest.mark.slow
@pytest.mark.parametrize("cell", ["serve-mistral7b-docqa", "train-mistral7b-8k"])
def test_the_control_comes_out_not_correct_through_the_harness_own_comparison(cell):
    """``--control`` puts the lower-precision control in the program's place (the
    server with its int8 path on; the int8 reference for the trainer): the same
    ``compared`` numbers, the same limits, and the last line says not correct."""
    line, err = _toy("--workload", cell, "--seed", "23", "--seconds", "3", "--trace", "0", "--control")
    assert line["toy_compare_ok"] is False and line["correct"] is False and line["failed"] == 0
    over = [k for k, c in line["compared"].items() if c["value"] > c["limit"]]
    assert over and all(f"compared {k} = " in err for k in line["compared"])


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["", "state_unchanged", "half_batch"])
def test_rehearsal_train_cell_end_to_end_and_its_faults(fault):
    line, err = _toy("--workload", "train-mistral7b-8k", "--seed", "19", "--seconds", "3",
                     "--trace", "0", *(["--fault", fault] if fault else []))
    _check_line(line, _metric_names())
    assert line["toy_compare_ok"] is (fault == "")
    assert set(line["compared"]) == {"loss_gap", "grad_norm_gap", "change_norm_gap"}
    assert err.count("compared ") == 3


@pytest.mark.slow
def test_no_accelerator_means_a_non_zero_exit_and_no_result(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/run.py"), "--workload", "train-mistral7b-8k",
         "--seed", "1", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert proc.returncode != 0 and not proc.stdout.strip()
