"""The training cells run the benchmark's own worker entry, not ``lm_train``
(PERF.md section 7).  This fails when the two build different steps: the
same declarations go through both up to the point where each hands its step to
``aot_compile``, and what they hand over has to lower to the same program on
the same feed."""

import json

import numpy as np
import pytest


class _Handed(Exception):
    pass


def _handed_to_aot_compile(monkeypatch, entry, ctx):
    import jax

    from polyaxon_tpu.runtime import compilecache

    seen = {}

    def capture(step, params, opt_state, batch, key):
        seen["hlo"] = step.lower(params, opt_state, batch, key).as_text()
        seen["batch"] = {k: np.asarray(v) for k, v in batch.items()}
        seen["params"] = [np.asarray(x) for x in jax.tree.leaves(params)]
        seen["key"] = np.asarray(jax.random.key_data(key))
        raise _Handed

    monkeypatch.setattr(compilecache, "aot_compile", capture)
    with pytest.raises(_Handed):
        entry(ctx)
    return seen


def test_the_entry_hands_aot_compile_the_step_and_feed_that_lm_train_does(monkeypatch, tmp_path):
    from benchmark.entries import lm_train_window
    from polyaxon_tpu.builtins.trainers import lm_train
    from polyaxon_tpu.tracking.context import Context

    job = tmp_path / "job.json"
    job.write_text(json.dumps({"seconds": 1.0, "warm_steps": 3, "reference_steps": 3}))
    params = {"vocab_size": 256, "d_model": 64, "n_layers": 2, "n_heads": 4, "head_dim": 16,
              "d_ff": 128, "n_kv_heads": 2, "ce_chunk": 32, "lr": 3e-4, "remat": True,
              "remat_policy": "save_attn", "attention_impl": "auto", "batch": 8, "seq": 64,
              "bench_job": str(job)}
    seed = 2**31 - 5
    theirs = _handed_to_aot_compile(monkeypatch, lm_train, Context(
        params=dict(params), seed=seed, outputs_path=str(tmp_path / "a")))
    ours = _handed_to_aot_compile(monkeypatch, lm_train_window.main, Context(
        params=dict(params), seed=seed, outputs_path=str(tmp_path / "b")))
    assert ours["hlo"] == theirs["hlo"]
    assert set(ours["batch"]) == set(theirs["batch"]) == {"tokens", "targets"}
    for k in ours["batch"]:
        assert np.array_equal(ours["batch"][k], theirs["batch"][k])
    assert len({tuple(row) for row in ours["batch"]["tokens"]}) == 8  # every row differs
    assert all(np.array_equal(a, b) for a, b in zip(ours["params"], theirs["params"]))
    assert np.array_equal(ours["key"], theirs["key"])
