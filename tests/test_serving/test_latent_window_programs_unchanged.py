"""The latent stack's and the window stack's paged programs are what they were
before the sparse latent model came in beside them, but for one change on
purpose to the latent stack's layer loop.

``models/latent_moe.py`` gained a lightning indexer, a key selection, YaRN on
latent attention and a group limit in the router, and ``models/window_moe.py``'s
YaRN blend became one function that both stacks call.  Each is switched on by a
configuration field whose default leaves it off, so ``joyai-llm-flash-serve``'s
and ``laguna-s-2.1-serve``'s programs must lower to the text they had.  The
guard: at each configuration's toy sizes, for both pool layouts, each program
lowers to HLO text with a digest taken with this file's own code (the style of
``test_dense_programs_unchanged.py``).  The window stack's four were taken at
34dbbc1, the commit the sparse latent model started from, before ``models/``
was edited; they still hold, so the window stack is untouched.  The latent
stack's four moved once, on purpose: its layer loop stopped cutting each run
of the pattern out of the stacked weights and reads each layer out of the
whole stacks inside the loop (the same logits, bit for bit; no copy of a run's
weights a call).  They were taken again after that change.  The text holds no
source locations, so it moves only when the program does: a digest that moves
means the chip's compile cache misses for those cells and their device
programs may differ.  A new jax may move all of them at once; then take the
window stack's again at 34dbbc1 and the latent stack's at the layer-loop
change.
"""

import hashlib
import json
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from polyaxon_tpu.models import TransformerConfig, decode, init_params, window_moe
from polyaxon_tpu.models.transformer import stack_module

ROOT = Path(__file__).resolve().parents[2]
#: Keys of an ``engine`` group that size the engine, not the model.
_ENGINE_ONLY = {"seq", "slots", "kv_blocks", "block_size", "prefill_chunk", "prefix_cache",
                "request_timeout_s", "state_snapshots"}
_MODEL = {"vocab_size": "vocab_size", "hidden_size": "d_model",
          "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
          "head_dim": "head_dim", "intermediate_size": "d_ff",
          "num_key_value_heads": "n_kv_heads"}


def toy(config: str):
    """A configuration's toy model as ``lm_server`` would build it from the
    harness's declarations, and its toy engine group."""
    conf = json.loads((ROOT / "benchmark/configs" / f"{config}.json").read_text())
    top = {**conf, **{k: v for k, v in conf["toy"].items() if k != "engine"}}
    engine = {**conf["engine"], **conf["toy"]["engine"]}
    fields = {ours: top[theirs] for theirs, ours in _MODEL.items()}
    for key, value in engine.items():
        if key in _ENGINE_ONLY:
            continue
        if key in ("layer_types", "mlp_layer_types"):
            value = tuple(value)
        elif key == "head_gate":
            value = bool(value)
        fields[key] = value
    return TransformerConfig(max_seq=engine["seq"], **fields), engine


JOYAI = toy("joyai-llm-flash-serve")
LAGUNA = toy("laguna-s-2.1-serve")

DIGESTS = {
    # taken after the layer loop began reading each layer out of the whole stacks
    "latent-prefill-kv": "9f6864a10daaa67f25c95eda37d9f046e6445fb8096703dcce5bfdde848961f2",
    "latent-prefill-int8": "bd2e9b4b203a51074d51c92146baf8ddd51914696fb21d636cc98ec6f2f7e552",
    "latent-decode-kv": "3ad57e12e5d6e5338d9ed560b86229b3b3f25ff276df49cbcff4095642212f7d",
    "latent-decode-int8": "a4e024883cb56d781ac7594c2f83db2b4c314d141305129371a93244cf7463d8",
    # taken at 34dbbc1
    "window-prefill-kv": "e6ed0fca9e769b9d08739fa10681f76c698ace7c9b050fc1a30c85594dea3e48",
    "window-prefill-int8": "8f008e4d98a2ad105d1d60d79ce97662b36f8cda27b71639f6d7a5361892fe06",
    "window-decode-kv": "ca477dcd66dff6737f8b4a42d1de39ea1396af03e1e9c5c825d87b3d068da78b",
    "window-decode-int8": "6dcc319f388c2b1aa1346a5b801d69e335d6d21201d4a9adb4acc43c46fcdac9",
}


def lowered_text(kind, kvq, cfg, engine):
    i32, sds = jnp.int32, jax.ShapeDtypeStruct
    S, W, C = engine["slots"], engine["seq"] // engine["block_size"], engine["prefill_chunk"]
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: decode.init_block_pool(
        cfg, engine["kv_blocks"], engine["block_size"], kv_dtype=kvq))
    slot = ()
    if cfg.stack == "window":  # the rings ride the pool; a chunk names its slot
        pool = {**pool, **jax.eval_shape(lambda: window_moe.init_rec_state(cfg, S, kvq))}
        slot = (sds((), i32),) if kind == "prefill" else ()
    stack = stack_module(cfg)
    fn, args = {
        "prefill": (stack.paged_prefill_chunk,
                    (sds((W,), i32), sds((C,), i32), sds((), i32), sds((), i32), *slot)),
        "decode": (stack.paged_decode_step,
                   (sds((S, W), i32), sds((S,), i32), sds((S,), i32), sds((S,), jnp.bool_))),
    }[kind]
    return jax.jit(partial(fn, cfg=cfg)).lower(params, pool, *args).as_text()


@pytest.mark.parametrize("kvq", [None, "int8"], ids=["kv", "int8"])
@pytest.mark.parametrize("kind", ["prefill", "decode"])
@pytest.mark.parametrize("model", ["latent", "window"])
def test_latent_and_window_programs_lower_to_the_text_they_had(model, kind, kvq):
    cfg, engine = {"latent": JOYAI, "window": LAGUNA}[model]
    assert cfg.stack == model
    text = lowered_text(kind, kvq, cfg, engine)
    assert hashlib.sha256(text.encode()).hexdigest() == DIGESTS[f"{model}-{kind}-{kvq or 'kv'}"]
