"""The dense model's three paged programs are what they were before the hybrid
stack came in beside them.

``models/decode.py``'s ``paged_prefill_chunk``, ``paged_decode_step`` and
``paged_verify_step`` hand a model WITH a layer pattern over to
``models/hybrid.py`` and run every other model through the code they had.  The
guard: at ``mistral-7b-v0.3-serve``'s toy sizes, for both pool layouts, each
program lowers to HLO text with the digest it had at PR 28 (the commit before
the hybrid stack; the digests were taken there with this file's own code, and
the engine's decode step in its ``_decode_hlo_text`` form beside them).  The
text holds no source locations, so it moves only when the program does: a
digest that moves means the chip's compile cache misses for the Mistral cells
and their device programs may differ, which is what the benchmark's
parent-against-change runs then have to answer for.  A new jax may move all of
them at once; then take them again at the same commit.

PR 30 retook ``engine-step`` and only that one: the engine now hands its
programs the embeddings and matmul weights in ``cfg.dtype`` already
(``decode.serving_params``), so the engine's decode step takes bfloat16 weight
arguments and its text has lost their ``convert`` lines.  The six program
digests above it are taken on the float32 tree as before and did not move:
``models/decode.py``'s programs are the ones PR 28 left.

PR 31 wrote the served layer once for both stacks (one walk through the block
table, one attention core, one MLP) and retook nothing: the seven digests above
are the parent's letter for letter.  It gave the hybrid stack's two programs
the same guard first: ``HYBRID_AT_PR_30``, at ``olmo-hybrid-7b-serve``'s toy
sizes (4 KV heads in pool rows of 8, so the padding is in the text), taken at
a66de81, the commit PR 31 started from, before ``models/`` was edited.

PR 39 retook ``engine-step`` and only that one: the engine hands its decode step
the tables, tokens, positions, mask, temperatures and key as ONE int32 buffer
that the step slices apart (``ServingEngine._pack_step``), because the call
transfers each host argument on its own; ``paged_decode_step``, whose six
digests are above, did not move.
"""

import hashlib
import json
import re
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from polyaxon_tpu.models import TransformerConfig, decode, hybrid, init_params

ROOT = Path(__file__).resolve().parents[2]
TOY = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.3-serve.json").read_text())["toy"]
ENGINE = TOY["engine"]
CFG = TransformerConfig(
    vocab_size=TOY["vocab_size"], d_model=TOY["hidden_size"], n_layers=TOY["num_hidden_layers"],
    n_heads=TOY["num_attention_heads"], head_dim=TOY["head_dim"], d_ff=TOY["intermediate_size"],
    n_kv_heads=TOY["num_key_value_heads"], max_seq=ENGINE["seq"])
S, W, T, C = ENGINE["slots"], ENGINE["seq"] // ENGINE["block_size"], 5, ENGINE["prefill_chunk"]

AT_PR_28 = {  # "engine-step" at PR 39, see above
    "prefill-kv": "8d6c3d807ac758f5308f1aa7fda3e10c8601bfd49b7e11256e75111a43917b1b",
    "decode-kv": "e470b812870f85b1c395f5c6105195e840b8b11bdb17511c71de9e446a18d21b",
    "verify-kv": "765cf1829bc346595072cc19f9eb9976c9e4a15a164a89940b0300e2c98069d5",
    "prefill-int8": "e31f6c878ae26266b070d421f292c27cc427fbf91cf194f6ea73b0ebdf38bbd8",
    "decode-int8": "9064dff56c128f91c404b6354f18f4a7197f2a17525c2805079e164e41a8c07e",
    "verify-int8": "29619d31e1a8de1decf241e91d76f0bbbb44288ab829e42678ce8861306f3a26",
    "engine-step": "fd75911210ac9d00b11eeadb20ecdec3eecbb31994002b4330e0910bacaf48d2",
}


# The hybrid stack's two programs, at ``olmo-hybrid-7b-serve``'s toy sizes over
# the configuration's own pattern and switches: see the docstring's last part.
_HYBRID = json.loads((ROOT / "benchmark/configs/olmo-hybrid-7b-serve.json").read_text())
_HYBRID = {**_HYBRID, **_HYBRID["toy"]}
HYBRID_ENGINE = _HYBRID["engine"]
HYBRID_CFG = TransformerConfig(
    vocab_size=_HYBRID["vocab_size"], d_model=_HYBRID["hidden_size"],
    n_layers=_HYBRID["num_hidden_layers"], n_heads=_HYBRID["num_attention_heads"],
    head_dim=_HYBRID["head_dim"], d_ff=_HYBRID["intermediate_size"],
    n_kv_heads=_HYBRID["num_key_value_heads"], max_seq=HYBRID_ENGINE["seq"],
    rope_theta=_HYBRID["rope_theta"], layer_types=tuple(_HYBRID["layer_types"]),
    **{k: v for k, v in _HYBRID.items() if k.startswith("linear_")})

HYBRID_AT_PR_30 = {
    "prefill-kv": "dfc70e50490ac7255b8d44c5ddad4e0556e5696a6bc3b038ced434bc4c4e22f5",
    "decode-kv": "67b94d4c18bc65ecb70a260f8edbd8c31f5c618e809278fcd06fa437df8a0911",
    "prefill-int8": "922225a09dff757ba5c0c2c344d53cbc9c747309d2710758d9ef38957549d589",
    "decode-int8": "1d3dba72c687adc409ec58852e67c2c02d503f467aeb1085965a2023d12390b5",
}


def _lowered_text(kind, kvq, cfg=CFG, engine=ENGINE):
    i32, sds = jnp.int32, jax.ShapeDtypeStruct
    S, W, C = engine["slots"], engine["seq"] // engine["block_size"], engine["prefill_chunk"]
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: decode.init_block_pool(
        cfg, engine["kv_blocks"], engine["block_size"], kv_dtype=kvq))
    slot = {}
    if cfg.layer_types is not None:  # the recurrent rows ride the pool; a chunk names its slot
        pool = {**pool, **jax.eval_shape(lambda: hybrid.init_rec_state(cfg, S))}
        slot = {"slot": sds((), i32)} if kind == "prefill" else {}
    lanes = (sds((S,), i32), sds((S,), i32))
    fn, args = {
        "prefill": (decode.paged_prefill_chunk,
                    (sds((W,), i32), sds((C,), i32), sds((), i32), sds((), i32))),
        "decode": (decode.paged_decode_step,
                   (sds((S, W), i32), *lanes, sds((S,), jnp.bool_))),
        "verify": (decode.paged_verify_step,
                   (sds((S, W), i32), sds((S, T), i32), *lanes, sds((S,), jnp.bool_))),
    }[kind]
    return jax.jit(partial(fn, cfg=cfg)).lower(params, pool, *args, **slot).as_text()


@pytest.mark.parametrize("kvq", [None, "int8"], ids=["kv", "int8"])
@pytest.mark.parametrize("kind", ["prefill", "decode", "verify"])
def test_dense_program_lowers_to_the_text_it_had(kind, kvq):
    digest = hashlib.sha256(_lowered_text(kind, kvq).encode()).hexdigest()
    assert digest == AT_PR_28[f"{kind}-{kvq or 'kv'}"]


@pytest.mark.parametrize("kvq", [None, "int8"], ids=["kv", "int8"])
@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_hybrid_program_lowers_to_the_text_it_had(kind, kvq):
    text = _lowered_text(kind, kvq, HYBRID_CFG, HYBRID_ENGINE)
    assert hashlib.sha256(text.encode()).hexdigest() == HYBRID_AT_PR_30[f"{kind}-{kvq or 'kv'}"]


def _engine():
    from polyaxon_tpu.serving import ServingEngine

    return ServingEngine(
        init_params(jax.random.PRNGKey(0), CFG), CFG, slots=S, block_size=ENGINE["block_size"],
        num_blocks=ENGINE["kv_blocks"], prefill_chunk=C, warmup=False)


def _weight_casts(text, params):
    """The ``f32 -> bf16`` converts of ``text`` whose operand has the shape of
    an embedding or a matmul weight (a block leaf whole, or one layer of it)."""
    shapes = {params["embed"].shape, params["unembed"].shape}
    for name in decode.QUANTIZED_BLOCK_WEIGHTS:
        shapes |= {params["block"][name].shape, params["block"][name].shape[1:]}
    found = re.findall(r"convert %\S+ : \(tensor<([0-9x]+)xf32>\) -> tensor<[0-9x]+xbf16>", text)
    return [dims for dims in found if tuple(map(int, dims.split("x"))) in shapes]


def test_engines_decode_step_casts_no_weight():
    engine = _engine()
    params = init_params(jax.random.PRNGKey(0), CFG)
    assert _weight_casts(engine._decode_hlo_text(), params) == []
    # the same step handed the float32 tree, as before PR 30, casts all nine
    engine._params = params
    assert len(_weight_casts(engine._decode_hlo_text(), params)) == 9


def test_engines_decode_step_lowers_to_the_text_it_had():
    engine = _engine()
    digest = hashlib.sha256(engine._decode_hlo_text().encode()).hexdigest()
    assert digest == AT_PR_28["engine-step"]
    # and a dense engine has no recurrent state, no store and no state phases
    stats = engine.stats()
    assert "loop_state_snapshot_s" not in stats and "loop_state_restore_s" not in stats
    assert stats["state_snapshots"] == stats["state_store_used"] == stats["prefix_floor_tokens"] == 0
    assert not any(name.startswith("rec_") for name in engine._pool)
