"""The decode step of the latent and the window stack COMPILED for a described
TPU v5e (no chip attached, nothing runs): the step kernel at the cells'
attention widths is taken by the chip's compiler, and the pool a step carries
is neither copied nor viewed (the compiler answers a reshape of a pool leaf,
or a row width that is no whole number of lane tiles, with pool-sized copies:
``PERF.md`` section 6, PRs 29, 34 and 37); and the latent stack's step and
chunk at DeepSeek-V3.2's widths cut no stacked weight down to a run of layers
before the layer loop.  What the interpreter on the CPU cannot show.

The topology is described inside a fixture, never at import: one process at a
time may load the TPU's library (``on-chip-measurement`` guide, section 2), and
every compile of this kind lives in this one file.
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from polyaxon_tpu.models import TransformerConfig, decode, init_params, window_moe
from polyaxon_tpu.models.window_moe import FULL, WINDOW
from polyaxon_tpu.parallel import flash

BLOCKS, BS, SLOTS, W = 8193, 16, 8, 1024  # the doc-QA cells' pool and tables


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler from being described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _latent():
    """JoyAI-LLM-Flash's attention widths (``benchmark/configs/joyai-llm-flash-serve.json``)
    over two layers and a small vocabulary: the pool's row is 576 held at 640."""
    return TransformerConfig(
        vocab_size=1024, d_model=2048, n_layers=2, n_heads=32, head_dim=64, d_ff=1024,
        max_seq=W * BS, dtype=jnp.bfloat16, rope_theta=32e6,
        layer_types=("dense_mlp", "dense_mlp"), q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)


def _window():
    """Laguna-S-2.1's attention widths (``benchmark/configs/laguna-s-2.1-serve.json``):
    48 query heads over 8 KV heads of 128 in the full layers, one window layer."""
    return TransformerConfig(
        vocab_size=1024, d_model=3072, n_layers=3, n_heads=48, sliding_n_heads=72, n_kv_heads=8,
        head_dim=128, d_ff=1024, max_seq=W * BS, dtype=jnp.bfloat16,
        layer_types=(FULL, WINDOW, FULL), mlp_layer_types=("dense",) * 3, sliding_window=512,
        head_gate=True, rope_theta=500000.0, partial_rotary_factor=0.5, sliding_rope_theta=10000.0)


def _sparse_latent():
    """DeepSeek-V3.2's widths (``benchmark/configs/deepseek-v3.2-serve.json``)
    over a dense layer and a run of two expert layers, and a small vocabulary."""
    return TransformerConfig(
        vocab_size=1024, d_model=7168, n_layers=3, n_heads=128, head_dim=64, d_ff=18432,
        max_seq=W * BS, dtype=jnp.bfloat16, rope_theta=10000.0,
        layer_types=("dense_mlp", "expert_mlp", "expert_mlp"), q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        index_n_heads=64, index_head_dim=128, index_topk=2048, n_routed_experts=256,
        experts_held=8, num_experts_per_tok=8, n_group=8, topk_group=4,
        moe_intermediate_size=2048, n_shared_experts=1, routed_scaling_factor=2.5,
        rope_yarn_factor=40.0, rope_yarn_original_max=4096, rope_yarn_beta_fast=32,
        rope_yarn_beta_slow=1, rope_yarn_mscale_all_dim=1.0)


@pytest.mark.parametrize("stack", ["latent", "window"])
def test_the_step_compiles_for_the_chip_and_leaves_the_pool_where_it_is(
        one_chip, monkeypatch, stack):
    cfg = _latent() if stack == "latent" else _window()

    def pool_of():
        pool = decode.init_block_pool(cfg, BLOCKS, BS)
        return {**pool, **window_moe.init_rec_state(cfg, SLOTS)} if stack == "window" else pool

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(
        lambda: decode.serving_params(init_params(jax.random.PRNGKey(0), cfg), cfg))
    pool = jax.eval_shape(pool_of)
    lanes = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    args = on_chip((params, pool, jax.ShapeDtypeStruct((SLOTS, W), jnp.int32), lanes, lanes,
                    jax.ShapeDtypeStruct((SLOTS,), jnp.bool_)))
    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)
    step = jax.jit(partial(decode.paged_decode_step, cfg=cfg), donate_argnums=(1,))
    compiled = step.lower(*args).compile()  # raises what the chip's compiler would raise
    text = compiled.as_text()
    assert "paged_step_attend" in text and "tpu_custom_call" in text
    kv = [leaf for name, leaf in pool.items() if name not in decode.SLOT_LEAVES]
    smallest = min(leaf.size * leaf.dtype.itemsize for leaf in kv)
    memory = compiled.memory_analysis()
    # the donated pool is the one that leaves, and no temporary is as large as one leaf of it
    assert memory.alias_size_in_bytes >= sum(leaf.size * leaf.dtype.itemsize for leaf in kv)
    assert memory.temp_size_in_bytes < smallest // 2, memory


def test_the_indexers_kernel_and_the_selecting_flash_kernel_compile_for_the_chip(
        one_chip, monkeypatch):
    """DeepSeek-V3.2's widths (``benchmark/configs/deepseek-v3.2-serve.json``):
    the indexer's 64 heads of 128 over a 1,024-row chunk and a 1,024-key tile,
    and the flash kernel over 128 heads of 192 / 128 masked by the tile's
    scores and the rows' thresholds, each taken by the chip's compiler."""
    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)
    C, H, d, N = 1024, 64, 128, 1024

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    index = jax.jit(partial(flash.dsa_index, name=f"dsa_index_{C}")).lower(
        sds((C, H, d), jnp.bfloat16), sds((C, H), jnp.float32), sds((N, d), jnp.bfloat16)
    ).compile()
    assert "tpu_custom_call" in index.as_text()

    def masked(q, k, v, scores, thresholds):
        return flash.flash_block_fwd(q, k, v, causal=True, sm_scale=0.1, q_offset=jnp.int32(0),
                                     name="dsa_chunk_tile", select=(scores, thresholds),
                                     block_q=512)

    tile = jax.jit(masked).lower(
        sds((128, C, 192), jnp.bfloat16), sds((128, N, 192), jnp.bfloat16),
        sds((128, N, 128), jnp.bfloat16), sds((C, N), jnp.float32), sds((C,), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in tile.as_text()


#: What a program's temporaries may come to (``memory_analysis``).  Where each
#: run of the pattern is cut out of the stacks before its loop, the step holds
#: 811 MB (``wo`` of two layers alone is 470 MB) and the chunk 1,447 MB; read
#: in place, 3.5 MB and 625 MB (at these three layers; PERF.md section 4 gives
#: the served configuration's five).
_TEMP_BOUND = {"step": 64 << 20, "chunk": 1 << 30}


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_the_latent_programs_read_each_layer_out_of_the_whole_stacks(
        one_chip, monkeypatch, program):
    """The latent stack's step and 1,024-row chunk at DeepSeek-V3.2's widths:
    the entry computation cuts no stacked block weight down to a run of the
    pattern (a copy of the run's layers written in every call), and the
    programs' temporaries stay under what such copies would take."""
    cfg = _sparse_latent()
    L, C = cfg.n_layers, 1024

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(
        lambda: decode.serving_params(init_params(jax.random.PRNGKey(0), cfg), cfg))
    pool = jax.eval_shape(lambda: decode.init_block_pool(cfg, BLOCKS, BS))
    i32 = jnp.int32
    if program == "step":
        fn = decode.paged_decode_step
        lanes = jax.ShapeDtypeStruct((SLOTS,), i32)
        rest = (jax.ShapeDtypeStruct((SLOTS, W), i32), lanes, lanes,
                jax.ShapeDtypeStruct((SLOTS,), jnp.bool_))
    else:
        fn = decode.paged_prefill_chunk
        one = jax.ShapeDtypeStruct((), i32)
        rest = (jax.ShapeDtypeStruct((W,), i32), jax.ShapeDtypeStruct((C,), i32), one, one)
    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)
    compiled = jax.jit(partial(fn, cfg=cfg), donate_argnums=(1,)).lower(
        *on_chip((params, pool, *rest))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    entry = text[text.index("\nENTRY"):]
    entry = entry[: entry.index("\n}")]
    stacked = {tuple(leaf.shape[1:]) for leaf in jax.tree.leaves(params["block"])
               if leaf.shape[0] == L}
    cuts = []
    for line in entry.splitlines():
        # a result in the chip's main memory (a prefetch into another space is marked S(n))
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([0-9,]+)\]\{([^}]*)\}", line)
        if not m or "S(" in m.group(2):
            continue
        dims = tuple(int(d) for d in m.group(1).split(","))
        if 1 < dims[0] < L and dims[1:] in stacked:
            cuts.append(line.strip()[:160])
    assert not cuts, cuts
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < _TEMP_BOUND[program], memory
