"""The decode step of the latent and the window stack COMPILED for a described
TPU v5e (no chip attached, nothing runs): the step kernel at the cells'
attention widths is taken by the chip's compiler, and the pool a step carries
is neither copied nor viewed (the compiler answers a reshape of a pool leaf,
or a row width that is no whole number of lane tiles, with pool-sized copies:
``PERF.md`` section 6, PRs 29, 34 and 37).  What the interpreter on the CPU
cannot show.

The topology is described inside a fixture, never at import: one process at a
time may load the TPU's library (``on-chip-measurement`` guide, section 2), and
every compile of this kind lives in this one file.
"""

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
