"""A decode step that attends through its block table in a kernel
(``parallel/flash.py:paged_step_attend``) against the plain rule: the same
rows appended, the WHOLE table gathered and one masked softmax
(``_attend_absorbed`` over ``_latent_gather`` for the latent pool's one head of
640 lanes whose value is its first 512; ``_attend_paged`` over ``_pool_gather``
for K and V leaves of ``Hkv`` heads of 128 lanes under a query group of 6).

CPU, float32, the kernel interpreted, matmuls at ``highest``: the two differ by
float32 summation order (the kernel sums a block at a time).  ``TOL`` = 2e-5
absolute on outputs of magnitude 0.3 to 3; they read 1e-6 to 3e-6 apart here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import TransformerConfig, decode, init_params, latent_moe, window_moe
from polyaxon_tpu.models.window_moe import FULL, WINDOW
from polyaxon_tpu.parallel import flash

TOL = 2e-5
BS, S = 16, 8
BLOCK = flash.STEP_BLOCK_KEYS
W = 3 * BLOCK // BS  # a table of three compute blocks
D = 64


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _latent_cfg():
    """The expert cell's attention widths (row 512 + 64 held at 640) on a narrow
    model: 4 query heads, two attention layers."""
    return TransformerConfig(
        vocab_size=64, d_model=D, n_layers=2, n_heads=4, head_dim=16, d_ff=64,
        max_seq=W * BS, dtype=jnp.float32, rope_theta=32e6,
        layer_types=("dense_mlp", "dense_mlp"), q_lora_rank=32, kv_lora_rank=512,
        qk_nope_head_dim=32, qk_rope_head_dim=64, v_head_dim=32)


def _window_cfg():
    """Two full layers of 2 KV heads x 128 under a query group of 6, and a
    window layer between them that this test never runs."""
    return TransformerConfig(
        vocab_size=64, d_model=D, n_layers=3, n_heads=12, sliding_n_heads=12, n_kv_heads=2,
        head_dim=128, d_ff=64, max_seq=W * BS, dtype=jnp.float32,
        layer_types=(FULL, WINDOW, FULL), mlp_layer_types=("dense",) * 3, sliding_window=16,
        head_gate=True, rope_theta=500000.0, partial_rotary_factor=0.5, sliding_rope_theta=10000.0)


_STACKS = {}

#: Live ends (keys a lane has: its position + 1) of the eight lanes, and which
#: lanes are active.
_ALL = np.ones(S, bool)
CASES = {
    "one": (np.full(S, 1), _ALL),
    "a-page-less-one": (np.full(S, BS - 1), _ALL),
    "a-page": (np.full(S, BS), _ALL),
    "a-block-less-one": (np.full(S, BLOCK - 1), _ALL),
    "a-block": (np.full(S, BLOCK), _ALL),
    "a-block-and-one": (np.full(S, BLOCK + 1), _ALL),
    "whole-table": (np.full(S, W * BS), _ALL),
    "eight-ends": (np.asarray([1, BS + 3, BLOCK - 5, BLOCK, BLOCK + 1, 2 * BLOCK + 77,
                               3 * BLOCK - BS, 3 * BLOCK]), _ALL),
    "inactive-lanes": (np.asarray([40, 900, 1, BLOCK + 9, 1300, 77, 1, 600]),
                       np.asarray([1, 1, 0, 1, 1, 0, 0, 1], bool)),
}


def _tables_and_pools(cfg, live, active, rng):
    """Scattered private blocks a lane, lanes 0 and 1 sharing what lies wholly
    under both their positions, unset entries at the trash block.  Returns the
    tables, a pool of normal rows, and the same pool with NaN wherever no lane
    may read: every row past a lane's live end in its last live block, the
    whole trash block, and every block no table names, layer 0 included."""
    n_blocks = 1 + S * W
    ids = 1 + rng.permutation(S * W)
    tables = np.zeros((S, W), np.int32)
    for s in range(S):
        if active[s]:
            used = -(-int(live[s]) // BS)
            tables[s, :used] = ids[s * W : s * W + used]
    shared = min(3, (int(min(live[0], live[1])) - 1) // BS) if active[0] and active[1] else 0
    tables[1, :shared] = tables[0, :shared]
    clean = {
        name: jnp.asarray(rng.normal(size=leaf.shape), leaf.dtype)
        for name, leaf in decode.init_block_pool(cfg, n_blocks, BS).items()}
    dead = np.ones((n_blocks, BS), bool)
    for s in range(S):
        if active[s]:
            at = np.arange(int(live[s]))
            dead[tables[s, at // BS], at % BS] = False
    dirty = {}
    for name, leaf in clean.items():
        hole = np.broadcast_to(dead.reshape((1, n_blocks, BS) + (1,) * (leaf.ndim - 3)), leaf.shape)
        hole = hole.copy()
        hole[0] = True  # the other layer: never read
        dirty[name] = jnp.where(jnp.asarray(hole), jnp.nan, leaf)
    return jnp.asarray(tables), clean, dirty


def _mixers(form, cfg, layer):
    """``(through the kernel, the plain rule)``, each ``(h, pool, tables, pos,
    active) -> (output, pool)`` for layer 1 of its kind: one compilation a form
    serves every case."""
    li = jnp.int32(1)

    def addresses(tables, pos, active):
        pos = jnp.where(active, pos, 0)
        write_blk = jnp.where(active, tables[jnp.arange(S), pos // BS], 0)
        return pos, write_blk, jnp.where(active, pos % BS, 0)

    def through_kernel(h, pool, tables, pos, active):
        pos, write_blk, write_off = addresses(tables, pos, active)
        make = latent_moe._step_mixer if form == "latent" else window_moe._full_step_mixer
        return make(cfg, pos[:, None], tables, write_blk, write_off, pos)(h, layer, li, pool)

    def plain(h, pool, tables, pos, active):
        pos, write_blk, write_off = addresses(tables, pos, active)
        if form == "latent":
            q_nope, q_rope = latent_moe._queries(h, layer, pos[:, None], cfg)
            row = latent_moe._latent_row(h, layer, pos[:, None], cfg)
            pool = decode._latent_append(pool, li, row[:, 0], write_blk, write_off)
            rows = decode._latent_gather(pool, li, tables, h.dtype, row.shape[-1])
            mask = (jnp.arange(rows.shape[1])[None] <= pos[:, None])[:, None, None, :]
            attn = latent_moe._attend_absorbed(q_nope, q_rope, rows, mask, layer, cfg)
            return decode._attn_out(attn, layer), pool
        q, k, v = window_moe._qkv_rotated(h, layer, pos[:, None], cfg, FULL)
        pool, ck, cv = decode._kv_through_table(
            pool, li, k, v, tables, write_blk, write_off, h.dtype)
        attn = decode._attend_paged(q, ck, cv, pos, cfg.n_heads // cfg.kv_heads)
        return window_moe._gated_out(attn, h, layer), pool

    return jax.jit(through_kernel), jax.jit(plain)


def _stack(form):
    cfg = _latent_cfg() if form == "latent" else _window_cfg()
    blk = init_params(jax.random.PRNGKey(7), cfg)["block"]
    tree = blk if form == "latent" else blk["full"]
    layer = jax.tree.map(lambda w: w[1], {n: w for n, w in tree.items() if hasattr(w, "shape")})
    return (cfg, *_mixers(form, cfg, layer))


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("form", ["latent", "kv"])
def test_a_step_through_the_kernel_is_the_plain_rule_over_the_whole_table(form, case):
    """The step mixer over a float pool (rows appended, then the kernel over
    each lane's pages up to its live end) against the rows appended the same
    way, ALL of the table gathered and one masked softmax.  The kernel runs on
    the pool whose dead rows, trash block and other layer are NaN: they are not
    read into the result, and a traced layer index of 1 reads layer 1."""
    if form not in _STACKS:
        _STACKS[form] = _stack(form)
    cfg, through_kernel, plain = _STACKS[form]
    live, active = CASES[case]
    rng = np.random.default_rng(len(case) + int(live.sum()))
    tables, clean, dirty = _tables_and_pools(cfg, live, active, rng)
    h = jnp.asarray(rng.normal(size=(S, 1, D)), jnp.float32)
    pos, on = jnp.asarray(live - 1, jnp.int32), jnp.asarray(active)
    got, pool_a = through_kernel(h, dirty, tables, pos, on)
    want, pool_b = plain(h, clean, tables, pos, on)
    assert through_kernel._cache_size() == 1  # one compilation for every state
    got, want = np.asarray(got)[active], np.asarray(want)[active]
    assert want.shape == (int(active.sum()), 1, D) and float(np.max(np.abs(want))) > 0.1
    assert np.all(np.isfinite(got)), "a dead row reached the result"
    assert float(np.max(np.abs(got - want))) < TOL
    # both wrote the same rows at the same places (read where the dirty pool is no NaN)
    for name in pool_b:
        a, b = np.asarray(pool_a[name]), np.asarray(pool_b[name])
        assert np.array_equal(a[~np.isnan(a)], b[~np.isnan(a)])


def test_two_heads_of_a_16_bit_pool_are_read_out_of_one_word():
    """K and V leaves in bfloat16: two KV heads of a row share a 32-bit word a
    lane, which the kernel reads once and splits.  Three heads of the row's
    four are the model's; the kernel against the plain rule over the same
    bfloat16 values, float32 sums on both sides."""
    rng = np.random.default_rng(3)
    L, NB, Hp, H, G, d = 2, 1 + 2 * W, 4, 3, 8, 128
    k, v = (jnp.asarray(rng.normal(size=(L, NB, BS, Hp, d)), jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(2, H, G, d)), jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(2 * W).reshape(2, W), jnp.int32)
    live = jnp.asarray([BLOCK + 17, 5], jnp.int32)
    got = jax.jit(lambda *a: flash.paged_step_attend(*a, sm_scale=d**-0.5))(
        q, k, v, jnp.int32(1), tables, live)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    keys, vals = (f32(x[1][tables]).reshape(2, W * BS, Hp, d)[:, :, :H] for x in (k, v))
    s = jnp.einsum("shgd,skhd->shgk", f32(q), keys) * d**-0.5
    mask = (jnp.arange(W * BS)[None] < live[:, None])[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    want = jnp.einsum("shgk,skhd->shgd", f32(p.astype(jnp.bfloat16)), vals)
    want = want / jnp.sum(f32(p.astype(jnp.bfloat16)), -1, keepdims=True) * jnp.sum(p, -1, keepdims=True)
    assert got.shape == (2, H, G, d) and float(jnp.max(jnp.abs(want))) > 0.5
    assert float(jnp.max(jnp.abs(got - want))) < 2e-2  # bfloat16 probabilities, block by block


def test_queries_that_do_not_fit_the_pages_are_refused():
    k = jnp.zeros((1, 4, BS, 2, 128))
    tables, live = jnp.zeros((1, 4), jnp.int32), jnp.ones(1, jnp.int32)
    with pytest.raises(ValueError, match="do not fit pages"):
        flash.paged_step_attend(jnp.zeros((1, 3, 8, 128)), k, k, 0, tables, live, sm_scale=1.0)
    with pytest.raises(ValueError, match="do not fit pages"):
        flash.paged_step_attend(
            jnp.zeros((1, 1, 8, 512)), jnp.zeros((1, 4, BS, 640)), None, 0, tables, live, sm_scale=1.0)


@pytest.mark.parametrize("stack,kv_dtype,ends,want", [
    ("latent", None, [1, BLOCK, BLOCK + 1, 5 * BLOCK], [BLOCK, BLOCK, 2 * BLOCK, 5 * BLOCK]),
    ("latent", "int8", [1, BLOCK + 1], [1024 * BS] * 2),
    ("window", None, [7, 3 * BLOCK - 1], [BLOCK, 3 * BLOCK]),
    ("uniform", None, [7, 3 * BLOCK - 1], [1024 * BS] * 2),
])
def test_the_hosts_count_of_a_steps_keys_follows_the_program(stack, kv_dtype, ends, want):
    cfg = {"latent": _latent_cfg, "window": _window_cfg, "uniform": lambda: TransformerConfig(
        vocab_size=64, d_model=D, n_layers=1, n_heads=4, head_dim=16, d_ff=64, max_seq=64)}[stack]()
    assert cfg.stack == stack
    assert decode.step_keys_attended(cfg, ends, 1024, BS, kv_dtype) == sum(want)
    assert decode.step_keys_attended(cfg, [], 1024, BS, kv_dtype) == 0
    # a table narrower than a compute block is one block
    assert decode.step_keys_attended(cfg, [3], 4, BS, None) == 4 * BS
