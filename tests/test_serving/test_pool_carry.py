"""The three paged programs carry the WHOLE KV pool through their layer loop.

``paged_prefill_chunk``, ``paged_decode_step`` and ``paged_verify_step`` used
to scan over the pool as per-layer slices: every layer cut its leaf out of the
stacked pool, scattered a few rows into the copy and stacked it into a second
pool — device traffic that grows with the pool, not with what a request wrote
or read.  Now the pool is the loop's carry, addressed by (layer, block,
offset).  Three guards, each over both pool layouts:

- the traced program's layer loop has no scanned input or output shaped like a
  pool leaf, and carries every leaf at its full stacked shape;
- logits and every pool leaf are bit-for-bit what a plain Python loop over
  layers gives that slices, appends, gathers and restacks per layer;
- the compiled decode step's temporaries stay under ONE layer's leaf, so a
  second pool cannot come back unseen.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import TransformerConfig, decode, init_params
from polyaxon_tpu.models.transformer import _dense_attention

CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    n_layers=3,
    n_heads=4,
    head_dim=8,
    d_ff=64,
    max_seq=48,
    n_kv_heads=2,
    dtype=jnp.float32,
)
BS, W, S, T, C = 4, 6, 3, 4, 8
NB = 1 + S * W

CASES = pytest.mark.parametrize("kvq", [None, "int8"], ids=["kv", "int8kv"])
KINDS = pytest.mark.parametrize("kind", ["prefill", "decode", "verify"])


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _filled_pool(kvq, num_blocks=NB, block_size=BS):
    """A pool whose every row already holds something (trash block 0
    included), so a wrong address or a lost row shows in ``array_equal``."""
    pool = decode.init_block_pool(CFG, num_blocks, block_size, kv_dtype=kvq)
    keys = jax.random.split(jax.random.PRNGKey(7), len(pool))
    out = {}
    for key, (name, leaf) in zip(keys, sorted(pool.items())):
        if leaf.dtype == jnp.int8:
            out[name] = jax.random.randint(key, leaf.shape, -127, 128, jnp.int32).astype(jnp.int8)
        else:
            out[name] = jax.random.uniform(key, leaf.shape, leaf.dtype, 0.01, 0.1)
    return out


def _tables():
    # Every lane owns W private blocks, scattered over the pool.
    perm = np.random.default_rng(3).permutation(np.arange(1, NB))
    return jnp.asarray(perm.reshape(S, W), jnp.int32)


PAGED = {
    "prefill": decode.paged_prefill_chunk,
    "decode": decode.paged_decode_step,
    "verify": decode.paged_verify_step,
}


def _args(kind, params, pool):
    """The array arguments of one call of the paged function ``kind`` (``cfg``
    goes by keyword): pad rows and an inactive lane, so the trash block is
    written too."""
    tables = _tables()
    rng = np.random.default_rng(11)
    active = jnp.asarray([True, False, True])
    if kind == "prefill":
        tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, C), jnp.int32)
        return params, pool, tables[0], tokens, jnp.int32(5), jnp.int32(C - 2)
    if kind == "decode":
        tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, S), jnp.int32)
        return params, pool, tables, tokens, jnp.asarray([9, 0, 17], jnp.int32), active
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (S, T)), jnp.int32)
    pos = jnp.asarray([9, 0, 14], jnp.int32)  # lane 2 crosses a block edge
    return params, pool, tables, tokens, pos, jnp.asarray([T, 1, 2], jnp.int32), active


# -- the structure of the traced program ------------------------------------


def _layer_scans(jaxpr):
    """Every ``scan`` equation of length n_layers, at any nesting depth."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == CFG.n_layers:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_layer_scans(sub))
    return found


@CASES
@KINDS
def test_layer_loop_carries_the_pool_and_scans_no_leaf(params, kind, kvq):
    pool = _filled_pool(kvq)
    traced = jax.make_jaxpr(partial(PAGED[kind], cfg=CFG))(*_args(kind, params, pool))
    [loop] = _layer_scans(traced.jaxpr)
    n_consts, n_carry = loop.params["num_consts"], loop.params["num_carry"]
    carried = [v.aval for v in loop.invars[n_consts:n_consts + n_carry]]
    scanned_in = [v.aval for v in loop.invars[n_consts + n_carry:]]
    scanned_out = [v.aval for v in loop.outvars[n_carry:]]
    for name, leaf in pool.items():
        per_layer = (leaf.shape[1:], leaf.dtype)
        for aval in scanned_in + scanned_out:
            # A scanned value is stacked over layers: its per-layer shape is
            # everything after the leading axis.
            assert (aval.shape[1:], aval.dtype) != per_layer, (
                f"{kind}: the layer loop scans over pool leaf {name!r} "
                f"({aval.str_short()}): a slice out and a restack every layer"
            )
        assert any(
            (aval.shape, aval.dtype) == (leaf.shape, leaf.dtype) for aval in carried
        ), f"{kind}: pool leaf {name!r} is not among the loop's carried values"


# -- bit-exactness against the per-layer form --------------------------------


def _append_l(pool_l, name, rows, write_blk, write_off):
    """Scatter into ONE layer's leaves (the form the scan used to run)."""
    if name + "_q" in pool_l:
        q, scale = decode._kv_quant(rows)
        return {
            **pool_l,
            name + "_q": pool_l[name + "_q"].at[write_blk, write_off].set(q),
            name + "_scale": pool_l[name + "_scale"].at[write_blk, write_off].set(scale),
        }
    leaf = pool_l[name]
    return {**pool_l, name: leaf.at[write_blk, write_off].set(rows.astype(leaf.dtype))}


def _gather_l(pool_l, name, table, dtype):
    if name + "_q" in pool_l:
        return decode._kv_dequant(
            pool_l[name + "_q"][table], pool_l[name + "_scale"][table], dtype
        )
    return pool_l[name][table]


def _layers_by_slices(x, params, pool, table, positions, rows, write_blk, write_off, attend):
    """A plain Python loop over layers: slice the layer's leaves out of the
    stacked pool, append ``rows(k)`` and ``rows(v)``, gather, and restack at
    the end."""
    c = CFG
    bs, Hkv, d = decode.pool_geometry(pool)
    n_keys = table.shape[-1] * bs
    stacked = {name: [] for name in pool}
    for li in range(c.n_layers):
        layer = jax.tree.map(lambda a: a[li], params["block"])
        pool_l = {name: leaf[li] for name, leaf in pool.items()}
        h = decode._rmsnorm(x, layer["attn_norm"])
        q = jnp.einsum("btd,dhk->bthk", h, layer["wq"].astype(h.dtype))
        k = jnp.einsum("btd,dhk->bthk", h, layer["wk"].astype(h.dtype))
        v = jnp.einsum("btd,dhk->bthk", h, layer["wv"].astype(h.dtype))
        q = decode._rope(q, positions, c.rope_theta)
        k = decode._rope(k, positions, c.rope_theta)
        pool_l = _append_l(pool_l, "k", rows(k), write_blk, write_off)
        pool_l = _append_l(pool_l, "v", rows(v), write_blk, write_off)
        ck = _gather_l(pool_l, "k", table, h.dtype).reshape(x.shape[0], n_keys, Hkv, d)
        cv = _gather_l(pool_l, "v", table, h.dtype).reshape(x.shape[0], n_keys, Hkv, d)
        attn = attend(q, ck, cv)
        x = x + jnp.einsum("bthk,hkd->btd", attn, layer["wo"].astype(h.dtype))
        h = decode._rmsnorm(x, layer["mlp_norm"])
        up = jnp.einsum("btd,df->btf", h, layer["wi"].astype(h.dtype))
        gate = jnp.einsum("btd,df->btf", h, layer["wg"].astype(h.dtype))
        x = x + jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * up, layer["wd"].astype(h.dtype))
        for name in pool:
            stacked[name].append(pool_l[name])
    x = decode._rmsnorm(x, params["final_norm"])
    logits = jnp.einsum("btd,dv->btv", x, params["unembed"].astype(x.dtype))
    return logits.astype(jnp.float32), {n: jnp.stack(v) for n, v in stacked.items()}


def _prefill_by_slices(params, pool, table, tokens, start, length, cfg):
    bs, group = decode.pool_geometry(pool)[0], cfg.n_heads // cfg.kv_heads
    n, width = tokens.shape[0], table.shape[0]
    qpos = start + jnp.arange(n)
    valid = jnp.arange(n) < length
    write_blk = jnp.where(valid, table[jnp.clip(qpos // bs, 0, width - 1)], 0)
    write_off = jnp.where(valid, qpos % bs, 0)
    kpos = jnp.arange(width * bs)[None]

    def attend(q, ck, cv):
        ck, cv = (jnp.repeat(a, group, axis=2) for a in (ck, cv))
        return _dense_attention(q, ck, cv, qpos[None], kpos)

    x = params["embed"].astype(cfg.dtype)[tokens][None]
    logits, new_pool = _layers_by_slices(
        x, params, pool, table, qpos[None], lambda a: a[0], write_blk, write_off, attend
    )
    return jnp.take(logits[0], length - 1, axis=0), new_pool


def _decode_by_slices(params, pool, tables, tokens, pos, active, cfg):
    bs, group = decode.pool_geometry(pool)[0], cfg.n_heads // cfg.kv_heads
    pos = jnp.where(active, pos, 0)
    write_blk = jnp.where(active, tables[jnp.arange(tables.shape[0]), pos // bs], 0)
    write_off = jnp.where(active, pos % bs, 0)
    x = params["embed"].astype(cfg.dtype)[tokens][:, None, :]
    logits, new_pool = _layers_by_slices(
        x, params, pool, tables, pos[:, None], lambda a: a[:, 0], write_blk, write_off,
        lambda q, ck, cv: decode._attend_paged(q, ck, cv, pos, group),
    )
    return logits[:, 0], new_pool


def _verify_by_slices(params, pool, tables, tokens, pos, n_tok, active, cfg):
    bs, group = decode.pool_geometry(pool)[0], cfg.n_heads // cfg.kv_heads
    n_lanes, width = tables.shape
    n_rows = tokens.shape[1]
    pos = jnp.where(active, pos, 0)
    qpos = pos[:, None] + jnp.arange(n_rows)[None, :]
    row_ok = active[:, None] & (jnp.arange(n_rows)[None, :] < n_tok[:, None])
    write_blk = jnp.where(
        row_ok, tables[jnp.arange(n_lanes)[:, None], jnp.clip(qpos // bs, 0, width - 1)], 0
    )
    write_off = jnp.where(row_ok, qpos % bs, 0)
    x = params["embed"].astype(cfg.dtype)[tokens]
    return _layers_by_slices(
        x, params, pool, tables, qpos, lambda a: a, write_blk, write_off,
        lambda q, ck, cv: decode._attend_spec(q, ck, cv, qpos, group),
    )


BY_SLICES = {
    "prefill": _prefill_by_slices,
    "decode": _decode_by_slices,
    "verify": _verify_by_slices,
}


@CASES
@KINDS
def test_bit_identical_to_the_per_layer_slices(params, kind, kvq):
    pool = _filled_pool(kvq)
    args = _args(kind, params, pool)
    logits, new_pool = jax.jit(partial(PAGED[kind], cfg=CFG))(*args)
    want_logits, want_pool = jax.jit(partial(BY_SLICES[kind], cfg=CFG))(*args)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    assert sorted(new_pool) == sorted(want_pool) == sorted(pool)
    for name in pool:
        got, want = np.asarray(new_pool[name]), np.asarray(want_pool[name])
        assert got.shape == pool[name].shape and got.dtype == pool[name].dtype
        np.testing.assert_array_equal(got, want, err_msg=f"{kind}: leaf {name!r}")
        # Trash block 0 took the pad rows' and the idle lane's garbage.
        assert not np.array_equal(got[:, 0], np.asarray(pool[name])[:, 0]), name


# -- the table walk into a pool whose rows hold more heads than the model ----

WIDE = 8  # heads a pool row holds (``TransformerConfig.pool_kv_heads`` of a hybrid model)


def _widened(pool, key):
    """``pool`` with every row padded to ``WIDE`` heads of GARBAGE: a walk that
    read the extra heads, or left them unwritten, shows."""
    out = {}
    for sub, (name, leaf) in zip(jax.random.split(key, len(pool)), sorted(pool.items())):
        shape = leaf.shape[:3] + (WIDE - leaf.shape[3],) + leaf.shape[4:]
        extra = jax.random.randint(sub, shape, 1, 100).astype(leaf.dtype)
        out[name] = jnp.concatenate([leaf, extra], axis=3)
    return out


@CASES
@KINDS
def test_table_walk_pads_rows_in_and_slices_them_out(kind, kvq):
    """``_kv_through_table`` on a pool of ``WIDE``-head rows gathers bit for bit
    what it gathers from the model's own heads, writes zeros into the extra
    heads of the rows it appends and touches no other row."""
    Hkv, d, li = CFG.kv_heads, CFG.head_dim, 1
    tables = _tables()
    lead, table = {"prefill": ((1, C), tables[0]), "decode": ((S, 1), tables),
                   "verify": ((S, T), tables)}[kind]
    n_rows = lead[0] * lead[1]
    at = (n_rows,) if kind != "verify" else lead
    k, v = jax.random.normal(jax.random.PRNGKey(5), (2,) + lead + (Hkv, d))
    # distinct addresses, trash block 0 among them
    where = np.random.default_rng(13).permutation(NB * BS)[:n_rows]
    write_blk = jnp.asarray(where // BS, jnp.int32).reshape(at)
    write_off = jnp.asarray(where % BS, jnp.int32).reshape(at)

    narrow = _filled_pool(kvq)
    wide = _widened(narrow, jax.random.PRNGKey(17))
    walk = jax.jit(decode._kv_through_table, static_argnames="dtype")
    want_pool, want_k, want_v = walk(narrow, li, k, v, table, write_blk, write_off, dtype=CFG.dtype)
    got_pool, got_k, got_v = walk(wide, li, k, v, table, write_blk, write_off, dtype=CFG.dtype)

    assert got_k.shape == got_v.shape == (lead[0], W * BS, Hkv, d)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    written = np.zeros((CFG.n_layers, NB, BS), bool)
    written[li, where // BS, where % BS] = True
    for name in narrow:
        got, before = np.asarray(got_pool[name]), np.asarray(wide[name])
        np.testing.assert_array_equal(got[:, :, :, :Hkv], np.asarray(want_pool[name]), err_msg=name)
        assert not got[written][:, Hkv:].any(), f"{name}: an appended row's extra heads are not zero"
        np.testing.assert_array_equal(got[~written], before[~written], err_msg=name)


# -- the compiled step holds no second pool ----------------------------------


@CASES
def test_compiled_decode_step_temporaries_stay_under_one_layer_leaf(params, kvq):
    # A pool that dwarfs the activations: 2,049 blocks against 3 lanes of 6.
    pool = _filled_pool(kvq, num_blocks=2049, block_size=BS)
    step = jax.jit(partial(decode.paged_decode_step, cfg=CFG), donate_argnums=(1,))
    compiled = step.lower(*_args("decode", params, pool)).compile()
    analysis = compiled.memory_analysis()
    if analysis is None or not hasattr(analysis, "temp_size_in_bytes"):
        pytest.skip("this backend reports no memory analysis")
    one_layer_leaf = max(leaf[0].nbytes for leaf in pool.values())
    pool_bytes = sum(leaf.nbytes for leaf in pool.values())
    assert analysis.alias_size_in_bytes >= pool_bytes, "the donated pool is not reused"
    assert analysis.temp_size_in_bytes < one_layer_leaf, (
        f"the decode step's temporaries ({analysis.temp_size_in_bytes} B) hold "
        f"a layer's leaf ({one_layer_leaf} B) or more: the pool is being copied"
    )
