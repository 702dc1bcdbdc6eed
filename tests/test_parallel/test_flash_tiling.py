"""The flash kernels against the TPU lowering, checked from the CPU.

What a sandbox can check without a chip: that ``_pick_block`` only ever
returns a tile the Pallas TPU lowering accepts (and raises a typed error
otherwise, instead of letting the interpreter on the CPU accept what the
chip refuses), that the kernels are interpreted on the ``cpu`` backend
only, and — by cross-lowering with ``jax.export`` for ``platforms=["tpu"]``
— that forward, dq and dkv each lower to a Mosaic custom call.  Mosaic's
own compile and the execution are the chip's to say (``chip_smoke.py``).
"""

import jax
import jax.numpy as jnp
import pytest

from polyaxon_tpu.exceptions import RuntimeLayerError
from polyaxon_tpu.parallel import flash


class TestPickBlock:
    @pytest.mark.parametrize(
        "t,want,tile",
        [
            (1024, 1024, 1024),   # the whole axis
            (8192, 1024, 1024),   # largest 128-multiple divisor <= want
            (2048, 1024, 1024),
            (1536, 1024, 768),
            (1152, 1024, 384),
            (128, 1024, 128),
            (16, 1024, 16),       # toy shapes: the whole axis, multiple of 8
            (8, 1024, 8),
            (640, 512, 128),
            (1000, 512, 200),     # no 128-multiple divides: sublane-aligned
            (16, 8, 8),
        ],
    )
    def test_lowerable_tiles(self, t, want, tile):
        b = flash._pick_block(t, want)
        assert b == tile
        assert t % b == 0 and b <= want
        # What the lowering accepts: sublane-aligned tile edges.
        assert b % 8 == 0

    @pytest.mark.parametrize("t", [1100, 1030, 4100, 12, 100])
    def test_untileable_lengths_raise_typed(self, t):
        # 1100 is the ISSUE's case: the old "any divisor" rule picked 550,
        # which the interpreter runs and the TPU lowering rejects.
        with pytest.raises(flash.FlashTilingError, match=str(t)) as e:
            flash._pick_block(t, 1024)
        assert isinstance(e.value, RuntimeLayerError)

    def test_raises_at_trace_time_through_the_model_path(self):
        from polyaxon_tpu.models import TransformerConfig, init_params, loss_fn

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=1, n_heads=2, head_dim=16,
            d_ff=64, max_seq=1100, attention_impl="flash", flash_block=1024,
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        tok = jnp.zeros((1, 1100), jnp.int32)
        with pytest.raises(flash.FlashTilingError, match="1100"):
            jax.eval_shape(
                lambda p: loss_fn(p, {"tokens": tok, "targets": tok}, cfg),
                params,
            )


class TestBackend:
    def test_interpreted_on_cpu_only(self, monkeypatch):
        assert flash.pallas_interpret() is True  # the suite runs on cpu
        assert flash.on_tpu() is False
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert flash.pallas_interpret() is False
        assert flash.on_tpu() is True

    def test_any_other_backend_is_an_error(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeLayerError, match="gpu"):
            flash.pallas_interpret()

    def test_unreadable_backend_is_an_error(self, monkeypatch):
        def no_backend():
            raise RuntimeError("Unable to initialize backend")

        monkeypatch.setattr(jax, "default_backend", no_backend)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            flash.pallas_interpret()
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            flash.on_tpu()


def test_flash_fwd_bwd_cross_lower_to_three_mosaic_calls():
    """(T, head_dim) = (1024, 64), bf16, compiled (interpret=False) and
    exported for the TPU: forward, dq and dkv are three tpu_custom_calls
    carrying their kernel names."""
    from jax import export

    cfg = (64**-0.5, 1024, 1024, False)

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: flash.flash_attention(cfg, q, k, v)
            .astype(jnp.float32)
            .sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    x = jax.ShapeDtypeStruct((2, 1024, 4, 64), jnp.bfloat16)
    text = export.export(jax.jit(grads), platforms=["tpu"])(x, x, x).mlir_module()
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert f'kernel_name = "{name}"' in text


@pytest.mark.parametrize("offset", [0, 24, -8, -40, 100])
def test_a_key_tile_under_a_query_offset_is_the_masked_softmax_and_merges(offset):
    """``flash_block_fwd`` with ``q_offset`` (the first query that far past the
    tile's first key, of either sign; keys wider than values): against the plain
    softmax under ``row + offset >= column``, a row the tile admits no key to
    comes back ``o = 0``, ``lse = -inf``, and two tiles' pairs merge to the
    softmax over both."""
    import numpy as np

    rng = np.random.default_rng(offset % 7)
    H, Tq, Tk, d, dv = 2, 32, 64, 24, 16
    q = jnp.asarray(rng.normal(size=(H, Tq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(H, 2 * Tk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(H, 2 * Tk, dv)), jnp.float32)
    scale = d**-0.5

    def plain(k, v, off):
        keep = (jnp.arange(Tq)[:, None] + off >= jnp.arange(k.shape[1])[None])[None]
        s = jnp.where(keep, jnp.einsum("hqd,hkd->hqk", q, k) * scale, -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.where(keep, jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 0.0)[..., None]), 0.0)
        return jnp.einsum("hqk,hkd->hqd", p, v), lse

    def tile(i, off):
        return flash.flash_block_fwd(
            q, k[:, i * Tk:(i + 1) * Tk], v[:, i * Tk:(i + 1) * Tk], causal=True,
            sm_scale=scale, block_q=16, block_k=32, q_offset=jnp.int32(off))

    with jax.default_matmul_precision("highest"):
        (o, lse), (want_o, want_lse) = tile(0, offset), plain(k[:, :Tk], v[:, :Tk], offset)
        dead = np.asarray(jnp.isneginf(want_lse))
        assert dead.any() == (offset < 0) and (np.asarray(jnp.isneginf(lse)) == dead).all()
        assert not np.asarray(o)[dead].any()
        assert float(jnp.max(jnp.abs(o - want_o))) < 1e-5
        assert float(jnp.max(jnp.abs(jnp.where(dead, 0.0, lse - want_lse)))) < 1e-5
        both = flash._merge(o, lse, *tile(1, offset - Tk))
        want_o, want_lse = plain(k, v, offset)
        assert float(jnp.max(jnp.abs(both[0] - want_o))) < 1e-5
        assert float(jnp.max(jnp.abs(jnp.where(dead, 0.0, both[1] - want_lse)))) < 1e-5


def _windowed(q, k, v, off, window, k_first, group):
    """The plain rule the window bound is held to: masked ``jax.numpy`` softmax,
    row ``i`` (at ``i + off`` on the key axis) admits column ``j`` if ``j <= i +
    off``, ``i + off - j < window`` and ``j >= k_first``; query head ``h`` reads
    KV head ``h // group``."""
    Tq, Tk = q.shape[1], k.shape[1]
    rows = jnp.arange(Tq)[:, None] + off
    cols = jnp.arange(Tk)[None]
    keep = ((rows >= cols) & (rows - cols < window) & (cols >= k_first))[None]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    s = jnp.where(keep, jnp.einsum("hqd,hkd->hqk", q, k) * q.shape[-1] ** -0.5, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.where(keep, jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 0.0)[..., None]), 0.0)
    return jnp.einsum("hqk,hkd->hqd", p, v), lse


@pytest.mark.parametrize("offset,window,k_first,group", [
    (16, 16, 0, 1),     # a ring of 16 before the queries: the window chunk's own layout
    (16, 16, 9, 3),     # ... the ring not yet filled, three query heads a KV head
    (0, 24, 0, 1),      # no keys before the first query
    (36, 8, 0, 2),      # a window narrower than a key tile: whole tiles skipped
    (-8, 16, 0, 1),     # the first rows stand before every key
    (100, 16, 0, 1),    # every key behind every row's window
], ids=["ring", "ring-unfilled-grouped", "no-ring", "narrow", "negative", "all-behind"])
def test_the_window_bound_is_the_masked_softmax(offset, window, k_first, group):
    """``flash_block_fwd(window=...)`` against a masked ``jax.numpy`` attention,
    offsets of either sign; a row no key is admitted to comes back ``o = 0``,
    ``lse = -inf``."""
    import numpy as np

    rng = np.random.default_rng(offset % 5 + window)
    Hkv, Tq, Tk, d = 2, 32, 64, 16
    q = jnp.asarray(rng.normal(size=(Hkv * group, Tq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(Hkv, Tk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(Hkv, Tk, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        o, lse = flash.flash_block_fwd(
            q, k, v, causal=True, sm_scale=d**-0.5, block_q=16, block_k=8,
            q_offset=jnp.int32(offset), window=window, k_first=jnp.int32(k_first), group=group)
        want_o, want_lse = _windowed(q, k, v, offset, window, k_first, group)
    dead = np.asarray(jnp.isneginf(want_lse))
    assert dead.any() == (offset in (-8, 100)) and dead.all() == (offset == 100)
    assert (np.asarray(jnp.isneginf(lse)) == dead).all() and not np.asarray(o)[dead].any()
    assert float(jnp.max(jnp.abs(o - want_o))) < 1e-5
    assert float(jnp.max(jnp.abs(jnp.where(dead, 0.0, lse - want_lse)))) < 1e-5


def test_a_window_needs_a_causal_block():
    x = jnp.zeros((1, 8, 8), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        flash.flash_block_fwd(x, x, x, causal=False, sm_scale=1.0, window=8)


@pytest.mark.parametrize("form,digest", [
    ("causal", "72f2901fb47edc85"), ("full", "532d62079b40a46f"), ("offset", "145977fb146be4ac"),
])
def test_without_a_window_the_forward_block_is_the_operations_it_was(form, digest):
    """The training step's caller (``causal``, ``full``) and the latent chunk's
    (``offset``) pass no window: their ``pallas_call``, kernel body, grid and
    block maps, prints as it did before the window was written (digests of the
    jaxpr's text taken at the parent commit, addresses struck out)."""
    import hashlib
    import re

    x = jax.ShapeDtypeStruct((4, 256, 64), jnp.bfloat16)
    kw = dict(sm_scale=0.125, block_q=128, block_k=128, interpret=False, causal=form != "full")
    if form == "offset":
        text = str(jax.make_jaxpr(
            lambda q, k, v, o: flash.flash_block_fwd(q, k, v, q_offset=o, **kw)
        )(x, x, x, jax.ShapeDtypeStruct((), jnp.int32)))
    else:
        text = str(jax.make_jaxpr(lambda q, k, v: flash.flash_block_fwd(q, k, v, **kw))(x, x, x))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
