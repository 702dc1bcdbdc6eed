"""Persistent prefix store: warm replica boot.

The acceptance bar: an engine persists its hot prefix blocks (chain
tokens + pool leaves verbatim, torn-write-safe) and a FRESH engine
pointed at the same store boots with those prefixes pre-installed — its
first request over a stored prefix is a cache HIT and its greedy output
is token-identical to a cold engine's.  Plus the store's durability
edges: unmarked (torn) versions are invisible, a geometry or signature
mismatch walks away instead of serving another model's KV, GC keeps the
newest two snapshots, and the fleet threads the warm-boot config into
every replica spec (scale-ups included).
"""

import dataclasses
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from polyaxon_tpu.models import TransformerConfig, decode, init_params
from polyaxon_tpu.serving import ServingEngine, kvstore

CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    n_layers=2,
    n_heads=4,
    head_dim=8,
    d_ff=64,
    max_seq=48,
    dtype=jnp.float32,
)
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def params():
    return init_params(KEY, CFG)


def _ref(params, prompt, max_new):
    out = decode.generate(
        params, jnp.asarray([prompt]), CFG, max_new_tokens=max_new
    )
    return np.asarray(out)[0].tolist()


def _entries(n, shape=(2, 3)):
    rng = np.random.default_rng(7)
    out = []
    for i in range(n):
        chain = tuple(range(4 * (i + 1)))
        data = {
            "k": rng.normal(size=shape).astype(np.float32),
            "v": rng.normal(size=shape).astype(np.float32),
        }
        out.append((chain, data))
    return out


META = {"sig": "m1", "kv_dtype": "float32", "block_size": 4}


class TestKVStore:
    def test_save_load_roundtrip_preserves_order_and_bits(self, tmp_path):
        entries = _entries(3)
        version = kvstore.save_prefix_store(tmp_path, entries, meta=META)
        assert version == 1
        loaded = kvstore.load_prefix_store(tmp_path, expect=META)
        assert [c for c, _ in loaded] == [c for c, _ in entries]
        for (_, want), (_, got) in zip(entries, loaded):
            for name in want:
                np.testing.assert_array_equal(want[name], got[name])

    def test_empty_entries_write_nothing(self, tmp_path):
        assert kvstore.save_prefix_store(tmp_path, [], meta=META) is None
        assert kvstore.load_prefix_store(tmp_path) is None

    def test_unmarked_version_is_invisible(self, tmp_path):
        kvstore.save_prefix_store(tmp_path, _entries(1), meta=META)
        # A crash after the data rename but before the marker: the dir
        # exists, the marker doesn't.  Readers must keep trusting v1.
        torn = tmp_path / "2"
        torn.mkdir()
        (torn / "meta.json").write_text("{ torn")
        assert kvstore.latest_complete_version(tmp_path) == 1
        assert len(kvstore.load_prefix_store(tmp_path, expect=META)) == 1
        # And the next writer claims PAST the torn dir, never into it.
        assert kvstore.save_prefix_store(tmp_path, _entries(1), meta=META) == 3

    def test_meta_mismatch_walks_away(self, tmp_path):
        kvstore.save_prefix_store(tmp_path, _entries(1), meta=META)
        assert kvstore.load_prefix_store(tmp_path, expect=META) is not None
        for bad in (
            {**META, "sig": "other-weights"},
            {**META, "block_size": 8},
            {**META, "kv_dtype": "int8"},
        ):
            assert kvstore.load_prefix_store(tmp_path, expect=bad) is None

    def test_gc_keeps_newest_two(self, tmp_path):
        for _ in range(4):
            kvstore.save_prefix_store(tmp_path, _entries(1), meta=META)
        assert kvstore.complete_versions(tmp_path) == [3, 4]
        assert not (tmp_path / "1").exists()
        assert not (tmp_path / ".complete" / "1").exists()

    def test_corrupt_payload_reads_as_missing(self, tmp_path):
        kvstore.save_prefix_store(tmp_path, _entries(1), meta=META)
        (tmp_path / "1" / "blocks.npz").write_bytes(b"not a zipfile")
        assert kvstore.load_prefix_store(tmp_path, expect=META) is None

    def test_bfloat16_leaves_roundtrip_to_their_dtype(self, tmp_path):
        """npz reads extension dtypes back as raw void bytes; the loader
        must view-cast to the recorded dtype or jit rejects the payload
        — bfloat16 is the TPU-default pool dtype, so this is the common
        production layout, not an edge case."""
        rng = np.random.default_rng(9)
        k = jnp.asarray(rng.normal(size=(2, 3)), dtype=jnp.bfloat16)
        entries = [((0, 1, 2, 3), {"k": np.asarray(k)})]
        meta = {**META, "kv_dtype": "bfloat16"}
        kvstore.save_prefix_store(tmp_path, entries, meta=meta)
        [(chain, data)] = kvstore.load_prefix_store(tmp_path, expect=meta)
        assert str(data["k"].dtype) == "bfloat16"
        np.testing.assert_array_equal(
            data["k"].view(np.uint16), np.asarray(k).view(np.uint16)
        )
        # And jit accepts it: the exact call the engine preload makes.
        jax.jit(lambda a: a + 0)(data["k"])


class TestWarmBoot:
    def test_restart_boots_prefix_warm_and_token_identical(
        self, params, tmp_path
    ):
        """Engine A serves, stops (final persist); engine B on the same
        store + signature preloads A's prefixes, hits on the first
        request, and answers token-identically."""
        rng = np.random.default_rng(11)
        p = list(rng.integers(0, 64, 12))  # 3 full blocks
        ref = _ref(params, p, 6)
        store = tmp_path / "kv"
        a = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store, kv_persist_sig="w1",
        ).start()
        try:
            assert a.submit(p, 6).wait(timeout=120) == ref
        finally:
            a.stop()
        assert kvstore.latest_complete_version(store) == 1
        assert a.stats()["kv_persisted_blocks"] == 3

        b = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store, kv_persist_sig="w1",
        ).start()
        try:
            assert b.wait_ready(timeout=60)
            assert b.stats()["kv_preloaded_blocks"] == 3
            assert len(b.prefix_cache) == 3
            assert b.submit(p, 6).wait(timeout=120) == ref
            # The preloaded entries carried the hit — the whole prompt
            # walk matched without recomputing a single prefix block.
            assert b.prefix_cache.hits >= 3
        finally:
            b.stop()

    def test_signature_mismatch_boots_cold(self, params, tmp_path):
        store = tmp_path / "kv"
        rng = np.random.default_rng(12)
        p = list(rng.integers(0, 64, 8))
        a = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store, kv_persist_sig="w1",
        ).start()
        try:
            a.submit(p, 4).wait(timeout=120)
        finally:
            a.stop()
        b = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store, kv_persist_sig="w2",
        ).start()
        try:
            assert b.wait_ready(timeout=60)
            assert b.stats()["kv_preloaded_blocks"] == 0
            assert len(b.prefix_cache) == 0
            # Cold but correct.
            assert b.submit(p, 4).wait(timeout=120) == _ref(params, p, 4)
        finally:
            b.stop()

    def test_demoted_entries_persist_from_host_payloads(
        self, params, tmp_path, monkeypatch
    ):
        """Entries already demoted to the host tier persist straight
        from their host payloads (no device traffic), and a warm-booted
        engine serves them token-identically."""
        # This test evicts and snapshots from the TEST thread; keep the
        # scheduler's own idle-time snapshot out of the way (it races the
        # explicit one for the store's next version and made this flake).
        monkeypatch.setenv("POLYAXON_TPU_KV_PERSIST_INTERVAL_S", "1e9")
        rng = np.random.default_rng(13)
        p = list(rng.integers(0, 64, 8))  # 2 full blocks
        ref = _ref(params, p, 4)
        store = tmp_path / "kv"
        a = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_offload=True,
            kv_persist_dir=store, kv_persist_sig="w1",
        ).start()
        try:
            assert a.submit(p, 4).wait(timeout=120) == ref
            assert a.prefix_cache.evict(need=2) == 2  # demote both
            assert a.prefix_cache.n_demoted == 2
            # Explicit snapshot with both entries demoted: the payloads
            # come out of the host tier, not the device pool.
            assert a.persist_prefixes() == 2
        finally:
            a.stop()
        assert a.stats()["kv_persisted_blocks"] == 2

        b = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store, kv_persist_sig="w1",
        ).start()
        try:
            assert b.wait_ready(timeout=60)
            assert b.stats()["kv_preloaded_blocks"] == 2
            assert b.submit(p, 4).wait(timeout=120) == ref
            assert b.prefix_cache.hits >= 2
        finally:
            b.stop()

    def test_bfloat16_pool_boots_warm(self, tmp_path):
        """End-to-end warm boot on a bfloat16 pool — the layout every
        TPU deployment uses.  Caught in a verify drive: bf16 leaves came
        back from npz as void arrays, preload raised inside the
        best-effort warmup guard, and every bf16 replica silently booted
        cold."""
        cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
        params = init_params(KEY, cfg)
        rng = np.random.default_rng(15)
        p = list(rng.integers(0, 64, 8))  # 2 full blocks
        store = tmp_path / "kv"
        a = ServingEngine(
            params, cfg, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store, kv_persist_sig="w1",
        ).start()
        try:
            ref = a.submit(p, 4).wait(timeout=120)
        finally:
            a.stop()
        assert a.stats()["kv_persisted_blocks"] == 2

        b = ServingEngine(
            params, cfg, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store, kv_persist_sig="w1",
        ).start()
        try:
            assert b.wait_ready(timeout=60)
            assert b.stats()["kv_preloaded_blocks"] == 2
            assert b.submit(p, 4).wait(timeout=120) == ref
            assert b.prefix_cache.hits >= 2
        finally:
            b.stop()

    def test_preload_never_takes_more_than_half_the_pool(
        self, params, tmp_path
    ):
        """A snapshot bigger than the pool must not gridlock a booting
        replica: preload stops at half the usable blocks and leaves the
        rest for live admissions."""
        rng = np.random.default_rng(14)
        store = tmp_path / "kv"
        a = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store, kv_persist_sig="w1",
        ).start()
        try:
            for _ in range(3):
                p = list(rng.integers(0, 64, 16))  # 4 full blocks each
                a.submit(p, 4).wait(timeout=120)
        finally:
            a.stop()
        assert a.stats()["kv_persisted_blocks"] >= 8

        b = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            num_blocks=9, prefix_cache=True,
            kv_persist_dir=store, kv_persist_sig="w1",
        ).start()
        try:
            assert b.wait_ready(timeout=60)
            assert b.stats()["kv_preloaded_blocks"] <= 4  # (9 - 1) // 2
            assert b.block_allocator.n_free >= 4
            p = list(rng.integers(0, 64, 8))
            assert b.submit(p, 4).wait(timeout=120) == _ref(params, p, 4)
        finally:
            b.stop()


class TestPersistFreshness:
    def test_content_churn_at_constant_size_republishes(
        self, params, tmp_path
    ):
        """The persist change-detector keys off the cache's mutation
        counter, not len(): replacing every entry with a DIFFERENT
        prefix at the same size must publish a new snapshot (a len()
        check leaves scale-up replicas preloading stale prefixes), and
        no churn at all must publish nothing."""
        rng = np.random.default_rng(20)
        p1 = list(rng.integers(0, 64, 8))  # 2 full blocks
        store = tmp_path / "kv"
        a = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store, kv_persist_sig="w1",
        ).start()
        try:
            a.submit(p1, 4).wait(timeout=120)
        finally:
            a.stop()  # final persist -> v1
        assert kvstore.latest_complete_version(store) == 1
        pc = a.prefix_cache
        # Unchanged cache: a forced pass must not write v2.
        a._maybe_persist(force=True)
        assert kvstore.latest_complete_version(store) == 1
        # Same size, different content (the len()-blind case).
        assert pc.evict(need=2, demote=False) == 2
        p2 = list(rng.integers(0, 64, 8))
        blocks = [a.block_allocator.alloc() for _ in range(2)]
        a.prefix_cache.offer(p2, blocks)
        assert len(pc) == 2
        a._maybe_persist(force=True)
        assert kvstore.latest_complete_version(store) == 2


class TestAutoSignature:
    def test_unsigned_store_derives_weight_fingerprint(
        self, params, tmp_path
    ):
        """kv_persist_dir without kv_persist_sig: the engine derives a
        weight fingerprint instead of persisting unsigned, and a second
        engine on the SAME weights derives the same sig — warm boot
        still works without threading an explicit identity."""
        store = tmp_path / "kv"
        rng = np.random.default_rng(21)
        p = list(rng.integers(0, 64, 8))  # 2 full blocks
        a = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store,
        ).start()
        try:
            assert a.kv_persist_sig.startswith("auto:")
            ref = a.submit(p, 4).wait(timeout=120)
        finally:
            a.stop()
        assert a.stats()["kv_persisted_blocks"] == 2

        b = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store,
        ).start()
        try:
            assert b.kv_persist_sig == a.kv_persist_sig
            assert b.wait_ready(timeout=60)
            assert b.stats()["kv_preloaded_blocks"] == 2
            assert b.submit(p, 4).wait(timeout=120) == ref
        finally:
            b.stop()

    def test_fingerprint_is_of_the_tree_handed_in_not_of_the_cast_one(
        self, params, tmp_path
    ):
        """At bfloat16 compute the engine holds a rounded tree, but the
        fingerprint stays the float32 weights': a store written by an
        engine that still read the float32 tree opens."""
        cfg = CFG.scaled(dtype=jnp.bfloat16)
        eng = ServingEngine(
            params, cfg, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=tmp_path / "kv", seed=3,
        )
        try:
            assert eng.weight_dtype == "bfloat16"
            assert eng.kv_persist_sig == ServingEngine._auto_persist_sig(
                params, None, 3
            )
            assert eng.kv_persist_sig != ServingEngine._auto_persist_sig(
                eng._params, None, 3
            )
        finally:
            eng.stop()

    def test_different_weights_never_share_an_unsigned_store(
        self, params, tmp_path
    ):
        """The bug the auto-sig closes: two unsigned replicas serving
        DIFFERENT weights used to produce identical fingerprints
        (geometry + dtype can't tell checkpoints apart) and exchange KV
        through a shared store.  Different weights must derive different
        sigs and boot cold off each other's snapshots."""
        store = tmp_path / "kv"
        rng = np.random.default_rng(22)
        p = list(rng.integers(0, 64, 8))
        a = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store,
        ).start()
        try:
            a.submit(p, 4).wait(timeout=120)
        finally:
            a.stop()
        assert a.stats()["kv_persisted_blocks"] == 2

        other = init_params(jax.random.PRNGKey(5), CFG)
        b = ServingEngine(
            other, CFG, slots=2, max_len=48, block_size=4,
            prefix_cache=True, kv_persist_dir=store,
        ).start()
        try:
            assert b.kv_persist_sig != a.kv_persist_sig
            assert b.wait_ready(timeout=60)
            assert b.stats()["kv_preloaded_blocks"] == 0
            assert len(b.prefix_cache) == 0
            # Cold but correct under ITS OWN weights.
            assert b.submit(p, 4).wait(timeout=120) == _ref(other, p, 4)
        finally:
            b.stop()


class TestFleetThreading:
    def test_replica_specs_carry_warm_boot_config(self, tmp_path):
        """Every replica the fleet launches — including autoscaler
        scale-ups, which re-enter launch_replica — gets the kv_offload /
        kv_persist config in its spec file."""
        from polyaxon_tpu.serving.fleet import LocalServingFleet

        class _FakeRef:
            def signal(self, sig):
                pass

            def wait(self, timeout=None):
                return 0

            def poll(self):
                return None

        class _FakeTransport:
            def launch(self, host, argv, env, **kwargs):
                return _FakeRef()

        fleet = LocalServingFleet(
            tmp_path, {"vocab_size": 64, "d_model": 32},
            replicas=1, kv_offload=True, kv_offload_blocks=32,
            kv_persist_dir=str(tmp_path / "kv"), kv_persist_sig="w1",
        )
        fleet.transport = _FakeTransport()
        name = fleet.launch_replica()
        scale_up = fleet.scale_up()
        for n in (name, scale_up):
            spec = json.loads((tmp_path / f"{n}.json").read_text())
            assert spec["kv_offload"] is True
            assert spec["kv_offload_blocks"] == 32
            assert spec["kv_persist_dir"] == str(tmp_path / "kv")
            assert spec["kv_persist_sig"] == "w1"

    def test_kv_cache_store_sync_roundtrip(self, tmp_path):
        """The store-layout leg: kv_cache/ syncs up to the artifact
        store and back down onto a fresh layout, snapshot markers
        included — how a warm store follows a fleet across hosts."""
        from polyaxon_tpu.stores.artifacts import (
            LocalArtifactStore,
            sync_kv_cache_down,
            sync_kv_cache_up,
        )
        from polyaxon_tpu.stores.layout import StoreLayout

        src = StoreLayout(tmp_path / "src")
        kvstore.save_prefix_store(
            src.kv_cache_dir, _entries(2), meta=META
        )
        store = LocalArtifactStore(tmp_path / "bucket")
        assert sync_kv_cache_up(store, src) >= 3  # npz + meta + marker

        dst = StoreLayout(tmp_path / "dst")
        assert sync_kv_cache_down(store, dst) >= 3
        loaded = kvstore.load_prefix_store(dst.kv_cache_dir, expect=META)
        assert loaded is not None and len(loaded) == 2
