"""Paged KV cache: block allocator, prefix sharing, chunked prefill.

The acceptance bar carried over from the slot engine, now with paging:
GREEDY outputs through the shared block pool are token-identical to
sequential ``generate()`` calls — with prefix sharing and chunked
prefill ENABLED — while the step function compiles exactly once and no
prefill bucket re-compiles after warmup.  Plus the block-level edge
cases: pool exhaustion parks and resumes without recompiling,
copy-on-write keeps shared prefixes immutable, and ref-counts
round-trip under admit/retire churn.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import TransformerConfig, decode, init_params
from polyaxon_tpu.serving import (
    BlockAllocator,
    HostKVTier,
    PrefixCache,
    ServingEngine,
)
from polyaxon_tpu.serving.paging import StateSnapshots

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


def _total_compiles(eng):
    """Step + every prefill-chunk bucket + the COW copy fn."""
    n = eng._step_fn._cache_size()
    for fn in eng._chunk_fns.values():
        n += fn._cache_size()
    if eng._copy_fn is not None:
        n += eng._copy_fn._cache_size()
    return n


class TestBlockAllocator:
    def test_alloc_order_and_exhaustion(self):
        a = BlockAllocator(4)  # block 0 reserved: 3 usable
        assert [a.alloc() for _ in range(3)] == [1, 2, 3]
        assert a.alloc() is None
        assert a.n_free == 0 and a.n_used == 3
        a.decref(2)
        assert a.n_free == 1
        assert a.alloc() == 2  # FIFO reuse

    def test_refcount_roundtrip(self):
        a = BlockAllocator(3)
        b = a.alloc()
        a.incref(b)
        a.incref(b)
        assert a.refcount(b) == 3
        assert a.decref(b) is False
        assert a.decref(b) is False
        assert a.refcount(b) == 1
        assert a.decref(b) is True  # last holder frees
        assert a.refcount(b) == 0
        assert a.n_free == 2

    def test_over_decref_and_foreign_blocks_are_loud(self):
        a = BlockAllocator(3)
        b = a.alloc()
        a.decref(b)
        with pytest.raises(ValueError, match="not allocated"):
            a.decref(b)
        with pytest.raises(ValueError, match="not allocated"):
            a.incref(2)  # never allocated
        with pytest.raises(ValueError, match="not allocated"):
            a.decref(0)  # the trash block is never allocated

    def test_too_small_pool_rejected(self):
        with pytest.raises(ValueError, match="at least 2"):
            BlockAllocator(1)


class TestPrefixCache:
    def _cache(self, num_blocks=8, block_size=4):
        alloc = BlockAllocator(num_blocks)
        return alloc, PrefixCache(alloc, block_size)

    def test_offer_then_match_increfs(self):
        alloc, pc = self._cache()
        prompt = list(range(8))  # two full blocks
        blocks = [alloc.alloc(), alloc.alloc()]
        pc.offer(prompt, blocks)
        assert alloc.refcount(blocks[0]) == 2  # ours + the cache's
        got = pc.match(prompt)
        assert got == blocks
        assert alloc.refcount(blocks[0]) == 3  # match took one for us
        assert pc.hits == 2 and pc.lookups == 2

    def test_match_stops_at_divergence(self):
        alloc, pc = self._cache()
        prompt = list(range(8))
        pc.offer(prompt, [alloc.alloc(), alloc.alloc()])
        other = prompt[:4] + [63, 62, 61, 60]
        got = pc.match(other)
        assert len(got) == 1  # first block shared, second diverges
        # A matching first block with different SECOND block contents
        # must not hit block two: keys chain over the whole prefix.
        assert pc.match([9] + prompt[1:]) == []

    def test_partial_blocks_never_cached(self):
        alloc, pc = self._cache(block_size=4)
        prompt = list(range(6))  # one full block + 2 leftover tokens
        pc.offer(prompt, [alloc.alloc()])
        assert len(pc) == 1
        assert len(pc.match(prompt)) == 1

    def test_evict_skips_blocks_still_referenced(self):
        alloc, pc = self._cache()
        p1, p2 = list(range(4)), list(range(10, 14))
        b1, b2 = alloc.alloc(), alloc.alloc()
        pc.offer(p1, [b1])
        pc.offer(p2, [b2])
        # b1 is still held by its "request"; b2's only ref is the cache's
        # after we drop ours.
        alloc.decref(b2)
        assert pc.evict(need=2) == 1  # only b2 is reclaimable
        assert pc.match(p2) == []
        assert pc.match(p1) == [b1]

    def test_mutation_counter_sees_churn_at_constant_size(self):
        """len() is blind to evict+offer of DIFFERENT prefixes at the
        same size; the mutation counter is what persistence freshness
        keys off, so it must move on content changes and hold still on
        pure hits."""
        alloc, pc = self._cache()
        p1, p2 = list(range(4)), list(range(10, 14))
        b1 = alloc.alloc()
        pc.offer(p1, [b1])
        alloc.decref(b1)
        m0 = pc.mutations
        assert m0 >= 1
        assert pc.evict(1) == 1
        b2 = alloc.alloc()
        pc.offer(p2, [b2])
        assert len(pc) == 1  # same size, different content...
        assert pc.mutations > m0  # ...and the counter knows
        m1 = pc.mutations
        pc.match(p2)  # a pure hit changes nothing persistable
        assert pc.mutations == m1


def _rule_victims(pc, alloc, need):
    """The plain reference, one block at a time: each freed block is that
    of the FIRST entry in the map's order whose block is on the device and
    held by the cache alone.  Whatever walk ``evict`` makes (ROADMAP S2:
    in place, a chunk's worth a call) has to free these, in this order."""
    gone, blocks = set(), []
    for _ in range(need):
        for key, (block, _) in list(pc._entries.items()):
            if key not in gone and block >= 0 and alloc.refcount(block) == 1:
                gone.add(key)
                blocks.append(block)
                break
        else:
            break
    return blocks


@functools.lru_cache(maxsize=None)
def _churn(variant, batched):
    """A seeded run of offer / match / decref / evict over one cache; an
    eviction of ``k`` blocks is ONE ``evict(k)`` (``batched``) or ``k``
    times ``evict(1)``.  Every eviction is held to :func:`_rule_victims`;
    returns what the run left after each operation."""
    bs = 4
    rng = np.random.default_rng(33)
    alloc = BlockAllocator(161)
    snaps = StateSnapshots(12) if variant == "snapshots" else None
    pc = PrefixCache(alloc, bs, snaps)
    tier = None
    log = []
    dropped = [0]  # snapshots that went because their entry did
    if snaps is not None:
        drop = snaps.drop

        def counting_drop(key):
            dropped[0] += key in snaps._by_key
            drop(key)

        snaps.drop = counting_drop

    def evict(k):
        expected = _rule_victims(pc, alloc, k)
        free0 = len(alloc._free)
        if batched:
            got = pc.evict(k)
        else:
            got = 0
            while got < k and pc.evict(1):
                got += 1
        assert list(alloc._free)[free0:] == expected  # victims, in order
        assert got == len(expected)
        return got

    if variant == "tier":
        # Three payloads: nearly every spill drops an older one, whose
        # on_drop deletes that entry while the eviction is still running.
        tier = HostKVTier(capacity_blocks=3)

        def alloc_retry():  # the engine's _alloc_block
            block = alloc.alloc()
            if block is None and pc.evict(1):
                block = alloc.alloc()
            return block

        pc.attach_tier(
            tier,
            spill=lambda block: tier.put({"block": block}),
            restore=lambda handle, block: tier.pop(handle),
            alloc=alloc_retry,
        )
    docs = [list(rng.integers(0, 50, int(n) * bs)) for n in rng.integers(6, 40, 10)]
    live = []
    for _ in range(400):
        op = rng.choice(["admit", "admit", "retire", "evict"])
        if op == "admit":
            doc = docs[int(rng.integers(len(docs)))]
            cut = int(rng.integers(1, len(doc) // bs + 1)) * bs
            prompt = doc[:cut] + list(rng.integers(50, 64, int(rng.integers(0, 12))))
            if snaps is not None:
                blocks, _ = pc.match_with_state(prompt)
            else:
                blocks = pc.match(prompt)
            want = len(prompt) // bs - len(blocks)
            if alloc.n_free < want:
                evict(want - alloc.n_free)
            if alloc.n_free < want:
                for block in blocks:
                    alloc.decref(block)
            else:
                blocks = blocks + [alloc.alloc() for _ in range(want)]
                pending = None
                if snaps is not None and len(blocks) >= 2:
                    pending = {len(blocks) // 2 * bs: snaps.alloc()}
                    pending = {p: i for p, i in pending.items() if i is not None}
                pc.offer(prompt, blocks, pending)
                live.append(blocks)
        elif op == "retire" and live:
            for block in live.pop(int(rng.integers(len(live)))):
                alloc.decref(block)
        elif op == "evict":
            evict(int(rng.integers(1, 65)))
        if snaps is not None:  # a dropped entry's snapshot went with it
            assert set(snaps._by_key) <= set(pc._entries)
        log.append((pc.hits, pc.lookups, pc.evictions, pc.demotions, len(pc),
                    pc.n_demoted, alloc.n_free, snaps.used if snaps else 0,
                    tier.dropped_total if tier else 0))
    assert pc.evictions + pc.demotions > 300, "the run hardly evicted"
    if tier is not None:
        assert tier.dropped_total > 50, "no capacity drop in mid-walk"
    if snaps is not None:
        assert dropped[0] > 10, "no snapshot went with its entry"
    return log


class TestEvictionOrderAndChains:
    """What ``evict`` frees, in what order, whatever ``need`` is asked at a
    time; and what an entry remembers of its prompt."""

    @pytest.mark.parametrize("batched", [False, True], ids=["k=1-repeated", "k-up-to-64"])
    @pytest.mark.parametrize("variant", ["plain", "snapshots", "tier"])
    def test_victims_are_the_one_by_one_rules(self, variant, batched):
        log = _churn(variant, batched)
        assert log == _churn(variant, not batched)

    def test_a_victim_lost_while_the_call_runs_is_skipped_and_made_up_for(self):
        """Every entry is looked at when the call reaches it, not when it
        started: here the first spill takes a reference on the next
        victim's block, so the call leaves it and frees the one after."""
        alloc = BlockAllocator(8)
        pc = PrefixCache(alloc, 2)
        blocks = [alloc.alloc() for _ in range(4)]
        for i, block in enumerate(blocks):
            pc.offer([i, i], [block])
            alloc.decref(block)
        tier = HostKVTier()

        def spill(block):
            if block == blocks[0]:
                alloc.incref(blocks[1])
            return tier.put({"block": block})

        pc.attach_tier(tier, spill=spill, restore=lambda h, b: tier.pop(h),
                       alloc=alloc.alloc)
        free0 = alloc.n_free
        assert pc.evict(2) == 2
        assert list(alloc._free)[free0:] == [blocks[0], blocks[2]]
        assert pc.demotions == 2 and alloc.refcount(blocks[1]) == 2

    @pytest.mark.parametrize("then", ["offer", "install", "evict-mid-chain"])
    def test_a_prompts_entries_share_one_token_tuple(self, then):
        """``hottest_chains`` returns what the parent's did: every entry's
        FULL prefix, ancestors first, cut from the one stored tuple."""
        bs, n = 4, 1000
        alloc = BlockAllocator(n + 8)
        pc = PrefixCache(alloc, bs)
        rng = np.random.default_rng(7)
        prompt = [int(t) for t in rng.integers(0, 64, n * bs + 3)]
        blocks = [alloc.alloc() for _ in range(n)]
        pc.offer(prompt, blocks)
        assert len(pc._chains) == n
        assert len({id(tokens) for tokens, _ in pc._chains.values()}) == 1
        want = [(tuple(prompt[: (i + 1) * bs]), blocks[i], None) for i in range(n)]
        if then == "install":
            longer = prompt[: n * bs] + [1, 2, 3, 4]
            fresh = alloc.alloc()
            assert pc.install(longer, fresh)
            want.append((tuple(longer), fresh, None))
        elif then == "evict-mid-chain":
            alloc.decref(blocks[500])  # the only block the cache holds alone
            assert pc.evict(8) == 1
            del want[500]
        assert pc.hottest_chains(2 * n) == want
        assert pc.hottest_chains(10) == want[:10]


class TestPagedParity:
    def test_greedy_parity_with_sharing_and_chunking_zero_recompiles(
        self, params
    ):
        """The acceptance test: prefix sharing ON, chunked prefill ON
        (chunk deliberately not block-aligned), mixed lengths including
        shared prefixes and an exact-duplicate prompt (the COW path) —
        every output token-identical to sequential ``generate()``, and
        the SECOND wave mints zero new XLA compilations."""
        rng = np.random.default_rng(21)
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48,
            block_size=8, prefill_chunk=5, prefix_cache=True,
        ).start()
        try:
            sys_prefix = list(rng.integers(0, 64, 16))  # two full blocks
            dup = list(rng.integers(0, 64, 16))  # block-aligned: COW bait
            wave1 = [
                (sys_prefix + list(rng.integers(0, 64, 7)), 6),
                (sys_prefix + list(rng.integers(0, 64, 3)), 4),
                (dup, 5),
                (dup, 5),  # full-block hit -> copy-on-write
                (list(rng.integers(0, 64, 12)), 8),
            ]
            for prompt, mn in wave1:
                assert eng.submit(prompt, mn).wait(timeout=120) == _ref(
                    params, prompt, mn
                ), "wave1"
            warm = _total_compiles(eng)
            assert eng._step_fn._cache_size() == 1
            assert eng.stats()["cow_copies"] >= 1
            wave2 = [
                (sys_prefix + list(rng.integers(0, 64, 9)), 7),
                (dup, 5),
                (list(rng.integers(0, 64, 11)), 6),
                (sys_prefix + list(rng.integers(0, 64, 2)), 3),
            ]
            reqs = [eng.submit(p, mn) for p, mn in wave2]
            outs = [r.wait(timeout=120) for r in reqs]
            for (prompt, mn), out in zip(wave2, outs):
                assert out == _ref(params, prompt, mn), "wave2"
            assert _total_compiles(eng) == warm, (
                "steady-state serving must not mint new compilations"
            )
            s = eng.stats()
            assert s["prefix_cache_hit_rate"] > 0
            assert s["prefix_cache_blocks"] >= 2
        finally:
            eng.stop()

    def test_cow_leaves_shared_prefix_intact(self, params):
        """After a full-hit COW and the copier's own generation, the
        ORIGINAL prompt must still match (and still hit the cache): the
        shared blocks were never written through."""
        rng = np.random.default_rng(22)
        prompt = list(rng.integers(0, 64, 16))  # exactly two 8-blocks
        ref = _ref(params, prompt, 6)
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48,
            block_size=8, prefix_cache=True,
        ).start()
        try:
            assert eng.submit(prompt, 6).wait(timeout=120) == ref
            assert eng.submit(prompt, 6).wait(timeout=120) == ref  # COW
            assert eng.stats()["cow_copies"] >= 1
            hits_before = eng.prefix_cache.hits
            assert eng.submit(prompt, 6).wait(timeout=120) == ref
            assert eng.prefix_cache.hits > hits_before
        finally:
            eng.stop()

    def test_divergent_prompts_share_only_common_blocks(self, params):
        rng = np.random.default_rng(23)
        head = list(rng.integers(0, 64, 8))  # one full 8-block
        a = head + list(rng.integers(0, 64, 5))
        b = head + list(rng.integers(0, 64, 9))
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48,
            block_size=8, prefix_cache=True,
        ).start()
        try:
            assert eng.submit(a, 6).wait(timeout=120) == _ref(params, a, 6)
            assert eng.submit(b, 6).wait(timeout=120) == _ref(params, b, 6)
            assert eng.prefix_cache.hits >= 1  # b reused head's block
            # and a again, to prove b's divergence didn't corrupt it
            assert eng.submit(a, 4).wait(timeout=120) == _ref(params, a, 4)
        finally:
            eng.stop()


class TestPoolExhaustion:
    def test_park_and_resume_without_recompile(self, params):
        """A pool too small for both requests' full spans: one parks at a
        block boundary mid-decode, resumes when its neighbor retires, and
        BOTH finish token-identical to generate() with the step still
        compiled exactly once."""
        rng = np.random.default_rng(24)
        pa = list(rng.integers(0, 64, 24))  # 6 blocks of prompt
        pb = list(rng.integers(0, 64, 4))
        # Spans: A writes through pos 30 -> 8 blocks; B writes through
        # pos 6 -> 2 blocks.  The shortest-remaining-first scheduler
        # prefills B first (1 block); A's prefill then takes 6 and B's
        # first boundary fault the 8th, so A's own decode fault comes up
        # empty-handed -> A parks with all its state.  B finishes on the
        # 2 blocks it holds, retirement frees them, A resumes.
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48,
            block_size=4, num_blocks=9, prefix_cache=False,
        ).start()
        try:
            ra = eng.submit(pa, 8)
            rb = eng.submit(pb, 4)
            assert ra.wait(timeout=120) == _ref(params, pa, 8)
            assert rb.wait(timeout=120) == _ref(params, pb, 4)
            s = eng.stats()
            assert s["block_parks"] >= 1, "pool pressure never parked"
            assert eng._step_fn._cache_size() == 1
            # Everything released on retirement.
            assert s["blocks_free"] == s["blocks_total"]
        finally:
            eng.stop()

    def test_true_deadlock_sheds_one_request_not_all(self, params):
        """Two requests whose combined spans can never fit and who both
        park: the engine sheds ONE (typed pool-exhausted error) instead
        of hanging, and the survivor completes token-identically."""
        rng = np.random.default_rng(30)
        pa = list(rng.integers(0, 64, 4))
        pb = list(rng.integers(0, 64, 4))
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48,
            block_size=4, num_blocks=9, prefix_cache=False,
        ).start()
        try:
            ra = eng.submit(pa, 24)  # 7 blocks
            rb = eng.submit(pb, 24)  # 7 blocks; 14 > 8 usable
            results = []
            for req, prompt in ((ra, pa), (rb, pb)):
                try:
                    results.append((req.wait(timeout=120), prompt))
                except RuntimeError as e:
                    assert "pool exhausted" in str(e)
            assert len(results) == 1, "exactly one request is shed"
            out, prompt = results[0]
            assert out == _ref(params, prompt, 24)
        finally:
            eng.stop()

    def test_oversized_request_rejected_up_front(self, params):
        eng = ServingEngine(
            params, CFG, slots=1, max_len=48, block_size=4, num_blocks=4
        )
        with pytest.raises(ValueError, match="KV blocks"):
            eng.submit([1] * 20, 10)
        eng.stop()


class TestRefcountChurn:
    def test_admit_retire_churn_returns_every_block(self, params):
        """Waves of shared-prefix traffic: after all retire, the only
        live references are the prefix cache's own (refcount exactly 1
        per cached entry) and free+used == total."""
        rng = np.random.default_rng(25)
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48,
            block_size=8, prefix_cache=True,
        ).start()
        try:
            head = list(rng.integers(0, 64, 8))
            for _ in range(3):
                reqs = [
                    eng.submit(head + list(rng.integers(0, 64, k)), 3)
                    for k in (2, 5, 7)
                ]
                [r.wait(timeout=120) for r in reqs]
            s = eng.stats()
            assert s["blocks_free"] + s["block_size"] >= 0  # shape sanity
            alloc = eng.block_allocator
            assert alloc.n_used == len(eng.prefix_cache)
            for block, _ in eng.prefix_cache._entries.values():
                assert alloc.refcount(block) == 1
            # Dropping the cache frees the pool completely.
            eng.prefix_cache.drop_all()
            assert alloc.n_used == 0
            assert alloc.n_free == alloc.num_blocks - 1
        finally:
            eng.stop()


class TestCancellation:
    def test_cancel_queued_request(self, params):
        eng = ServingEngine(params, CFG, slots=1, max_len=48).start()
        try:
            first = eng.submit([1, 2, 3], 30)
            queued = eng.submit([4, 5, 6], 30)
            assert eng.cancel(queued.id) is True
            with pytest.raises(RuntimeError, match="cancelled"):
                queued.wait(timeout=10)
            assert first.wait(timeout=120)  # neighbor unaffected
            assert eng.stats()["requests_cancelled"] == 1
        finally:
            eng.stop()

    def test_cancel_inflight_frees_slot_and_blocks(self, params):
        eng = ServingEngine(params, CFG, slots=1, max_len=48).start()
        try:
            req = eng.submit([1, 2, 3, 4], 40)
            assert req.stream.get(timeout=60) is not None  # decoding now
            assert eng.cancel(req.id) is True
            with pytest.raises(RuntimeError, match="cancelled"):
                req.wait(timeout=30)
            deadline = time.time() + 30
            while time.time() < deadline:
                s = eng.stats()
                if s["slots_active"] == 0 and s["blocks_free"] == s["blocks_total"]:
                    break
                time.sleep(0.05)
            s = eng.stats()
            assert s["slots_active"] == 0
            assert s["blocks_free"] == s["blocks_total"]
            # The freed slot is immediately serviceable.
            out = eng.submit([7, 8], 3).wait(timeout=60)
            assert out == _ref(params, [7, 8], 3)
        finally:
            eng.stop()

    def test_cancel_unknown_or_finished_returns_false(self, params):
        eng = ServingEngine(params, CFG, slots=1, max_len=48).start()
        try:
            req = eng.submit([1, 2], 2)
            req.wait(timeout=60)
            assert eng.cancel(req.id) is False
            assert eng.cancel(10**9) is False
        finally:
            eng.stop()


class TestStopDrain:
    def test_stop_with_inflight_drains_deterministically(self, params):
        """Regression for the shutdown audit: stop() mid-flight must hand
        EVERY unfinished request exactly one None sentinel and an error —
        actively-decoding, queued, and mid-prefill alike — so no client
        thread is left blocked on ``stream.get()``."""
        eng = ServingEngine(params, CFG, slots=1, max_len=48).start()
        active = eng.submit([1, 2, 3], 40)
        queued = [eng.submit([4, 5, 6], 40) for _ in range(2)]
        assert active.stream.get(timeout=60) is not None  # mid-flight now
        eng.stop()
        for req in [active] + queued:
            assert req.done.is_set()
            assert req.error == "engine stopped"
            sentinels, tokens = 0, 0
            while not req.stream.empty():
                item = req.stream.get_nowait()
                if item is None:
                    sentinels += 1
                else:
                    tokens += 1
            assert sentinels == 1, "exactly one None sentinel per request"
            # wait() reports the failure instead of hanging.
            with pytest.raises(RuntimeError, match="stopped"):
                req.wait(timeout=5)

    def test_stop_mid_prefill_drains_chunk_queue(self, params):
        """A request still in the prefill-chunk queue at stop() time gets
        the same sentinel treatment (it sits in both _slot_req and the
        job deque — it must be failed exactly once)."""
        rng = np.random.default_rng(26)
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48, prefill_chunk=2
        ).start()
        reqs = [
            eng.submit(list(rng.integers(0, 64, 40)), 4) for _ in range(3)
        ]
        eng.stop()
        for req in reqs:
            assert req.done.is_set()
            sentinels = 0
            while not req.stream.empty():
                if req.stream.get_nowait() is None:
                    sentinels += 1
            assert sentinels == 1


class TestChunkedPrefill:
    def test_chunked_prefill_interleaves_with_decode(self, params):
        """While a LONG prompt prefills in chunks, an already-active
        short request keeps emitting tokens — its stream must deliver
        tokens before the long prompt's first token arrives."""
        rng = np.random.default_rng(27)
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48,
            prefill_chunk=2, prefix_cache=False,
        ).start()
        try:
            short = eng.submit(list(rng.integers(0, 64, 3)), 20)
            assert short.stream.get(timeout=60) is not None  # decoding
            long_prompt = list(rng.integers(0, 64, 40))  # 20 chunks
            longr = eng.submit(long_prompt, 4)
            got_short_during_long_prefill = 0
            while True:
                try:
                    tok = short.stream.get(timeout=60)
                except Exception:
                    break
                if tok is None:
                    break
                if not longr.tokens:
                    got_short_during_long_prefill += 1
            assert got_short_during_long_prefill >= 1, (
                "chunked prefill must not stall the active decode batch"
            )
            assert longr.wait(timeout=120) == _ref(params, long_prompt, 4)
            assert short.tokens == _ref(params, short.prompt, 20)
        finally:
            eng.stop()


class TestLoadHarnessFast:
    def test_poisson_load_smoke(self, params):
        """Tier-1 fast variant of the bench harness: a handful of
        requests at an aggressive rate, every metric key present and
        every request completed."""
        from polyaxon_tpu.serving.loadgen import poisson_load

        rng = np.random.default_rng(28)
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48, prefill_chunk=4
        ).start()
        try:
            prompts = [list(rng.integers(0, 64, k)) for k in (3, 9, 5, 12)]
            res = poisson_load(
                eng, prompts, 4, rate_rps=50.0, seed=3, timeout_s=120
            )
        finally:
            eng.stop()
        assert res["n_requests"] == 4
        assert res["completed"] == 4
        assert res["errors"] == 0
        assert res["total_tokens"] == 16
        assert res["ttft_p99_s"] > 0
        assert res["ttft_p50_s"] <= res["ttft_p99_s"]
        assert {"tokens_per_s", "wall_s", "offered_rps"} <= set(res)

    def test_poisson_load_rejects_bad_rate(self, params):
        from polyaxon_tpu.serving.loadgen import poisson_load

        eng = ServingEngine(params, CFG, slots=1, max_len=48)
        with pytest.raises(ValueError, match="rate_rps"):
            poisson_load(eng, [[1, 2]], 2, rate_rps=0.0)
        eng.stop()


@pytest.mark.slow
class TestLoadHarnessSlow:
    def test_chunked_vs_full_prefill_under_identical_load(self, params):
        """The bench A/B as a test: the SAME Poisson schedule offered to
        a chunked and an unchunked engine; both complete everything.
        (The directional TTFT claim is asserted in bench.py where the
        offered load is calibrated; here we assert correctness under
        load, not the magnitude.)"""
        from polyaxon_tpu.serving.loadgen import poisson_load

        rng = np.random.default_rng(29)
        prompts = []
        for i in range(12):
            k = 40 if i % 4 == 3 else int(rng.integers(3, 12))
            prompts.append(list(rng.integers(0, 64, k)))

        def run(chunk):
            eng = ServingEngine(
                params, CFG, slots=2, max_len=48,
                prefill_chunk=chunk, prefix_cache=False,
            ).start()
            try:
                return poisson_load(
                    eng, prompts, 6, rate_rps=4.0, seed=5, timeout_s=300
                )
            finally:
                eng.stop()

        full = run(None)
        chunked = run(4)
        for res in (full, chunked):
            assert res["completed"] == len(prompts)
            assert res["errors"] == 0
            assert res["ttft_p99_s"] > 0


class TestQuantizedPool:
    """``kv_quantize="int8"``: the HBM claim (pool leaves under 0.55× the
    f32 pool at equal blocks), greedy parity within tolerance, and every
    paging behaviour — prefix hit, COW, park/resume — on quantized
    leaves.  Quantized decode is NOT bit-identical to the f32 pool (each
    appended KV row rounds to int8 once), so parity asserts a token
    agreement fraction instead of equality."""

    def test_pool_bytes_at_most_055x_f32(self):
        f32 = decode.init_block_pool(CFG, 13, 4)
        q = decode.init_block_pool(CFG, 13, 4, kv_dtype="int8")
        fb = sum(x.nbytes for x in jax.tree_util.tree_leaves(f32))
        qb = sum(x.nbytes for x in jax.tree_util.tree_leaves(q))
        assert qb <= 0.55 * fb
        assert q["k_q"].dtype == jnp.int8
        assert q["k_scale"].dtype == jnp.float32
        # The sizing helper agrees with the real leaves — it's what the
        # bench's fixed-HBM A/B uses to pick the block counts.
        assert decode.kv_block_bytes(CFG, 4) * 13 == fb
        assert decode.kv_block_bytes(CFG, 4, "int8") * 13 == qb

    def test_bad_kv_dtype_rejected(self, params):
        with pytest.raises(ValueError, match="kv_dtype"):
            decode.init_block_pool(CFG, 4, 4, kv_dtype="fp8")
        with pytest.raises(ValueError, match="kv_quantize"):
            ServingEngine(params, CFG, slots=1, kv_quantize="int4")

    def test_greedy_parity_within_tolerance(self, params):
        rng = np.random.default_rng(40)
        cases = [(list(rng.integers(0, 64, t)), mn)
                 for t, mn in [(5, 10), (9, 8), (13, 6), (24, 12)]]
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48,
            block_size=4, kv_quantize="int8",
        ).start()
        try:
            agree = total = 0
            for prompt, mn in cases:
                out = eng.submit(prompt, mn).wait(timeout=120)
                ref = _ref(params, prompt, mn)
                assert len(out) == mn
                assert all(0 <= t < CFG.vocab_size for t in out)
                agree += sum(a == b for a, b in zip(out, ref))
                total += mn
            assert agree / total >= 0.75, (
                f"int8 KV drifted too far from f32: {agree}/{total} tokens"
            )
        finally:
            eng.stop()

    def test_prefix_hit_and_cow_on_quantized_pool(self, params):
        """A full-block prefix hit COWs quantized leaves bit-exact: the
        copier and the original produce the SAME tokens, and the shared
        blocks survive the copier's writes."""
        rng = np.random.default_rng(41)
        prompt = list(rng.integers(0, 64, 16))  # two full 8-blocks
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48,
            block_size=8, prefix_cache=True, kv_quantize="int8",
        ).start()
        try:
            first = eng.submit(prompt, 6).wait(timeout=120)
            second = eng.submit(prompt, 6).wait(timeout=120)  # COW path
            assert second == first
            assert eng.stats()["cow_copies"] >= 1
            hits_before = eng.prefix_cache.hits
            assert eng.submit(prompt, 6).wait(timeout=120) == first
            assert eng.prefix_cache.hits > hits_before
        finally:
            eng.stop()

    def test_park_resume_and_shed_on_quantized_pool(self, params):
        """The TestPoolExhaustion scenarios on int8 leaves: pool pressure
        parks and resumes (same tokens as an uncontended int8 engine),
        and a true deadlock sheds exactly one request."""
        rng = np.random.default_rng(42)
        pa = list(rng.integers(0, 64, 24))
        pb = list(rng.integers(0, 64, 4))
        roomy = ServingEngine(
            params, CFG, slots=2, max_len=48,
            block_size=4, prefix_cache=False, kv_quantize="int8",
        ).start()
        try:
            ref_a = roomy.submit(pa, 8).wait(timeout=120)
            ref_b = roomy.submit(pb, 4).wait(timeout=120)
        finally:
            roomy.stop()
        eng = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            num_blocks=9, prefix_cache=False, kv_quantize="int8",
        ).start()
        try:
            ra = eng.submit(pa, 8)
            rb = eng.submit(pb, 4)
            assert ra.wait(timeout=120) == ref_a
            assert rb.wait(timeout=120) == ref_b
            s = eng.stats()
            assert s["block_parks"] >= 1, "pool pressure never parked"
            assert s["blocks_free"] == s["blocks_total"]
            # Deadlock: two spans that can never fit together.
            r1 = eng.submit(list(rng.integers(0, 64, 4)), 24)
            r2 = eng.submit(list(rng.integers(0, 64, 4)), 24)
            done = 0
            for req in (r1, r2):
                try:
                    out = req.wait(timeout=120)
                    assert len(out) == 24
                    done += 1
                except RuntimeError as e:
                    assert "pool exhausted" in str(e)
            assert done == 1, "exactly one request is shed"
        finally:
            eng.stop()

    def test_stats_report_kv_dtype_and_pool_bytes(self, params):
        f32 = ServingEngine(params, CFG, slots=2, max_len=48, block_size=4)
        q = ServingEngine(
            params, CFG, slots=2, max_len=48, block_size=4,
            kv_quantize="int8",
        )
        try:
            sf, sq = f32.stats(), q.stats()
            assert sf["kv_dtype"] == "float32"
            assert sq["kv_dtype"] == "int8"
            assert sq["kv_pool_bytes"] <= 0.55 * sf["kv_pool_bytes"]
            assert sq["kv_pool_bytes"] == sum(
                x.nbytes for x in jax.tree_util.tree_leaves(q._pool)
            )
        finally:
            f32.stop()
            q.stop()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
    def test_stats_report_weight_dtype_and_bytes(self, params, dtype):
        """The tree the programs READ: at bfloat16 compute the embeddings
        and matmul weights are held rounded, about half the float32 bytes
        (the norm scales stay), whatever dtype the engine was handed."""
        cfg = CFG.scaled(dtype=dtype)
        handed = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
        eng = ServingEngine(params, cfg, slots=2, max_len=48, block_size=4)
        try:
            stats = eng.stats()
            assert stats["weight_dtype"] == jnp.dtype(dtype).name
            assert stats["weight_bytes"] == sum(
                x.nbytes for x in jax.tree_util.tree_leaves(eng._params)
            )
            if dtype == jnp.float32:
                assert stats["weight_bytes"] == handed
            else:
                assert 0.5 * handed < stats["weight_bytes"] < 0.51 * handed
        finally:
            eng.stop()
