"""Host-side bookkeeping for the paged KV cache.

Three pieces, all pure Python (the device side lives in
``models/decode.py``):

:class:`BlockAllocator` — a ref-counted free list over a fixed pool of
KV blocks.  Every in-flight sequence holds one reference per block in
its table; the shared-prefix cache holds one more per block it has
published.  A block returns to the free list only when its last holder
lets go, which is exactly the property that makes prefix SHARING safe:
retiring the request that originally computed a system prompt cannot
invalidate the neighbors still reading it.

:class:`PrefixCache` — a block-granular LRU map from token-prefix hash
chains to physical blocks.  Keys are chained per block
(``hash((prev_key, block_tokens))``), so a lookup walks the prompt one
block at a time and stops at the first miss; the stored token tuple is
compared on every hit, so a hash collision degrades to a miss instead
of serving another prompt's KV.  Eviction only considers entries whose
block has a single reference left (the cache's own) — evicting a block
a live request still reads would free nothing.

:class:`HostKVTier` — the host-memory tier under the device pool.  It
stores exported block payloads (numpy leaf trees mirroring the pool
layout bit-exact) for two populations: a parked sequence's spilled
private blocks (pinned — correctness state) and demoted prefix-cache
blocks (a bounded LRU — pure cache).  The device copies themselves live
in the engine; this class is pure bookkeeping.

:class:`StateSnapshots` — the places of the device store that keeps a
hybrid model's RECURRENT state at chosen prefix positions.  KV can be
resumed at every block; a linear-attention layer only where its state
was kept.  A snapshot belongs to the :class:`PrefixCache` entry of the
block it ends on: offered with it, found through it, dropped with it.
"""

from __future__ import annotations

from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

#: Chain seed — any fixed value distinct from real chain keys' structure.
_CHAIN_SEED = "kv-prefix"

#: Physical block 0 is never handed out: the engine points inactive
#: lanes, prompt-pad writes, and unset table entries at it (see
#: models/decode.py), so its contents are garbage by design.
TRASH_BLOCK = 0


class BlockAllocator:
    """Ref-counted FIFO free list over ``num_blocks`` physical KV blocks.

    Block :data:`TRASH_BLOCK` (0) is reserved and never allocated, so a
    pool of ``num_blocks`` serves ``num_blocks - 1`` real blocks.
    ``alloc()`` returns a block with refcount 1 (or ``None`` when the
    pool is exhausted — the engine's cue to evict cached prefixes or
    park the request); ``incref``/``decref`` adjust sharing, and the
    last ``decref`` returns the block to the BACK of the free list so
    reuse order is release order.
    """

    def __init__(self, num_blocks: int) -> None:
        if num_blocks < 2:
            raise ValueError(
                f"need at least 2 blocks (1 usable + trash), got {num_blocks}"
            )
        self.num_blocks = int(num_blocks)
        self._free: deque = deque(range(1, self.num_blocks))
        self._refs: Dict[int, int] = {}

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        block = self._free.popleft()
        self._refs[block] = 1
        return block

    def incref(self, block: int) -> None:
        if block not in self._refs:
            raise ValueError(f"block {block} is not allocated")
        self._refs[block] += 1

    def decref(self, block: int) -> bool:
        """Drop one reference; returns True when the block was freed."""
        refs = self._refs.get(block)
        if refs is None:
            raise ValueError(f"block {block} is not allocated")
        if refs == 1:
            del self._refs[block]
            self._free.append(block)
            return True
        self._refs[block] = refs - 1
        return False

    def refcount(self, block: int) -> int:
        return self._refs.get(block, 0)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.num_blocks - 1 - len(self._free)


def truncate_table(
    table, allocator: BlockAllocator, next_pos: int, block_size: int
) -> int:
    """Speculative-decoding rollback: trim a slot's block table to the
    blocks a sequence whose next write lands at ``next_pos`` still needs.

    A verify step writes KV rows for every drafted token before knowing
    which ones the model accepts; when the accept run stops short, the
    tail rows are garbage.  Rows sharing the next-write block are simply
    overwritten in place (and masked out of attention until then), but
    blocks that lie ENTIRELY beyond ``next_pos`` hold nothing the
    sequence will read before rewriting — so this drops one reference on
    each (``table`` entries after the block containing ``next_pos``,
    reset to -1) and returns how many references were dropped.

    Uses ``decref``, never a force-free: a dropped block returns to the
    free list only when no other holder remains, so prefix-cache shares
    and COW invariants survive rollback by construction.  (In practice
    the trimmed blocks are always private — they were faulted for this
    lane's own draft span, past the prompt blocks sharing could cover.)

    ``table`` is the engine's host-side row (a mutable int array,
    -1 = unset), mutated in place.
    """
    keep = int(next_pos) // int(block_size)
    freed = 0
    for bi in range(keep + 1, len(table)):
        block = int(table[bi])
        if block < 0:
            break  # tables fill contiguously; nothing set past here
        allocator.decref(block)
        table[bi] = -1
        freed += 1
    return freed


#: Entry-block sentinel for a prefix-cache entry whose payload lives in
#: the host tier (no device block); ``PrefixCache._demoted`` maps the
#: entry's key to its tier handle.
DEMOTED = -1


class HostKVTier:
    """Host-memory KV block store — the offload tier under the device pool.

    Entries are opaque payloads (dicts of numpy arrays, one per pool
    leaf, so an int8 pool spills int8 rows + scales bit-exact) keyed by
    a monotonically increasing handle.  Two populations share the tier:

    - **pinned** — a parked sequence's spilled private blocks.  This is
      correctness state (the KV exists nowhere else), so pinned entries
      are never dropped and don't count against ``capacity_blocks``.
    - **unpinned** — demoted prefix-cache blocks.  Pure cache: bounded
      by ``capacity_blocks`` (0 = unbounded) with LRU drop; each drop
      invokes ``on_drop(handle)`` so the owning cache forgets the entry.
    """

    def __init__(self, capacity_blocks: int = 0) -> None:
        if capacity_blocks < 0:
            raise ValueError(
                f"capacity_blocks must be >= 0, got {capacity_blocks}"
            )
        self.capacity_blocks = int(capacity_blocks)
        self._data: "OrderedDict[int, Dict[str, Any]]" = OrderedDict()
        self._pinned: set = set()
        self._next_handle = 1
        self.on_drop: Optional[Callable[[int], None]] = None
        self.spilled_total = 0
        self.restored_total = 0
        self.dropped_total = 0

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, handle: int) -> bool:
        return handle in self._data

    @property
    def n_pinned(self) -> int:
        return len(self._pinned)

    @property
    def n_unpinned(self) -> int:
        return len(self._data) - len(self._pinned)

    @property
    def nbytes(self) -> int:
        """Host bytes currently held (all payload leaves)."""
        return sum(
            arr.nbytes
            for tree in self._data.values()
            for arr in tree.values()
        )

    def put(self, data: Dict[str, Any], pinned: bool = False) -> Optional[int]:
        """Admit one payload; returns its handle, or ``None`` when the
        unpinned budget is exhausted and nothing can be dropped (pinned
        admissions never fail — losing parked state would lose KV)."""
        if not pinned and self.capacity_blocks:
            while self.n_unpinned >= self.capacity_blocks:
                victim = next(
                    (h for h in self._data if h not in self._pinned), None
                )
                if victim is None:
                    return None
                self._drop(victim)
        handle = self._next_handle
        self._next_handle += 1
        self._data[handle] = data
        if pinned:
            self._pinned.add(handle)
        self.spilled_total += 1
        return handle

    def get(self, handle: int) -> Dict[str, Any]:
        """Read a payload without removing it (refreshes LRU position)."""
        data = self._data[handle]
        self._data.move_to_end(handle)
        return data

    def pop(self, handle: int) -> Dict[str, Any]:
        """Remove and return a payload (the restore path)."""
        self._pinned.discard(handle)
        self.restored_total += 1
        return self._data.pop(handle)

    def discard(self, handle: int) -> None:
        """Drop a payload without restoring it (retire/fail paths);
        unknown handles are ignored."""
        self._pinned.discard(handle)
        self._data.pop(handle, None)

    def _drop(self, handle: int) -> None:
        self._data.pop(handle)
        self.dropped_total += 1
        if self.on_drop is not None:
            self.on_drop(handle)


class StateSnapshots:
    """Which place of a fixed store of ``capacity`` recurrent-state
    snapshots holds what (the bytes live on the device, in the engine).

    A place is FREE, PENDING (allocated to a prefill in flight, not yet
    attached to a chain entry: never evicted) or ATTACHED to a
    prefix-cache chain key.  ``alloc()`` takes a free place or, with
    none left, the least recently used attached one.  Keys are the
    prefix cache's chain keys, so a snapshot is only ever found under
    the token prefix that produced it.
    """

    def __init__(self, capacity: int) -> None:
        if capacity < 1:
            raise ValueError(f"need at least one snapshot place, got {capacity}")
        self.capacity = int(capacity)
        self._free: deque = deque(range(self.capacity))
        self._by_key: "OrderedDict[int, int]" = OrderedDict()  # LRU first
        self.taken = 0
        self.evictions = 0

    @property
    def used(self) -> int:
        """Places not free (attached or pending)."""
        return self.capacity - len(self._free)

    def alloc(self) -> Optional[int]:
        """A place for a new snapshot, evicting the least recently used
        attached one if none is free; ``None`` when every place is pending."""
        if self._free:
            idx = self._free.popleft()
        elif self._by_key:
            _, idx = self._by_key.popitem(last=False)
            self.evictions += 1
        else:
            return None
        self.taken += 1
        return idx

    def release(self, idx: int) -> None:
        """Give back a pending place (its prefill failed, or another
        request's snapshot already stands at the same prefix)."""
        self._free.append(idx)

    def attach(self, key: int, idx: int) -> None:
        """Pending place ``idx`` now stands at chain key ``key``; first
        writer wins (the state is a function of the prefix alone)."""
        if key in self._by_key:
            self.release(idx)
        else:
            self._by_key[key] = idx

    def lookup(self, key: int) -> Optional[int]:
        return self._by_key.get(key)

    def touch(self, key: int) -> None:
        self._by_key.move_to_end(key)

    def drop(self, key: int) -> None:
        """The chain entry is gone: so is its snapshot."""
        idx = self._by_key.pop(key, None)
        if idx is not None:
            self._free.append(idx)
            self.evictions += 1


class PrefixCache:
    """Block-granular shared-prefix cache over a :class:`BlockAllocator`.

    ``match()`` walks a prompt's full blocks against the chain map and
    returns the longest run of cached blocks, taking one reference per
    returned block on the caller's behalf.  ``offer()`` publishes a
    finished prompt's blocks (taking the cache's own reference on each
    newly published block).  ``evict()`` reclaims LRU entries whose
    block nobody else holds.

    With a host tier attached (:meth:`attach_tier`), eviction DEMOTES
    instead: the cold entry's payload moves to host memory, its device
    block frees, and the entry stays matchable — a later hit restores it
    through a fresh device block (verify-on-hit unchanged, since the
    stored token tuple never leaves the entry).  Entries also remember
    their FULL prefix token chain, which is what makes them persistable:
    chain keys are built with Python's process-randomized string hash,
    so a store must carry tokens, not keys, and rebuild keys on load.

    With ``snapshots`` (a hybrid model: :class:`StateSnapshots`) a hit is
    only as long as the recurrent state can follow:
    :meth:`match_with_state` cuts the KV match back to the newest position
    where a snapshot stands, :meth:`offer` attaches a prefill's snapshots
    to the entries of the blocks they end on, and every path that forgets
    an entry drops its snapshot.
    """

    def __init__(
        self,
        allocator: BlockAllocator,
        block_size: int,
        snapshots: Optional[StateSnapshots] = None,
    ) -> None:
        if block_size < 1:
            raise ValueError(f"block_size must be positive, got {block_size}")
        self._alloc = allocator
        self.block_size = int(block_size)
        self._snaps = snapshots
        #: Tokens the KV chain matched that were prefilled again because no
        #: snapshot stood that far (``match_with_state``).
        self.floor_tokens = 0
        # chain key -> (physical block | DEMOTED, the block's token tuple)
        self._entries: "OrderedDict[int, Tuple[int, Tuple[int, ...]]]" = (
            OrderedDict()
        )
        # chain key -> (the offered token tuple, n): the FULL prefix token
        # chain ending at this block (ancestors included) is its first n
        # tokens — the persistable identity of an entry.  Every entry one
        # offer() publishes shares the one tuple; hottest_chains() cuts it.
        self._chains: Dict[int, Tuple[Tuple[int, ...], int]] = {}
        # Demoted entries: chain key <-> host tier handle.
        self._demoted: Dict[int, int] = {}
        self._handle_key: Dict[int, int] = {}
        self._tier: Optional[HostKVTier] = None
        self._spill: Optional[Callable[[int], Optional[int]]] = None
        self._restore: Optional[Callable[[int, int], None]] = None
        self._alloc_fn: Optional[Callable[[], Optional[int]]] = None
        self.hits = 0
        self.lookups = 0
        self.evictions = 0
        self.demotions = 0
        self.demote_restores = 0
        #: Monotonic content-change counter: bumped whenever the entry
        #: SET changes (offer/install adds, evict/demote/restore/drop
        #: removals or tier moves).  len() can't detect churn at
        #: constant size, so persistence freshness keys off this.
        self.mutations = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def hit_rate(self) -> float:
        """Block-granular hit rate over the cache's lifetime."""
        return self.hits / self.lookups if self.lookups else 0.0

    @property
    def misses(self) -> int:
        return self.lookups - self.hits

    @property
    def n_demoted(self) -> int:
        """Entries currently resident in the host tier (no device block)."""
        return len(self._demoted)

    def attach_tier(
        self,
        tier: HostKVTier,
        spill: Callable[[int], Optional[int]],
        restore: Callable[[int, int], None],
        alloc: Callable[[], Optional[int]],
    ) -> None:
        """Arm demotion over ``tier``.  ``spill(block)`` copies a device
        block's payload into the tier (returns its handle, or ``None``
        when the tier refuses — then the entry hard-evicts as before);
        ``restore(handle, block)`` writes a payload back into a fresh
        device block and removes it from the tier; ``alloc()`` provides
        that fresh block (the engine passes its evict-then-retry
        allocator, so restoring a hot prefix may demote a colder one).
        The tier's ``on_drop`` is wired back here so a capacity drop
        forgets the corresponding entry."""
        self._tier = tier
        self._spill = spill
        self._restore = restore
        self._alloc_fn = alloc
        tier.on_drop = self._forget_handle

    def _forget_handle(self, handle: int) -> None:
        """Host-tier capacity drop: the demoted entry's payload is gone,
        so the entry itself must go too (a match against it would
        otherwise restore garbage)."""
        key = self._handle_key.pop(handle, None)
        if key is None:
            return
        self._demoted.pop(key, None)
        self._forget(key)
        self.evictions += 1
        self.mutations += 1

    def _forget(self, key: int) -> None:
        """Remove an entry from the maps, with the snapshot that stood on it."""
        self._entries.pop(key, None)
        self._chains.pop(key, None)
        if self._snaps is not None:
            self._snaps.drop(key)

    def _keys_for(self, prompt: Sequence[int]) -> List[Tuple[int, Tuple[int, ...]]]:
        """Chained (key, tokens) per FULL block of the prompt."""
        out = []
        key: object = _CHAIN_SEED
        for i in range(len(prompt) // self.block_size):
            toks = tuple(prompt[i * self.block_size : (i + 1) * self.block_size])
            key = hash((key, toks))
            out.append((key, toks))
        return out

    def match(self, prompt: Sequence[int]) -> List[int]:
        """Longest cached block-prefix of ``prompt``; increfs each
        returned block (the caller owns those references).  A demoted
        entry on the walk restores through a fresh device block first
        (host→device copy); if the pool can't provide one even after
        demoting colder entries, the walk stops there — a miss, never an
        error."""
        return self._walk(prompt)[0]

    def _walk(self, prompt: Sequence[int]) -> Tuple[List[int], List[int]]:
        """The matched blocks and, beside each, its chain key."""
        blocks: List[int] = []
        keys: List[int] = []
        for key, toks in self._keys_for(prompt):
            self.lookups += 1
            entry = self._entries.get(key)
            if entry is None or entry[1] != toks:
                break
            block = entry[0]
            if block < 0:
                block = self._restore_entry(key, toks)
                if block is None:
                    break
            self.hits += 1
            self._entries.move_to_end(key)
            self._alloc.incref(block)
            blocks.append(block)
            keys.append(key)
        return blocks, keys

    def match_with_state(
        self, prompt: Sequence[int]
    ) -> Tuple[List[int], Optional[int]]:
        """:meth:`match` for a model with recurrent layers: the KV match cut
        back to the newest position, at or below it and below the prompt's
        last token (whose logits have to be computed from the state BEFORE
        it), where a snapshot stands.  Returns the kept blocks and the
        snapshot's place in the store (``(.., None)``: no snapshot on the
        chain, start from position 0).  The blocks beyond are released,
        ``hits`` counts only what is left, and the difference goes to
        ``floor_tokens``."""
        blocks, keys = self._walk(prompt)
        keep, place = 0, None
        for i in range(len(blocks) - 1, -1, -1):
            if (i + 1) * self.block_size >= len(prompt):
                continue
            place = self._snaps.lookup(keys[i])
            if place is not None:
                keep = i + 1
                self._snaps.touch(keys[i])
                break
        for block in blocks[keep:]:
            self._alloc.decref(block)
        self.hits -= len(blocks) - keep
        self.floor_tokens += (len(blocks) - keep) * self.block_size
        return blocks[:keep], place

    def _restore_entry(self, key: int, toks: Tuple[int, ...]) -> Optional[int]:
        """Bring one demoted entry back on-device; returns its fresh
        block or ``None`` (allocation failed — entry stays demoted)."""
        handle = self._demoted.get(key)
        if handle is None or self._restore is None:
            return None
        # MRU first on BOTH levels: the allocation below may demote LRU
        # entries to make room (cache side), and each demotion's
        # tier.put may LRU-drop tier payloads (tier side) — neither
        # cascade may land on the entry being restored.
        self._entries.move_to_end(key)
        if self._tier is not None and handle in self._tier:
            self._tier.get(handle)
        alloc = self._alloc_fn or self._alloc.alloc
        block = alloc()
        if block is None:
            return None
        # A tier smaller than the eviction cascade can still have
        # dropped this handle during alloc (on_drop already forgot the
        # entry): the payload is gone, so treat it as a miss.
        if self._demoted.get(key) != handle or (
            self._tier is not None and handle not in self._tier
        ):
            self._alloc.decref(block)
            return None
        self._restore(handle, block)
        del self._demoted[key]
        self._handle_key.pop(handle, None)
        self._entries[key] = (block, toks)
        self.demote_restores += 1
        self.mutations += 1
        return block

    def offer(
        self,
        prompt: Sequence[int],
        blocks: Sequence[int],
        snapshots: Optional[Dict[int, int]] = None,
    ) -> None:
        """Publish a prompt's full blocks.  ``blocks[i]`` must hold block
        ``i``'s KV; already published prefixes keep their existing block
        (first writer wins — later identical blocks stay private).
        ``snapshots`` maps a position (a multiple of the block size) to the
        pending place that holds the recurrent state after that many
        tokens: each is attached to the entry of the block it ends on."""
        tokens: Optional[Tuple[int, ...]] = None
        pending = dict(snapshots or {})
        for i, ((key, toks), block) in enumerate(zip(self._keys_for(prompt), blocks)):
            entry = self._entries.get(key)
            if entry is None:
                if tokens is None:
                    tokens = tuple(prompt)
                self._alloc.incref(block)
                self._entries[key] = (block, toks)
                self._chains[key] = (tokens, (i + 1) * self.block_size)
                self.mutations += 1
            self._entries.move_to_end(key)
            place = pending.pop((i + 1) * self.block_size, None)
            if place is not None:
                self._snaps.attach(key, place)
        for place in pending.values():  # stood on no published block
            self._snaps.release(place)

    def install(self, chain_tokens: Sequence[int], block: int) -> bool:
        """Register a persisted prefix block (warm boot): ``chain_tokens``
        is the FULL token prefix ending at this block, and the caller —
        who has already written the block's KV — transfers its fresh
        refcount-1 allocation to the cache.  First writer wins like
        ``offer``: a pre-existing entry keeps its block and the caller's
        is freed.  Returns True when the entry was installed."""
        keys = self._keys_for(chain_tokens)
        if not keys:
            self._alloc.decref(block)
            return False
        key, toks = keys[-1]
        if key in self._entries:
            self._alloc.decref(block)
            return False
        self._entries[key] = (block, toks)
        tokens = tuple(int(t) for t in chain_tokens)
        self._chains[key] = (tokens, len(tokens))
        self._entries.move_to_end(key)
        self.mutations += 1
        return True

    def hottest_chains(
        self, limit: int
    ) -> List[Tuple[Tuple[int, ...], int, Optional[int]]]:
        """Up to ``limit`` entries worth persisting, hottest-first WITH
        chain closure: an entry only helps a future ``match`` walk if its
        ancestors are stored too, so each hot entry pulls in its whole
        chain root-first.  (Taking the raw MRU tail would do the
        opposite — ``match`` moves ancestors to the end *before* their
        descendants, so a tail cut keeps children and orphans parents.)
        Returns ``(full_chain_tokens, block_or_DEMOTED, handle_or_None)``
        tuples, ancestors before descendants."""
        out: List[Tuple[Tuple[int, ...], int, Optional[int]]] = []
        seen: set = set()
        for key in reversed(self._entries):
            if len(out) >= limit:
                break
            chain = self._chains.get(key)
            if chain is None:
                continue
            for k2, _ in self._keys_for(chain[0][: chain[1]]):
                if k2 in seen or len(out) >= limit:
                    continue
                entry = self._entries.get(k2)
                chain2 = self._chains.get(k2)
                if entry is None or chain2 is None:
                    continue
                seen.add(k2)
                tokens, n = chain2
                out.append((tokens[:n], entry[0], self._demoted.get(k2)))
        return out

    def evict(self, need: int = 1, demote: Optional[bool] = None) -> int:
        """Reclaim up to ``need`` device blocks from LRU entries whose
        block only the cache still references; returns how many device
        blocks freed.  With a host tier attached (and ``demote`` not
        forced off) the entry's payload moves to the tier instead of
        vanishing — the device block frees either way, but a demoted
        entry stays matchable.  A tier refusal (unpinned capacity
        exhausted) falls back to the hard evict."""
        if demote is None:
            demote = self._tier is not None
        freed = 0
        for key in list(self._entries):
            if freed >= need:
                break
            # The demote branch's spill can re-enter _forget_handle (a
            # tier capacity drop fires on_drop) and delete OTHER demoted
            # entries mid-iteration, so keys from the snapshot above may
            # be gone by the time the walk reaches them.
            entry = self._entries.get(key)
            if entry is None:
                continue
            block, toks = entry
            if block < 0:
                continue  # already demoted: holds no device block
            if self._alloc.refcount(block) != 1:
                continue
            if demote and self._spill is not None:
                handle = self._spill(block)
                if handle is not None:
                    self._demoted[key] = handle
                    self._handle_key[handle] = key
                    self._entries[key] = (DEMOTED, toks)
                    self._alloc.decref(block)
                    self.demotions += 1
                    self.mutations += 1
                    freed += 1
                    continue
            self._forget(key)
            self._alloc.decref(block)
            self.evictions += 1
            self.mutations += 1
            freed += 1
        return freed

    def drop_all(self) -> int:
        """Evict everything evictable (shutdown / tests) — hard evicts,
        never demotes, and forgets demoted entries' host payloads too."""
        freed = self.evict(need=len(self._entries), demote=False)
        for key in [k for k, e in self._entries.items() if e[0] < 0]:
            handle = self._demoted.pop(key)
            self._handle_key.pop(handle, None)
            if self._tier is not None:
                self._tier.discard(handle)
            del self._entries[key]
            self._chains.pop(key, None)
            self.evictions += 1
            self.mutations += 1
        return freed
