"""Continuous-batching generation engine over a PAGED KV cache.

Iteration-level scheduling (Orca) over block-table KV management
(vLLM's PagedAttention) with Sarathi-style chunked prefill: the engine
owns one ``[L, num_blocks, block_size, Hkv, d]`` block POOL and ONE
jitted :func:`~polyaxon_tpu.models.decode.paged_decode_step` whose
shapes depend only on (slots, pool size, table width) — per-slot block
tables, positions, and the active mask are DATA, so steady-state
serving never recompiles.  Each scheduler iteration:

1. **admit** — move queued requests into free slots and enqueue a
   prefill job per admission; the shared-prefix cache
   (:class:`~polyaxon_tpu.serving.paging.PrefixCache`) maps any cached
   block-prefix of the prompt straight into the request's table (a
   block-aligned FULL hit copies the last block private first —
   copy-on-write — and recomputes only the final prompt token);
2. **prefill tick** — run ONE chunk (``prefill_chunk`` tokens) of the
   oldest pending prefill via
   :func:`~polyaxon_tpu.models.decode.paged_prefill_chunk`, allocating
   table blocks lazily from the ref-counted
   :class:`~polyaxon_tpu.serving.paging.BlockAllocator`; a long prompt
   therefore interleaves with decode instead of stalling the batch;
3. **step** — one batched decode step advances every active slot one
   token; a slot that faults a new block on an exhausted pool PARKS
   (state and blocks kept, active mask cleared — still just data) and
   resumes when references drop;
4. **retire** — finished slots free their blocks back to the pool
   (shared prefix blocks merely drop one reference) and publish their
   prompt blocks to the prefix cache for the next request.

Tokens stream back per-request as they land; ``cancel()`` releases a
request's slot, blocks, and prefix references immediately, and
``stop()`` drains deterministically — every still-pending request gets
an error and exactly one ``None`` stream sentinel.  Greedy outputs are
token-identical to sequential
:func:`~polyaxon_tpu.models.decode.generate` calls with paging, prefix
sharing, and chunked prefill all enabled
(tests/test_serving/test_paging.py asserts it per request).

Sharded + quantized serving compose exactly like the slot-granular
path did: place the params (and the int8 ``(q, scale)`` tree) with
``decode_param_shardings`` / ``quantized_weight_shardings`` and GSPMD
propagates head-sharding through the chunked prefill and the paged
step — the block pool lives on the gang mesh.
"""

from __future__ import annotations

import functools
import itertools
import queue
import random
import threading
import time
import traceback
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from polyaxon_tpu.conf.knobs import knob_bool, knob_float, knob_int, knob_str
from polyaxon_tpu.serving.paging import (
    BlockAllocator,
    HostKVTier,
    PrefixCache,
    StateSnapshots,
    truncate_table,
)
from polyaxon_tpu.stats import MemoryStats
from polyaxon_tpu.stats.tsdb import RatioWindow
from polyaxon_tpu.tracking.flightrec import get_progress
from polyaxon_tpu.tracking.trace import PhaseSnapshot, TraceContext, get_tracer


#: The engine loop's phases (``tracking/trace.py:PhaseClock``): every instant
#: of the scheduler thread belongs to exactly one.  ``/v1/stats`` reports each
#: as ``loop_<phase>_s`` / ``loop_<phase>_n`` (``serving.`` and ``loop.``
#: dropped, dots to underscores) beside ``loop_wall_s``.
PH_MATCH = "serving.paging.match"  # PrefixCache.match at admission, its increfs
PH_OFFER = "serving.paging.offer"  # PrefixCache.offer when a prompt is in
PH_ALLOC = "serving.paging.alloc"  # allocator, PrefixCache.evict, every decref
PH_ADMIT = "serving.loop.admit"  # queue pop, slot, drafter seeding, job creation
PH_PREFILL_HOST = "serving.loop.prefill_host"  # a chunk's inputs, uploads, dispatch
PH_DECODE_HOST = "serving.loop.decode_host"  # a step's inputs, uploads, dispatch
PH_DEVICE_WAIT = "serving.loop.device_wait"  # the blocking device-to-host reads
PH_EMIT = "serving.loop.emit"  # tokens out, stream puts, retire, trace finalize
PH_BOOKKEEPING = "serving.loop.bookkeeping"  # gauges, ledger, histograms, beacon, spans
PH_OTHER = "serving.loop.other"  # the loop itself, cancels, park/restore, drafting
PH_IDLE = "serving.loop.idle"  # the cv.wait with nothing to do
LOOP_PHASES = (
    PH_MATCH, PH_OFFER, PH_ALLOC, PH_ADMIT, PH_PREFILL_HOST, PH_DECODE_HOST,
    PH_DEVICE_WAIT, PH_EMIT, PH_BOOKKEEPING, PH_OTHER, PH_IDLE,
)
#: Two more on the clock of an engine whose model has recurrent layers (a
#: dense model's clock, and its ``/v1/stats``, have neither).
PH_SNAPSHOT = "serving.state.snapshot"  # copying a slot's recurrent rows into the store
PH_RESTORE = "serving.state.restore"  # copying a snapshot into an admitted slot's rows
STATE_PHASES = (PH_SNAPSHOT, PH_RESTORE)
#: The laps of a decode step's host side (``PhaseClock.lap``): parts of
#: ``serving.loop.decode_host``'s seconds, in the order a step runs them.
#: ``/v1/stats``: ``decode_host_<lap>_s``.
LAP_INPUTS = "inputs"  # the fault loop's own time, participants, tables, key counts
LAP_KEY = "key"  # the step's key, drawn on the host (``_draw_key``)
LAP_UPLOAD = "upload"  # packing the step's host arguments into one buffer
LAP_DISPATCH = "dispatch"  # the call, the buffer's transfer included, until it returns
STEP_LAPS = (LAP_INPUTS, LAP_KEY, LAP_UPLOAD, LAP_DISPATCH)
#: The phases the engine's thread does not compute in: the wall seconds of
#: the others less the thread's CPU seconds outside these is ``host_off_cpu_s``.
WAIT_PHASES = (PH_DEVICE_WAIT, PH_IDLE)
#: A blocking read back sooner than this found its result ready: the device
#: had finished before the host asked (``device_reads_ready``).
READ_READY_S = 100e-6


def _in_phase(phase: str):
    """Run a ``ServingEngine`` method as ``phase`` of the engine's clock."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(self, *args, **kwargs):
            with self._clock.phase(phase):
                return fn(self, *args, **kwargs)

        return inner

    return wrap


def _phase_key(phase: str) -> str:
    """``serving.paging.match`` -> ``paging_match``; a lap's
    ``serving.loop.decode_host.key`` -> ``decode_host_key``."""
    return phase[len("serving."):].replace("loop.", "").replace(".", "_")


def _stats_key(phase: str) -> str:
    """``serving.paging.match`` -> ``loop_paging_match``."""
    return "loop_" + _phase_key(phase)


class EngineDrainingError(RuntimeError):
    """Raised by :meth:`ServingEngine.submit` once :meth:`drain` has been
    called — the engine finishes in-flight work but admits nothing new."""


#: Typed per-request speculative modes (``GenerationRequest.spec_mode``):
#: ``off`` (engine not speculating), ``greedy`` (drafted + verified), or
#: ``fallback:sampled`` (temperature>0 — sampling must see the model's
#: real distribution each step, so the request transparently rides
#: single-token rows of the batch; counted on ``spec_fallback_total``).
SPEC_MODE_OFF = "off"
SPEC_MODE_GREEDY = "greedy"
SPEC_MODE_FALLBACK_SAMPLED = "fallback:sampled"


class NgramDrafter:
    """Per-request prompt-lookup drafter (self-drafting, no draft model).

    Keeps the request's full context (prompt + every accepted token) and
    a suffix index mapping each ``n``-gram to the END positions of its
    two most recent occurrences.  ``draft(k)`` matches the context's
    last ``n`` tokens against the index and proposes the continuation of
    the previous occurrence — the prompt-lookup scheme (Saxena), which
    wins exactly on templated/repetitive traffic.  O(1) per appended
    token and per lookup; the index is built during prefill (over the
    prompt) and updated per accepted token, so draft cost never scales
    with context length.
    """

    __slots__ = ("n", "tokens", "_index")

    def __init__(self, n: int) -> None:
        if n < 1:
            raise ValueError(f"ngram length must be positive, got {n}")
        self.n = int(n)
        self.tokens: List[int] = []
        # ngram -> (second-latest end, latest end).  Two-deep because the
        # context's own suffix is always the LATEST occurrence of itself;
        # drafting wants the one before it.
        self._index: Dict[tuple, tuple] = {}

    def extend(self, toks) -> None:
        for t in toks:
            self.append(int(t))

    def append(self, tok: int) -> None:
        self.tokens.append(int(tok))
        if len(self.tokens) >= self.n:
            key = tuple(self.tokens[-self.n :])
            prev = self._index.get(key)
            self._index[key] = (prev[1] if prev else None, len(self.tokens))

    def draft(self, k: int) -> List[int]:
        """Up to ``k`` proposed continuation tokens ([] = no match)."""
        t = self.tokens
        if k < 1 or len(t) < self.n:
            return []
        ends = self._index.get(tuple(t[-self.n :]))
        if ends is None:
            return []
        end = ends[1] if ends[1] < len(t) else ends[0]
        if end is None:
            return []
        return t[end : end + k]


class _RequestTrace:
    """Per-request distributed-trace state.

    ``ctx`` is the propagated :class:`TraceContext` (one trace id across
    router → replica → engine); ``root_id`` is the engine-side request
    span every phase span parents to.  ``park_s`` accumulates wall time
    spent parked so the waterfall can split decode wall-clock into
    device time vs capacity stalls.  Phase accounting is *interval*
    based (queue_wait / prefill / decode / parked partition the
    request's wall clock), so the waterfall always sums to the server-
    side total regardless of how many sub-spans were hot-sampled away.
    """

    __slots__ = ("ctx", "root_id", "parked_at", "park_s", "ttft_s")

    def __init__(self, ctx: TraceContext, root_id: str) -> None:
        self.ctx = ctx
        self.root_id = root_id
        self.parked_at: Optional[float] = None
        self.park_s = 0.0
        self.ttft_s: Optional[float] = None


class _SlowExemplars:
    """Bounded ring of the N slowest fully-traced requests per window.

    ``offer`` keeps the slowest ``n`` finished-request trace summaries
    whose finish time falls inside the sliding window; ``snapshot``
    returns them slowest-first.  Exposed on ``/v1/stats`` and attached
    as the artifact when the ``serving_ttft_p99`` alert fires, so every
    SLO breach ships its own explanation.
    """

    def __init__(self, n: int, window_s: float) -> None:
        self.n = int(n)
        self.window_s = float(window_s)
        self._lock = threading.Lock()
        self._entries: List[Dict[str, Any]] = []

    def offer(self, summary: Dict[str, Any]) -> None:
        if self.n <= 0:
            return
        now = time.time()
        with self._lock:
            self._entries = [
                e
                for e in self._entries
                if now - e.get("finished_at", now) <= self.window_s
            ]
            self._entries.append(summary)
            self._entries.sort(
                key=lambda e: e.get("total_s", 0.0), reverse=True
            )
            del self._entries[self.n :]

    def snapshot(self) -> List[Dict[str, Any]]:
        now = time.time()
        with self._lock:
            return [
                dict(e)
                for e in self._entries
                if now - e.get("finished_at", now) <= self.window_s
            ]


class GenerationRequest:
    """One queued generation: its prompt, its budget, and its results.

    ``stream`` yields token ids as they are generated (a ``None``
    sentinel marks completion); ``done`` is set when the request has
    finished (or failed — see ``error``; ``error_kind`` is the
    machine-readable class: ``shed`` / ``cancelled`` / ``stopped``).
    ``tokens`` accumulates the generated ids in order.
    """

    _ids = itertools.count()

    def __init__(
        self,
        prompt: List[int],
        max_new_tokens: int,
        temperature: float = 0.0,
    ) -> None:
        self.id = next(self._ids)
        self.prompt = list(prompt)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.tokens: List[int] = []
        self.spec_mode: str = SPEC_MODE_OFF
        self.stream: "queue.Queue[Optional[int]]" = queue.Queue()
        self.done = threading.Event()
        self.error: Optional[str] = None
        self.error_kind: Optional[str] = None
        self.submitted_at = time.time()
        self.started_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        #: Distributed-trace state (None = untraced request).
        self.trace: Optional[_RequestTrace] = None
        #: Waterfall summary, filled once when the request finishes.
        self.trace_summary: Optional[Dict[str, Any]] = None

    def wait(self, timeout: Optional[float] = None) -> List[int]:
        """Block until done; raise on engine-side failure."""
        if not self.done.wait(timeout):
            raise TimeoutError(f"request {self.id} still running")
        if self.error:
            raise RuntimeError(self.error)
        return self.tokens


class SlotAllocator:
    """FIFO free-list over ``n`` cache slots.

    Freed slots go to the BACK of the list, so reuse order is the order
    slots were released — the coldest slot is reused first, which keeps
    any one slot's stale KV rows short-lived (and makes the admit/evict/
    reuse sequence deterministic for tests).
    """

    def __init__(self, n: int) -> None:
        if n < 1:
            raise ValueError(f"need at least one slot, got {n}")
        self.n = n
        self._free: deque = deque(range(n))
        self._held: set = set()

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        slot = self._free.popleft()
        self._held.add(slot)
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._held:
            raise ValueError(f"slot {slot} is not allocated")
        self._held.discard(slot)
        self._free.append(slot)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return len(self._held)


class _PrefillJob:
    """One admitted request's remaining prompt insertion, advanced one
    chunk per scheduler iteration."""

    __slots__ = ("req", "slot", "next_pos", "cow_pending")

    def __init__(self, req: GenerationRequest, slot: int) -> None:
        self.req = req
        self.slot = slot
        self.next_pos = 0  # first prompt position not yet inserted
        self.cow_pending = False  # full prefix hit: copy last block first


class ServingEngine:
    """The continuous-batching scheduler: one thread owns the device.

    Parameters
    ----------
    params, cfg : the model (a ``TransformerConfig`` tree).  The engine
        holds ``decode.serving_params(params, cfg)``: the embeddings and
        matmul weights rounded to ``cfg.dtype`` once, and no reference to
        ``params`` itself; a caller that wants the ``param_dtype`` bytes
        off the device drops its own before ``start()`` warms up.
    slots : concurrent sequences the batch holds (the static batch dim).
    max_len : per-request sequence capacity (default ``cfg.max_seq``).
    block_size : tokens per KV block — the paging granularity.  Smaller
        blocks waste less tail capacity and share shorter prefixes;
        larger blocks shrink tables and gather indices.
    num_blocks : physical pool size INCLUDING the reserved trash block.
        Defaults to ``1 + slots * ceil(max_len / block_size)`` — enough
        for every slot to reach ``max_len`` with no sharing, i.e. the
        old slot-granular footprint plus one block.  Size it below that
        to overcommit on prefix sharing: exhaustion parks decodes until
        references drop (and sheds the newest blocked request if nobody
        can ever free one).
    prefill_chunk : prompt tokens inserted per scheduler iteration.
        ``None`` inserts each prompt whole (one chunk); a finite chunk
        bounds how long any prefill can stall the decode batch, which
        is what keeps TTFT p99 flat under load.
    prefix_cache : share KV blocks between requests with identical
        token-block prefixes (copy-on-write at the divergence point).
    qweights : int8 tree from ``decode.quantize_weights`` — the paged
        step streams int8 exactly like the slot step did.
    kv_quantize : ``"int8"`` stores the KV pool itself quantized —
        int8 rows plus one f32 scale per (block row, kv head), under
        0.3× the f32 pool's HBM at the same ``num_blocks`` — so a fixed
        memory budget holds >2× the live blocks.  Appends quantize
        once; attention reads dequantize fused into the gather (the
        ``_wdq`` pattern applied to KV).  Composes with ``qweights``
        (weight int8) and with prefix sharing/COW, which copy the
        quantized leaves bit-exact.  ``None``/falsey keeps the full
        compute-dtype pool.  Greedy outputs are near- but not bit-
        identical to the full-precision pool (see docs/serving.md).
    mesh / param_shardings / qweights_shardings : multi-chip serving;
        when given, params (and qweights) are placed on the mesh and
        GSPMD propagates the sharding through prefill and the step.
    eos_id : optional token id that retires a slot early.
    seed : RNG seed for the sampling path (greedy ignores it).
    warmup : pre-compile the whole compiled-fn family (the decode step,
        every prefill chunk bucket up to ``prefill_chunk``, the COW copy
        fn) at the top of the scheduler loop before serving traffic, so
        the first request never eats a compile.  ``wait_ready()`` blocks
        on the gate; ``stats()['state']`` reports ``warming|ready``, or
        ``failed`` (with ``start_error``) when the warmup raised.
        With the persistent compile cache armed
        (``runtime/compilecache.py``) a restarted replica warms from
        disk instead of compiling cold.  ``False`` skips straight to
        ready — compiles then happen lazily mid-traffic and show up on
        the ``serving.steady_state_compiles`` counter.  Default (None)
        reads ``POLYAXON_TPU_SERVING_WARMUP`` (on unless ``0``/``false``
        /``off``).
    spec_decode / spec_k / spec_min_ngram : speculative decoding — a
        host-side prompt-lookup drafter proposes up to ``spec_k``
        continuation tokens per greedy lane (matching the context's last
        ``spec_min_ngram`` tokens against the request's own suffix
        index) and ONE ``paged_verify_step`` scores the whole run;
        accepted tokens append, the block table rolls back past the
        rejection point.  Greedy outputs stay token-identical to the
        non-speculative engine (the accept rule emits exactly the
        model's own argmax run); temperature>0 requests transparently
        fall back to single-token rows (``spec_fallback_total``).
        Defaults read the ``POLYAXON_TPU_SERVING_SPEC_*`` knobs (off).
    kv_offload / kv_offload_blocks : the host-memory KV tier.  When on,
        a parked sequence's private blocks spill to host memory (pinned
        — parking RELEASES pool capacity instead of sitting on it, so
        oversubscription costs restore latency instead of sheds) and
        cold prefix-cache entries DEMOTE to the tier instead of hard-
        evicting (a later hit restores them through a fresh block; the
        verify-on-hit token compare is unchanged).  Both copies move the
        pool's storage leaves bit-exact — an int8 pool spills int8 rows
        + scales, values never requantize.  ``kv_offload_blocks`` bounds
        the DEMOTED population (LRU drop; 0 = unbounded); pinned spills
        never count.  Defaults read ``POLYAXON_TPU_KV_OFFLOAD`` /
        ``POLYAXON_TPU_KV_OFFLOAD_BLOCKS``.
    kv_persist_dir / kv_persist_blocks / kv_persist_sig : the persistent
        prefix store (``serving/kvstore.py``; point ``kv_persist_dir``
        at ``StoreLayout.kv_cache_dir``).  The engine snapshots its
        hottest ``kv_persist_blocks`` prefix blocks — torn-write-safe,
        idle-time throttled by ``POLYAXON_TPU_KV_PERSIST_INTERVAL_S``,
        plus a final snapshot on ``stop()`` — and warmup preloads the
        newest complete snapshot before the ready gate opens, so a
        replacement/scale-up replica serves its first request
        prefix-warm.  ``kv_persist_sig`` is the model-identity
        fingerprint stored with (and required of) a snapshot: pass
        something that changes with the weights (seed, checkpoint step)
        so a replica never preloads KV another model computed.  Left
        empty, the engine derives one by fingerprinting the weights
        themselves (geometry can't tell checkpoints apart, so an
        unsigned store is never written).
        Defaults read the ``POLYAXON_TPU_KV_PERSIST_*`` knobs (off).
    state_snapshot_every / state_snapshots : a model with linear-attention
        layers (``cfg.layer_types``) keeps, beside the KV pool, a float32
        recurrent state per slot, and can resume a cached prefix only
        where that state was kept.  The engine copies a slot's state into
        a preallocated device store wherever a prefill chunk ends on a
        multiple of ``state_snapshot_every`` tokens (a multiple of the
        block size; chunks are cut to end there; default: the prefill
        chunk, else 1024) and the store holds ``state_snapshots`` of them
        (default 4 per slot; least recently used goes first).  A prefix
        hit is cut back to the newest snapshot on its chain.  Such a model
        refuses ``spec_decode``, ``kv_offload``, ``kv_persist_dir`` and a
        ``mesh`` with :class:`~polyaxon_tpu.models.hybrid.RecurrentStateError`.
        A latent-attention model (``cfg.kv_lora_rank``, ``models/latent_moe.py``)
        keeps one latent row a token a layer in the pool and takes no option of
        its own; it refuses ``spec_decode`` and a ``mesh`` with
        :class:`~polyaxon_tpu.models.latent_moe.LatentStackError`.
        A model of window and full attention layers (``cfg.mlp_layer_types``,
        ``models/window_moe.py``) keeps a ring of ``sliding_window`` K and V
        rows a slot for each window layer beside the blocks of its full
        layers, snapshotted by the two options above as a hybrid model's
        state is, and refuses ``spec_decode``, ``kv_offload``,
        ``kv_persist_dir`` and ``mesh`` with
        :class:`~polyaxon_tpu.models.window_moe.WindowStackError`.
    stats : a stats backend receiving latency histograms
        (``serving.queue_wait_s`` / ``serving.ttft_s`` /
        ``serving.decode_step_s`` / ``serving.batch_occupancy``) and
        paging gauges (``serving.block_occupancy`` /
        ``serving.prefix_cache_hit_rate`` /
        ``serving.prefill_backlog_chunks``); defaults to a private
        :class:`MemoryStats` — ``lm_server`` passes the process-wide
        registry so ``/metrics`` exports them.
    """

    #: Padding buckets for prompt chunks: powers of two bound the number
    #: of prefill compilations at log2(max_len) regardless of traffic.
    @staticmethod
    def _bucket(t: int, max_len: int) -> int:
        b = 8
        while b < t:
            b *= 2
        return min(b, max_len)

    def __init__(
        self,
        params: Any,
        cfg: Any,
        *,
        slots: int = 4,
        max_len: Optional[int] = None,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        prefix_cache: bool = True,
        qweights: Optional[Any] = None,
        kv_quantize: Optional[str] = None,
        mesh: Any = None,
        param_shardings: Optional[Any] = None,
        qweights_shardings: Optional[Any] = None,
        eos_id: Optional[int] = None,
        seed: int = 0,
        stats: Optional[Any] = None,
        warmup: Optional[bool] = None,
        spec_decode: Optional[bool] = None,
        spec_k: Optional[int] = None,
        spec_min_ngram: Optional[int] = None,
        kv_offload: Optional[bool] = None,
        kv_offload_blocks: Optional[int] = None,
        kv_persist_dir: Optional[str] = None,
        kv_persist_blocks: Optional[int] = None,
        kv_persist_sig: str = "",
        state_snapshot_every: Optional[int] = None,
        state_snapshots: Optional[int] = None,
    ) -> None:
        import jax

        from polyaxon_tpu.models import decode
        from polyaxon_tpu.models.transformer import stack_module

        if max_len is None:
            max_len = cfg.max_seq
        if max_len > cfg.max_seq:
            raise ValueError(
                f"max_len ({max_len}) exceeds the model's max_seq "
                f"({cfg.max_seq})"
            )
        if block_size < 1:
            raise ValueError(f"block_size must be positive, got {block_size}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be positive or None, got {prefill_chunk}"
            )
        self.cfg = cfg
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.block_size = int(block_size)
        self.prefill_chunk = prefill_chunk
        self.eos_id = eos_id
        self._mesh = mesh
        if param_shardings is not None:
            params = jax.device_put(params, param_shardings)
        if qweights is not None and qweights_shardings is not None:
            qweights = jax.device_put(qweights, qweights_shardings)
        # The programs read the embeddings and matmul weights only through
        # a cast to ``cfg.dtype``: make it once, here (a cast under a
        # leaf's sharding keeps it), and keep no reference to the tree that
        # was handed in, so a caller that drops its own frees the float32
        # bytes.  ``qweights`` were made from that tree before this.
        self._params = decode.serving_params(params, cfg)
        self._qweights = qweights
        #: Device bytes of the tree the programs read and the dtype of its
        #: matmul leaves, beside ``kv_pool_bytes`` / ``kv_dtype``.
        self.weight_bytes = int(
            sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(self._params))
        )
        self.weight_dtype = self._params["unembed"].dtype.name

        # Table width: logical blocks a max_len sequence spans.  The
        # default pool matches the old slot-granular footprint (every
        # slot can reach max_len unshared) plus the trash block.
        self._table_width = -(-self.max_len // self.block_size)
        if num_blocks is None:
            num_blocks = 1 + self.slots * self._table_width
        self.block_allocator = BlockAllocator(num_blocks)
        # Per-sequence state of fixed size (a hybrid model's recurrent rows, a
        # window layer's ring of K and V): per-slot rows in the pool, and the
        # snapshot store that prefix reuse resumes from.  The model file says
        # whether it keeps any (``init_rec_state``).
        self._stack = stack_module(cfg) if cfg.stack != "uniform" else None
        self._recurrent = hasattr(self._stack, "init_rec_state")
        self._snaps: Optional[StateSnapshots] = None
        self._snap_store: Optional[Any] = None
        self._snap_every = 0
        if self._recurrent and prefix_cache:
            every = int(state_snapshot_every or prefill_chunk or 1024)
            if every < 1 or every % self.block_size:
                raise ValueError(
                    f"state_snapshot_every ({every}) must be a positive "
                    f"multiple of block_size ({self.block_size})"
                )
            self._snap_every = every
            self._snaps = StateSnapshots(
                int(state_snapshots) if state_snapshots else 4 * self.slots
            )
        self.prefix_cache = (
            PrefixCache(self.block_allocator, self.block_size, self._snaps)
            if prefix_cache
            else None
        )
        kvq = "" if kv_quantize in (None, False) else str(kv_quantize).lower()
        if kvq in ("", "0", "false", "no", "off", "none"):
            self.kv_quantize: Optional[str] = None
        elif kvq in ("1", "true", "yes", "on", "int8"):
            self.kv_quantize = "int8"
        else:
            raise ValueError(
                f"unsupported kv_quantize {kv_quantize!r} (int8 or off)"
            )
        self._pool = decode.init_block_pool(
            cfg, num_blocks, self.block_size, kv_dtype=self.kv_quantize
        )
        #: What the pool leaves actually store ("int8" or the compute
        #: dtype name) and their total device bytes — surfaced on
        #: ``/v1/stats``, the ``serving.kv_pool_bytes`` gauge, and the
        #: final ledger row so goodput HBM accounting sees pool shrink.
        self.kv_dtype = self.kv_quantize or str(jax.numpy.dtype(cfg.dtype).name)
        self.kv_pool_bytes = int(
            sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(self._pool))
        )
        #: Pool bytes ONE token costs over all layers: 2 x Hkv x d a layer for
        #: K and V, one latent row a layer for a latent-attention model.
        self.kv_row_bytes = (
            decode.kv_block_bytes(cfg, self.block_size, self.kv_quantize)
            // self.block_size
        )
        #: Key positions a prompt chunk that ends at ``live_end`` attends.
        self._chunk_keys = lambda live_end: decode.chunk_keys_attended(
            cfg, live_end, self._table_width, self.block_size
        )
        #: Key positions a decode step attends over lanes with ``live_ends`` keys.
        self._step_keys = lambda live_ends: decode.step_keys_attended(
            cfg, live_ends, self._table_width, self.block_size, self.kv_quantize
        )
        # Routed experts: each program returns what it routed in the call
        # beside its result (``experts.COUNT_NAMES``).  The scheduler keeps
        # the counts of the calls it has dispatched and fetches them in the
        # blocking read it makes anyway (``_host_read``).
        self._moe = bool(cfg.n_routed_experts)
        self._moe_pending: List[Tuple[int, Any]] = []  # (rows of the call's shape, counts)
        self._moe_totals: Dict[str, int] = {}
        #: By the rows of a call's shape (tokens of the program's shape x
        #: choices): ``[the expert product's calls (program calls x expert
        #: layers), rows held, experts that had a row]``.
        self._moe_shapes: Dict[int, List[int]] = {}
        if self._moe:
            from polyaxon_tpu.parallel.experts import COUNT_NAMES

            self._moe_totals = dict.fromkeys(COUNT_NAMES, 0)
            self._moe_layers = self._stack.expert_layers(cfg)
        self._state_row_bytes = 0
        if self._recurrent:
            # The per-slot rows ride the pool dict: the paged programs
            # carry and donate one tree, KV blocks and per-slot state alike.
            self._pool.update(
                self._stack.init_rec_state(cfg, self.slots, self.kv_quantize)
            )
            self._state_row_bytes = self._stack.rec_row_bytes(cfg, self.kv_quantize)
            if self._snaps is not None:
                self._snap_store = self._stack.init_rec_state(
                    cfg, self._snaps.capacity, self.kv_quantize
                )
        #: A model with window layers: by the rows of a chunk's shape (its
        #: bucket, which the window kernel's name carries) ``[the kernel's
        #: calls (one a window layer a chunk), the (query, key) pairs admitted
        #: in them]``, counted on the host from a chunk's start and length.
        self._window_layers = (
            cfg.layer_types.count("sliding_attention") if cfg.stack == "window" else 0
        )
        self._window_shapes: Dict[int, List[int]] = {}
        #: slot -> {position: place}: snapshots of the slot's prefill in
        #: flight, attached to their chain entries when the prompt is in.
        self._pending_snaps: Dict[int, Dict[int, int]] = {}
        self._n_state_restores = 0
        # Per-slot block tables (host truth): -1 = unset, mapped to the
        # trash block when shipped to the device.
        self._tables = np.full(
            (self.slots, self._table_width), -1, np.int32
        )

        # Host-side per-slot state: the NEXT token to feed, its absolute
        # position, the active mask, and each slot's sampling temperature.
        self._tok = np.zeros(self.slots, np.int32)
        self._pos = np.zeros(self.slots, np.int32)
        self._active = np.zeros(self.slots, bool)
        self._temps = np.zeros(self.slots, np.float32)
        self._slot_req: List[Optional[GenerationRequest]] = [None] * self.slots

        self.allocator = SlotAllocator(self.slots)
        self._queue: "deque[GenerationRequest]" = deque()
        self._prefill: "deque[_PrefillJob]" = deque()
        self._parked: List[int] = []
        self._cancels: set = set()
        self._cv = threading.Condition()
        self._stop = threading.Event()
        self._draining = False
        self._thread: Optional[threading.Thread] = None

        # Warmup / readiness gate: the scheduler thread compiles the fn
        # family before its first iteration; requests submitted while
        # warming just queue.  The steady-state compile counter watches
        # total jit cache size growth after ready — the "zero
        # steady-state recompiles" invariant, monitored in production
        # rather than only asserted in tests.
        if warmup is None:
            warmup = knob_bool("POLYAXON_TPU_SERVING_WARMUP")
        self._warmup = bool(warmup)
        self._ready = threading.Event()
        #: Set once the warmup pass has ended either way; ``start_error``
        #: then says whether the engine is serving or failed to start.
        self._warm_settled = threading.Event()
        self.start_error: Optional[str] = None
        self._warmup_total = 0
        self._warmup_done = 0
        self._warmup_s = 0.0
        self._n_steady_compiles = 0
        self._compiled_baseline: Optional[int] = None

        # Speculative decoding: self-drafting multi-token steps.  All
        # three default from the POLYAXON_TPU_SERVING_SPEC_* knobs.
        if spec_decode is None:
            spec_decode = knob_bool("POLYAXON_TPU_SERVING_SPEC_DECODE")
        self.spec_decode = bool(spec_decode)
        self.spec_k = int(
            spec_k if spec_k is not None
            else knob_int("POLYAXON_TPU_SERVING_SPEC_K")
        )
        self.spec_min_ngram = int(
            spec_min_ngram if spec_min_ngram is not None
            else knob_int("POLYAXON_TPU_SERVING_SPEC_MIN_NGRAM")
        )
        if self.spec_decode and self.spec_k < 1:
            raise ValueError(f"spec_k must be positive, got {self.spec_k}")
        if self.spec_decode and self.spec_min_ngram < 1:
            raise ValueError(
                f"spec_min_ngram must be positive, got {self.spec_min_ngram}"
            )
        #: Per-slot drafter (None: slot empty, spec off, or the request
        #: is sampled — the typed fallback path).
        self._drafters: List[Optional[NgramDrafter]] = [None] * self.slots
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_fallbacks = 0
        self._spec_steps = 0

        # Hierarchical KV: the host offload tier and the persistent
        # prefix store, both defaulting from the POLYAXON_TPU_KV_* knobs.
        if kv_offload is None:
            kv_offload = knob_bool("POLYAXON_TPU_KV_OFFLOAD")
        self.kv_offload = bool(kv_offload)
        self.kv_offload_blocks = int(
            kv_offload_blocks if kv_offload_blocks is not None
            else knob_int("POLYAXON_TPU_KV_OFFLOAD_BLOCKS")
        )
        if kv_persist_dir is None:
            kv_persist_dir = knob_str("POLYAXON_TPU_KV_PERSIST_DIR")
        self.kv_persist_dir = str(kv_persist_dir) if kv_persist_dir else None
        self.kv_persist_blocks = int(
            kv_persist_blocks if kv_persist_blocks is not None
            else knob_int("POLYAXON_TPU_KV_PERSIST_BLOCKS")
        )
        self.kv_persist_sig = str(kv_persist_sig or "")
        if self.kv_persist_dir and not self.kv_persist_sig:
            # No model identity provided: the store meta's geometry +
            # dtype cannot tell two checkpoints of the same config
            # apart, and an empty sig would let replicas serving
            # DIFFERENT weights exchange KV through a shared store.
            # Derive a fingerprint from the weights themselves; if that
            # fails, disable persistence rather than silently allow it.
            self.kv_persist_sig = self._auto_persist_sig(
                params, qweights, seed
            )
            if not self.kv_persist_sig:
                import warnings

                warnings.warn(
                    "kv_persist_dir is set but no kv_persist_sig was "
                    "given and no weight fingerprint could be derived; "
                    "disabling KV persistence (an unsigned shared store "
                    "could serve KV computed by a different model)",
                    RuntimeWarning,
                    stacklevel=2,
                )
                self.kv_persist_dir = None
        self._refuse_what_the_stack_cannot_follow()
        self._kv_persist_interval_s = knob_float(
            "POLYAXON_TPU_KV_PERSIST_INTERVAL_S"
        )
        self._host_tier = (
            HostKVTier(self.kv_offload_blocks) if self.kv_offload else None
        )
        self._export_fn: Optional[Any] = None
        self._import_fn: Optional[Any] = None
        #: Parked-sequence spill map: slot -> {table index: tier handle}.
        self._spilled: Dict[int, Dict[int, int]] = {}
        self._n_spilled_blocks = 0
        self._n_restored_blocks = 0
        self._n_shed = 0
        self._kv_preloaded_blocks = 0
        self._kv_persisted_blocks = 0
        self._last_persist_t = 0.0
        self._last_persist_mut = -1
        if self._host_tier is not None and self.prefix_cache is not None:
            self.prefix_cache.attach_tier(
                self._host_tier,
                spill=self._spill_to_tier,
                restore=self._restore_from_tier,
                alloc=self._alloc_block,
            )

        # A step's key is drawn on the host and rides in the step's call
        # with its other host arguments: no program of its own.  Its stream
        # is apart from ``_rng``'s (the first tokens' picks).
        self._key_spec = jax.eval_shape(jax.random.PRNGKey, 0)
        self._key_rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(1)[0])
        self._rng = np.random.default_rng(seed)
        self._chunk_fns: Dict[int, Any] = {}
        self._snapshot_fn: Optional[Any] = None
        self._restore_fn: Optional[Any] = None
        self._copy_fn: Optional[Any] = None
        self._verify_fns: Dict[int, Any] = {}
        self._step_fn = self._build_step()

        # Stats: lifetime counters plus a sliding window for tokens/s;
        # latency distributions go to the (possibly shared) histogram
        # registry so /metrics can export percentiles.
        self.stats_registry = stats if stats is not None else MemoryStats()
        # Decode ticks feed the process's stall watchdog: a serving worker
        # that stops emitting tokens is as stuck as a hung train step.
        self._progress = get_progress()
        # On-demand capture (control-plane `profile` commands): decode
        # iterations drive the same per-step hook trainers use, so a
        # capture window is N decode steps.  Gated on the readiness event
        # in _step_once — a warmup compile storm is not steady-state
        # serving and must not satisfy a profile command's window.
        from polyaxon_tpu.tracking.capture import get_capture_agent

        self._capture = get_capture_agent()
        self._stats_lock = threading.Lock()
        self._n_submitted = 0
        self._n_finished = 0
        self._n_cancelled = 0
        self._n_tokens = 0
        self._n_steps = 0
        self._n_parks = 0
        self._n_cow = 0
        # Key positions the prompt chunks attended, and what their tables held.
        self._n_keys_attended = 0
        self._n_keys_table = 0
        # The same for the decode steps' active lanes.
        self._n_step_keys_attended = 0
        self._n_step_keys_table = 0
        self._backlog_chunks = 0
        self._prefill_jobs = 0
        self._window: "deque[tuple]" = deque()  # (t, n_tokens)
        # Windowed variants of the lifetime cumulative ratios exposed by
        # /v1/stats: dashboards and the router's affinity slack should
        # see current behavior, not boot-averaged history.  Horizon 2× so
        # the baseline sample at-or-before the window start survives.
        self._stats_window_s = knob_float("POLYAXON_TPU_SERVING_STATS_WINDOW_S")
        self._pc_window = RatioWindow(self._stats_window_s * 2.0)
        self._spec_window = RatioWindow(self._stats_window_s * 2.0)
        # Request-scoped distributed tracing: master switch plus the
        # slow-request exemplar ring (`/v1/stats` + the serving_ttft_p99
        # alert's attached artifact).
        self.trace_requests = knob_bool("POLYAXON_TPU_TRACE_REQUESTS")
        self._exemplars = _SlowExemplars(
            knob_int("POLYAXON_TPU_TRACE_EXEMPLARS"),
            knob_float("POLYAXON_TPU_TRACE_EXEMPLAR_WINDOW_S"),
        )
        # The scheduler thread's exclusive phase clock: where the loop's
        # wall time goes, as whole-run counters in stats() and, during an
        # xplane capture, as annotations in the device trace.
        self._clock = get_tracer().phase_clock(
            LOOP_PHASES + (STATE_PHASES if self._recurrent else ()),
            PH_OTHER,
            laps={PH_DECODE_HOST: STEP_LAPS},
            waits=WAIT_PHASES,
        )
        self._n_device_reads = 0  # the loop's blocking reads
        self._n_device_reads_ready = 0  # ... back within READ_READY_S
        # Decode-side utilization ledger (armed in start()): the seconds
        # of prefill and decode ticks weighted by slot occupancy — the
        # serving analogue of train-side goodput/MFU.
        self._ledger: Optional[Any] = None
        self._occ_weighted_s = 0.0

    def _refuse_what_the_stack_cannot_follow(self) -> None:
        """The model file's ``REFUSED`` (``models/hybrid.py``,
        ``models/latent_moe.py``, ``models/window_moe.py``): each option asked
        for raises its typed error by name, here where the engine is built,
        rather than run on the KV blocks alone."""
        refused = getattr(self._stack, "REFUSED", {})
        for option, asked in (
            ("spec_decode", self.spec_decode),
            ("kv_offload", self.kv_offload),
            ("kv_persist_dir", self.kv_persist_dir),
            ("mesh", self._mesh is not None),
        ):
            if asked and option in refused:
                raise self._stack.refusal(option)

    def _note_counts(self, tokens: int, counts: Sequence[Any]) -> None:
        """Keep what a dispatched program routed (a device array, not read
        here) until the next blocking read; ``tokens`` is the program's shape."""
        if self._moe:
            self._moe_pending.append(
                (tokens * self.cfg.num_experts_per_tok, counts[0])
            )

    def _read_returned(self, t0: float) -> None:
        """The blocking read begun at ``t0`` (the clock's reading at the
        transition into ``serving.loop.device_wait``) is back: nothing is
        dispatched any more."""
        ready = time.perf_counter() - t0 < READ_READY_S
        self._clock.drained()
        with self._stats_lock:
            self._n_device_reads += 1
            self._n_device_reads_ready += ready

    @_in_phase(PH_DEVICE_WAIT)
    def _host_read(self, result: Any) -> np.ndarray:
        """The loop's blocking read of a program's result.  The expert counts
        of the calls dispatched since the last one come to the host in the
        same fetch: they are ready when ``result`` is."""
        t0 = self._clock.t
        if not self._moe_pending:
            result = np.asarray(result)
            self._read_returned(t0)
            return result
        import jax

        pending, self._moe_pending = self._moe_pending, []
        result, counted = jax.device_get((result, [c for _, c in pending]))
        self._read_returned(t0)
        with self._stats_lock:
            for (rows, _), got in zip(pending, counted):
                got = dict(zip(self._moe_totals, map(int, got)))
                for name, n in got.items():
                    self._moe_totals[name] += n
                shape = self._moe_shapes.setdefault(rows, [0, 0, 0])
                shape[0] += self._moe_layers
                shape[1] += got["moe_rows_held"]
                shape[2] += got["moe_experts_hit"]
        return result

    # -- compiled functions ----------------------------------------------------

    def _donate(self) -> tuple:
        # Pool donation halves peak memory for the engine's largest
        # buffer — and without it every chunk/step call COPIES the whole
        # pool on its way out, a per-call cost that grows with the pool
        # and multiplies under chunked prefill.  All current backends
        # (CPU included) honor donation for same-shape aliasing.
        return (1,)

    def _draw_key(self) -> np.ndarray:
        """A step's PRNG key, drawn on the host: the key's shape and dtype."""
        spec = self._key_spec
        return self._key_rng.integers(0, 2**32, spec.shape, dtype=spec.dtype)

    def _pack_step(self, tables: np.ndarray, key: np.ndarray) -> np.ndarray:
        """A decode step's host arguments in ONE int32 buffer, which the
        step's program slices apart (``_build_step``): the tables, ``tok``,
        ``pos``, ``active``, the bits of ``temps``, the key's words.  The
        call's dispatch transfers each numpy argument on its own, about a
        tenth of a millisecond each on the chip (PERF.md, PR 39)."""
        S, n = self.slots, tables.size
        buf = np.empty(n + 4 * S + key.size, np.int32)
        buf[:n] = tables.reshape(-1)
        buf[n : n + S] = self._tok
        buf[n + S : n + 2 * S] = self._pos
        buf[n + 2 * S : n + 3 * S] = self._active
        buf[n + 3 * S : n + 4 * S] = self._temps.view(np.int32)
        buf[n + 4 * S :] = key.reshape(-1).view(np.int32)
        return buf

    def _chunk_args(
        self, table: np.ndarray, chunk: np.ndarray, start: int, length: int, slot: int
    ) -> tuple:
        """A prompt chunk's arguments after the pool, as the loop and the
        warm-up pass them: host values, which the call's dispatch transfers;
        a recurrent model's chunk also names its slot."""
        tail = (np.int32(slot),) if self._recurrent else ()
        return (table, chunk, np.int32(start), np.int32(length), *tail)

    def _build_step(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from polyaxon_tpu.models.decode import paged_decode_step

        cfg = self.cfg
        S, W, kspec = self.slots, self._table_width, self._key_spec

        def step(params, pool, packed, qweights):
            n = S * W  # ``_pack_step``'s layout
            tables = packed[:n].reshape(S, W)
            tokens, pos = packed[n : n + S], packed[n + S : n + 2 * S]
            active = packed[n + 2 * S : n + 3 * S] != 0
            temps = lax.bitcast_convert_type(packed[n + 3 * S : n + 4 * S], jnp.float32)
            words = lax.bitcast_convert_type(packed[n + 4 * S :], kspec.dtype)
            # ``counts``: what a model with routed experts routed, else nothing
            logits, pool, *counts = paged_decode_step(
                params, pool, tables, tokens, pos, active, cfg,
                qweights=qweights,
            )
            greedy_tok = jnp.argmax(logits, axis=-1)
            # Per-slot keys: a slot's sample must not depend on which
            # neighbors happen to be in flight.
            keys = jax.random.split(words.reshape(kspec.shape), logits.shape[0])
            safe = jnp.where(temps > 0, temps, 1.0)
            sampled = jax.vmap(jax.random.categorical)(
                keys, logits / safe[:, None]
            )
            tok = jnp.where(temps > 0, sampled, greedy_tok)
            return (jnp.where(active, tok, 0).astype(jnp.int32), pool, *counts)

        return jax.jit(step, donate_argnums=self._donate())

    def _get_chunk(self, c_pad: int):
        import jax

        from polyaxon_tpu.models.decode import paged_prefill_chunk

        if c_pad not in self._chunk_fns:
            cfg = self.cfg

            if self._recurrent:
                # The same program, told whose recurrent rows it advances.
                def chunk_fn(params, pool, table, tokens, start, length, slot):
                    return paged_prefill_chunk(
                        params, pool, table, tokens, start, length, cfg,
                        slot=slot,
                    )

            else:

                def chunk_fn(params, pool, table, tokens, start, length):
                    return paged_prefill_chunk(
                        params, pool, table, tokens, start, length, cfg
                    )

            self._chunk_fns[c_pad] = jax.jit(
                chunk_fn, donate_argnums=(1,) if self._donate() else ()
            )
        return self._chunk_fns[c_pad]

    def _get_copy(self):
        import jax

        from polyaxon_tpu.models.decode import copy_block

        if self._copy_fn is None:
            self._copy_fn = jax.jit(
                copy_block, donate_argnums=(0,) if self._donate() else ()
            )
        return self._copy_fn

    def _get_snapshot(self):
        import jax

        if self._snapshot_fn is None:
            # The STORE is donated; the pool is only read (and is donated
            # to the next chunk or step, which the runtime orders after).
            self._snapshot_fn = jax.jit(
                self._stack.take_snapshot, donate_argnums=(0,)
            )
        return self._snapshot_fn

    def _get_restore(self):
        import jax

        if self._restore_fn is None:
            self._restore_fn = jax.jit(
                self._stack.restore_snapshot, donate_argnums=(0,)
            )
        return self._restore_fn

    def _get_export(self):
        import jax

        from polyaxon_tpu.models.decode import export_block

        if self._export_fn is None:
            # NO donation, deliberately: the slice's result must be an
            # independent buffer, because the pool is donated to every
            # later step/chunk/import call — the runtime orders the read
            # before any subsequent donated write, which is what lets the
            # device→host copy drain while serving moves on.
            self._export_fn = jax.jit(export_block)
        return self._export_fn

    def _get_import(self):
        import jax

        from polyaxon_tpu.models.decode import import_block

        if self._import_fn is None:
            self._import_fn = jax.jit(
                import_block, donate_argnums=(0,) if self._donate() else ()
            )
        return self._import_fn

    def _export_blocks(self, blocks: List[int]) -> List[Dict[str, np.ndarray]]:
        """Device→host copy of ``blocks``' payloads, double-buffered:
        every block's slice is DISPATCHED before any is materialized, so
        block i+1's device-side copy overlaps block i's host conversion
        — the ``runtime/pipeline.py`` prefetch idea applied to spill."""
        fn = self._get_export()
        pending = [fn(self._pool, np.int32(b)) for b in blocks]
        payloads = [
            {name: np.asarray(leaf) for name, leaf in tree.items()}
            for tree in pending
        ]
        self._clock.drained()
        return payloads

    def _import_block(self, block: int, data: Dict[str, np.ndarray]) -> None:
        """Host→device copy of one payload into pool block ``block``."""
        self._pool = self._get_import()(self._pool, data, np.int32(block))
        self._clock.dispatched()
        with self._stats_lock:
            self._n_restored_blocks += 1

    def _spill_to_tier(self, block: int) -> Optional[int]:
        """PrefixCache demotion callback: move one cached block's payload
        host-side; returns its tier handle (None = tier refused, entry
        hard-evicts instead)."""
        [data] = self._export_blocks([block])
        handle = self._host_tier.put(data, pinned=False)
        if handle is not None:
            with self._stats_lock:
                self._n_spilled_blocks += 1
        return handle

    def _restore_from_tier(self, handle: int, block: int) -> None:
        """PrefixCache restore callback: write a demoted entry's payload
        back into the freshly allocated device block."""
        self._import_block(block, self._host_tier.pop(handle))

    def _spec_widths(self) -> List[int]:
        """The verify-step width family: draft-count buckets (powers of
        two capped at ``spec_k``) plus one row for the current token.
        Bucketing bounds compilations at log2(spec_k) whatever draft-
        length mix live traffic produces; ``n_tok`` is data inside each
        bucket."""
        if not self.spec_decode:
            return []
        out = set()
        k = 1
        while k < self.spec_k:
            out.add(k + 1)
            k *= 2
        out.add(self.spec_k + 1)
        return sorted(out)

    def _width_for(self, max_draft: int) -> int:
        """Smallest warm verify width that fits ``max_draft`` drafts."""
        for w in self._spec_widths():
            if w >= max_draft + 1:
                return w
        return self.spec_k + 1

    def _get_verify(self, width: int):
        """The jitted verify step for one padded draft width: the kernel
        plus on-device accept/sample resolution, so only [S, width]
        tokens and [S] emit counts ever cross back to the host."""
        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models.decode import paged_verify_step

        if width not in self._verify_fns:
            cfg = self.cfg

            def verify(
                params, pool, tables, tokens, pos, n_tok, active, temps,
                key, qweights,
            ):
                logits, pool = paged_verify_step(
                    params, pool, tables, tokens, pos, n_tok, active, cfg,
                    qweights=qweights,
                )
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # Row 0 is always emitted; sampled lanes (which never
                # draft) sample it exactly like the single-token step.
                keys = jax.random.split(key, logits.shape[0])
                safe = jnp.where(temps > 0, temps, 1.0)
                sampled = jax.vmap(jax.random.categorical)(
                    keys, logits[:, 0] / safe[:, None]
                )
                first = jnp.where(
                    temps > 0, sampled, greedy[:, 0]
                ).astype(jnp.int32)
                out = jnp.concatenate([first[:, None], greedy[:, 1:]], axis=1)
                # Accept mask: draft j+1 survives iff it equals the
                # model's own pick after row j AND every draft before it
                # survived (cumprod) — the Leviathan greedy accept rule.
                drafts_ok = (
                    jnp.arange(1, tokens.shape[1])[None, :] < n_tok[:, None]
                ) & (temps[:, None] <= 0)
                match = (tokens[:, 1:] == greedy[:, :-1]) & drafts_ok
                n_emit = 1 + jnp.cumprod(
                    match.astype(jnp.int32), axis=1
                ).sum(axis=1)
                out = jnp.where(active[:, None], out, 0)
                n_emit = jnp.where(active, n_emit, 0).astype(jnp.int32)
                return out, n_emit, pool

            self._verify_fns[width] = jax.jit(
                verify, donate_argnums=self._donate()
            )
        return self._verify_fns[width]

    def _compiled_count(self) -> int:
        """Total compiled entries across the engine's jitted fns."""
        fns = [
            self._step_fn,
            *self._chunk_fns.values(),
            *self._verify_fns.values(),
        ]
        if self._copy_fn is not None:
            fns.append(self._copy_fn)
        if self._export_fn is not None:
            fns.append(self._export_fn)
        if self._import_fn is not None:
            fns.append(self._import_fn)
        if self._snapshot_fn is not None:
            fns.append(self._snapshot_fn)
        if self._restore_fn is not None:
            fns.append(self._restore_fn)
        return sum(int(fn._cache_size()) for fn in fns)

    def _warmup_buckets(self) -> List[int]:
        """The chunk-bucket family live traffic can mint: every
        ``_bucket`` value for chunk lengths up to ``prefill_chunk`` (the
        whole prompt when unchunked), capped at ``max_len``."""
        cap = min(self.prefill_chunk or self.max_len, self.max_len)
        out = set()
        b = 8
        while True:
            out.add(min(b, self.max_len))
            if b >= cap:
                break
            b *= 2
        return sorted(out)

    def _run_warmup(self) -> None:
        """Compile the whole fn family before serving traffic (scheduler
        thread, before its first iteration — it owns the pool, so there
        is no device race with live requests, which queue meanwhile).

        Every call EXECUTES its fn — ``lower().compile()`` would not
        populate the jit dispatch cache — with arguments whose writes
        all land in the reserved trash block 0: the decode step with an
        all-inactive mask, each chunk bucket with ``length=0``, and the
        COW copy as a trash self-copy.  Each call takes the KINDS of
        argument the loop passes (host values: ``_pack_step``,
        ``_chunk_args``): a numpy argument is a jit cache entry of its own,
        apart from a device array's.  A failure here (a compile the
        backend refuses, an HBM overflow, a step that died after the
        pool was donated) is a failed start: the error is recorded, the
        readiness gate stays shut, ``stats()['state']`` reads
        ``failed`` and the scheduler loop exits.
        """
        import jax

        tracer = get_tracer()
        t0 = time.perf_counter()
        spillers = self._host_tier is not None or bool(self.kv_persist_dir)
        buckets = self._warmup_buckets() if self._warmup else []
        widths = self._spec_widths() if self._warmup else []
        self._warmup_total = (
            len(buckets) + len(widths) + 2 + (1 if spillers else 0)
            + (1 if self._snaps is not None else 0)
            if self._warmup
            else 0
        )
        gauge = getattr(self.stats_registry, "gauge", None)

        def _tick() -> None:
            self._warmup_done += 1
            if gauge is not None and self._warmup_total:
                gauge(
                    "serving.warmup_progress",
                    self._warmup_done / self._warmup_total,
                )

        try:
            # Warm replica boot: hydrate the prefix cache from the
            # persisted store BEFORE the ready gate opens, so a scale-up
            # replica's first request already walks a warm cache.  (A
            # missing/torn/mismatched store loads as nothing and boots
            # cold; a device error importing a block is a failed start.)
            self._preload_prefixes()
            if self._warmup:
                with tracer.span("serving.warmup", buckets=len(buckets)):
                    tables = np.where(
                        self._tables >= 0, self._tables, 0
                    ).astype(np.int32)
                    toks, self._pool, *_ = self._step_fn(
                        self._params,
                        self._pool,
                        self._pack_step(tables, self._draw_key()),
                        self._qweights,
                    )
                    jax.block_until_ready(toks)
                    _tick()
                    zero = np.int32(0)
                    for c_pad in buckets:
                        if self._stop.is_set():
                            break
                        # A recurrent model's chunk also names a slot: slot
                        # 0, whose rows a chunk of length 0 leaves zero.
                        logits, self._pool, *_ = self._get_chunk(c_pad)(
                            self._params,
                            self._pool,
                            *self._chunk_args(
                                np.zeros(self._table_width, np.int32),
                                np.zeros(c_pad, np.int32),
                                0,
                                0,
                                0,
                            ),
                        )
                        jax.block_until_ready(logits)
                        _tick()
                    if self._snaps is not None:
                        # Snapshot and restore through place 0 and slot 0
                        # (all zeros either way): both programs compiled.
                        self._snap_store = self._get_snapshot()(
                            self._snap_store, self._pool, zero, zero
                        )
                        self._pool = self._get_restore()(
                            self._pool, self._snap_store, zero, zero
                        )
                        jax.block_until_ready(self._pool)
                        _tick()
                    # The verify family: every width bucket speculative
                    # traffic can request, warmed all-inactive so writes
                    # land in the trash block.
                    for width in widths:
                        if self._stop.is_set():
                            break
                        out, n_emit, self._pool = self._get_verify(width)(
                            self._params,
                            self._pool,
                            tables,
                            np.zeros((self.slots, width), np.int32),
                            self._pos,
                            np.ones(self.slots, np.int32),
                            self._active,
                            self._temps,
                            self._draw_key(),
                            self._qweights,
                        )
                        jax.block_until_ready(out)
                        _tick()
                    self._pool = self._get_copy()(self._pool, zero, zero)
                    jax.block_until_ready(self._pool)
                    _tick()
                    if spillers:
                        # Spill/restore round trip through the trash
                        # block: compiles export+import so steady-state
                        # park-spill and demotion never mint a compile.
                        [data] = self._export_blocks([0])
                        self._pool = self._get_import()(self._pool, data, zero)
                        jax.block_until_ready(self._pool)
                        _tick()
        except Exception as e:
            traceback.print_exc()
            self.start_error = f"{type(e).__name__}: {e}"
        else:
            self._compiled_baseline = self._compiled_count()
            # Lazy HLO source for on-demand captures: lowering text is only
            # produced if a profile command actually fires (no extra
            # compile — .lower() stops before XLA).
            self._capture.register_executable(
                "serving_decode_step",
                type("_LazyHLO", (), {"as_text": lambda _s: self._decode_hlo_text()})(),
            )
            self._ready.set()
            if gauge is not None:
                gauge("serving.warmup_progress", 1.0)
        finally:
            self._warmup_s = time.perf_counter() - t0
            self._warm_settled.set()

    def _decode_hlo_text(self) -> str:
        """Lower the decode step against the engine's live shapes and
        render its HLO text (capture-time only; best-effort)."""
        tables = np.where(self._tables >= 0, self._tables, 0).astype(np.int32)
        key = np.zeros(self._key_spec.shape, self._key_spec.dtype)
        lowered = self._step_fn.lower(
            self._params, self._pool, self._pack_step(tables, key), self._qweights
        )
        return lowered.as_text()

    def _check_steady_compiles(self) -> None:
        """Post-ready jit cache growth = a steady-state compile stalled
        the batch (a config edge bucket, a changed donation layout):
        record an ``engine.compile`` span + counter so the invariant is
        observable, not just asserted in tests."""
        if self._compiled_baseline is None:
            return
        n = self._compiled_count()
        grew = n - self._compiled_baseline
        if grew <= 0:
            return
        self._compiled_baseline = n
        with self._stats_lock:
            self._n_steady_compiles += grew
        incr = getattr(self.stats_registry, "incr", None)
        if incr is not None:
            try:
                incr("serving.steady_state_compiles", grew)
            except Exception:
                pass
        with get_tracer().span("engine.compile", n=grew, total=n):
            pass

    # -- public API ------------------------------------------------------------

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        """Block until the warmup pass has ended (or was skipped); True
        when the engine is ready to serve, False on timeout or a failed
        start (``start_error`` says which)."""
        self._warm_settled.wait(timeout)
        return self._ready.is_set()

    def start(self) -> "ServingEngine":
        if self._thread is None:
            from polyaxon_tpu.tracking.ledger import get_ledger

            self._ledger = get_ledger().start(source="serving")
            self._thread = threading.Thread(
                target=self._loop, name="serving-engine", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        # Final prefix-store snapshot (scheduler thread is down — the
        # pool is ours again): whatever this replica learned, the next
        # one boots with.
        self._maybe_persist(force=True)
        if self._ledger is not None:
            paging = self._paging_snapshot()
            spec = self._spec_snapshot()
            self._ledger.merge_extra(
                **self._utilization_snapshot(),
                block_occupancy=paging["block_occupancy"],
                prefix_cache_hit_rate=paging["prefix_cache_hit_rate"],
                prefix_cache_hits=paging["prefix_cache_hits"],
                prefix_cache_misses=paging["prefix_cache_misses"],
                prefix_cache_evictions=paging["prefix_cache_evictions"],
                prefix_cache_demotions=paging["prefix_cache_demotions"],
                prefix_cache_restores=paging["prefix_cache_restores"],
                parked_sequences=paging["parked_sequences"],
                requests_shed=paging["requests_shed"],
                host_spilled_blocks_total=paging["host_spilled_blocks_total"],
                host_restored_blocks_total=paging["host_restored_blocks_total"],
                prefill_backlog_chunks=paging["prefill_backlog_chunks"],
                kv_pool_bytes=paging["kv_pool_bytes"],
                kv_row_bytes=paging["kv_row_bytes"],
                **{k: v for k, v in paging.items() if k.startswith("moe_rows_")},
                kv_dtype=paging["kv_dtype"],
                weight_bytes=paging["weight_bytes"],
                weight_dtype=paging["weight_dtype"],
                spec_proposed_total=spec["spec_proposed_total"],
                spec_accepted_total=spec["spec_accepted_total"],
                spec_accept_rate=spec["spec_accept_rate"],
            )
            self._ledger.flush(final=True)
            self._ledger = None
        # Deterministic drain: every request still holding a waiter gets
        # its error and exactly ONE None stream sentinel — queued,
        # mid-prefill, parked, or actively decoding alike (requests in
        # the prefill deque also sit in _slot_req; the id-keyed dict
        # de-dupes them).
        with self._cv:
            pending = list(self._queue)
            self._queue.clear()
        drain: Dict[int, GenerationRequest] = {r.id: r for r in pending}
        for job in self._prefill:
            drain.setdefault(job.req.id, job.req)
        self._prefill.clear()
        for req in self._slot_req:
            if req is not None:
                drain.setdefault(req.id, req)
        for req in drain.values():
            if not req.done.is_set():
                self._fail_request(req, "engine stopped", "stopped", "stopped")

    def drain(self) -> None:
        """Stop admitting new requests; in-flight work runs to completion.

        The readiness state flips to ``"draining"`` (so health probes and
        routers stop sending traffic) and :meth:`submit` raises
        :class:`EngineDrainingError`.  Non-blocking — callers poll
        ``stats()`` for ``slots_active == 0 and queue_depth == 0`` to know
        the drain has finished, then :meth:`stop`.
        """
        with self._cv:
            self._draining = True
            self._cv.notify_all()

    def submit(
        self,
        prompt: List[int],
        max_new_tokens: int,
        temperature: float = 0.0,
        trace: Optional[TraceContext] = None,
    ) -> GenerationRequest:
        """Validate and enqueue; returns immediately with the request.

        ``trace`` opts the request into distributed tracing: its
        lifecycle phases are recorded as spans under the propagated
        trace id (remote parent = the caller's span), and the finished
        request carries a latency-waterfall ``trace_summary``.
        """
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if any(t < 0 or t >= self.cfg.vocab_size for t in prompt):
            raise ValueError("token id out of vocabulary range")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be positive")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the engine's max_len ({self.max_len})"
            )
        needed = -(-(len(prompt) + max_new_tokens) // self.block_size)
        usable = self.block_allocator.num_blocks - 1
        if needed > usable:
            raise ValueError(
                f"request spans {needed} KV blocks but the pool only has "
                f"{usable}; raise num_blocks or shorten the request"
            )
        req = GenerationRequest(prompt, max_new_tokens, temperature)
        if trace is not None and self.trace_requests and trace.sampled:
            req.trace = _RequestTrace(trace, get_tracer().next_span_id())
        with self._cv:
            if self.start_error is not None:
                raise RuntimeError(
                    f"engine failed to start: {self.start_error}"
                )
            if self._stop.is_set():
                raise RuntimeError("engine is stopped")
            if self._draining:
                raise EngineDrainingError(
                    "engine is draining (no new admissions)"
                )
            self._queue.append(req)
            self._n_submitted += 1
            self._cv.notify_all()
        return req

    def cancel(self, request_id: int) -> bool:
        """Best-effort immediate release of one request.

        A queued request fails in place; an in-flight one (prefilling,
        parked, or decoding) is failed by the scheduler thread on its
        next iteration, releasing its slot, KV blocks, and prefix-cache
        references.  Returns ``False`` for unknown or already-finished
        ids.  The cancelled request's waiters observe a ``RuntimeError``
        ("request cancelled") and one ``None`` stream sentinel.
        """
        with self._cv:
            for req in list(self._queue):
                if req.id == request_id:
                    self._queue.remove(req)
                    with self._stats_lock:
                        self._n_cancelled += 1
                    self._fail_request(
                        req, "request cancelled", "cancelled", "cancelled"
                    )
                    return True
            for req in self._slot_req:
                if (
                    req is not None
                    and req.id == request_id
                    and not req.done.is_set()
                ):
                    self._cancels.add(request_id)
                    self._cv.notify_all()
                    return True
        return False

    def generate(
        self,
        prompt: List[int],
        max_new_tokens: int,
        temperature: float = 0.0,
        timeout: Optional[float] = None,
    ) -> List[int]:
        """Blocking convenience: submit + wait."""
        return self.submit(prompt, max_new_tokens, temperature).wait(timeout)

    def _utilization_snapshot(
        self, clock: Optional[PhaseSnapshot] = None
    ) -> Dict[str, float]:
        """Host-loop utilization from the phase clock: the share of the
        scheduler loop's wall time it was not idle (HOST-loop busy, which
        is not device busy: it holds paging and bookkeeping, and the device
        also works while the host builds the next call), the mean slot
        occupancy of its prefill and decode ticks over that busy time, and
        their product — the serving equivalent of the train ledger's
        goodput × MFU."""
        wall, seconds = (clock or self._clock.snapshot())[:2]
        with self._stats_lock:
            occw = self._occ_weighted_s
        busy = wall - seconds[PH_IDLE]
        busy_frac = busy / wall if wall > 0 else 0.0
        occ = occw / busy if busy > 0 else 0.0
        return {
            "decode_busy_frac": round(busy_frac, 6),
            "slot_occupancy": round(occ, 6),
            "decode_utilization": round(busy_frac * occ, 6),
        }

    @staticmethod
    def _loop_snapshot(clock: PhaseSnapshot, cpu: Optional[float]) -> Dict[str, Any]:
        """The phase clock as flat monotone counters.  ``loop_wall_s`` and,
        per phase, its seconds and the times it was entered: the ``loop_*_s``
        keys alone sum to ``loop_wall_s``.  Beside them, each a part of those
        seconds: ``uncovered_<phase>_s`` (the phase's seconds with nothing
        dispatched to the device; idling is no work, so it has none) and
        their sum ``uncovered_s``, the laps ``decode_host_<lap>_s``, and
        ``host_cpu_s`` / ``host_off_cpu_s`` (``cpu``: the thread's CPU
        seconds outside ``WAIT_PHASES``, and the wall seconds there less them:
        the thread waiting for a CPU or the interpreter lock; absent where
        the platform has no per-thread CPU clock).  Differences of two
        ``/v1/stats`` reads split the window between them."""
        out: Dict[str, Any] = {"loop_wall_s": round(clock.wall, 6)}
        for phase, seconds in clock.seconds.items():
            key = _stats_key(phase)
            out[key + "_s"] = round(seconds, 6)
            out[key + "_n"] = clock.counts[phase]
        uncovered = {
            f"uncovered_{_phase_key(phase)}_s": round(seconds, 6)
            for phase, seconds in clock.uncovered.items()
            if phase != PH_IDLE
        }
        out["uncovered_s"] = round(sum(uncovered.values()), 6)
        out.update(uncovered)
        for lap, seconds in clock.laps.items():
            out[_phase_key(lap) + "_s"] = round(seconds, 6)
        if cpu is not None:
            host = clock.wall - sum(clock.seconds[p] for p in WAIT_PHASES)
            out["host_cpu_s"] = round(cpu, 6)
            out["host_off_cpu_s"] = round(host - cpu, 6)
        return out

    def _paging_snapshot(self) -> Dict[str, Any]:
        """Block-pool / prefix-cache / prefill-backlog state, shared by
        ``stats()``, the Prometheus gauges, and the final ledger row."""
        alloc = self.block_allocator
        total = alloc.num_blocks - 1
        pc = self.prefix_cache
        tier = self._host_tier
        snaps = self._snaps
        with self._stats_lock:
            backlog = self._backlog_chunks
            jobs = self._prefill_jobs
            parks = self._n_parks
            cow = self._n_cow
            keys_attended = self._n_keys_attended
            keys_table = self._n_keys_table
            step_keys_attended = self._n_step_keys_attended
            step_keys_table = self._n_step_keys_table
            cancelled = self._n_cancelled
            shed = self._n_shed
            spilled = self._n_spilled_blocks
            restored = self._n_restored_blocks
            preloaded = self._kv_preloaded_blocks
            persisted = self._kv_persisted_blocks
            state_restores = self._n_state_restores
            window = (
                {"window_pairs": sum(v[1] for v in self._window_shapes.values()),
                 "window_chunk_calls": sum(v[0] for v in self._window_shapes.values()),
                 "window_call_shapes": {
                     str(rows): {"calls": v[0], "pairs": v[1]}
                     for rows, v in sorted(self._window_shapes.items())
                 }}
                if self._window_layers else {}
            )
            moe = dict(self._moe_totals)
            if self._moe:
                moe["moe_call_shapes"] = {
                    str(rows): dict(zip(("calls", "rows_held", "experts_hit"), v))
                    for rows, v in sorted(self._moe_shapes.items())
                }
            now = time.time()
            pc_rate_window = 0.0
            if pc is not None:
                self._pc_window.observe(pc.hits, pc.hits + pc.misses, now)
                windowed = self._pc_window.ratio(self._stats_window_s, now)
                # Window not yet established (one sample): fall back to
                # the lifetime ratio instead of reporting a false zero.
                pc_rate_window = (
                    round(windowed, 6) if windowed is not None
                    else round(pc.hit_rate, 6)
                )
        return {
            "block_size": self.block_size,
            "kv_dtype": self.kv_dtype,
            "kv_pool_bytes": self.kv_pool_bytes,
            "kv_row_bytes": self.kv_row_bytes,
            # Routed experts (absent for a model without): token x choice rows
            # the programs routed, those that fell to experts held here, the
            # largest single expert's rows summed over calls and layers
            # (busiest x experts held / held = the straggler over the mean),
            # the experts that had a row likewise; and ``moe_call_shapes``, by
            # the rows of a call's shape, the expert product's calls with the
            # rows held and the experts hit in them.
            **moe,
            "weight_dtype": self.weight_dtype,
            "weight_bytes": self.weight_bytes,
            "blocks_total": total,
            "blocks_free": alloc.n_free,
            "block_occupancy": (
                round(alloc.n_used / total, 6) if total else 0.0
            ),
            "prefix_cache_blocks": len(pc) if pc is not None else 0,
            "prefix_cache_hit_rate": (
                round(pc.hit_rate, 6) if pc is not None else 0.0
            ),
            "prefix_cache_hit_rate_window": pc_rate_window,
            "prefix_cache_hits": pc.hits if pc is not None else 0,
            "prefix_cache_misses": pc.misses if pc is not None else 0,
            "prefix_cache_evictions": pc.evictions if pc is not None else 0,
            "prefix_cache_demotions": pc.demotions if pc is not None else 0,
            "prefix_cache_restores": (
                pc.demote_restores if pc is not None else 0
            ),
            "parked_sequences": len(self._parked),
            "requests_shed": shed,
            "kv_offload": self.kv_offload,
            "host_tier_blocks": len(tier) if tier is not None else 0,
            "host_tier_bytes": tier.nbytes if tier is not None else 0,
            "host_spilled_blocks_total": spilled,
            "host_restored_blocks_total": restored,
            "kv_preloaded_blocks": preloaded,
            "kv_persisted_blocks": persisted,
            "prefill_backlog_chunks": backlog,
            "prefill_jobs": jobs,
            "block_parks": parks,
            "cow_copies": cow,
            # Key positions the prompt chunks attended against what their block
            # tables span: equal where a chunk attends the whole table, fewer
            # where it stops at its own live end (the latent stack's tiles).
            "prefill_keys_attended": keys_attended,
            "prefill_keys_table": keys_table,
            # The same for the decode steps, over their active lanes: fewer
            # where a step reads each lane's pages up to its own live end (the
            # latent stack, the window stack's full layers), equal where it
            # gathers the table's width a lane.
            "decode_keys_attended": step_keys_attended,
            "decode_keys_table": step_keys_table,
            # A model with window layers (absent for one without): the (query,
            # key) pairs the window kernel admitted over its calls, one call a
            # window layer a chunk; ``window_call_shapes`` the same by the rows
            # of a chunk's shape, which the kernel's name ends in.
            **window,
            "requests_cancelled": cancelled,
            # Recurrent state (all 0 for a dense model): snapshots taken,
            # snapshots restored into a slot, snapshots lost (the store's
            # LRU, or the chain entry they stood on evicted), what the
            # store holds now, and the tokens a KV match had to give back
            # for want of a snapshot.
            "state_snapshots": snaps.taken if snaps is not None else 0,
            "state_restores": state_restores,
            "state_snapshot_evictions": snaps.evictions if snaps is not None else 0,
            "state_store_used": snaps.used if snaps is not None else 0,
            "state_snapshot_bytes": (
                snaps.used * self._state_row_bytes if snaps is not None else 0
            ),
            "prefix_floor_tokens": pc.floor_tokens if pc is not None else 0,
        }

    def _spec_snapshot(self) -> Dict[str, Any]:
        """Speculative-decoding acceptance state, shared by ``stats()``
        (→ ``/v1/stats``), the gauges, and the final ledger row."""
        with self._stats_lock:
            proposed = self._spec_proposed
            accepted = self._spec_accepted
            fallbacks = self._spec_fallbacks
            steps = self._spec_steps
            now = time.time()
            self._spec_window.observe(accepted, proposed, now)
            windowed = self._spec_window.ratio(self._stats_window_s, now)
        lifetime_rate = round(accepted / proposed, 6) if proposed else 0.0
        return {
            "spec_decode": self.spec_decode,
            "spec_k": self.spec_k,
            "spec_steps": steps,
            "spec_proposed_total": proposed,
            "spec_accepted_total": accepted,
            "spec_fallback_total": fallbacks,
            "spec_accept_rate": lifetime_rate,
            "spec_accept_rate_window": (
                round(windowed, 6) if windowed is not None else lifetime_rate
            ),
        }

    def _ledger_account(self, dt: float, occ_frac: float, tokens: int) -> None:
        """Fold one prefill or decode tick into the utilization ledger."""
        with self._stats_lock:
            self._occ_weighted_s += dt * occ_frac
        led = self._ledger
        if led is None:
            return
        led.account("step_compute_s", dt)
        if tokens:
            led.step(tokens=tokens)
        led.merge_extra(**self._utilization_snapshot())
        led.maybe_flush()

    def stats(self) -> Dict[str, Any]:
        # CPU first, so the snapshot's wall seconds hold the CPU seconds: the
        # other way round the gap between the two reads would count against
        # the time the thread waited for a CPU.
        cpu = self._clock.cpu_seconds()
        clock = self._clock.snapshot()
        util = self._utilization_snapshot(clock)
        loop = self._loop_snapshot(clock, cpu)
        paging = self._paging_snapshot()
        spec = self._spec_snapshot()
        with self._stats_lock:
            now = time.time()
            while self._window and now - self._window[0][0] > 10.0:
                self._window.popleft()
            window_tokens = sum(n for _, n in self._window)
            window_span = (
                now - self._window[0][0] if len(self._window) > 1 else 0.0
            )
            tps = window_tokens / window_span if window_span > 0 else 0.0
            return {
                "state": (
                    "failed"
                    if self.start_error is not None
                    else "draining"
                    if self._draining
                    else "ready" if self._ready.is_set() else "warming"
                ),
                "start_error": self.start_error,
                "warmup": {
                    "done": self._warmup_done,
                    "total": self._warmup_total,
                    "ready_s": round(self._warmup_s, 6),
                },
                "steady_state_compiles": self._n_steady_compiles,
                "slots": self.slots,
                "slots_active": self.allocator.n_active,
                "queue_depth": len(self._queue),
                "requests_submitted": self._n_submitted,
                "requests_finished": self._n_finished,
                "tokens_generated": self._n_tokens,
                "decode_steps": self._n_steps,
                "device_reads": self._n_device_reads,
                "device_reads_ready": self._n_device_reads_ready,
                "tokens_per_s": round(tps, 1),
                "max_len": self.max_len,
                "trace_exemplars": self._exemplars.snapshot(),
                **paging,
                **spec,
                **util,
                **loop,
            }

    def latency_summaries(self) -> Dict[str, Dict[str, float]]:
        """Histogram summaries (count/mean/p50/p95/p99) per latency key."""
        summaries_fn = getattr(self.stats_registry, "summaries", None)
        if summaries_fn is None:
            return {}
        wanted = {
            "serving.queue_wait_s": "queue_wait_s",
            "serving.ttft_s": "ttft_s",
            "serving.decode_step_s": "decode_step_s",
            "serving.batch_occupancy": "batch_occupancy",
        }
        out: Dict[str, Dict[str, float]] = {}
        for key, summary in summaries_fn().items():
            if key in wanted:
                out[wanted[key]] = {k: round(v, 6) for k, v in summary.items()}
        return out

    # -- persistent prefix store (warm replica boot) ---------------------------

    @staticmethod
    def _auto_persist_sig(params: Any, qweights: Any, seed: int) -> str:
        """Weight-identity fingerprint for an unsigned persistent store:
        tree structure plus a bounded byte sample (head + tail) of every
        weight leaf — cheap (a few tiny device→host reads) yet it
        changes with the checkpoint, which geometry alone cannot.
        Returns ``""`` when the weights can't be sampled."""
        import hashlib

        import jax

        try:
            h = hashlib.sha256()
            h.update(f"seed:{int(seed)};wq:{qweights is not None};".encode())
            for tree in (params, qweights):
                if tree is None:
                    continue
                leaves, treedef = jax.tree_util.tree_flatten(tree)
                h.update(str(treedef).encode())
                for leaf in leaves:
                    flat = (
                        leaf if hasattr(leaf, "reshape") else np.asarray(leaf)
                    ).reshape(-1)
                    sample = np.concatenate(
                        [
                            np.asarray(jax.device_get(flat[:16])),
                            np.asarray(jax.device_get(flat[-16:])),
                        ]
                    )
                    h.update(str(sample.dtype).encode())
                    h.update(str(flat.shape).encode())
                    h.update(sample.tobytes())
            return "auto:" + h.hexdigest()[:16]
        except Exception:
            return ""

    def _kv_store_meta(self) -> Dict[str, Any]:
        """The compatibility fingerprint a snapshot must match exactly:
        pool geometry + storage dtype (shape compatibility) and the
        caller's model signature (weight identity — geometry alone can't
        tell two checkpoints apart)."""
        c = self.cfg
        return {
            "sig": self.kv_persist_sig,
            "kv_dtype": self.kv_dtype,
            "block_size": self.block_size,
            "n_layers": int(c.n_layers),
            "kv_heads": int(c.kv_heads),
            "head_dim": int(c.head_dim),
            "vocab_size": int(c.vocab_size),
            # a latent pool's row is sized by neither of the two above
            **({"kv_row_bytes": self.kv_row_bytes} if c.stack == "latent" else {}),
        }

    def persist_prefixes(self) -> int:
        """Snapshot the hottest prefix-cache blocks (chain-closed, see
        ``PrefixCache.hottest_chains``) to ``kv_persist_dir``; returns
        blocks written.  Demoted entries persist straight from their
        host payloads — no device traffic.  Must run on whichever thread
        owns the pool (the scheduler loop, or any thread after join)."""
        pc = self.prefix_cache
        if not self.kv_persist_dir or pc is None:
            return 0
        from polyaxon_tpu.serving import kvstore

        entries = []
        for chain, block, handle in pc.hottest_chains(self.kv_persist_blocks):
            if block >= 0:
                [data] = self._export_blocks([block])
            elif handle is not None and self._host_tier is not None:
                data = self._host_tier.get(handle)
            else:
                continue
            entries.append((chain, data))
        if not entries:
            return 0
        version = kvstore.save_prefix_store(
            self.kv_persist_dir, entries, meta=self._kv_store_meta()
        )
        if version is None:
            return 0
        self._last_persist_t = time.monotonic()
        self._last_persist_mut = pc.mutations
        with self._stats_lock:
            self._kv_persisted_blocks = len(entries)
        return len(entries)

    def _maybe_persist(self, force: bool = False) -> None:
        """Throttled best-effort snapshot: at most one per
        ``POLYAXON_TPU_KV_PERSIST_INTERVAL_S``, and only when the cache
        changed since the last write.  The scheduler calls this from its
        idle branch — incumbents must publish while still RUNNING,
        because scale-up replicas boot exactly when nobody is stopping."""
        pc = self.prefix_cache
        if not self.kv_persist_dir or pc is None or not len(pc):
            return
        # Content churn at constant size (evict+offer of different
        # prefixes, demotions/restores) must re-persist, so freshness
        # keys off the cache's mutation counter, never its len().
        if pc.mutations == self._last_persist_mut:
            return
        if not force:
            now = time.monotonic()
            if now - self._last_persist_t < self._kv_persist_interval_s:
                return
        try:
            self.persist_prefixes()
        except Exception:
            pass

    def _preload_prefixes(self) -> None:
        """Warm boot: hydrate the prefix cache from the newest complete
        snapshot under ``kv_persist_dir`` (scheduler thread, before the
        ready gate).  Loads stop at pool pressure — a preload must never
        starve live admissions of their whole pool."""
        pc = self.prefix_cache
        if not self.kv_persist_dir or pc is None:
            return
        from polyaxon_tpu.serving import kvstore

        loaded = kvstore.load_prefix_store(
            self.kv_persist_dir, expect=self._kv_store_meta()
        )
        if not loaded:
            return
        n = 0
        # Never fill the pool completely: leave at least half for live
        # traffic (preloaded entries are refcount-1 cache entries, so
        # demotion/eviction can reclaim them, but starting gridlocked
        # would stall first admissions behind evictions).
        budget = max(0, (self.block_allocator.num_blocks - 1) // 2)
        for chain, data in loaded:
            if n >= budget:
                break
            block = self.block_allocator.alloc()
            if block is None:
                break
            self._import_block(block, data)
            if not pc.install(chain, block):
                continue
            n += 1
        with self._stats_lock:
            self._kv_preloaded_blocks = n
        # A freshly preloaded cache equals the stored one — don't turn
        # around and persist it right back.
        self._last_persist_mut = pc.mutations
        self._last_persist_t = time.monotonic()

    # -- scheduler loop --------------------------------------------------------

    def _loop(self) -> None:
        self._run_warmup()
        if self.start_error is not None:
            self._fail_start()
            return
        self._clock.start()
        try:
            self._serve()
        finally:
            self._clock.stop()

    def _serve(self) -> None:
        """The scheduler loop proper.  What it does itself is phase
        ``serving.loop.other``; every callee enters its own."""
        idle = self._clock.phase(PH_IDLE)
        while not self._stop.is_set():
            self._process_cancels()
            self._admit()
            progressed = self._resume_parked()
            # Prefill under a per-iteration TOKEN BUDGET of one chunk:
            # either a single chunk of a long prompt, or several whole
            # short prompts coalesced — a burst of shorts doesn't pay a
            # decode-step round-trip each, while device time between
            # decode steps stays bounded.  Jobs are picked shortest-
            # remaining-work-first: chunk boundaries are preemption
            # points, so a short prompt arriving behind a half-done long
            # one overtakes it instead of waiting out the whole thing.
            # (min() is stable — equal-length jobs stay FIFO.)
            budget = self.prefill_chunk or 0
            spent = 0
            while self._prefill:
                job = min(
                    self._prefill,
                    key=lambda j: len(j.req.prompt) - j.next_pos,
                )
                if job is not self._prefill[0]:
                    self._prefill.remove(job)
                    self._prefill.appendleft(job)
                remaining = len(job.req.prompt) - job.next_pos
                spent += min(remaining, budget) if budget else remaining
                try:
                    did = self._prefill_tick()
                except Exception as e:
                    if self._prefill and self._prefill[0] is job:
                        self._prefill.popleft()
                    self._fail_slot(job.slot, f"prefill failed: {e!r}")
                    progressed = True
                    break
                if not did:
                    break  # blocked on the block pool; retry next iteration
                progressed = True
                if not budget or spent >= budget:
                    break
            if self._active.any():
                try:
                    self._step_once()
                except Exception as e:  # fail in-flight, keep serving
                    for slot in np.nonzero(self._active)[0]:
                        self._fail_slot(int(slot), f"decode step failed: {e!r}")
                continue
            if progressed:
                continue
            if self._parked or self._prefill:
                # Nothing active, nothing moved, eviction already tried:
                # the requests still waiting on blocks are deadlocked —
                # shed one so the rest can make progress.
                self._resolve_block_deadlock()
                continue
            # Fully idle: a good moment to snapshot the prefix store
            # (throttled; scale-up replicas preload whatever incumbents
            # last published).
            self._maybe_persist()
            self._clock.anchor()
            with self._cv:
                if not self._queue and not self._stop.is_set():
                    with idle:
                        self._cv.wait(timeout=0.2)

    def _fail_start(self) -> None:
        """A failed warmup serves nothing: whoever queued while warming
        gets the start error instead of waiting on a dead scheduler."""
        with self._cv:
            pending = list(self._queue)
            self._queue.clear()
        for req in pending:
            self._fail_request(
                req,
                f"engine failed to start: {self.start_error}",
                "engine_error",
                "failed",
            )

    def _fail_request(
        self, req: GenerationRequest, msg: str, kind: str, outcome: str
    ) -> None:
        """Resolve a request that holds no slot (queued, or being drained
        at stop): its error, its trace, and exactly ONE stream sentinel."""
        req.error = msg
        req.error_kind = kind
        self._finalize_trace(req, outcome)
        req.stream.put(None)
        req.done.set()

    @_in_phase(PH_ADMIT)
    def _admit(self) -> None:
        """Move queued requests into free slots (queue order) and enqueue
        their prefill jobs; the prefix cache shortens a job to its first
        uncached block."""
        clock = self._clock
        while True:
            with self._cv:
                if not self._queue:
                    return
                slot = self.allocator.alloc()
                if slot is None:
                    return
                req = self._queue.popleft()
            req.started_at = time.time()
            with clock.phase(PH_BOOKKEEPING):
                self.stats_registry.timing(
                    "serving.queue_wait_s", req.started_at - req.submitted_at
                )
                self._trace_span(
                    req,
                    "serving.queue_wait",
                    req.submitted_at,
                    req.started_at - req.submitted_at,
                )
                self._trace_span(
                    req, "serving.admit", req.started_at, 0.0, slot=slot
                )
            self._slot_req[slot] = req
            # Speculative path selection is typed per request at
            # admission: greedy requests get a drafter (its suffix index
            # seeded from the prompt here — the prefix-cache path may
            # skip recomputing matched tokens, but the drafter must
            # still see them); sampled requests must see the model's
            # true distribution every step, so they transparently ride
            # single-token rows instead.
            if self.spec_decode:
                if req.temperature > 0:
                    req.spec_mode = SPEC_MODE_FALLBACK_SAMPLED
                    with self._stats_lock:
                        self._spec_fallbacks += 1
                    incr = getattr(self.stats_registry, "incr", None)
                    if incr is not None:
                        incr("serving.spec_fallback_total", 1)
                else:
                    req.spec_mode = SPEC_MODE_GREEDY
                    drafter = NgramDrafter(self.spec_min_ngram)
                    drafter.extend(req.prompt)
                    self._drafters[slot] = drafter
            job = _PrefillJob(req, slot)
            if self.prefix_cache is not None:
                place = None
                with clock.phase(PH_MATCH):
                    if self._snaps is not None:
                        # KV can resume at any block, the recurrent layers
                        # only at a snapshot: the hit ends at the newest.
                        matched, place = self.prefix_cache.match_with_state(
                            req.prompt
                        )
                    else:
                        matched = self.prefix_cache.match(req.prompt)
                if place is not None:
                    self._restore_state(slot, place)
                for i, block in enumerate(matched):
                    self._tables[slot, i] = block
                m = len(matched) * self.block_size
                if matched:
                    self._trace_span(
                        req,
                        "serving.prefix_cache.hit",
                        time.time(),
                        0.0,
                        blocks=len(matched),
                        tokens=m,
                    )
                if m and m == len(req.prompt):
                    # Every prompt block hit.  The last token's LOGITS
                    # still must be recomputed, and its KV row lands in
                    # the final SHARED block — copy it private first
                    # (copy-on-write), then re-run just that one token.
                    job.cow_pending = True
                    job.next_pos = m - 1
                else:
                    job.next_pos = m
            self._prefill.append(job)
            self._record_gauges()

    @_in_phase(PH_RESTORE)
    def _restore_state(self, slot: int, place: int) -> None:
        """Copy snapshot ``place`` into ``slot``'s recurrent rows: the
        request's prefill goes on from there."""
        self._pool = self._get_restore()(
            self._pool, self._snap_store, np.int32(place), np.int32(slot)
        )
        self._clock.dispatched()
        with self._stats_lock:
            self._n_state_restores += 1

    @_in_phase(PH_SNAPSHOT)
    def _snapshot_state(self, slot: int, pos: int) -> None:
        """Keep ``slot``'s recurrent rows as they stand after ``pos`` prompt
        tokens (the chunk that got there is dispatched; the copy is ordered
        after it).  Pending until the prompt is in and its blocks are
        offered; skipped when every place of the store is pending."""
        place = self._snaps.alloc()
        if place is None:
            return
        self._snap_store = self._get_snapshot()(
            self._snap_store, self._pool, np.int32(slot), np.int32(place)
        )
        self._clock.dispatched()
        self._pending_snaps.setdefault(slot, {})[pos] = place

    def _release_pending_snapshots(self, slot: int) -> None:
        for place in self._pending_snaps.pop(slot, {}).values():
            self._snaps.release(place)

    @_in_phase(PH_ALLOC)
    def _alloc_block(self) -> Optional[int]:
        """Allocate one pool block, evicting a cold cached prefix if the
        free list is empty."""
        block = self.block_allocator.alloc()
        if block is None and self.prefix_cache is not None:
            if self.prefix_cache.evict(1):
                block = self.block_allocator.alloc()
        return block

    @_in_phase(PH_PREFILL_HOST)
    def _prefill_tick(self) -> bool:
        """Run ONE chunk of the oldest pending prefill.  Returns True if
        the device did work; False means the job is blocked on the block
        pool (it stays at the head and retries next iteration)."""
        clock = self._clock
        t0 = clock.t  # the transition into this phase
        bookkeeping = clock.phase(PH_BOOKKEEPING)
        job = self._prefill[0]
        req, slot = job.req, job.slot
        bs = self.block_size
        t = len(req.prompt)
        if job.cow_pending:
            fresh = self._alloc_block()
            if fresh is None:
                return False
            bi = (t - 1) // bs
            shared = int(self._tables[slot, bi])
            self._pool = self._get_copy()(
                self._pool, np.int32(shared), np.int32(fresh)
            )
            clock.dispatched()
            with clock.phase(PH_ALLOC):
                self.block_allocator.decref(shared)
            self._tables[slot, bi] = fresh
            job.cow_pending = False
            with self._stats_lock:
                self._n_cow += 1
        n = t - job.next_pos
        if self.prefill_chunk:
            n = min(n, self.prefill_chunk)
        if self._snaps is not None:
            # A chunk ends where a snapshot is due, whatever its start.
            n = min(n, self._snap_every - job.next_pos % self._snap_every)
        # Lazy block faults for the chunk's span; partial allocations are
        # kept on exhaustion (the retry only fills what's still unset).
        first_bi = job.next_pos // bs
        last_bi = (job.next_pos + n - 1) // bs
        for bi in range(first_bi, last_bi + 1):
            if self._tables[slot, bi] < 0:
                fresh = self._alloc_block()
                if fresh is None:
                    return False
                self._tables[slot, bi] = fresh
        c_pad = self._bucket(n, self.max_len)
        chunk = np.zeros(c_pad, np.int32)
        chunk[:n] = req.prompt[job.next_pos : job.next_pos + n]
        table = np.where(self._tables[slot] >= 0, self._tables[slot], 0)
        logits, self._pool, *counts = self._get_chunk(c_pad)(
            self._params,
            self._pool,
            *self._chunk_args(table.astype(np.int32), chunk, job.next_pos, n, slot),
        )
        clock.dispatched()
        self._note_counts(c_pad, counts)
        job.next_pos += n
        with self._stats_lock:
            self._n_keys_attended += self._chunk_keys(job.next_pos)
            self._n_keys_table += self._table_width * bs
            if self._window_layers:
                shape = self._window_shapes.setdefault(c_pad, [0, 0])
                shape[0] += self._window_layers
                shape[1] += self._window_layers * self._stack.chunk_window_pairs(
                    self.cfg, job.next_pos - n, n
                )
        if self._snaps is not None and job.next_pos % self._snap_every == 0:
            self._snapshot_state(slot, job.next_pos)
        done = job.next_pos >= t
        with bookkeeping as t1:
            self._trace_span(
                req,
                "serving.prefill.chunk",
                clock.epoch + t0,
                t1 - t0,
                tokens=n,
                pos=job.next_pos,
            )
            # The tick serves one request; only the final chunk
            # emits a token.
            self._ledger_account(
                t1 - t0, 1.0 / self.slots, tokens=1 if done else 0
            )
        if done:
            self._prefill.popleft()
            # Every chunk before this one was only dispatched: here
            # the host waits for the device to finish the prompt.
            logits = self._host_read(logits)
            self._finalize_prefill(job, logits)
        with bookkeeping:
            self._record_gauges()
            self._progress.beat(step=self._n_steps)
        return True

    def _finalize_prefill(self, job: _PrefillJob, logits: np.ndarray) -> None:
        """Prompt fully inserted: publish its blocks, pick the first
        token from the last chunk's logits, activate the slot."""
        req, slot = job.req, job.slot
        t = len(req.prompt)
        clock = self._clock
        if self.prefix_cache is not None:
            with clock.phase(PH_OFFER):
                full = t // self.block_size
                self.prefix_cache.offer(
                    req.prompt,
                    [int(self._tables[slot, i]) for i in range(full)],
                    self._pending_snaps.pop(slot, None),
                )
        with clock.phase(PH_EMIT):
            first = self._pick_first(logits, req.temperature)
            # Time-to-first-token: prefill produced it, the client can read it.
            ttft = time.time() - req.submitted_at
            with clock.phase(PH_BOOKKEEPING):
                self.stats_registry.timing("serving.ttft_s", ttft)
                if req.trace is not None:
                    req.trace.ttft_s = ttft
                    self._trace_span(
                        req, "serving.first_token", time.time(), 0.0,
                        ttft_s=round(ttft, 6),
                    )
            self._emit(slot, req, first)
            if not req.done.is_set():
                self._tok[slot] = first
                self._pos[slot] = t
                self._temps[slot] = req.temperature
                self._active[slot] = True

    def _pick_first(self, logits: np.ndarray, temperature: float) -> int:
        """First generated token comes from the prefill logits (exactly
        like ``generate()``'s post-prefill pick)."""
        if temperature <= 0.0:
            return int(logits.argmax())
        z = logits.astype(np.float64) / temperature
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    @_in_phase(PH_OTHER)
    def _park(self, slot: int) -> None:
        """Pool exhausted at a block boundary: deactivate the slot with
        its state intact.  The active mask is data, so parking and
        resuming never recompile.  With the host tier armed, the slot's
        PRIVATE blocks (refcount 1 — shared prefix blocks stay resident,
        they cost the parked slot nothing) spill to pinned host memory
        and their device blocks free: parking RELEASES capacity instead
        of sitting on it, so an oversubscribed pool trades restore
        latency for sheds."""
        self._active[slot] = False
        self._parked.append(slot)
        req = self._slot_req[slot]
        if req is not None and req.trace is not None:
            req.trace.parked_at = time.time()
        with self._stats_lock:
            self._n_parks += 1
        if self._host_tier is not None:
            self._spill_slot(slot)

    def _spill_slot(self, slot: int) -> None:
        """Move a parked slot's private blocks to the host tier (pinned)."""
        alloc = self.block_allocator
        spill_bi: List[int] = []
        for bi in range(self._table_width):
            block = int(self._tables[slot, bi])
            if block >= 0 and alloc.refcount(block) == 1:
                spill_bi.append(bi)
        if not spill_bi:
            return
        payloads = self._export_blocks(
            [int(self._tables[slot, bi]) for bi in spill_bi]
        )
        handles = self._spilled.setdefault(slot, {})
        for bi, data in zip(spill_bi, payloads):
            handles[bi] = self._host_tier.put(data, pinned=True)
            with self._clock.phase(PH_ALLOC):
                alloc.decref(int(self._tables[slot, bi]))
            self._tables[slot, bi] = -1
        req = self._slot_req[slot]
        if req is not None:
            self._trace_span(
                req, "serving.spill", time.time(), 0.0, blocks=len(spill_bi)
            )
        with self._stats_lock:
            self._n_spilled_blocks += len(spill_bi)

    def _restore_slot(self, slot: int) -> tuple:
        """Stream a parked slot's spilled blocks back (host→device into
        fresh blocks).  ALL-OR-NOTHING: restoration starts only once the
        pool — after demoting/evicting cold prefixes — covers the slot's
        whole remaining need, faulted pos block included.  A partial
        restore would hold device blocks while still parked, and that
        hold-and-wait livelocks against a mid-prefill job holding the
        rest: the restore pass runs FIRST each loop, so it re-grabs
        every block the prefill frees and neither side ever finishes.
        Refusing to start leaves the free list to whoever can actually
        use it.  Returns ``(moved, complete)``."""
        handles = self._spilled.get(slot)
        if not handles:
            return False, True
        need = len(handles)
        bi_pos = int(self._pos[slot]) // self.block_size
        if self._tables[slot, bi_pos] < 0 and bi_pos not in handles:
            need += 1  # the faulted pos block resumes alongside
        alloc = self.block_allocator
        clock = self._clock
        if alloc.n_free < need and self.prefix_cache is not None:
            with clock.phase(PH_ALLOC):
                self.prefix_cache.evict(need - alloc.n_free)
        if alloc.n_free < need:
            return False, False
        n_restore = len(handles)
        with clock.phase(PH_OTHER) as t0:
            for bi in sorted(handles):
                fresh = self._alloc_block()
                self._import_block(fresh, self._host_tier.pop(handles.pop(bi)))
                self._tables[slot, bi] = fresh
            self._spilled.pop(slot, None)
        req = self._slot_req[slot]
        if req is not None:
            self._trace_span(
                req,
                "serving.restore",
                clock.epoch + t0,
                clock.t - t0,
                blocks=n_restore,
            )
        return True, True

    def _resume_parked(self) -> bool:
        """Give parked slots another shot at their faulted block, oldest
        first (spilled blocks restore before the fault retries — the
        sequence needs its whole KV back to decode)."""
        progressed = False
        for slot in list(self._parked):
            moved, complete = self._restore_slot(slot)
            if moved:
                progressed = True
            if not complete:
                continue
            bi = int(self._pos[slot]) // self.block_size
            if self._tables[slot, bi] < 0:
                fresh = self._alloc_block()
                if fresh is None:
                    continue
                self._tables[slot, bi] = fresh
            self._unpark(slot)
            self._active[slot] = True
            progressed = True
        return progressed

    def _unpark(self, slot: int) -> None:
        """The ONE bookkeeping site for leaving the parked list — resume,
        retire, and failure all funnel here, so parked-list membership
        and the spill map can never drift apart.  Any payload still
        spilled is discarded (the resume path has already drained its
        map; retire/fail genuinely abandon theirs)."""
        if slot in self._parked:
            self._parked.remove(slot)
            req = self._slot_req[slot]
            rt = req.trace if req is not None else None
            if rt is not None and rt.parked_at is not None:
                parked_s = time.time() - rt.parked_at
                rt.park_s += parked_s
                rt.parked_at = None
                self._trace_span(
                    req, "serving.park", time.time() - parked_s, parked_s
                )
        handles = self._spilled.pop(slot, None)
        if handles and self._host_tier is not None:
            for handle in handles.values():
                self._host_tier.discard(handle)

    def _resolve_block_deadlock(self) -> None:
        """Nobody active, nobody progressing, eviction exhausted: shed
        the newest parked request (it holds blocks, so shedding is
        guaranteed to free some), else the head prefill job.

        Newest-parked is the DELIBERATE victim policy, not an accident
        of list order: the newest parked slot has the least compute
        invested and the oldest has waited longest (parking order is
        arrival order at the wall), so LIFO shedding minimizes wasted
        work while keeping rough arrival fairness for the survivors —
        the same reasoning as classic LIFO preemption under overload.
        With the host tier armed a parked slot has already spilled its
        private blocks, so this path fires only when host+device
        together can't cover the working set (the tier makes sheds
        rare, not cheap).  A FULLY-spilled parked slot holds no device
        blocks at all — shedding it frees nothing — so the victim scan
        prefers parked slots still holding blocks, then the head
        prefill job (whose partial KV is what a true prefill gridlock
        is made of), and only then a spilled slot (unservable: the pool
        can't cover its restore even with everything else idle)."""
        holding = [
            slot
            for slot in self._parked
            if bool((self._tables[slot] >= 0).any())
        ]
        if holding:
            self._fail_slot(
                holding[-1],
                "KV block pool exhausted (request shed)",
                kind="shed",
            )
            return
        if self._prefill:
            job = self._prefill.popleft()
            self._fail_slot(
                job.slot,
                "KV block pool exhausted (request shed)",
                kind="shed",
            )
            return
        if self._parked:
            self._fail_slot(
                self._parked[-1],
                "KV block pool exhausted (request shed)",
                kind="shed",
            )

    def _process_cancels(self) -> None:
        """Apply cancellations to in-flight requests (scheduler thread:
        it owns the tables and allocators)."""
        with self._cv:
            if not self._cancels:
                return
            ids, self._cancels = self._cancels, set()
        for rid in ids:
            for job in list(self._prefill):
                if job.req.id == rid:
                    self._prefill.remove(job)
            for slot, req in enumerate(self._slot_req):
                if req is not None and req.id == rid:
                    self._fail_slot(slot, "request cancelled", kind="cancelled")
                    with self._stats_lock:
                        self._n_cancelled += 1
        self._record_gauges()

    @_in_phase(PH_DECODE_HOST)
    def _step_once(self) -> None:
        clock = self._clock
        t0 = clock.t  # the transition into this phase
        clock.lap(LAP_INPUTS)
        bs = self.block_size
        # Block-boundary faults: a slot whose next write crosses into an
        # unallocated block needs one now — or parks until the pool can
        # provide it.
        for slot in np.nonzero(self._active)[0]:
            slot = int(slot)
            bi = int(self._pos[slot]) // bs
            if self._tables[slot, bi] < 0:
                fresh = self._alloc_block()
                if fresh is None:
                    self._park(slot)
                else:
                    self._tables[slot, bi] = fresh
                clock.lap(LAP_INPUTS)  # the fault was paging's; the loop goes on
        if not self._active.any():
            return
        drafts: Dict[int, List[int]] = {}
        if self.spec_decode:
            drafts = self._collect_drafts()
            clock.lap(LAP_INPUTS)
        participants = [
            self._slot_req[int(s)]
            for s in np.nonzero(self._active)[0]
            if self._slot_req[int(s)] is not None
            and self._slot_req[int(s)].trace is not None
        ]
        tables = np.where(self._tables >= 0, self._tables, 0).astype(np.int32)
        n_live = int(self._active.sum())
        with self._stats_lock:
            self._n_step_keys_attended += self._step_keys(self._pos[self._active] + 1)
            self._n_step_keys_table += n_live * self._table_width * bs
        clock.lap(LAP_KEY)
        key = self._draw_key()
        emitted = 0
        if drafts:
            emitted = self._verify_once(drafts, tables, key)
        else:
            clock.lap(LAP_UPLOAD)
            packed = self._pack_step(tables, key)
            clock.lap(LAP_DISPATCH)
            toks, self._pool, *counts = self._step_fn(
                self._params, self._pool, packed, self._qweights
            )
            clock.dispatched()
            self._note_counts(self.slots, counts)
            toks = self._host_read(toks)  # host sync — the loop's one device read
            with clock.phase(PH_EMIT):
                for slot in np.nonzero(self._active)[0]:
                    slot = int(slot)
                    req = self._slot_req[slot]
                    tok = int(toks[slot])
                    self._pos[slot] += 1
                    self._tok[slot] = tok
                    self._emit(slot, req, tok)
                    emitted += 1
        with clock.phase(PH_BOOKKEEPING) as t1:
            with self._stats_lock:
                self._n_steps += 1
                self._window.append((time.time(), emitted))
            # The step advances every live slot ≥1 token, so its wall time
            # (block faults and drafting included) IS the per-token decode
            # latency each of those requests observed (amortized over the
            # accept run on speculative steps).
            step_dt = t1 - t0
            self.stats_registry.timing("serving.decode_step_s", step_dt)
            self.stats_registry.observe("serving.batch_occupancy", float(n_live))
            # Per-request decode-step spans ride at the hot-sample rate; the
            # waterfall's decode phase is interval-based, so these are pure
            # detail and sampling them away loses nothing but zoom.
            for req in participants:
                self._trace_hot(
                    req,
                    "serving.decode.step",
                    clock.epoch + t0,
                    step_dt,
                    batch=n_live,
                )
            self._ledger_account(step_dt, n_live / self.slots, tokens=emitted)
            self._record_gauges()
            if self._ready.is_set():
                self._capture.on_step(self._n_steps)
            self._progress.beat(step=self._n_steps)

    @_in_phase(PH_OTHER)
    def _collect_drafts(self) -> Dict[int, List[int]]:
        """Ask each active greedy lane's drafter for a proposal, clipped
        to the request's remaining budget (emits = accepts + 1 can never
        overshoot ``max_new_tokens``) and to the KV blocks the pool can
        actually cover — pool pressure degrades a draft to fewer tokens
        (ultimately a plain single-token step) instead of parking."""
        drafts: Dict[int, List[int]] = {}
        bs = self.block_size
        for slot in np.nonzero(self._active)[0]:
            slot = int(slot)
            drafter = self._drafters[slot]
            if drafter is None:
                continue
            req = self._slot_req[slot]
            budget = req.max_new_tokens - len(req.tokens)
            k = min(self.spec_k, budget - 1)
            if k < 1:
                continue
            prop = drafter.draft(k)
            if not prop:
                continue
            # Block faults for the draft span (row j writes pos+j; the
            # pos block was faulted by the caller's boundary loop).
            pos = int(self._pos[slot])
            for j in range(1, len(prop) + 1):
                bi = (pos + j) // bs
                if self._tables[slot, bi] < 0:
                    fresh = self._alloc_block()
                    if fresh is None:
                        prop = prop[: j - 1]
                        break
                    self._tables[slot, bi] = fresh
            if prop:
                drafts[slot] = prop
                self._trace_hot(
                    req, "serving.spec.draft", time.time(), 0.0,
                    proposed=len(prop),
                )
        return drafts

    def _verify_once(
        self, drafts: Dict[int, List[int]], tables: np.ndarray, key: np.ndarray
    ) -> int:
        """One draft→verify→rollback iteration: score every lane's run
        in a single forward pass, append the accepted tokens, truncate
        each table past its rolled-back write position.  Returns tokens
        emitted."""
        clock = self._clock
        clock.lap(LAP_INPUTS)
        width = self._width_for(max(len(p) for p in drafts.values()))
        tok_in = np.zeros((self.slots, width), np.int32)
        tok_in[:, 0] = self._tok
        n_tok = np.ones(self.slots, np.int32)
        for slot, prop in drafts.items():
            tok_in[slot, 1 : 1 + len(prop)] = prop
            n_tok[slot] = 1 + len(prop)
        clock.lap(LAP_UPLOAD)
        args = (tables, tok_in, self._pos, n_tok, self._active, self._temps, key)
        clock.lap(LAP_DISPATCH)
        out, n_emit, self._pool = self._get_verify(width)(
            self._params, self._pool, *args, self._qweights
        )
        clock.dispatched()
        with clock.phase(PH_DEVICE_WAIT) as t0:
            out = np.asarray(out)  # host sync — the loop's one device read
            n_emit = np.asarray(n_emit)
            self._read_returned(t0)
        emitted = 0
        n_proposed = n_accepted = 0
        observe = getattr(self.stats_registry, "observe", None)
        with clock.phase(PH_EMIT):
            for slot in np.nonzero(self._active)[0]:
                slot = int(slot)
                req = self._slot_req[slot]
                e = int(n_emit[slot])
                prop = drafts.get(slot)
                if prop is not None:
                    n_proposed += len(prop)
                    n_accepted += e - 1
                    with clock.phase(PH_BOOKKEEPING):
                        if observe is not None:
                            observe("serving.spec_accept_len", float(e - 1))
                        self._trace_hot(
                            req,
                            "serving.spec.verify",
                            time.time(),
                            0.0,
                            proposed=len(prop),
                            accepted=e - 1,
                        )
                self._pos[slot] += e
                self._tok[slot] = int(out[slot, e - 1])
                # Rollback: rows past the accept run are garbage; blocks
                # wholly beyond the next write position go back to the pool.
                with clock.phase(PH_ALLOC):
                    truncate_table(
                        self._tables[slot],
                        self.block_allocator,
                        int(self._pos[slot]),
                        self.block_size,
                    )
                for j in range(e):
                    self._emit(slot, req, int(out[slot, j]))
                    emitted += 1
                    if req.done.is_set():
                        break  # eos/budget retired the slot mid-run
        with clock.phase(PH_BOOKKEEPING):
            with self._stats_lock:
                self._spec_steps += 1
                self._spec_proposed += n_proposed
                self._spec_accepted += n_accepted
            incr = getattr(self.stats_registry, "incr", None)
            if incr is not None:
                if n_proposed:
                    incr("serving.spec_proposed_total", n_proposed)
                if n_accepted:
                    incr("serving.spec_accepted_total", n_accepted)
        return emitted

    @_in_phase(PH_BOOKKEEPING)
    def _record_gauges(self) -> None:
        """Refresh paging gauges + backlog counters (scheduler thread)."""
        self._check_steady_compiles()
        backlog = 0
        for job in self._prefill:
            remaining = len(job.req.prompt) - job.next_pos
            step = self.prefill_chunk or max(remaining, 1)
            backlog += max(1, -(-remaining // step))
        with self._stats_lock:
            self._backlog_chunks = backlog
            self._prefill_jobs = len(self._prefill)
        gauge = getattr(self.stats_registry, "gauge", None)
        if gauge is None:
            return
        alloc = self.block_allocator
        total = alloc.num_blocks - 1
        gauge(
            "serving.block_occupancy",
            round(alloc.n_used / total, 6) if total else 0.0,
        )
        gauge("serving.blocks_free", float(alloc.n_free))
        gauge("serving.kv_pool_bytes", float(self.kv_pool_bytes))
        pc = self.prefix_cache
        gauge(
            "serving.prefix_cache_hit_rate",
            round(pc.hit_rate, 6) if pc is not None else 0.0,
        )
        gauge("serving.prefill_backlog_chunks", float(backlog))
        gauge("serving.parked_sequences", float(len(self._parked)))
        if pc is not None:
            gauge("serving.prefix_cache_evictions", float(pc.evictions))
            gauge("serving.prefix_cache_demotions", float(pc.demotions))
            gauge("serving.prefix_cache_restores", float(pc.demote_restores))
        if self._host_tier is not None:
            gauge("serving.host_tier_blocks", float(len(self._host_tier)))
            gauge("serving.host_tier_bytes", float(self._host_tier.nbytes))
        if self.spec_decode:
            with self._stats_lock:
                proposed, accepted = self._spec_proposed, self._spec_accepted
            gauge(
                "serving.spec_accept_rate",
                round(accepted / proposed, 6) if proposed else 0.0,
            )

    # -- request-scoped tracing ------------------------------------------------

    def _trace_span(
        self,
        req: GenerationRequest,
        name: str,
        start: float,
        duration: float,
        **attrs: Any,
    ) -> None:
        """Record one phase span under the request's trace (no-op for
        untraced requests)."""
        rt = req.trace
        if rt is None:
            return
        get_tracer().record_span(
            name,
            start=start,
            duration=duration,
            trace_id=rt.ctx.trace_id,
            parent_id=rt.root_id,
            request_id=req.id,
            **attrs,
        )

    def _trace_hot(
        self,
        req: GenerationRequest,
        name: str,
        start: float,
        duration: float,
        **attrs: Any,
    ) -> None:
        """Hot-path phase span (per decode step / spec verify): recorded
        at the tracer's hot-sample rate.  Waterfall phase accounting is
        interval-based and never depends on these, so sampling them away
        cannot break the waterfall sums."""
        rt = req.trace
        if rt is None:
            return
        rate = get_tracer().hot_sample
        if rate < 1.0 and (rate <= 0.0 or random.random() >= rate):
            return
        self._trace_span(req, name, start, duration, **attrs)

    def _finalize_trace(self, req: GenerationRequest, outcome: str) -> None:
        """Close the request's trace: emit the root span, build the
        latency waterfall, and offer it to the slow-request exemplars.

        Runs for every terminal path — finish, shed, cancel, engine
        stop, deadlock shed — so a traced request can never leak an
        open span."""
        rt = req.trace
        if rt is None or req.trace_summary is not None:
            return
        now = req.finished_at if req.finished_at is not None else time.time()
        req.finished_at = now
        if rt.parked_at is not None:  # failed while parked
            rt.park_s += now - rt.parked_at
            rt.parked_at = None
        total = max(0.0, now - req.submitted_at)
        started = req.started_at
        first = req.first_token_at
        waterfall: Dict[str, float] = {
            "queue_wait_s": max(
                0.0, (started if started is not None else now) - req.submitted_at
            ),
        }
        if started is not None:
            prefill_end = first if first is not None else now
            waterfall["prefill_s"] = max(0.0, prefill_end - started)
        if first is not None:
            waterfall["decode_s"] = max(0.0, now - first - rt.park_s)
        if rt.park_s > 0:
            waterfall["parked_s"] = rt.park_s
        # The request root span: its id is what every phase span parents
        # to; its own parent is the remote caller's span (router attempt
        # or lm_server handler), stitching the cross-process timeline.
        get_tracer().record_span(
            "serving.request",
            start=req.submitted_at,
            duration=total,
            trace_id=rt.ctx.trace_id,
            span_id=rt.root_id,
            parent_id=rt.ctx.span_id or None,
            request_id=req.id,
            outcome=outcome,
            tokens=len(req.tokens),
        )
        self._trace_span(
            req, "serving.finish", now, 0.0, outcome=outcome
        )
        req.trace_summary = {
            "trace_id": rt.ctx.trace_id,
            "span_id": rt.root_id,
            "request_id": req.id,
            "outcome": outcome,
            "total_s": round(total, 6),
            "ttft_s": (
                round(rt.ttft_s, 6) if rt.ttft_s is not None else None
            ),
            "tokens": len(req.tokens),
            "finished_at": now,
            "waterfall": {k: round(v, 6) for k, v in waterfall.items()},
        }
        self._exemplars.offer(req.trace_summary)

    def _emit(self, slot: int, req: GenerationRequest, tok: int) -> None:
        """Record one generated token; retire the slot when done."""
        if req.first_token_at is None:
            req.first_token_at = time.time()
        drafter = self._drafters[slot]
        if drafter is not None:
            drafter.append(tok)  # accepted tokens extend the suffix index
        req.tokens.append(tok)
        req.stream.put(tok)
        with self._stats_lock:
            self._n_tokens += 1
        hit_eos = self.eos_id is not None and tok == self.eos_id
        if len(req.tokens) >= req.max_new_tokens or hit_eos:
            self._retire(slot, req)

    @_in_phase(PH_ALLOC)
    def _release_slot_blocks(self, slot: int) -> None:
        """Drop the slot's reference on every block in its table.  Blocks
        a neighbor or the prefix cache still references stay allocated —
        the defining safety property of sharing."""
        for bi in range(self._table_width):
            block = int(self._tables[slot, bi])
            if block >= 0:
                self.block_allocator.decref(block)
        self._tables[slot, :] = -1

    def _retire(self, slot: int, req: GenerationRequest) -> None:
        req.finished_at = time.time()
        self._active[slot] = False
        self._unpark(slot)
        self._finalize_trace(req, "completed")
        req.stream.put(None)
        req.done.set()
        self._release_slot_blocks(slot)
        self._release_pending_snapshots(slot)
        self._slot_req[slot] = None
        self._drafters[slot] = None
        self.allocator.free(slot)
        with self._stats_lock:
            self._n_finished += 1
        # Waiters in submit-order take freed slots on the NEXT admit —
        # i.e. immediately, mid-flight of every other slot.
        with self._cv:
            self._cv.notify_all()

    def _fail_slot(self, slot: int, msg: str, kind: Optional[str] = None) -> None:
        req = self._slot_req[slot]
        self._active[slot] = False
        self._unpark(slot)
        self._release_slot_blocks(slot)
        self._release_pending_snapshots(slot)
        self._slot_req[slot] = None
        self._drafters[slot] = None
        self.allocator.free(slot)
        if kind == "shed":
            with self._stats_lock:
                self._n_shed += 1
        if req is not None and not req.done.is_set():
            req.error = msg
            req.error_kind = kind
            self._finalize_trace(req, kind or "error")
            req.stream.put(None)
            req.done.set()
