"""Pallas flash attention for the ring body: O(T_local) memory per shard.

The last TPU-native mile of long-context sequence parallelism (SURVEY §5;
the reference platform has no analogue — its compute lived in user
containers).  ``parallel.ring`` rotates K/V blocks around a mesh axis; this
module supplies the *per-block* kernel so the [T_local, T_local] score
matrix never materializes either: scores live in VMEM tiles, the kernel
streams K/V blocks through the MXU with an online-softmax accumulator, and
each block call returns ``(o, lse)`` so the ring loop can merge blocks with
the standard log-sum-exp combine.

Differentiation is handled at the *ring* level (``ring_flash_attention``)
with a custom VJP — the canonical ring-attention backward: a second ring
pass rotates ``(k, v, dk, dv)`` together so each block's gradient
accumulates on whichever device currently holds it and arrives home after a
full cycle, while ``dq`` accumulates locally.  Per-block gradients are two
pallas kernels (dq-pass and dk/dv-pass) using the saved ``lse`` and the
``delta = rowsum(do * o)`` trick, so backward memory is O(T_local) too.

On the ``cpu`` backend the kernels run in pallas interpret mode —
numerically exact and mesh-compatible, which is how the 8-device
virtual-CPU suite verifies ring+flash numerics and how
``dryrun_multichip`` validates the sharded path.  On ``tpu`` they compile
through Mosaic; any other backend is an error (:func:`pallas_interpret`),
so a kernel never runs interpreted without anyone asking for it.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from polyaxon_tpu.exceptions import RuntimeLayerError

_NEG_BIG = -1e30  # mask value; finite so masked rows stay NaN-free


class FlashTilingError(RuntimeLayerError):
    """A sequence length the flash kernels cannot tile for the TPU."""


def on_tpu() -> bool:
    """Whether the default backend is the TPU (``"auto"`` attention picks
    the flash kernels there).  A backend that cannot be read raises."""
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Whether the pallas kernels run interpreted: only on ``cpu``.  On
    ``tpu`` they compile; anything else has no flash path."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeLayerError(
        f"the flash kernels compile on 'tpu' and are interpreted on 'cpu'; "
        f"the default backend is {backend!r}"
    )


def _pick_block(t: int, want: int) -> int:
    """The tile edge for an axis of length ``t``: a tile the Pallas TPU
    lowering accepts, or a :class:`FlashTilingError` at trace time.

    The lowering wants a block's second-to-last dim divisible by 8 unless
    the block spans the whole axis (the last dims here are ``head_dim``
    and the 128-lane row statistics, whole or aligned by construction).
    So: the whole axis when it fits in ``want``, else the largest divisor
    of ``t`` that is <= ``want`` and a multiple of 128 (MXU- and
    lane-aligned score tiles), else one that is a multiple of 8.  The
    interpreter on the CPU would take any divisor; the same rule holds
    there so a shape that passes the CPU suite lowers on the chip.
    """
    if t % 8 == 0:
        if t <= want:
            return t
        for step in (128, 8):
            for b in range(want - want % step, 0, -step):
                if t % b == 0:
                    return b
    raise FlashTilingError(
        f"flash attention cannot tile a sequence axis of length {t}: the "
        f"TPU lowering needs a tile edge <= {want} that divides it and is "
        f"a multiple of 8 — pad the sequence to a multiple of 8 or set "
        f"attention_impl=dense"
    )


# ---------------------------------------------------------------------------
# Forward block kernel: q[BH,Tq,d] x k,v[BH,Tk,d] -> o[BH,Tq,d] f32, lse f32
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, sm_scale, causal, bq, bk, nk, offset=False, window=None,
                select=False):
    # With ``offset`` the first ref is a prefetched scalar: how far the first
    # query row lies past the first key in a causal block (see flash_block_fwd).
    # With ``window`` a row admits only the last ``window`` keys up to itself,
    # and a second prefetched scalar names the first key that is there at all.
    # With ``select`` two more inputs follow v: the block's index scores and
    # the rows' thresholds; a key scored under its row's threshold is masked,
    # and the grid runs the heads inside the query tiles (see flash_block_fwd).
    if offset:
        q0_ref, *refs = refs
    if window is not None:
        k0_ref, *refs = refs
    if select:
        q_ref, k_ref, v_ref, sc_ref, th_ref, o_ref, lse_ref, acc, m_scr, l_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr = refs
    qi = pl.program_id(0 if select else 1)
    ki = pl.program_id(2)
    row0 = qi * bq
    if offset:
        row0 = row0 + q0_ref[0]

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)

    # Causal: blocks entirely above the diagonal contribute nothing.
    run = (ki * bk <= row0 + bq - 1) if causal else (ki >= 0)
    if window is not None:
        # ... and so do blocks entirely behind the first row's window.
        run = run & (ki * bk + bk - 1 > row0 - window) & (ki * bk + bk - 1 >= k0_ref[0])

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        if causal:
            rows = row0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = rows >= cols
            if window is not None:
                keep = keep & (rows - cols < window) & (cols >= k0_ref[0])
            if select:
                keep = keep & (sc_ref[...] >= th_ref[:, :1])
            s = jnp.where(keep, s, _NEG_BIG)
        m_prev = m_scr[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)  # m_prev=-inf -> 0
        p = jnp.exp(s - m_cur)
        if causal:
            p = jnp.where(keep, p, 0.0)  # rows masked-so-far: m_cur=_NEG_BIG
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(
            p.astype(v_ref.dtype),
            v_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc[...] = acc[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)

    @pl.when(ki == nk - 1)
    def _write():
        l = l_scr[:, :1]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc[...] / safe).astype(o_ref.dtype)
        # lse rides a lane-replicated [bq, 128] layout: Mosaic requires
        # the last block dim be 128-aligned (or the full array dim), so a
        # [bq]-shaped output cannot lower on real TPUs.
        lse = jnp.where(
            l > 0, m_scr[:, :1] + jnp.log(jnp.maximum(l, 1e-38)), -jnp.inf
        )
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def flash_block_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    sm_scale: float,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool | None = None,
    q_offset: jax.Array | None = None,
    name: str = "flash_fwd",
    window: int | None = None,
    k_first: jax.Array | int = 0,
    group: int = 1,
    select: Tuple[jax.Array, jax.Array] | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """One attention block: returns ``(o, lse)`` with o float32-normalized.

    q: [BH, Tq, d]; k: [BH, Tk, d]; v: [BH, Tk, dv].  ``causal`` masks assuming
    q and k share a global offset (the ring's diagonal block), or, with
    ``q_offset`` (a traced int32 scalar: first query position less first key
    position, of either sign), a query that stands that far past the keys: a
    key tile of a longer sequence.  A row that admits no key of the block
    comes back as ``o = 0``, ``lse = -inf``, which :func:`_merge` folds away.
    ``window`` (causal only) bounds the keys from below too: the query at
    position ``i`` admits the keys of ``(i - window, i]``, and a key tile wholly
    behind a query tile's window is skipped, not masked; ``k_first`` (a traced
    int32 scalar) is the first key of the block that is there at all, the ones
    before it stale rows of a ring not yet filled.  With ``group`` > 1 k
    and v hold ``BH // group`` heads and query head ``b`` reads head ``b //
    group`` of them where it lies: grouped-query attention without a copy of
    the keys at the query heads' count.  ``select = (scores [Tq, Tk] float32,
    thresholds [Tq] float32)`` (causal only) is a key selection shared by
    every head: a row admits only the keys scored at or above its threshold
    (the lightning indexer's, :func:`dsa_index`).  The grid then runs the heads
    inside each query tile, so that a tile's scores are fetched once for all
    heads and not once a head.
    """
    if window is not None and not causal:
        raise ValueError("a window bounds a causal block")
    if select is not None and not causal:
        raise ValueError("a key selection masks a causal block")
    if interpret is None:
        interpret = pallas_interpret()
    BH, Tq, d = q.shape
    Tk, dv = k.shape[1], v.shape[2]
    bq = _pick_block(Tq, block_q)
    bk = _pick_block(Tk, block_k)
    nq, nk = Tq // bq, Tk // bk
    from jax.experimental.pallas import tpu as pltpu

    offset = q_offset is not None
    scalars = [q_offset] if offset else []
    if window is not None:
        scalars.append(k_first)
    if select is None:
        kernel = functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal, bq=bq, bk=bk, nk=nk, offset=offset,
            window=window,
        )
        grid, selected, at = (BH, nq, nk), [], lambda index: index  # noqa: E731
    else:
        kernel = functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal, bq=bq, bk=bk, nk=nk, offset=offset,
            window=window, select=True,
        )
        # (query tile, head, key tile): a head's turn keeps the tile's scores.
        grid = (nq, BH, nk)

        def at(index):
            return lambda i, b, j, *_: index(b, i, j)

        scores, thresholds = select
        selected = [scores.astype(jnp.float32),
                    jnp.broadcast_to(thresholds.astype(jnp.float32)[:, None], (Tq, 128))]
    if group == 1:
        kv_at = lambda b, i, j, *_: (b, j, 0)  # noqa: E731
    else:
        kv_at = lambda b, i, j, *_: (b // group, j, 0)  # noqa: E731
    select_specs = [
        pl.BlockSpec((bq, bk), at(lambda b, i, j, *_: (i, j))),
        pl.BlockSpec((bq, 128), at(lambda b, i, j, *_: (i, 0))),
    ] if selected else []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), at(lambda b, i, j, *_: (b, i, 0))),
            pl.BlockSpec((1, bk, d), at(kv_at)),
            pl.BlockSpec((1, bk, dv), at(kv_at)),
            *select_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), at(lambda b, i, j, *_: (b, i, 0))),
            pl.BlockSpec((1, bq, 128), at(lambda b, i, j, *_: (b, i, 0))),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )
    o, lse_pad = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, dv), jnp.float32),
            jax.ShapeDtypeStruct((BH, Tq, 128), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(*(jnp.reshape(x, (1,)).astype(jnp.int32) for x in scalars), q, k, v, *selected)
    return o, lse_pad[:, :, 0]


# ---------------------------------------------------------------------------
# The lightning indexer's scores: q[C,H,d], w[C,H] x k[N,d] -> I[C,N] f32
# ---------------------------------------------------------------------------


def _dsa_index_kernel(q_ref, w_ref, k_ref, o_ref, *, heads):
    # One (row block, key block): every head's dot products in ONE product,
    # [rows x heads, d] x [keys, d]^T, then ReLU, the head's weight and the sum
    # over heads, all in VMEM.
    s = lax.dot_general(
        q_ref[...], k_ref[...], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = jnp.maximum(s, 0.0) * w_ref[...]
    o_ref[...] = jnp.sum(s.reshape(-1, heads, s.shape[-1]), axis=1)


def dsa_index(
    q: jax.Array,
    w: jax.Array,
    k: jax.Array,
    *,
    block_rows: int = 16,
    block_keys: int = 512,
    interpret: bool | None = None,
    name: str = "dsa_index",
) -> jax.Array:
    """The lightning indexer's scores of ``C`` query rows against ``N`` keys:
    ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])``, float32.

    ``q [C, H, d]`` and ``k [N, d]`` at the compute dtype, ``w [C, H]`` float32
    (the head weights with their scales in them).  A grid step multiplies
    ``block_rows`` rows' ``H`` heads against ``block_keys`` keys at once; the
    ``[rows, H, keys]`` intermediate and the ReLU stay in VMEM and only the
    head sum leaves.  No mask: the caller masks what a row may not see."""
    if interpret is None:
        interpret = pallas_interpret()
    C, H, d = q.shape
    N = k.shape[0]
    br = next((b for b in range(min(block_rows, C), 0, -1) if C % b == 0 and b % 8 == 0), C)
    bk = _pick_block(N, block_keys)
    return pl.pallas_call(
        functools.partial(_dsa_index_kernel, heads=H),
        grid=(C // br, N // bk),
        in_specs=[
            pl.BlockSpec((br * H, d), lambda i, j: (i, 0)),
            pl.BlockSpec((br * H, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((br, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((C, N), jnp.float32),
        interpret=interpret,
        name=name,
    )(q.reshape(C * H, d), w.astype(jnp.float32).reshape(C * H, 1), k)


# ---------------------------------------------------------------------------
# A decode step through its block table: q[S,Hkv,G,dk] over the pool's pages
# ---------------------------------------------------------------------------

#: Keys of one compute block of :func:`paged_step_attend`'s walk through a
#: block table.  A constant of the shapes, as ``decode.TILE_KEYS`` is (half of
#: it: K and V of two blocks in flight are 4 MB at 8 KV heads of 128, a quarter
#: of the VMEM a kernel may use).
STEP_BLOCK_KEYS = 512


def step_block_pages(table_width: int, block_size: int) -> int:
    """Pages of one compute block: ``STEP_BLOCK_KEYS`` keys, or the whole of a
    narrower table."""
    return min(max(STEP_BLOCK_KEYS // block_size, 1), table_width)


def _paged_step_kernel(
    layer_ref, tables_ref, live_ref, q_ref, *refs,
    sm_scale, pages, page, width, heads, dv, shared,
):
    # One grid turn is one slot.  The slot's compute blocks (``pages`` pages of
    # ``page`` rows) are copied out of the pool, which stays where it is, one
    # block ahead of the one attended; the trip count is the slot's own.
    if shared:  # the value is the first lanes of the key's row: one copy
        k_hbm, o_ref, k_buf, sems, m_scr, l_scr, acc = refs
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc = refs
    s = pl.program_id(0)
    layer = layer_ref[0]
    live = live_ref[s]
    bk = pages * page
    blocks = (live + bk - 1) // bk
    from jax.experimental.pallas import tpu as pltpu

    def copies(i, buf):
        out = []
        for p in range(pages):
            at = tables_ref[s * width + i * pages + p]
            rows = pl.ds(p * page, page)
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, at], k_buf.at[buf, rows], sems.at[0, buf]))
            if not shared:
                out.append(pltpu.make_async_copy(
                    v_hbm.at[layer, at], v_buf.at[buf, rows], sems.at[1, buf]))
        return out

    def head_rows(ref, buf, first, count):
        """Rows ``[bk, d]`` of heads ``first .. first + count - 1`` of a block in
        ``ref [2, bk, Hp, d]``: a head's rows lie ``Hp`` apart.  Two heads of a
        16-bit dtype share a 32-bit word a lane (the even head its low half):
        one strided read of words, then each half widened where it stands."""
        flat = ref.at[buf].reshape(bk * ref.shape[2], ref.shape[3])
        if count == 1:
            return [flat[pl.ds(first, bk, stride=ref.shape[2])]]
        words = flat.bitcast(jnp.uint32)[pl.ds(first // 2, bk, stride=ref.shape[2] // 2)]
        halves = (words << 16, words & jnp.uint32(0xFFFF0000))
        return [pltpu.bitcast(w, jnp.float32).astype(ref.dtype) for w in halves]

    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc[...] = jnp.zeros_like(acc)
    for c in copies(0, 0):
        c.start()

    def fold(i, buf, masked):
        # ``masked``: the block that holds the live end.  Rows past it are
        # another sequence's, or never written: out of the scores AND out of
        # the values (0 x NaN is NaN).
        if masked:
            col_ok = i * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1) < live
            row_ok = i * bk + lax.broadcasted_iota(jnp.int32, (bk, 1), 0) < live

        def one(h, k, v):
            sc = lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * sm_scale
            if masked:
                sc = jnp.where(col_ok, sc, _NEG_BIG)
                v = jnp.where(row_ok, v, jnp.zeros_like(v))
            m_prev = m_scr[h, :, :1]
            m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)  # m_prev=-inf -> 0
            p = jnp.exp(sc - m_cur)
            if masked:
                p = jnp.where(col_ok, p, 0.0)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            acc[h] = acc[h] * alpha + pv
            m_scr[h] = jnp.broadcast_to(m_cur, m_scr.shape[1:])

        if shared:  # a row is one head: its key the row, its value the first lanes
            one(0, k_buf[buf], k_buf[buf, :, :dv])
            return
        count = 4 // jnp.dtype(k_buf.dtype).itemsize  # heads a 32-bit word holds
        for h0 in range(0, heads, count):
            ks, vs = head_rows(k_buf, buf, h0, count), head_rows(v_buf, buf, h0, count)
            for h in range(h0, min(h0 + count, heads)):
                one(h, ks[h - h0], vs[h - h0])

    def body(i, _):
        buf = i % 2

        @pl.when(i + 1 < blocks)
        def _ahead():
            for c in copies(i + 1, 1 - buf):
                c.start()

        for c in copies(i, buf):
            c.wait()

        @pl.when(i + 1 < blocks)
        def _whole():
            fold(i, buf, masked=False)

        @pl.when(i + 1 == blocks)
        def _last():
            fold(i, buf, masked=True)

        return ()

    lax.fori_loop(0, blocks, body, ())
    l = l_scr[:, :, :1]
    o_ref[0] = (acc[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def paged_step_attend(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array | None,
    layer_idx: jax.Array,
    tables: jax.Array,
    live: jax.Array,
    *,
    sm_scale: float,
    v_lanes: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """One query token a slot against the keys its block table names, read out
    of the pool's pages where they lie: a decode step's attention with nothing
    of the table's width formed.

    ``k_pages`` / ``v_pages``: pool leaves WHOLE and as the pool holds them;
    only layer ``layer_idx`` (a traced scalar) is read, through page copies the
    kernel issues itself, so no layer's slice is cut out of the carried pool
    and no view of it is formed (the TPU compiler answers a reshape of a pool
    leaf with a copy of it).  Two page forms.  ``[layers, blocks, page rows,
    Hp, d]``, K and V leaves: KV head ``h`` of a row, ``Hp`` heads of which the
    first ``Hkv`` are the model's, is read by a strided load from the copied
    page.  ``[layers, blocks, page rows, lanes]`` with ``v_pages`` None: a row
    is ONE head whose key is the whole row and whose value is its first
    ``v_lanes`` lanes, read from the same copy in VMEM (a latent pool's ``[c |
    k_rope | pad]``).

    q: ``[S, Hkv, G, d]`` (``d = lanes`` for the second form), the ``G`` query
    heads of a KV head its rows.  tables: ``[S, W]`` int32 block ids; live:
    ``[S]`` int32, the keys slot ``s`` has (its position + 1, >= 1): the slot
    walks ``ceil(live / STEP_BLOCK_KEYS)`` compute blocks, a traced trip
    count, so one compilation serves every state; the last block's rows past
    ``live`` are masked out of scores and values; table entries past them are
    read as block ids (any valid id does, the trash block as well) and never
    attended.  Scores, maximum and sum are float32, the probabilities go to
    the pool's dtype for the second product, as :func:`flash_block_fwd` has
    it.  Returns ``[S, Hkv, G, v_lanes or d]`` float32, normalised.
    """
    if interpret is None:
        interpret = pallas_interpret()
    from jax.experimental.pallas import tpu as pltpu

    S, heads, G, d = q.shape
    shared = v_pages is None
    page, row = k_pages.shape[2], k_pages.shape[3:]
    dv = v_lanes if shared and v_lanes is not None else d
    fits = (row == (d,) and heads == 1 and dv <= d) if shared else (
        len(row) == 2 and row[1] == d and heads <= row[0] and v_pages.shape == k_pages.shape)
    if not fits:
        raise ValueError(
            f"queries {q.shape} do not fit pages {k_pages.shape}"
            + ("" if shared else f" / {v_pages.shape}") + f" (values of {dv})"
        )
    W = tables.shape[1]
    pages = step_block_pages(W, page)
    tables = jnp.pad(tables.astype(jnp.int32), ((0, 0), (0, -W % pages)))
    kernel = functools.partial(
        _paged_step_kernel, sm_scale=sm_scale, pages=pages, page=page, width=tables.shape[1],
        heads=heads, dv=dv, shared=shared,
    )
    leaves = [k_pages] if shared else [k_pages, v_pages]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, heads, G, d), lambda s, *_: (s, 0, 0, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY) for _ in leaves],
        out_specs=pl.BlockSpec((1, heads, G, dv), lambda s, *_: (s, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, pages * page, *row), leaf.dtype) for leaf in leaves] + [
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((heads, G, 128), jnp.float32),
            pltpu.VMEM((heads, G, 128), jnp.float32),
            pltpu.VMEM((heads, G, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, heads, G, dv), jnp.float32),
        interpret=interpret,
        name="paged_step_attend",
    )(
        jnp.reshape(layer_idx, (1,)).astype(jnp.int32), tables.reshape(-1),
        live.astype(jnp.int32), q.astype(k_pages.dtype), *leaves,
    )


# ---------------------------------------------------------------------------
# Backward block kernels (flash-2 style, using saved lse and delta)
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, sm_scale, causal, bq, bk, nk,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = (ki * bk <= qi * bq + bq - 1) if causal else (ki >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        lse = lse_ref[0][:, :1]  # lane-replicated [bq, 128] input
        p = jnp.exp(s - lse)
        if causal:
            rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            p = jnp.where(rows >= cols, p, 0.0)
        dp = lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0][:, :1]) * sm_scale
        dq_acc[...] += lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _write():
        dq_ref[0] = dq_acc[...]


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, sm_scale, causal, bq, bk, nq,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (ki * bk <= qi * bq + bq - 1) if causal else (qi >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        lse = lse_ref[0][:, :1]  # lane-replicated [bq, 128] input
        p = jnp.exp(s - lse)
        if causal:
            rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            p = jnp.where(rows >= cols, p, 0.0)
        do = do_ref[0]
        dv_acc[...] += lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0][:, :1]) * sm_scale
        dk_acc[...] += lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _write():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


def flash_block_bwd(
    q, k, v, do, lse, delta, *, causal, sm_scale,
    block_q: int = 1024, block_k: int = 1024, interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Gradients for one block pair: returns ``(dq, dk, dv)`` float32."""
    if interpret is None:
        interpret = pallas_interpret()
    BH, Tq, d = q.shape
    Tk = k.shape[1]
    bq = _pick_block(Tq, block_q)
    bk = _pick_block(Tk, block_k)
    nq, nk = Tq // bq, Tk // bk
    from jax.experimental.pallas import tpu as pltpu

    # Row statistics ride lane-replicated [BH, Tq, 128] (Mosaic block
    # tiling: the last dim must be 128-aligned or the full array dim).
    lse128 = jnp.broadcast_to(lse[:, :, None], (BH, Tq, 128))
    delta128 = jnp.broadcast_to(delta[:, :, None], (BH, Tq, 128))

    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal, bq=bq, bk=bk, nk=nk
        ),
        grid=(BH, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((BH, Tq, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse128, delta128)[0]

    # dk/dv pass: grid iterates q blocks innermost for each k block.
    qT_spec = pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0))
    kT_spec = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    rowT_spec = pl.BlockSpec((1, bq, 128), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal, bq=bq, bk=bk, nq=nq
        ),
        grid=(BH, nk, nq),
        in_specs=[qT_spec, kT_spec, kT_spec, qT_spec, rowT_spec, rowT_spec],
        out_specs=[kT_spec, kT_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, d), jnp.float32),
            jax.ShapeDtypeStruct((BH, Tk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse128, delta128)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Ring-level flash attention with custom VJP (per-shard code, runs inside
# shard_map; cfg = (axis_name, sm_scale, block_q, block_k, interpret))
# ---------------------------------------------------------------------------


def _merge(o, lse, o_b, lse_b):
    """Log-sum-exp combine of two normalized partial attentions."""
    lse_new = jnp.logaddexp(lse, lse_b)
    w_old = jnp.where(jnp.isneginf(lse_new), 0.0, jnp.exp(lse - lse_new))
    w_new = jnp.where(jnp.isneginf(lse_new), 0.0, jnp.exp(lse_b - lse_new))
    o = o * w_old[..., None] + o_b * w_new[..., None]
    return o, lse_new


def _hop_case(i, idx):
    """0 = diagonal (causal), 1 = full block, 2 = skip (future keys)."""
    return jnp.where(i == 0, 0, jnp.where(i <= idx, 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def ring_flash_attention(cfg, q, k, v):
    """Causal ring attention with pallas flash blocks.

    q: [B,Tl,H,d]; k/v: [B,Tl,Hkv,d] with Hkv dividing H (GQA) — the ring
    rotates the UNEXPANDED KV blocks (ppermute payload shrinks by H/Hkv)
    and broadcasts them to the query heads only at each kernel call.
    """
    return _ring_flash_fwd(cfg, q, k, v)[0]


def _bhd(x):
    B, T, H, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, d)


def _unbhd(x, B, H):
    BH, T, d = x.shape
    return x.reshape(B, H, T, d).transpose(0, 2, 1, 3)


def _gqa_expand(x, B, group):
    """[B*Hkv, T, d] → [B*H, T, d] by repeating each KV head ``group``×."""
    if group == 1:
        return x
    BHkv, T, d = x.shape
    return jnp.repeat(x.reshape(B, BHkv // B, T, d), group, axis=1).reshape(
        B * (BHkv // B) * group, T, d
    )


def _gqa_reduce(dx, B, group):
    """Transpose of :func:`_gqa_expand`: sum query-head grads per KV head."""
    if group == 1:
        return dx
    BH, T, d = dx.shape
    return (
        dx.reshape(B, BH // B // group, group, T, d)
        .sum(axis=2)
        .reshape(BH // group, T, d)
    )


def _ring_flash_fwd(cfg, q, k, v):
    axis_name, sm_scale, block_q, block_k, interpret = cfg
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Tl, H, d = q.shape
    group = H // k.shape[2]
    qf, kf, vf = _bhd(q), _bhd(k), _bhd(v)
    perm = [(j, (j + 1) % n) for j in range(n)]

    o0 = jnp.zeros((B * H, Tl, d), jnp.float32)
    lse0 = jnp.full((B * H, Tl), -jnp.inf, jnp.float32)

    def block(causal):
        def run(args):
            o, lse, kc, vc = args
            o_b, lse_b = flash_block_fwd(
                qf,
                _gqa_expand(kc, B, group),
                _gqa_expand(vc, B, group),
                causal=causal, sm_scale=sm_scale,
                block_q=block_q, block_k=block_k, interpret=interpret,
            )
            return _merge(o, lse, o_b, lse_b)
        return run

    def body(i, carry):
        o, lse, kc, vc = carry
        o, lse = lax.switch(
            _hop_case(i, idx),
            [block(True), block(False), lambda a: (a[0], a[1])],
            (o, lse, kc, vc),
        )
        kc, vc = lax.ppermute((kc, vc), axis_name, perm)
        return o, lse, kc, vc

    o, lse, _, _ = lax.fori_loop(0, n, body, (o0, lse0, kf, vf))
    out = _unbhd(o, B, H).astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(cfg, res, do):
    axis_name, sm_scale, block_q, block_k, interpret = cfg
    q, k, v, out, lse = res
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Tl, H, d = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qf, kf, vf = _bhd(q), _bhd(k), _bhd(v)
    dof = _bhd(do.astype(q.dtype))
    of = _bhd(out)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    perm = [(j, (j + 1) % n) for j in range(n)]

    dq0 = jnp.zeros((B * H, Tl, d), jnp.float32)
    dkv0 = jnp.zeros((B * Hkv, Tl, d), jnp.float32)

    def block(causal):
        def run(args):
            kc, vc = args
            dq_i, dk_i, dv_i = flash_block_bwd(
                qf,
                _gqa_expand(kc, B, group),
                _gqa_expand(vc, B, group),
                dof, lse, delta, causal=causal,
                sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                interpret=interpret,
            )
            return dq_i, _gqa_reduce(dk_i, B, group), _gqa_reduce(dv_i, B, group)
        return run

    def skip(args):
        return dq0, dkv0, dkv0

    def body(i, carry):
        dq, kc, vc, dkc, dvc = carry
        dq_i, dk_i, dv_i = lax.switch(
            _hop_case(i, idx), [block(True), block(False), skip], (kc, vc)
        )
        dq = dq + dq_i
        # dk/dv accumulators travel WITH their k/v block: after the full
        # cycle of n hops each block (and its gradient) is home again.
        kc, vc, dkc, dvc = lax.ppermute(
            (kc, vc, dkc + dk_i, dvc + dv_i), axis_name, perm
        )
        return dq, kc, vc, dkc, dvc

    dq, _, _, dk, dv = lax.fori_loop(0, n, body, (dq0, kf, vf, dkv0, dkv0))
    return (
        _unbhd(dq, B, H).astype(q.dtype),
        _unbhd(dk, B, Hkv).astype(k.dtype),
        _unbhd(dv, B, Hkv).astype(v.dtype),
    )


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


# ---------------------------------------------------------------------------
# Single-device causal flash (no ring): the same block kernels over the
# full sequence, with the standard flash VJP. Measured 1.9x the jax-bundled
# pallas flash kernel in full train steps at T=8192 on v5e (8.4k vs 4.4k
# tok/s — docs/bench-notes.md), so this is the kernel behind
# attention_impl="flash" everywhere.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def flash_attention(cfg, q, k, v):
    """Causal flash attention. q/k/v: [B,T,H,d]; cfg=(sm_scale, block_q,
    block_k, interpret)."""
    return _flash_fwd(cfg, q, k, v)[0]


def _flash_fwd(cfg, q, k, v):
    sm_scale, block_q, block_k, interpret = cfg
    B, T, H, d = q.shape
    o, lse = flash_block_fwd(
        _bhd(q), _bhd(k), _bhd(v), causal=True, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    out = _unbhd(o, B, H).astype(q.dtype)
    return out, (q, k, v, out, lse)


def _flash_bwd(cfg, res, do):
    sm_scale, block_q, block_k, interpret = cfg
    q, k, v, out, lse = res
    B, T, H, d = q.shape
    qf, kf, vf = _bhd(q), _bhd(k), _bhd(v)
    dof = _bhd(do.astype(q.dtype))
    delta = jnp.sum(
        dof.astype(jnp.float32) * _bhd(out).astype(jnp.float32), axis=-1
    )
    dq, dk, dv = flash_block_bwd(
        qf, kf, vf, dof, lse, delta, causal=True, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return (
        _unbhd(dq, B, H).astype(q.dtype),
        _unbhd(dk, B, H).astype(k.dtype),
        _unbhd(dv, B, H).astype(v.dtype),
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)
