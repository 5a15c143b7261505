"""Paged decode attention as one Pallas TPU kernel (two bodies, by the
head's width: see below): a decode step's cache-read ``fused_attention``
(one query token a row) reading K and V FROM THE POOLS IN PLACE, page by
page through each row's block table, only as far as the row's live
context.

The composition it replaces gathers ``[B, T, H]`` out of each pool
(``T = max_blocks_per_seq * block_size``) for K and for V in every layer
of every decode step, then scores and masks all ``T`` positions
whatever the rows hold: on the v5e that was 45 % of the serving cell's
device time for context that is 72 % padding (PERF.md, PR 32).  Here:

* the pools stay ``[num_blocks, block_size, H]`` in HBM
  (``memory_space=pl.ANY``); the block table and ``ctx_len`` are scalar
  prefetched; the kernel copies row ``b``'s pages ``0 .. ceil(ctx_len[b]
  / block_size) - 1`` with its own DMAs, ``PAGES_PER_STEP`` pages a
  step (all of a step's copies in flight together), and fetches nothing
  past a row's last live page;
* a page holds all heads side by side in the op's ``(..., H)`` layout
  and is used as it lies: no head split, no relayout of the pool.
  ``q * k`` is an elementwise f32 product; ``_head_sum`` adds it up over
  each head's lanes by a lane butterfly and leaves the head's score in
  EVERY lane of that head, so the softmax and ``p * v`` are elementwise
  on whole vregs;
* float32 arithmetic throughout, on the VPU and the XLU: no MXU pass
  rounds an operand to bf16.  The pools are float32 or bfloat16: pages
  are copied as they lie (a bfloat16 page is half the bytes) and widened
  to float32 in VMEM, where they are scored;
* the online softmax runs as eight independent streams, one a sublane
  (position ``t`` belongs to stream ``t % 8``), merged once per row —
  nothing reduces across sublanes inside the loop over a row's steps.

**Two bodies, picked by the head's width** (the route table,
ops/op_specs.py: ``paged_decode_attention_wide`` is tried first).

*Narrow* (:func:`paged_decode_attention`; ``head_dim`` divides 128 —
``bert_decoder.chat_closed``: 12 heads of 64, float32 pools, two heads a
lane tile; a head of exactly 128 it can take too, and the route table
gives that to the wide body).  The form described above, as PR 32 landed
it: one buffer, so a step's copies are waited for before they are
scored; 4 pages a step; the head sum a lane butterfly on the XLU, the
softmax over ``[positions, H]`` where ``[positions, heads]`` carries the
information.  It is not the fastest that was measured at these shapes
(PERF.md section 6, PR 32), and it stays byte for byte until that cell's
traffic file can feed a faster kernel (its fixed supply of requests
cannot carry more than ~8 650 tokens/s: PERF.md question 29, ROADMAP
I6); then S10 brings the wide body's steps to narrow heads.

*Wide* (:func:`paged_decode_attention_wide`; ``head_dim`` a multiple of
128 — ``olmo_hybrid_serve.doc_closed``: 30 heads of 128, bfloat16 pools,
contexts of thousands).  A head's slab of a page is then a tile-aligned
lane slice, which the MXU takes as it lies, and nothing wide ever meets
the VPU:

* scores: 8 heads a pass (``HEAD_GROUP``).  The query's rows are laid
  block-diagonally — row ``r`` keeps head ``r``'s lanes of the group and
  is zero elsewhere — and multiplied against the group's lanes of the
  K pages, contracting the lanes: ``[8, positions]`` a group, ``[heads,
  positions]`` in all (4 vregs per 128 positions at 30 heads).  Mask,
  running max, ``exp``, ``l`` and ``alpha`` run on that;
* ``p . V``: ``p``'s 8 rows of a group against the group's lanes of the
  V pages, ``[8, 8 * head_dim]``, of which row ``r`` is wanted in head
  ``r``'s lanes; the accumulator keeps all of it (``[8, H]`` float32) and
  the row's one output write picks each head's own row;
* float32 arithmetic still: a page goes to the MXU in the dtype it is
  stored in; the float32 operand (``q * scale``, ``p``) is cut EXACTLY
  into three bfloat16 pieces stacked along the rows of one pass
  (bfloat16 pools), or multiplied at ``Precision.HIGHEST`` (float32
  pools); accumulation is float32.  No operand is rounded that is not
  bfloat16 as stored;
* two buffers: the next step's page copies — at a row's last step the
  NEXT ROW's first pages — are in flight while this step is scored;
* ``PAGES_PER_STEP_WIDE`` pages a step, fewer where four buffers of that
  many would pass ``WIDE_BUFFER_BYTES``.

On the v5e at ``doc_closed``'s shapes (64 rows, ~199k live positions a
call, 12 calls; PERF.md section 6, PR 37): narrow 658 ms; MXU scores
with one buffer 86.6; with two buffers 49.7 — 90 % of what the pages'
bytes take at the HBM peak (45 ms), so a step's size no longer matters
(49.7 at 4 and at 8 pages, 50.1 at 16).

*Grouped* (:func:`paged_gqa_decode`, kernel ``paged_gqa_decode``; grouped
K/V heads or a sliding window — ``laguna_s_serve.code_closed``: 48 or 72
heads of 128 on 8 K/V heads, window 512).  The wide body's page walk and
two buffers; the query rows of one K/V head (its group, padded to whole
sublane tiles) are one MXU operand against that head's lanes of the
pages, and under a window a row's walk starts at the page of ``ctx -
window``.

What a paged read must get right and the gather never met (each has its
case in tests/test_paged_decode_attention.py): positions ``>= ctx_len``
in the last live page and the dead pages of a row's last step (a reused
block's leftovers, never-written slots, an earlier row's pages still in
the buffer, or nothing yet) are masked by a SELECT on position, on the
score and on the V row (``0 * NaN`` is NaN); table entries past the
live pages (0: a block that belongs to someone) are never fetched;
the softmax state of a row lives in loop carries initialised per row,
not in scratch a previous row wrote; the output row is written once,
after the row's last step; ``ctx_len`` past ``T`` (a finished row
inside a chain) reads ``T`` positions; a row with no live position
reads page 0 and writes zeros.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
#: pages one DMA step copies and one pass of the arithmetic scores (on
#: the v5e at 16-token pages, 12 calls at the cell's shapes: 13.6 ms at
#: 4, 11.5 at 8, 11.0 at 16; see the module docstring for why 4)
PAGES_PER_STEP = 4
#: running max of a stream that has met no live position, and the score
#: of a masked position: exp(MASKED - m) is exactly 0 even at m == EMPTY
EMPTY = -1e30
MASKED = -2e30


def supported(sq, hidden, n_head, block_size, dtype="float32",
              has_qpos=False):
    """Static shape rule → (ok, reason): is this cache-read attention a
    decode step the kernel takes?"""
    if sq != 1:
        return False, f"paged-decode:sq:{sq}"
    if has_qpos:
        return False, "paged-decode:qpos"
    if jnp.dtype(dtype) not in (jnp.float32, jnp.bfloat16):
        return False, f"paged-decode:dtype:{jnp.dtype(dtype).name}"
    # a page is whole sublane tiles of its dtype: 8 rows of float32, 16 of
    # bfloat16
    if block_size % (SUBLANES * 4 // jnp.dtype(dtype).itemsize):
        return False, f"paged-decode:block-size:{block_size}"
    if hidden % LANES:
        return False, f"paged-decode:hidden:{hidden}"
    d = hidden // max(1, n_head)
    if n_head <= 0 or hidden % n_head or LANES % d:
        return False, f"paged-decode:head-dim:{d}"
    return True, ""


def _head_sum(x, d):
    """(R, H) f32 -> (R, H): every lane holds the sum over the lanes of
    its head (``d`` consecutive lanes, ``d`` a power of two dividing
    128).  A butterfly inside each 128-lane tile: lane ``i`` adds lane
    ``i ^ s`` for s = d/2 .. 1 (two lane rotations and a select a step),
    so all lanes of a head end bitwise equal."""
    lane = lax.broadcasted_iota(jnp.int32, (x.shape[0], LANES), 1)
    tiles = []
    for j in range(x.shape[1] // LANES):
        t = x[:, j * LANES:(j + 1) * LANES]
        s = d // 2
        while s:
            t = t + jnp.where((lane & s) != 0, pltpu.roll(t, s, 1),
                              pltpu.roll(t, LANES - s, 1))
            s //= 2
        tiles.append(t)
    return jnp.concatenate(tiles, axis=1)


def _fold(x, op):
    """(R, H) -> (8, H): ``op`` over the rows of each sublane stream."""
    parts = [x[i:i + SUBLANES] for i in range(0, x.shape[0], SUBLANES)]
    return functools.reduce(op, parts)


def _kernel(ctx_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            *, head_dim, scale):
    n_rows, h = q_ref.shape
    num_blocks, bs, _ = k_hbm.shape
    pages_per_seq = tbl_ref.shape[0] // n_rows
    rows = kbuf.shape[0]            # positions one step copies and scores
    pps = rows // bs                # pages a step

    def fetch(b, c, live_pages):
        """Copy the live pages of step ``c`` of row ``b`` into the
        buffers: every copy started, then every copy waited for.  A dead
        page is neither."""
        copies = []
        for j in range(pps):
            entry = tbl_ref[b * pages_per_seq
                            + jnp.minimum(c * pps + j, pages_per_seq - 1)]
            blk = jnp.clip(entry, 0, num_blocks - 1)
            dst = pl.ds(j * bs, bs)
            copies.append((j < live_pages,
                           pltpu.make_async_copy(k_hbm.at[blk],
                                                 kbuf.at[dst], sem.at[0]),
                           pltpu.make_async_copy(v_hbm.at[blk],
                                                 vbuf.at[dst], sem.at[1])))
        for live, ck, cv in copies:
            @pl.when(live)
            def _():
                ck.start()
                cv.start()
        for live, ck, cv in copies:
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    row_in_step = lax.broadcasted_iota(jnp.int32, (rows, h), 0)

    def row_body(b, _):
        ctx = ctx_ref[b]
        # live pages: at least page 0, at most the table
        pages = jnp.clip((ctx + bs - 1) // bs, 1, pages_per_seq)
        q = jnp.broadcast_to(q_ref[pl.ds(b, 1), :] * scale, (rows, h))

        def step_body(c, state):
            m, l, acc = state
            fetch(b, c, pages - c * pps)
            live = row_in_step < ctx - c * rows
            s = jnp.where(live, _head_sum(
                kbuf[...].astype(jnp.float32) * q, head_dim), MASKED)
            m_new = jnp.maximum(m, _fold(s, jnp.maximum))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - jnp.concatenate([m_new] * (rows // SUBLANES),
                                            axis=0))
            l = alpha * l + _fold(p, jnp.add)
            acc = alpha * acc + _fold(
                p * jnp.where(live, vbuf[...].astype(jnp.float32), 0.0),
                jnp.add)
            return m_new, l, acc

        zeros = jnp.zeros((SUBLANES, h), jnp.float32)
        m, l, acc = lax.fori_loop(
            0, (pages + pps - 1) // pps, step_body,
            (jnp.full((SUBLANES, h), EMPTY, jnp.float32), zeros, zeros))
        # merge the eight streams
        w = jnp.exp(m - jnp.max(m, axis=0, keepdims=True))
        total = jnp.sum(l * w, axis=0, keepdims=True)
        out = jnp.sum(acc * w, axis=0, keepdims=True) \
            / jnp.maximum(total, 1e-30)
        o_ref[pl.ds(b, 1), :] = out.astype(o_ref.dtype)
        return 0

    lax.fori_loop(0, n_rows, row_body, 0)


@functools.partial(jax.jit, static_argnames=("n_head", "pages_per_step",
                                             "interpret"))
def paged_decode_attention(q, k_pool, v_pool, block_table, ctx_len, *,
                           n_head, pages_per_step=PAGES_PER_STEP,
                           interpret=False):
    """q: (B, 1, H) one query token a row; k_pool / v_pool:
    (num_blocks, block_size, H) float32 or bfloat16; block_table: (B,
    max_blocks_per_seq) int32 pool blocks of each row's pages; ctx_len:
    (B,) int32 live positions of each row.  Returns the context, (B, 1,
    H).  Raises ValueError for what supported() rejects — call it
    first."""
    b, sq, h = q.shape
    _, bs, _ = k_pool.shape
    pages_per_seq = block_table.shape[1]
    ok, why = supported(sq, h, n_head, bs, k_pool.dtype)
    if not ok:
        raise ValueError(f"paged_decode_attention: unsupported ({why})")
    d = h // n_head
    pps = min(int(pages_per_step), pages_per_seq)
    # every page live
    table_bytes = 2 * b * pages_per_seq * bs * h * k_pool.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, head_dim=d, scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec((b, h), lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((b, h), lambda i, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((pps * bs, h), k_pool.dtype),
                            pltpu.VMEM((pps * bs, h), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        # float32 whatever the query's dtype: a row is written alone, and
        # one row is not a whole tile of a packed dtype
        out_shape=jax.ShapeDtypeStruct((b, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=table_bytes, bytes_accessed=table_bytes,
            transcendentals=table_bytes // 8),
        interpret=interpret,
        name="paged_decode_attn",
    )(ctx_len.astype(jnp.int32), block_table.reshape(-1).astype(jnp.int32),
      q.reshape(b, h).astype(jnp.float32), k_pool, v_pool)
    return out.reshape(b, 1, h).astype(q.dtype)


# ---------------------------------------------------------------------------
# heads that are whole lane tiles: scores on the MXU, two buffers
# ---------------------------------------------------------------------------

#: heads one MXU pass scores together: the rows of one float32 tile
HEAD_GROUP = 8
#: pages a step of the wide body copies and scores: 128 positions, one
#: MXU tile of keys (on the v5e, 12 calls at doc_closed's shapes: 49.71 ms
#: at 4, 49.69 at 8, 50.07 at 16 — the copies set the pace at every size,
#: and 8 is the smallest that fills a tile)
PAGES_PER_STEP_WIDE = 8
#: most VMEM the wide body's four page buffers may take together: half the
#: default scoped limit, the rest is the operands' and the compiler's
WIDE_BUFFER_BYTES = 8 << 20


def supported_wide(sq, hidden, n_head, block_size, dtype="float32",
                   has_qpos=False):
    """Static shape rule -> (ok, reason) of the wide body: what
    :func:`supported` takes at a head of one lane tile, for heads that
    are whole lane tiles (a head's K and V slab is then a tile-aligned
    lane slice of the page, which the MXU takes as it lies)."""
    ok, why = supported(sq, hidden, max(1, hidden // LANES), block_size,
                        dtype, has_qpos)
    if not ok:
        return ok, why
    d = hidden // max(1, n_head)
    if n_head <= 0 or hidden % n_head or d % LANES:
        return False, f"paged-decode-wide:head-dim:{d}"
    return True, ""


def supported_gqa(sq, hidden, n_head, n_kv, block_size, dtype="float32",
                  has_qpos=False):
    """Static shape rule -> (ok, reason) of the wide body's grouped form:
    ``n_head`` query heads of ``hidden / n_head`` on ``n_kv`` K/V heads
    (pools ``n_kv`` heads wide), whole lane tiles each."""
    if n_head <= 0 or n_kv <= 0 or hidden % n_head or n_head % n_kv:
        return False, f"paged-gqa:heads:{n_head}/{n_kv}"
    d = hidden // n_head
    return supported_wide(sq, n_kv * d, n_kv, block_size, dtype, has_qpos)


def _mxu_lhs(a, dtype):
    """The (8, K) float32 operand ``a`` as the MXU takes it against pages
    of ``dtype``.  Float32 pages: as it is (the product runs at
    ``Precision.HIGHEST``).  bfloat16 pages: cut EXACTLY into three
    bfloat16 pieces (8 + 8 + 8 bits of the 24) stacked along the rows,
    (32, K) with a tile of zeros: one pass of bfloat16 products, every
    one exact in float32, gives all three partial results."""
    if dtype == jnp.float32:
        return a
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    rest = a - hi
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.concatenate([hi, mid, rest - mid, jnp.zeros_like(a)],
                           axis=0).astype(jnp.bfloat16)


def _mxu_dot(lhs, pages, dims, g=HEAD_GROUP):
    """``lhs`` (from :func:`_mxu_lhs` of ``g`` rows) times a slab of
    pages, float32 accumulation, (g, N): the pages go to the MXU in their
    own dtype."""
    if pages.dtype == jnp.float32:
        return lax.dot_general(lhs, pages, dims,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    o = lax.dot_general(lhs, pages, dims,
                        preferred_element_type=jnp.float32)
    return o[:g] + o[g:2 * g] + o[2 * g:3 * g]


_NT = (((1,), (1,)), ((), ()))      # (M, K) x (N, K) -> (M, N)
_NN = (((1,), (0,)), ((), ()))      # (M, K) x (K, N) -> (M, N)


def _page_walk(ctx_ref, tbl_ref, k_hbm, v_hbm, kbuf, vbuf, sem, n_rows,
               window=0):
    """The wide body's page copies, shared by its two scorings: (first
    page of a row, live pages of a row, start step ``c`` of row ``b`` into
    buffer ``slot``, wait for it).  A row's live pages run from page 0,
    or under a ``window`` from the page of its first visible position
    (``ctx - window``), to the page of its last; a dead page is neither
    started nor waited for."""
    num_blocks, bs, _ = k_hbm.shape
    pages_per_seq = tbl_ref.shape[0] // n_rows
    pps = kbuf.shape[1] // bs       # pages a step

    def live_pages(b):
        # at least page 0, at most the table
        return jnp.clip((ctx_ref[b] + bs - 1) // bs, 1, pages_per_seq)

    def first_page(b):
        if not window:
            return 0
        ctx = jnp.minimum(ctx_ref[b], pages_per_seq * bs)
        return jnp.maximum(ctx - window, 0) // bs

    def copies(b, c, slot):
        """The page copies of step ``c`` of row ``b`` into buffer
        ``slot``, each with whether its page is live."""
        out, live = [], live_pages(b)
        first = first_page(b) if window else None
        for j in range(pps):
            page = None if first is None else first + c * pps + j
            entry = tbl_ref[b * pages_per_seq + jnp.minimum(
                c * pps + j if page is None else page, pages_per_seq - 1)]
            blk = jnp.clip(entry, 0, num_blocks - 1)
            dst = pl.ds(j * bs, bs)
            out.append((c * pps + j < live if page is None else page < live,
                        pltpu.make_async_copy(k_hbm.at[blk],
                                              kbuf.at[slot, dst],
                                              sem.at[slot]),
                        pltpu.make_async_copy(v_hbm.at[blk],
                                              vbuf.at[slot, dst],
                                              sem.at[slot])))
        return out

    def start(b, c, slot):
        for live, ck, cv in copies(b, c, slot):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(b, c, slot):
        for live, ck, cv in copies(b, c, slot):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    return first_page, live_pages, start, wait


def _prefetch(start, b, c, steps, slot, n_rows):
    """Start the copies of this row's next step, or of the next row's
    first, into the other buffer."""
    more = c + 1 < steps
    nb = jnp.where(more, b, b + 1)

    @pl.when(nb < n_rows)
    def _():
        start(jnp.minimum(nb, n_rows - 1), jnp.where(more, c + 1, 0),
              1 - slot)


def _kernel_wide(ctx_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
                 sem, *, head_dim, scale, prefetch):
    n_rows, h = q_ref.shape
    _, bs, _ = k_hbm.shape
    pages_per_seq = tbl_ref.shape[0] // n_rows
    rows = kbuf.shape[1]            # positions one step copies and scores
    pps = rows // bs                # pages a step
    g = HEAD_GROUP
    # a group: g heads side by side, the last one what is left
    groups = [slice(at, min(at + g * head_dim, h))
              for at in range(0, h, g * head_dim)]
    _, live_pages, start, wait = _page_walk(
        ctx_ref, tbl_ref, k_hbm, v_hbm, kbuf, vbuf, sem, n_rows)

    # row r of a group's operand is head r of the group: it keeps that
    # head's lanes and is zero in the other heads'
    own = (lax.broadcasted_iota(jnp.int32, (g, h), 1) // head_dim) % g \
        == lax.broadcasted_iota(jnp.int32, (g, h), 0)
    pos_s = lax.broadcasted_iota(jnp.int32, (g * len(groups), rows), 1)

    def row_body(b, first_slot):
        ctx = jnp.minimum(ctx_ref[b], pages_per_seq * bs)
        steps = (live_pages(b) + pps - 1) // pps
        q = _mxu_lhs(jnp.where(own, q_ref[pl.ds(b, 1), :] * scale, 0.0),
                     kbuf.dtype)

        def step_body(c, state):
            m, l, acc = state
            slot = (first_slot + c) % 2
            if prefetch:
                _prefetch(start, b, c, steps, slot, n_rows)
            else:
                start(b, c, slot)
            wait(b, c, slot)
            left = ctx - c * rows

            @pl.when(left < rows)
            def _():
                # 0 * NaN is NaN: a V row past the context is zero
                # before the MXU meets it
                v = vbuf[slot]
                pos_v = lax.broadcasted_iota(jnp.int32, v.shape, 0)
                vbuf[slot] = jnp.where(pos_v < left, v, jnp.zeros_like(v))

            s = jnp.concatenate(
                [_mxu_dot(q[:, gl], kbuf[slot, :, gl], _NT)
                 for gl in groups], axis=0)
            s = jnp.where(pos_s < left, s, MASKED)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = jnp.concatenate(
                [alpha[i * g:(i + 1) * g] * acc[:, gl] + _mxu_dot(
                    _mxu_lhs(p[i * g:(i + 1) * g], vbuf.dtype),
                    vbuf[slot, :, gl], _NN)
                 for i, gl in enumerate(groups)], axis=1)
            return m_new, l, acc

        heads = g * len(groups)
        m, l, acc = lax.fori_loop(
            0, steps, step_body,
            (jnp.full((heads, 1), EMPTY, jnp.float32),
             jnp.zeros((heads, 1), jnp.float32),
             jnp.zeros((g, h), jnp.float32)))
        l = jnp.maximum(l, 1e-30)
        out = jnp.concatenate(
            [acc[:, gl] / l[i * g:(i + 1) * g]
             for i, gl in enumerate(groups)], axis=1)
        # head r of a group is row r of its lanes
        o_ref[pl.ds(b, 1), :] = jnp.sum(
            jnp.where(own, out, 0.0), axis=0, keepdims=True
        ).astype(o_ref.dtype)
        return (first_slot + steps) % 2

    if prefetch:
        start(0, 0, 0)
    lax.fori_loop(0, n_rows, row_body, 0)


def _kernel_gqa(ctx_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
                sem, *, head_dim, rows_per_kv, window):
    """The wide body's walk with grouped K/V heads and a window: the
    ``rows_per_kv`` query rows of K/V head ``k`` (its ``group`` heads,
    padded to whole sublane tiles, scaled on the way in) are ONE MXU
    operand against that head's lanes of the pages, ``[rows_per_kv,
    positions]`` a head; ``p . V`` the same way.  Keys outside ``[ctx -
    window, ctx)`` get an exactly-zero weight, and their V rows are zero
    before the MXU meets them."""
    n_rows = q_ref.shape[0]
    n_kv = k_hbm.shape[2] // head_dim
    _, bs, _ = k_hbm.shape
    pages_per_seq = tbl_ref.shape[0] // n_rows
    rows = kbuf.shape[1]            # positions one step copies and scores
    pps = rows // bs                # pages a step
    gp, d = rows_per_kv, head_dim
    first_page, live_pages, start, wait = _page_walk(
        ctx_ref, tbl_ref, k_hbm, v_hbm, kbuf, vbuf, sem, n_rows, window)
    at_s = lax.broadcasted_iota(jnp.int32, (n_kv * gp, rows), 1)
    at_v = lax.broadcasted_iota(jnp.int32, (rows, n_kv * d), 0)

    def row_body(b, first_slot):
        ctx = jnp.minimum(ctx_ref[b], pages_per_seq * bs)
        lo = jnp.maximum(ctx - window, 0) if window else None
        first = first_page(b)
        steps = (live_pages(b) - first + pps - 1) // pps
        q = q_ref[b]
        lhs = [_mxu_lhs(q[k * gp:(k + 1) * gp], kbuf.dtype)
               for k in range(n_kv)]

        def step_body(c, state):
            m, l, acc = state
            slot = (first_slot + c) % 2
            _prefetch(start, b, c, steps, slot, n_rows)
            wait(b, c, slot)
            at = (first + c * pps) * bs     # position of the step's row 0
            edge = at + rows > ctx
            if lo is not None:
                edge = edge | (at < lo)

            def visible(pos):
                ok = pos < ctx
                return ok if lo is None else ok & (pos >= lo)

            @pl.when(edge)
            def _():
                # 0 * NaN is NaN: a V row outside the visible keys is
                # zero before the MXU meets it
                v = vbuf[slot]
                vbuf[slot] = jnp.where(visible(at + at_v), v,
                                       jnp.zeros_like(v))

            s = jnp.concatenate(
                [_mxu_dot(lhs[k], kbuf[slot, :, k * d:(k + 1) * d], _NT, gp)
                 for k in range(n_kv)], axis=0)
            s = jnp.where(visible(at + at_s), s, MASKED)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            pv = jnp.concatenate(
                [_mxu_dot(_mxu_lhs(p[k * gp:(k + 1) * gp], vbuf.dtype),
                          vbuf[slot, :, k * d:(k + 1) * d], _NN, gp)
                 for k in range(n_kv)], axis=0)
            return m_new, l, alpha * acc + pv

        m, l, acc = lax.fori_loop(
            0, steps, step_body,
            (jnp.full((n_kv * gp, 1), EMPTY, jnp.float32),
             jnp.zeros((n_kv * gp, 1), jnp.float32),
             jnp.zeros((n_kv * gp, d), jnp.float32)))
        o_ref[b] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return (first_slot + steps) % 2

    start(0, 0, 0)
    lax.fori_loop(0, n_rows, row_body, 0)


@functools.partial(jax.jit, static_argnames=("n_head", "pages_per_step",
                                             "prefetch", "interpret"))
def paged_decode_attention_wide(q, k_pool, v_pool, block_table, ctx_len, *,
                                n_head, pages_per_step=PAGES_PER_STEP_WIDE,
                                prefetch=True, interpret=False):
    """:func:`paged_decode_attention` for heads of whole lane tiles (the
    same arguments and result).  ``prefetch=False`` waits for a step's
    copies before it scores them, as the narrow body does: it is there to
    be timed against (``chip_smoke.py`` leg H).  Raises ValueError for
    what supported_wide() rejects — call it first."""
    b, sq, h = q.shape
    _, bs, _ = k_pool.shape
    pages_per_seq = block_table.shape[1]
    ok, why = supported_wide(sq, h, n_head, bs, k_pool.dtype)
    if not ok:
        raise ValueError(f"paged_decode_attention_wide: unsupported ({why})")
    d = h // n_head
    page_bytes = bs * h * k_pool.dtype.itemsize
    pps = max(1, min(int(pages_per_step), pages_per_seq,
                     WIDE_BUFFER_BYTES // (4 * page_bytes)))
    # every page live
    table_bytes = 2 * b * pages_per_seq * page_bytes
    out = pl.pallas_call(
        functools.partial(_kernel_wide, head_dim=d,
                          scale=1.0 / math.sqrt(d), prefetch=prefetch),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec((b, h), lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((b, h), lambda i, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((2, pps * bs, h), k_pool.dtype),
                            pltpu.VMEM((2, pps * bs, h), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((b, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * table_bytes, bytes_accessed=table_bytes,
            transcendentals=table_bytes // (2 * d)),
        interpret=interpret,
        name="paged_decode_attn_wide",
    )(ctx_len.astype(jnp.int32), block_table.reshape(-1).astype(jnp.int32),
      q.reshape(b, h).astype(jnp.float32), k_pool, v_pool)
    return out.reshape(b, 1, h).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "num_kv_heads", "window", "pages_per_step", "interpret"))
def paged_gqa_decode(q, k_pool, v_pool, block_table, ctx_len, *, n_head,
                     num_kv_heads, window=0,
                     pages_per_step=PAGES_PER_STEP_WIDE, interpret=False):
    """:func:`paged_decode_attention_wide` over grouped K/V heads and a
    window (:func:`_kernel_gqa`): the pools hold ``num_kv_heads`` heads
    and query head ``h`` reads K/V head ``h // (n_head / num_kv_heads)``;
    under ``window`` a row sees its last ``window`` positions only (``ctx
    - window <= t < ctx``).  Raises ValueError for what supported_gqa()
    rejects — call it first."""
    n_kv, window = int(num_kv_heads), int(window or 0)
    b, sq, h = q.shape
    _, bs, hkv = k_pool.shape
    pages_per_seq = block_table.shape[1]
    ok, why = supported_gqa(sq, h, n_head, n_kv, bs, k_pool.dtype)
    if not ok or hkv * n_head != h * n_kv:
        raise ValueError(f"paged_gqa_decode: unsupported "
                         f"({why or f'pool width {hkv}'})")
    d, group = h // n_head, n_head // n_kv
    # a K/V head's query rows, in whole sublane tiles
    gp = -(-group // SUBLANES) * SUBLANES
    qg = q.reshape(b, n_kv, group, d).astype(jnp.float32) \
        * (1.0 / math.sqrt(d))
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    page_bytes = bs * hkv * k_pool.dtype.itemsize
    pps = max(1, min(int(pages_per_step), pages_per_seq,
                     WIDE_BUFFER_BYTES // (4 * page_bytes)))
    # the pages a row reads at most
    pages = min(pages_per_seq, -(-window // bs) + 1) if window \
        else pages_per_seq
    read_bytes = 2 * b * pages * page_bytes
    # query and output blocks (two buffers each) beside the page buffers
    vmem = 4 * b * n_kv * gp * d * 4 + 4 * pps * page_bytes
    out = pl.pallas_call(
        functools.partial(_kernel_gqa, head_dim=d, rows_per_kv=gp,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec((b, n_kv * gp, d),
                                   lambda i, *_: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((b, n_kv * gp, d),
                                   lambda i, *_: (0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, pps * bs, hkv), k_pool.dtype),
                            pltpu.VMEM((2, pps * bs, hkv), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((b, n_kv * gp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        cost_estimate=pl.CostEstimate(
            flops=2 * group * read_bytes, bytes_accessed=read_bytes,
            transcendentals=group * read_bytes // (2 * d)),
        interpret=interpret,
        name="paged_gqa_decode",
    )(ctx_len.astype(jnp.int32), block_table.reshape(-1).astype(jnp.int32),
      qg.reshape(b, n_kv * gp, d), k_pool, v_pool)
    out = out.reshape(b, n_kv, gp, d)[:, :, :group]
    return out.reshape(b, 1, h).astype(q.dtype)
