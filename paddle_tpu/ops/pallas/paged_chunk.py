"""Paged chunk attention as one Pallas TPU kernel: the cached read of a
query of MORE than one token (a prefill chunk, whose ``QPos`` gives each
query its absolute position) straight from the K/V pools, page by page
through the block table, each query block only as far as its causal
frontier.

The composition it replaces (``lower_cached_attention(use_flash=True)``)
gathers the row's whole table out of each pool, ``[B, T, H]`` with ``T =
max_blocks_per_seq * block_size``, builds a ``[B, 1, Sq, T]`` bias and
scores all ``T`` positions in the blockwise flash kernel, whatever the
context holds: in ``olmo_hybrid_serve.doc_closed`` (a 16 384-position
table, prompts of 1-12k tokens, 1 024-token chunks) that was 59 % of the
cell's device time on a v5e (PERF.md section 5).  Here:

* the pools stay ``[num_blocks, block_size, H]`` in HBM
  (``memory_space=pl.ANY``); the block table and each query block's
  frontier are scalar prefetched;
* query block ``j`` of a row (``q_block`` queries) reads pages ``0 ..
  ceil(hi_j / block_size) - 1`` only, ``hi_j = min(CtxLen, max QPos of
  the block + 1)``: pages past the frontier are never fetched.  The
  copies run ``KEY_BLOCK`` positions a step on two buffers: the next
  step's pages are in flight while this one is scored;
* the grid is (row, query block, head group); a group is the heads of
  one step, side by side in the op's ``(..., H)`` layout (``group_heads``):
  a page's lanes of the group are copied as they lie, no head split and
  no relayout of the pool;
* each head's scores are one MXU product ``[q_block, d] x [d, KEY_BLOCK]``
  with float32 accumulation, an online softmax with float32 state, and
  ``p . V`` on the MXU; heads are whole lane tiles (``d % 128 == 0``),
  so a head's slab is a tile-aligned lane slice;
* the causal and context mask (key ``t`` is visible to query ``i`` iff
  ``t < min(QPos_i + 1, CtxLen)``) is applied on the steps that cross a
  query block's diagonal or its context's end alone; the steps wholly
  below every query's bound run without it.

**The same numbers as the gather.**  Masked pairs get an EXACTLY-zero
weight (a select on the score, never an added bias), and V rows past the
frontier are zeroed before the MXU meets them (``0 * NaN`` is NaN: a
page not fetched holds whatever the buffer held).  The products are
those of the flash kernel behind the gather: bfloat16 query and pages
multiplied as they are, float32 accumulation, the score scaled after
the product, ``p`` rounded to the pages' dtype for ``p . V``, in blocks
of 128 keys from position 0 — so co-batched, chunked and one-shot
prefill read the same bytes with the same arithmetic.  Float32 operands
(either side) are multiplied at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import EMPTY, LANES, MASKED, SUBLANES

#: queries a block (the largest power of two dividing the chunk, at most
#: this): a block's pages are copied once for all of its queries
Q_BLOCK = 256
#: key positions one step copies and scores: one MXU tile of keys, the
#: flash kernel's key block (8 pages of 16)
KEY_BLOCK = 128
#: most bytes of one query's row of a grid step's lanes (heads x head_dim)
#: in the wider of the query's and the pools' dtypes: bounds the page
#: buffers, the query and output blocks and the float32 accumulator in
#: VMEM — 2 048 lanes of bfloat16, 1 024 of float32 (on the v5e, one row's
#: 1 024-query bfloat16 chunk at 30 heads of 128, 12 calls: 23.1 ms at
#: 1 024 lanes (6 heads a step), 16.5 at 2 048 (15), 18.7 with all 30 at
#: 128-query blocks; all 30 at 256 overrun the scoped VMEM)
GROUP_BYTES = 4096

_NT = (((1,), (1,)), ((), ()))      # (M, K) x (N, K) -> (M, N)
_NN = (((1,), (0,)), ((), ()))      # (M, K) x (K, N) -> (M, N)


def q_block(sq: int) -> int:
    """Queries a block for a chunk of ``sq``."""
    return math.gcd(int(sq), Q_BLOCK)


def supported(sq, hidden, n_head, block_size, dtype="float32",
              has_qpos=True, n_kv=None):
    """Static shape rule -> (ok, reason): is this cache-read attention a
    chunk the kernel takes?  A query of more than one token with
    ``QPos``, float32 or bfloat16 pools in pages of whole sublane tiles,
    heads of whole lane tiles (``n_kv`` K/V heads, a divisor of
    ``n_head``), and a chunk that divides into query blocks of whole
    bfloat16 tiles."""
    if n_kv is not None and (n_kv <= 0 or n_head % n_kv):
        return False, f"paged-chunk:heads:{n_head}/{n_kv}"
    if not has_qpos:
        return False, "paged-chunk:no-qpos"
    if sq <= 1:
        return False, f"paged-chunk:sq:{sq}"
    if jnp.dtype(dtype) not in (jnp.float32, jnp.bfloat16):
        return False, f"paged-chunk:dtype:{jnp.dtype(dtype).name}"
    if block_size % (SUBLANES * 4 // jnp.dtype(dtype).itemsize):
        return False, f"paged-chunk:block-size:{block_size}"
    d = hidden // max(1, n_head)
    if n_head <= 0 or hidden % n_head or d % LANES:
        return False, f"paged-chunk:head-dim:{d}"
    if q_block(sq) % 16:
        return False, f"paged-chunk:q-block:{sq}"
    return True, ""


def group_heads(n_head: int, head_dim: int, itemsize: int,
                kv_group: int = 1) -> int:
    """Heads of one grid step: the most that divide ``n_head``, are whole
    groups of the ``kv_group`` query heads one K/V head serves, and fit
    ``GROUP_BYTES`` at ``itemsize`` bytes a lane (at least one group)."""
    return max([g for g in range(kv_group, n_head + 1, kv_group)
                if n_head % g == 0
                and g * head_dim * itemsize <= GROUP_BYTES] or [kv_group])


def frontiers(q_pos, ctx_len, sq, positions):
    """Per query block of each row, ``(hi, lo)``: ``hi`` the positions the
    block reads (its last visible key + 1: ``min(ctx, max QPos + 1)``),
    ``lo`` the positions EVERY query of the block sees (``min(ctx, min
    QPos + 1)``); and ``bound`` ``[B, Sq, 1]``, each query's own.  Works
    on jax and NumPy arrays alike (the engine counts the pages a chunk
    reads with it, on the host)."""
    xp = jnp if isinstance(q_pos, jax.Array) else np
    b = q_pos.shape[0]
    ctx = xp.clip(ctx_len.astype(xp.int32), 0, positions)[:, None]
    pos = q_pos.astype(xp.int32).reshape(b, sq // q_block(sq), q_block(sq))
    hi = xp.minimum(ctx, pos.max(axis=2) + 1)
    lo = xp.minimum(ctx, pos.min(axis=2) + 1)
    bound = xp.minimum(ctx, q_pos.astype(xp.int32) + 1)
    return hi, lo, bound[:, :, None]


def window_bounds(q_pos, ctx_len, sq, window):
    """Under a ``window`` (key ``t`` visible to query ``p`` iff ``p -
    window < t``), per query block of each row ``(first, edge)``: ``first``
    the block's first visible key (its earliest query's), ``edge`` the
    first key every query of the block sees from below (its latest
    query's); and ``lower`` ``[B, Sq, 1]``, each query's own."""
    xp = jnp if isinstance(q_pos, jax.Array) else np
    b = q_pos.shape[0]
    lower = xp.maximum(q_pos.astype(xp.int32) - (window - 1), 0)
    blocks = lower.reshape(b, sq // q_block(sq), q_block(sq))
    return blocks.min(axis=2), blocks.max(axis=2), lower[:, :, None]


def pages_read(hi, block_size: int, pages_per_seq: int):
    """The pages each query block fetches: page 0 at least, the table at
    most."""
    return np.clip(-(-np.asarray(hi) // block_size), 1, pages_per_seq)


def _kernel(tbl_ref, hi_ref, lo_ref, q_ref, bound_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, m_ref, l_ref, acc_ref, **kw):
    _chunk_body(tbl_ref, hi_ref, lo_ref, q_ref, bound_ref, k_hbm, v_hbm,
                o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref, **kw)


def _kernel_window(tbl_ref, hi_ref, lo_ref, first_ref, edge_ref, q_ref,
                   bound_ref, lower_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
                   sem, m_ref, l_ref, acc_ref, **kw):
    _chunk_body(tbl_ref, hi_ref, lo_ref, q_ref, bound_ref, k_hbm, v_hbm,
                o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref,
                window=(first_ref, edge_ref, lower_ref), **kw)


def _chunk_body(tbl_ref, hi_ref, lo_ref, q_ref, bound_ref, k_hbm, v_hbm,
                o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *, heads,
                head_dim, pages_per_seq, scale, exact, kv_group=1,
                window=None):
    """One grid step: ``heads`` query heads of one query block, against
    their ``heads / kv_group`` K/V heads' lanes of the pages.  ``window``:
    the refs of the block's first visible key, the first key all its
    queries see from below, and each query's lower bound; the walk then
    starts at the key block of the first."""
    b, j, g = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(1)
    num_blocks, bs, _ = k_hbm.shape
    rows = kbuf.shape[1]            # positions one step copies and scores
    pps = rows // bs                # pages a step
    width = heads * head_dim
    kv_width = width // kv_group
    # the group's K/V lanes of every page
    cols = pl.ds(pl.multiple_of(g * kv_width, LANES), kv_width)
    hi = hi_ref[b * n_q + j]
    pages = jnp.clip((hi + bs - 1) // bs, 1, pages_per_seq)
    steps = (pages + pps - 1) // pps
    # steps wholly below every query's bound need no mask
    unmasked = lo_ref[b * n_q + j] // rows
    first_key = first = above = None
    if window is not None:
        first_ref, edge_ref, lower_ref = window
        first_key = first_ref[b * n_q + j]
        first = first_key // rows
        # and, under a window, wholly above every query's lower bound
        above = (edge_ref[b * n_q + j] + rows - 1) // rows

    def copies(c, slot):
        """The page copies of step ``c`` into buffer ``slot``, each with
        whether its page is live: a page past the frontier is neither
        started nor waited for."""
        out = []
        for p in range(pps):
            page = c * pps + p
            entry = tbl_ref[b * pages_per_seq
                            + jnp.minimum(page, pages_per_seq - 1)]
            blk = jnp.clip(entry, 0, num_blocks - 1)
            dst = pl.ds(p * bs, bs)
            out.append((page < pages,
                        pltpu.make_async_copy(k_hbm.at[blk, :, cols],
                                              kbuf.at[slot, dst],
                                              sem.at[slot]),
                        pltpu.make_async_copy(v_hbm.at[blk, :, cols],
                                              vbuf.at[slot, dst],
                                              sem.at[slot])))
        return out

    def start(c, slot):
        for live, ck, cv in copies(c, slot):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(c, slot):
        for live, ck, cv in copies(c, slot):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    if first is None:
        start(0, 0)
    else:
        start(first, first % 2)
    m_ref[...] = jnp.full_like(m_ref, EMPTY)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    qb = q_ref.shape[1]
    pos = lax.broadcasted_iota(jnp.int32, (qb, rows), 1)

    def score(c, slot, masked):
        bound = bound_ref[0] - c * rows             # (qb, 1)
        for i in range(heads):
            lanes = slice(i * head_dim, (i + 1) * head_dim)
            kv = i // kv_group
            kv_lanes = slice(kv * head_dim, (kv + 1) * head_dim)
            qh, kh = q_ref[0, :, lanes], kbuf[slot, :, kv_lanes]
            vh = vbuf[slot, :, kv_lanes]
            if exact:
                s = lax.dot_general(qh.astype(jnp.float32),
                                    kh.astype(jnp.float32), _NT,
                                    precision=lax.Precision.HIGHEST,
                                    preferred_element_type=jnp.float32)
            else:
                s = lax.dot_general(qh, kh, _NT,
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if masked:
                seen = pos < bound
                if window is not None:
                    seen = seen & (pos >= lower_ref[0] - c * rows)
                s = jnp.where(seen, s, MASKED)
            m_prev = m_ref[i]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[i] = alpha * l_ref[i] + jnp.sum(p, axis=1, keepdims=True)
            # float32 pages at full precision; bfloat16 ones take ``p``
            # rounded to them, as the flash kernel does
            pv = lax.dot_general(
                p.astype(vh.dtype), vh, _NN,
                precision=lax.Precision.HIGHEST
                if vh.dtype == jnp.float32 else None,
                preferred_element_type=jnp.float32)
            acc_ref[:, lanes] = alpha * acc_ref[:, lanes] + pv
            m_ref[i] = m_new

    def step(c, _):
        slot = c % 2

        @pl.when(c + 1 < steps)
        def _():
            start(c + 1, 1 - slot)
        wait(c, slot)
        left = hi - c * rows
        edge = left < rows
        if first_key is not None:
            edge = edge | (c * rows < first_key)

        @pl.when(edge)
        def _():
            # a V row outside the block's keys is zero before the MXU
            # meets it: past the context, before the window, or a page
            # not fetched (0 * NaN is NaN)
            v = vbuf[slot]
            at = lax.broadcasted_iota(jnp.int32, v.shape, 0)
            keep = at < left
            if first_key is not None:
                keep = keep & (at >= first_key - c * rows)
            vbuf[slot] = jnp.where(keep, v, jnp.zeros_like(v))

        plain = c < unmasked
        if above is not None:
            plain = plain & (c >= above)

        @pl.when(plain)
        def _():
            score(c, slot, False)

        @pl.when(jnp.logical_not(plain) if above is not None
                 else c >= unmasked)
        def _():
            score(c, slot, True)
        return 0

    lax.fori_loop(0 if first is None else first, steps, step, 0)
    for i in range(heads):
        lanes = slice(i * head_dim, (i + 1) * head_dim)
        o_ref[0, :, lanes] = (acc_ref[:, lanes]
                              / jnp.maximum(l_ref[i], 1e-30)
                              ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_head", "num_kv_heads",
                                             "window", "interpret"))
def paged_chunk_attention(q, k_pool, v_pool, block_table, ctx_len, q_pos, *,
                          n_head, num_kv_heads=None, window=0,
                          interpret=False):
    """q: (B, Sq, H) a chunk of queries a row; k_pool / v_pool:
    (num_blocks, block_size, H_kv) float32 or bfloat16 (``num_kv_heads``
    heads, ``n_head`` when None: query head ``h`` reads K/V head ``h //
    (n_head / num_kv_heads)``); block_table: (B, max_blocks_per_seq)
    int32; ctx_len: (B,) live positions of each row (the chunk's own
    included); q_pos: (B, Sq) each query's absolute position; ``window``:
    key ``t`` is visible to query ``p`` iff ``p - window < t`` too.
    Returns the context, (B, Sq, H) in q's dtype.  Raises ValueError for
    what supported() rejects — call it first."""
    b, sq, h = q.shape
    _, bs, hkv = k_pool.shape
    pages_per_seq = block_table.shape[1]
    n_kv, window = int(num_kv_heads or n_head), int(window or 0)
    ok, why = supported(sq, h, n_head, bs, k_pool.dtype, n_kv=n_kv)
    if not ok or hkv * n_head != h * n_kv:
        raise ValueError(f"paged_chunk_attention: unsupported "
                         f"({why or f'pool width {hkv}'})")
    d = h // n_head
    kv_group = n_head // n_kv
    heads = group_heads(n_head, d, max(q.dtype.itemsize,
                                       k_pool.dtype.itemsize), kv_group)
    width = heads * d
    kv_width = width // kv_group
    qb = q_block(sq)
    rows = max(1, KEY_BLOCK // bs) * bs      # whole pages a step
    hi, lo, bound = frontiers(q_pos, ctx_len, sq, pages_per_seq * bs)
    exact = not (q.dtype == k_pool.dtype == v_pool.dtype == jnp.bfloat16)
    # every query block against half the table (or its window), an
    # estimate for the scheduler
    half = pages_per_seq * bs // 2
    if window:
        half = min(half, window)
    pairs = b * sq * half
    kw = dict(heads=heads, head_dim=d, pages_per_seq=pages_per_seq,
              scale=1.0 / math.sqrt(d), exact=exact)
    prefetched = [block_table.reshape(-1).astype(jnp.int32), hi.reshape(-1),
                  lo.reshape(-1)]
    q_spec = pl.BlockSpec((1, qb, width), lambda i, j, g, *_: (i, j, g))
    row_spec = pl.BlockSpec((1, qb, 1), lambda i, j, g, *_: (i, j, 0))
    blocks, specs, kernel = [q, bound], [q_spec, row_spec], _kernel
    if kv_group > 1:
        kw["kv_group"] = kv_group
    if window:
        first, edge, lower = window_bounds(q_pos, ctx_len, sq, window)
        prefetched += [first.reshape(-1), edge.reshape(-1)]
        blocks.append(lower)
        specs.append(row_spec)
        kernel = _kernel_window
    out = pl.pallas_call(
        functools.partial(kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(b, sq // qb, h // width),
            in_specs=specs + [pl.BlockSpec(memory_space=pl.ANY),
                              pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, qb, width),
                                   lambda i, j, g, *_: (i, j, g)),
            scratch_shapes=[pltpu.VMEM((2, rows, kv_width), k_pool.dtype),
                            pltpu.VMEM((2, rows, kv_width), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((heads, qb, 1), jnp.float32),
                            pltpu.VMEM((heads, qb, 1), jnp.float32),
                            pltpu.VMEM((qb, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, sq, h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * pairs * h,
            bytes_accessed=2 * b * (sq // qb) * half * hkv
            * k_pool.dtype.itemsize,
            transcendentals=pairs * n_head),
        interpret=interpret,
        name="paged_chunk_attn",
    )(*prefetched, *blocks, k_pool, v_pool)
    return out
