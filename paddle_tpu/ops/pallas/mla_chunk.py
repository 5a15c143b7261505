"""Chunked prefill over the PAGED LATENT CACHE as one Pallas TPU kernel:
the cached read of a query of more than one token (``QPos`` gives each
query its absolute position) in latent attention's EXPANDED form,
straight from the latent pool, each query block only as far as its causal
frontier.

Latent attention keeps one row a token and layer, ``[c_kv | k_rope | 0]``
(``dc + dr`` live values padded to a width ``W`` of whole lane tiles); a
head's key and value are up-projections of ``c_kv`` (``[k_nope_h | v_h] =
c_kv W_kvb[:, h]``) and every head shares the rotary key.  The composition
this replaces (``mla_ops.chunk_attention``, still the CPU path and the
specification) is an XLA loop over key blocks that gathers, up-projects
and scores each block into a ``[H, Sq, keys]`` float32 tensor in HBM, to
the longest live context.  Here:

* the pool stays ``[num_blocks, block_size, W]`` in HBM
  (``memory_space=pl.ANY``); the block table, each row's context and each
  query block's frontier (``paged_chunk.frontiers``) are scalar
  prefetched;
* the grid is (row, head group, query block), query blocks innermost.  On
  a group's first query block the kernel walks the row's live pages,
  ``KEY_STEP`` positions a step on two buffers (the next step's copies in
  flight while this one is up-projected), and up-projects each step ONCE
  for the group's heads: one MXU product ``[keys, dc] x [dc, heads *
  (dn + dv)]`` with float32 accumulation, rounded to the pool's dtype as
  ``mla_ops._expand`` rounds it, into VMEM that holds the group's keys and
  values for the whole table; the group's ``W_kvb`` columns stay resident
  across its query blocks.  So the up-projection costs one pass over the
  context a layer, whatever the number of query blocks;
* query block ``j`` then scores only the steps up to its frontier ``hi_j
  = min(CtxLen, max QPos + 1)``: per head ``q_nope . k_nope^T + q_rope .
  k_rope^T`` (the rotary query padded with zeros to the row's lanes past
  ``dc``), scaled after the product, an online softmax with float32
  state, ``p`` rounded to the values' dtype for ``p . V`` with a float32
  accumulator.  The running maximum and sum are kept a lane tile wide,
  every lane alike, so they meet the scores and the accumulator without a
  relayout (on the v5e a 1 024-query chunk at ``CtxLen`` 2 048 took 2.56
  ms a layer so, 4.05 ms with one lane).  No ``[H, Sq, T]`` tensor is
  ever written to HBM;
* key ``t`` is visible to query ``i`` iff ``t < min(QPos_i + 1,
  CtxLen)``, by a SELECT on the score, applied on the steps that cross a
  query block's diagonal or its context's end alone.  A padded query row
  (position 0) sees key 0, as in the specification.

**No stale byte reaches the MXU.**  Latent rows at or past ``CtxLen``
(the last page's tail, pages not fetched, an earlier step's leftovers)
are zeroed by a select before the up-projection, so every key and value
a query block can score is finite; masked pairs get an exactly-zero
weight.  Float32 operands (either side) are multiplied at
``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mla_paged import EMPTY, LANES, MASKED
from .paged_attention import SUBLANES
from .paged_chunk import frontiers, q_block

#: key positions one step copies, up-projects and scores (16 pages of 16;
#: steps of 512 and groups of 2 or 8 heads timed the same on the v5e at
#: ``reason_closed``'s chunk)
KEY_STEP = 256
#: the most heads of one grid step
MAX_GROUP = 4
#: bytes of VMEM a grid step may plan for (the group's up-projected keys
#: and values for the whole table dominate); the scoped limit is set a
#: quarter above it
VMEM_BUDGET = 40 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))      # (M, K) x (N, K) -> (M, N)
_NN = (((1,), (0,)), ((), ()))      # (M, K) x (K, N) -> (M, N)


def key_step(pages_per_seq: int, block_size: int,
             pages_per_step=None) -> int:
    """Positions a step: whole pages, a divisor of the table, at most
    ``KEY_STEP`` (or ``pages_per_step`` pages)."""
    want = max(1, pages_per_step or KEY_STEP // block_size)
    return block_size * max(p for p in range(1, min(want, pages_per_seq) + 1)
                            if pages_per_seq % p == 0)


def vmem_bytes(heads, sq, nope_dim, v_dim, latent_dim, width, block_size,
               pages_per_seq, itemsize) -> int:
    """VMEM one grid step of ``heads`` heads plans for: the group's keys,
    values and the shared rotary lanes for every table position, the two
    latent buffers, the double-buffered query / ``W_kvb`` / output blocks,
    the float32 state and the up-projection's product."""
    positions = pages_per_seq * block_size
    rope = width - latent_dim
    rows = key_step(pages_per_seq, block_size)
    qb = q_block(sq)
    per_head = (positions * (nope_dim + v_dim) * itemsize
                + 2 * qb * (nope_dim + rope) * itemsize
                + 2 * latent_dim * (nope_dim + v_dim) * itemsize
                + 2 * qb * v_dim * itemsize
                + qb * v_dim * 4 + 2 * qb * LANES * 4
                + rows * (nope_dim + v_dim) * 4)
    shared = (positions * rope * itemsize + 2 * rows * width * itemsize
              + 2 * qb * LANES * 4 + 4 * qb * rows * 4)
    return heads * per_head + shared


def group_heads(n_head, sq, nope_dim, v_dim, latent_dim, width, block_size,
                pages_per_seq, itemsize) -> int:
    """Heads of one grid step: the most that divide ``n_head``, at most
    ``MAX_GROUP``, whose step fits ``VMEM_BUDGET`` (0: not even one)."""
    fits = [g for g in range(1, min(MAX_GROUP, n_head) + 1)
            if n_head % g == 0 and vmem_bytes(
                g, sq, nope_dim, v_dim, latent_dim, width, block_size,
                pages_per_seq, itemsize) <= VMEM_BUDGET]
    return max(fits or [0])


def supported(sq, n_head, nope_dim, rope_dim, v_dim, latent_dim,
              block_size, width, pages_per_seq, dtype="bfloat16",
              has_qpos=True):
    """Static shape rule -> (ok, reason): is this cached latent read a
    chunk the kernel takes?  A query of more than one token with
    ``QPos``; float32 or bfloat16 pools in pages of whole sublane tiles;
    the latent, the row and the per-head key and value widths in whole
    lane tiles, the rotary key inside the row's lanes past the latent;
    query blocks of whole bfloat16 tiles; and one head's keys and values
    for the whole table inside the VMEM budget."""
    if not has_qpos:
        return False, "mla-chunk:no-qpos"
    if sq <= 1:
        return False, f"mla-chunk:sq:{sq}"
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False, f"mla-chunk:dtype:{dtype.name}"
    if block_size % (SUBLANES * 4 // dtype.itemsize):
        return False, f"mla-chunk:block-size:{block_size}"
    if latent_dim % LANES or width % LANES \
            or width < latent_dim + rope_dim:
        return False, f"mla-chunk:latent:{latent_dim}+{rope_dim}/{width}"
    if n_head <= 0 or nope_dim % LANES or v_dim % LANES:
        return False, f"mla-chunk:head:{n_head}x{nope_dim}/{v_dim}"
    if q_block(sq) % 16:
        return False, f"mla-chunk:q-block:{sq}"
    if not group_heads(n_head, sq, nope_dim, v_dim, latent_dim, width,
                       block_size, pages_per_seq, dtype.itemsize):
        return False, f"mla-chunk:vmem:{pages_per_seq}x{block_size}"
    return True, ""


def _lanes(x, n):
    """``x`` (rows, 128), every lane alike -> (rows, n)."""
    if n % LANES:
        return x[:, :1]
    return x if n == LANES else pltpu.repeat(x, n // LANES, 1)


def _dot(a, b, dims, exact):
    if exact:
        return lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               dims, precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _kernel(tbl_ref, ctx_ref, hi_ref, lo_ref, q_ref, bound_ref, w_ref,
            pool_hbm, o_ref, lat, sem, kc, vc, rc, m_ref, l_ref, acc_ref, *,
            heads, nope_dim, v_dim, latent_dim, pages_per_seq, scale,
            exact):
    b, j = pl.program_id(0), pl.program_id(2)
    n_q = pl.num_programs(2)
    num_blocks, bs, width = pool_hbm.shape
    rows = lat.shape[1]             # positions one step copies and scores
    pps = rows // bs                # pages a step
    dn, dv, dc = nope_dim, v_dim, latent_dim
    rope = width - dc
    ctx = jnp.clip(ctx_ref[b], 0, pages_per_seq * bs)

    @pl.when(j == 0)
    def _up_project():
        """The group's keys, values and the rotary lanes of every live
        step of the row's table, into VMEM."""
        pages = jnp.clip((ctx + bs - 1) // bs, 1, pages_per_seq)
        steps = (pages + pps - 1) // pps

        def copies(c, slot):
            out = []
            for p in range(pps):
                page = c * pps + p
                entry = tbl_ref[b * pages_per_seq
                                + jnp.minimum(page, pages_per_seq - 1)]
                blk = jnp.clip(entry, 0, num_blocks - 1)
                out.append((page < pages, pltpu.make_async_copy(
                    pool_hbm.at[blk], lat.at[slot, pl.ds(p * bs, bs)],
                    sem.at[slot])))
            return out

        def start(c, slot):
            for live, cp in copies(c, slot):
                @pl.when(live)
                def _():
                    cp.start()

        def wait(c, slot):
            for live, cp in copies(c, slot):
                @pl.when(live)
                def _():
                    cp.wait()

        start(0, 0)
        at_row = lax.broadcasted_iota(jnp.int32, (rows, width), 0)

        def step(c, _):
            slot = c % 2

            @pl.when(c + 1 < steps)
            def _():
                start(c + 1, 1 - slot)
            wait(c, slot)
            # a row past the context (the last page's tail, a page not
            # fetched) is zero before the MXU meets it: 0 * NaN is NaN
            x = jnp.where(at_row < ctx - c * rows, lat[slot],
                          jnp.zeros((rows, width), lat.dtype))
            kv = _dot(x[:, :dc], w_ref[...], _NN, exact).astype(kc.dtype)
            at = pl.ds(pl.multiple_of(c * rows, rows), rows)
            for i in range(heads):
                base = i * (dn + dv)
                kc[at, i * dn:(i + 1) * dn] = kv[:, base:base + dn]
                vc[at, i * dv:(i + 1) * dv] = kv[:, base + dn:base + dn + dv]
            rc[at, :] = x[:, dc:]
            return 0

        lax.fori_loop(0, steps, step, 0)

    hi = hi_ref[b * n_q + j]
    steps = jnp.clip((hi + rows - 1) // rows, 1, pages_per_seq * bs // rows)
    # steps wholly below every query's bound need no mask
    unmasked = lo_ref[b * n_q + j] // rows
    m_ref[...] = jnp.full_like(m_ref, EMPTY)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    qb = q_ref.shape[1]
    pos = lax.broadcasted_iota(jnp.int32, (qb, rows), 1)

    def score(c, masked):
        at = pl.ds(pl.multiple_of(c * rows, rows), rows)
        k_rope = rc[at, :]
        bound = bound_ref[0] - c * rows                 # (qb, 1)
        for i in range(heads):
            lanes = i * (dn + rope)
            q_nope = q_ref[0, :, lanes:lanes + dn]
            q_rope = q_ref[0, :, lanes + dn:lanes + dn + rope]
            s = _dot(q_nope, kc[at, i * dn:(i + 1) * dn], _NT, exact) \
                + _dot(q_rope, k_rope, _NT, exact)
            s = s * scale
            if masked:
                s = jnp.where(pos < bound, s, MASKED)
            m_prev = m_ref[i]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes(m_new, rows))
            l_ref[i] = alpha * l_ref[i] + jnp.sum(p, axis=1, keepdims=True)
            v = vc[at, i * dv:(i + 1) * dv]
            out = slice(i * dv, (i + 1) * dv)
            acc_ref[:, out] = _lanes(alpha, dv) * acc_ref[:, out] + _dot(
                p.astype(v.dtype), v, _NN, exact)
            m_ref[i] = m_new

    def step(c, _):
        @pl.when(c < unmasked)
        def _():
            score(c, False)

        @pl.when(c >= unmasked)
        def _():
            score(c, True)
        return 0

    lax.fori_loop(0, steps, step, 0)
    for i in range(heads):
        out = slice(i * dv, (i + 1) * dv)
        o_ref[0, :, out] = (acc_ref[:, out] / _lanes(
            jnp.maximum(l_ref[i], 1e-30), dv)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "nope_dim", "rope_dim", "v_dim", "scale", "pages_per_step",
    "interpret"))
def mla_chunk_attention(q, pool, block_table, ctx_len, q_pos, wkvb, *,
                        n_head, nope_dim, rope_dim, v_dim, scale,
                        pages_per_step=None, interpret=False):
    """q: (B, Sq, H * (dn + dr)) a chunk of queries a row, ``[q_nope |
    q_rope]`` per head, the rotary part rotated; pool: (num_blocks,
    block_size, W) float32 or bfloat16, rows ``[c_kv (dc) | k_rope (dr) |
    0]``; block_table: (B, max_blocks_per_seq) int32; ctx_len: (B,) live
    positions of each row (the chunk's own included); q_pos: (B, Sq) each
    query's absolute position; wkvb: (dc, H * (dn + dv)), ``[k_nope | v]``
    per head; ``pages_per_step``: the pages of a key step (default
    ``KEY_STEP`` positions).  Returns the context, (B, Sq, H * dv) in q's
    dtype — what ``mla_ops.chunk_attention`` returns.  Raises ValueError
    for what supported() rejects — call it first."""
    b, sq, _ = q.shape
    _, bs, width = pool.shape
    dc = wkvb.shape[0]
    h, dn, dr, dv = n_head, nope_dim, rope_dim, v_dim
    pages_per_seq = block_table.shape[1]
    ok, why = supported(sq, h, dn, dr, dv, dc, bs, width, pages_per_seq,
                        pool.dtype)
    if ok and (q.shape[-1] != h * (dn + dr) or wkvb.shape[1] != h * (dn + dv)):
        ok, why = False, f"mla-chunk:widths:{q.shape}/{wkvb.shape}"
    if not ok:
        raise ValueError(f"mla_chunk_attention: unsupported ({why})")
    rope = width - dc
    itemsize = pool.dtype.itemsize
    heads = group_heads(h, sq, dn, dv, dc, width, bs, pages_per_seq,
                        itemsize)
    qb = q_block(sq)
    rows = key_step(pages_per_seq, bs, pages_per_step)
    exact = not (q.dtype == pool.dtype == wkvb.dtype == jnp.bfloat16)
    # each head's query as [q_nope | q_rope | 0]: the rotary part padded
    # to the row's lanes past the latent, which hold [k_rope | 0]
    qh = q.reshape(b, sq, h, dn + dr)
    qk = jnp.concatenate(
        [qh, jnp.zeros((b, sq, h, rope - dr), q.dtype)], axis=-1
    ).reshape(b, sq, h * (dn + rope))
    hi, lo, bound = frontiers(q_pos, ctx_len, sq, pages_per_seq * bs)
    ctx = jnp.clip(ctx_len.astype(jnp.int32), 0, pages_per_seq * bs)
    # every query block against half the table, an estimate for the
    # scheduler: the scoring, and one up-projection of the table a group
    half = pages_per_seq * bs // 2
    flops = 2 * b * sq * half * h * (dn + rope + dv) \
        + 2 * b * 2 * half * dc * h * (dn + dv)
    prefetched = [block_table.reshape(-1).astype(jnp.int32), ctx,
                  hi.reshape(-1), lo.reshape(-1)]
    kw = dict(heads=heads, nope_dim=dn, v_dim=dv, latent_dim=dc,
              pages_per_seq=pages_per_seq, scale=float(scale), exact=exact)
    limit = vmem_bytes(heads, sq, dn, dv, dc, width, bs, pages_per_seq,
                       itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(b, h // heads, sq // qb),
            in_specs=[
                pl.BlockSpec((1, qb, heads * (dn + rope)),
                             lambda i, g, j, *_: (i, j, g)),
                pl.BlockSpec((1, qb, 1), lambda i, g, j, *_: (i, j, 0)),
                pl.BlockSpec((dc, heads * (dn + dv)),
                             lambda i, g, j, *_: (0, g)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, qb, heads * dv),
                                   lambda i, g, j, *_: (i, j, g)),
            scratch_shapes=[
                pltpu.VMEM((2, rows, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((pages_per_seq * bs, heads * dn), pool.dtype),
                pltpu.VMEM((pages_per_seq * bs, heads * dv), pool.dtype),
                pltpu.VMEM((pages_per_seq * bs, rope), pool.dtype),
                pltpu.VMEM((heads, qb, LANES), jnp.float32),
                pltpu.VMEM((heads, qb, LANES), jnp.float32),
                pltpu.VMEM((qb, heads * dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, sq, h * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=int(limit * 1.25)),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=b * (h // heads) * half * width * itemsize
            + b * sq * h * (dn + rope + dv) * itemsize,
            transcendentals=b * sq * half * h),
        interpret=interpret,
        name="mla_chunk_attn",
    )(*prefetched, qk, bound, wkvb, pool)
