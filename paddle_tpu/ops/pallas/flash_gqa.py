"""Causal flash attention with grouped K/V heads and an optional sliding
window, as Pallas TPU kernels (forward, dq, dk/dv).

What flash_attention.py's kernels do not have, and a long-sequence
decoder needs:

* **grouped heads**: ``H`` query heads read ``Hkv`` K/V heads (query
  head ``h`` reads ``h // (H / Hkv)``) straight through the K/V block's
  index map — K and V are never repeated in memory.  A grid step fetches
  one K/V block and the query block of the group's heads together (they
  are adjacent lane blocks of the op's layout; at most ``HEADS`` of them,
  for VMEM) and loops over the heads, so K and V are read once a group,
  not once a query head; the dk/dv kernel sums over the group's heads;
* **a window**: position ``i`` sees ``j <= i`` with ``i - j < window``.
  Key blocks wholly outside the window (and above the diagonal) are
  SKIPPED, not masked: the key axis of the grid spans only the blocks a
  query block can see (3 of 16 at window 1024, blocks of 512), the index
  maps clamp to the last block in range so a step out of range moves no
  data, and its body does not run;
* **two tiles**.  The DMA tile (``pick_block``: 512, 256 or 128 square)
  is the unit of the grid, of the pipeline's fetches and of the block
  skipping above.  A body works through it in SUB-TILES (``pick_sub``:
  up to 256 rows by 128 lanes, read from the tile's size, never from a
  flag).  What the diagonal or the window's edge does to a sub-tile is
  static arithmetic on the tile's offset (``_plan``, which
  ``subtile_counts`` counts): one wholly hidden is skipped — no product,
  no ``exp``; one wholly visible runs plain, with no mask; one that an
  edge crosses is masked with an iota compare.  At window 1024 a query
  block's 24 sub-tiles are 4 skipped, 8 masked, 12 plain; each diagonal
  tile of a full layer is 2 / 4 / 2.  Each tile offset that an edge
  crosses gets a body specialised to it (``_each_tile``) and the tiles
  none crosses share one, so no ``lax.cond`` carries a score tile.
  Why: a v5e's VPU and EUP are f32 only, so the softmax over a 512 x 512
  tile takes ~1.3 us beside ~0.7 us of MXU time for its two products,
  and as one ``[512, 512]`` f32 array (1 MiB, ``p`` another) every pass
  is a round trip through VMEM, one after the other.  In sub-tiles the
  intermediates are a quarter the size, the hidden ones cost nothing,
  and one sub-tile's softmax overlaps the next one's product (PERF.md,
  PR 35: 24 -> 44 % of the bf16 peak);
* **statistics that broadcast without a relayout**: the running max and
  sum are lane-replicated ``[rows, 128]`` (the sum a per-lane partial
  until the end); ``lse`` and ``delta = rowsum(dO * O)`` live in HBM as
  lane-dense rows ``[B, H, 1, S]``; the dq kernel makes ``delta`` and
  turns both into columns once a query block, the dk/dv kernel computes
  the scores TRANSPOSED (keys down the rows) so the rows broadcast down
  the sublanes and dk, dv are plain products;
* **the op's own layout**: Q ``[B, S, H * D]``, K/V ``[B, S, Hkv * D]``
  and the output are blocked as they are (head ``h`` is lane block ``h``
  of the last axis), so there is no head split or merge around the
  kernels (PERF.md, PR 28: those relayouts cost more than the attention);
* MXU operands stay in their own dtype (bf16 under AMP) with f32
  accumulation; the scores are scaled and the softmax statistics kept in
  f32.

No bias and no dropout: a decoder's causal mask and window are
arithmetic on positions.  Residuals are O(S): the output and the per-row
logsumexp ``[B, H, 1, S]``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK = 512              # the largest DMA tile
ROWS, LANES = 256, 128   # the largest sub-tile
HEADS = 8                # most query heads of a group to a grid step


class Tiling(NamedTuple):
    """``blk``: the tile the grid steps over, the DMAs fetch and block
    skipping drops.  ``rows`` x ``lanes``: the sub-tile of it that is
    skipped, masked or left alone; a body computes on one row of them at
    a time."""
    blk: int
    rows: int
    lanes: int


def pick_block(seq: int, block=None) -> int:
    """The largest of 512/256/128 that divides ``seq`` (a caller's
    ``block`` — tests in interpret mode — wins)."""
    if block:
        return int(block)
    for b in (BLOCK, 256, 128):
        if seq % b == 0:
            return b
    raise ValueError(f"flash_gqa: sequence {seq} is not a multiple of 128")


def pick_sub(blk: int, sub=None) -> Tiling:
    """The sub-tiles of a ``blk`` tile: up to 256 rows by 128 lanes
    (PERF.md, PR 35: rows of 128 skip more and run slower).  A caller's
    ``sub`` — tests in interpret mode — wins: ``(rows, lanes)``, or one
    number for both."""
    rows, lanes = (sub if isinstance(sub, tuple) else (sub, sub)) if sub \
        else (min(ROWS, blk), min(LANES, blk))
    if blk % rows or blk % lanes:
        raise ValueError(f"flash_gqa: sub-tile {rows} x {lanes} does not "
                         f"divide the tile {blk}")
    return Tiling(int(blk), int(rows), int(lanes))


def _key_range(qi, blk, window):
    """First and last key block that query block ``qi`` can see."""
    lo = jnp.maximum(qi * blk - (window - 1), 0) // blk if window else 0
    return lo, qi


def _query_range(kj, blk, window, num_q):
    """First and last query block that can see key block ``kj``."""
    hi = jnp.minimum((kj * blk + blk + window - 2) // blk, num_q - 1) \
        if window else num_q - 1
    return kj, hi


def grid_steps(seq, blk, window) -> int:
    """Blocks along the inner grid axis: all of them, or the most one
    block can see through the window (both directions alike)."""
    if not window:
        return seq // blk
    return min(seq // blk, (blk + window - 2) // blk + 1)


# -- what the causal window does to a sub-tile: static arithmetic ---------

DEAD, FULL, EDGE = "dead", "full", "edge"


def _span(off, qa, nq, ka, nk):
    """Least and greatest ``i - j`` over queries ``[qa, qa + nq)`` and
    keys ``[ka, ka + nk)`` of a tile whose first query lies ``off``
    positions after its first key."""
    return off + qa - (ka + nk - 1), off + qa + nq - 1 - ka


def _kind(lo, hi, window):
    """DEAD: no pair of the span is visible (skipped); FULL: every pair
    (no mask); EDGE: the diagonal or the window's edge crosses it."""
    if hi < 0 or (window and lo >= window):
        return DEAD
    if lo >= 0 and not (window and hi >= window):
        return FULL
    return EDGE


def _plan(off, til, window, by_query):
    """The live sub-tiles of the tile at offset ``off``: for each row of
    sub-tiles (queries down the rows if ``by_query``, else keys) the
    ``(lane index, lo, hi)`` of those not DEAD.  The band is convex, so
    they are consecutive."""
    plan = []
    for r in range(til.blk // til.rows):
        cells = []
        for c in range(til.blk // til.lanes):
            ra, ca = (r * til.rows, til.rows), (c * til.lanes, til.lanes)
            lo, hi = _span(off, *ra, *ca) if by_query \
                else _span(off, *ca, *ra)
            if _kind(lo, hi, window) != DEAD:
                cells.append((c, lo, hi))
        plan.append(cells)
    return plan


def subtile_counts(seq, blk, sub, window):
    """How often the mechanism engages: ``(run_plain, run_masked,
    skipped)`` sub-tiles of the tiles the grid executes, a kernel call
    and (batch, query head), read off the plan the bodies are unrolled
    from.  ``sub``: as ``pick_sub`` takes it (``None``: its choice);
    ``sub = blk`` is whole tiles, nothing skipped."""
    til = pick_sub(blk, sub)
    per_tile = (blk // til.rows) * (blk // til.lanes)
    plain = masked = skipped = 0
    for dist in range(grid_steps(seq, blk, window)):
        kinds = [_kind(lo, hi, window)
                 for cells in _plan(dist * blk, til, window, True)
                 for _, lo, hi in cells]
        tiles = seq // blk - dist              # (qi, qi - dist), qi >= dist
        plain += tiles * kinds.count(FULL)
        masked += tiles * kinds.count(EDGE)
        skipped += tiles * (per_tile - len(kinds))
    return plain, masked, skipped


# -- kernel bodies -----------------------------------------------------------

def _dot_nt(a, b):
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _dot(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _lanes(x, n):
    """A lane-replicated ``[rows, lanes]`` statistic at width ``n`` (the
    head size: the same 128 on the chip)."""
    return x if n == x.shape[1] else jnp.broadcast_to(
        x[:, :1], (x.shape[0], n))


def _masked(x, cells, til, window, by_query):
    """The scores ``x`` of a row of live sub-tiles as a list of them,
    those the diagonal or the window's edge crosses masked to -inf."""
    shape = (til.rows, til.lanes)
    diff = None
    out = []
    for i, (_, lo, hi) in enumerate(cells):
        s = x[:, i * til.lanes:(i + 1) * til.lanes]
        if _kind(lo, hi, window) == EDGE:
            if diff is None:       # query minus key position, from (0, 0)
                diff = lax.broadcasted_iota(jnp.int32, shape, 0) \
                    - lax.broadcasted_iota(jnp.int32, shape, 1)
                diff = diff if by_query else -diff
            rel0 = lo + (til.lanes if by_query else til.rows) - 1
            ok = diff >= -rel0 if lo < 0 else None
            if window and hi >= window:
                inside = diff < window - rel0
                ok = inside if ok is None else ok & inside
            s = jnp.where(ok, s, NEG_INF)
        out.append(s)
    return out


def _lane_slice(cells, til):
    return pl.ds(cells[0][0] * til.lanes, len(cells) * til.lanes)


def _each_head(heads, body):
    """``body(g)`` for each of the step's heads, as a loop: unrolled in
    Python every head adds its equations to a kernel that the harness
    lowers twice a run (PERF.md, PR 35: 1.4 s of set-up)."""
    lax.fori_loop(0, heads, lambda g, c: body(g) or c, 0)


def _each_tile(dist, live, n, til, window, heads, body):
    """Run ``body(off, g)`` for each of the step's ``heads`` on the live
    tile at block distance ``dist`` (< ``n``): a loop specialised to each
    offset the diagonal or the window's edge crosses, one shared by the
    tiles neither does."""
    def each_head(off):
        return lambda: _each_head(heads, functools.partial(body, off))

    plain = None
    for d in range(n):
        kind = _kind(*_span(d * til.blk, 0, til.blk, 0, til.blk), window)
        if kind == EDGE:
            pl.when(live & (dist == d))(each_head(d * til.blk))
            live = live & (dist != d)
        elif kind == FULL:
            plain = d * til.blk
    if plain is not None:
        pl.when(live)(each_head(plain))


def _head(ref, g, d, rows=slice(None)):
    """Rows ``rows`` (all of them unless given) of head ``g`` in the
    step's ``[blk, heads * d]`` block."""
    return ref.at[rows, pl.ds(pl.multiple_of(g * d, d), d)]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, til, window, steps, heads, d):
    qi, t = pl.program_id(2), pl.program_id(3)
    lo, hi = _key_range(qi, til.blk, window)
    kj = lo + t

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(off, g):
        for r, cells in enumerate(_plan(off, til, window, True)):
            if not cells:
                continue
            at, keys = pl.ds(r * til.rows, til.rows), _lane_slice(cells, til)
            acc_at = _head(acc_ref, g, d, at)
            m, v = m_ref[g, at, :], v_ref[0, keys, :]
            s = _masked(scale * _dot_nt(_head(q_ref.at[0], g, d, at)[...],
                                        k_ref[0, keys, :]),
                        cells, til, window, True)
            m_new = jnp.maximum(m, jnp.max(
                functools.reduce(jnp.maximum, s), axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = [jnp.exp(x - m_new) for x in s]
            m_ref[g, at, :] = m_new
            # l stays a per-lane partial sum until _finalize
            l_ref[g, at, :] = alpha * l_ref[g, at, :] \
                + functools.reduce(jnp.add, p)
            acc_at[...] = _lanes(alpha, d) * acc_at[...] + _dot(
                jnp.concatenate(p, axis=-1).astype(v.dtype), v)

    _each_tile(qi - kj, kj <= hi, steps, til, window, heads, tile)

    @pl.when(t == steps - 1)
    def _finalize():
        def head(g):
            # every row sees at least its own position, so l > 0
            l = jnp.sum(l_ref[g], axis=-1, keepdims=True)
            _head(o_ref.at[0], g, d)[...] = (
                _head(acc_ref, g, d)[...] / l).astype(o_ref.dtype)
            lse_ref[0, g] = (m_ref[g] + jnp.log(l)).T[:1]
        _each_head(heads, head)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                   delta_ref, acc_ref, lse_col, delta_col, *, scale, til,
                   window, steps, heads, d):
    qi, t = pl.program_id(2), pl.program_id(3)
    lo, hi = _key_range(qi, til.blk, window)
    kj = lo + t

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def head(g):
            delta = jnp.broadcast_to(jnp.sum(
                _head(do_ref.at[0], g, d)[...].astype(jnp.float32)
                * _head(o_ref.at[0], g, d)[...].astype(jnp.float32),
                axis=-1, keepdims=True), delta_col.shape[1:])
            delta_col[g] = delta
            delta_ref[0, g] = delta.T[:1]
            lse_col[g] = jnp.broadcast_to(lse_ref[0, g],
                                          lse_col.shape[:0:-1]).T
        _each_head(heads, head)

    def tile(off, g):
        for r, cells in enumerate(_plan(off, til, window, True)):
            if not cells:
                continue
            at, keys = pl.ds(r * til.rows, til.rows), _lane_slice(cells, til)
            k, v = k_ref[0, keys, :], v_ref[0, keys, :]
            lse, delta = lse_col[g, at, :], delta_col[g, at, :]
            s = _masked(scale * _dot_nt(_head(q_ref.at[0], g, d, at)[...], k),
                        cells, til, window, True)
            dp = _dot_nt(_head(do_ref.at[0], g, d, at)[...], v)
            ds = [jnp.exp(x - lse)
                  * (dp[:, i * til.lanes:(i + 1) * til.lanes] - delta)
                  for i, x in enumerate(s)]
            _head(acc_ref, g, d, at)[...] += _dot(
                jnp.concatenate(ds, axis=-1).astype(k.dtype), k)

    _each_tile(qi - kj, kj <= hi, steps, til, window, heads, tile)

    @pl.when(t == steps - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                    dv_ref, dk_acc, dv_acc, *, scale, til, window, steps,
                    heads, parts, d, num_q):
    """Scores transposed, keys down the rows and queries along the lanes:
    ``lse`` and ``delta`` are lane-dense rows that broadcast down the
    sublanes, and dk and dv are plain products of ``[keys, queries]``."""
    kj, t = pl.program_id(2), pl.program_id(3)
    lo, hi = _query_range(kj, til.blk, window, num_q)
    qi = lo + t % steps

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(off, g):
        for r, cells in enumerate(_plan(off, til, window, False)):
            if not cells:
                continue
            at, qs = pl.ds(r * til.rows, til.rows), _lane_slice(cells, til)
            q, do = _head(q_ref.at[0], g, d, qs)[...], \
                _head(do_ref.at[0], g, d, qs)[...]
            s = jnp.concatenate(_masked(scale * _dot_nt(k_ref[0, at, :], q),
                                        cells, til, window, False), axis=-1)
            p = jnp.exp(s - lse_ref[0, g, :, qs])
            ds = p * (_dot_nt(v_ref[0, at, :], do) - delta_ref[0, g, :, qs])
            dv_acc[at, :] += _dot(p.astype(do.dtype), do)
            dk_acc[at, :] += _dot(ds.astype(q.dtype), q)

    _each_tile(qi - kj, qi <= hi, steps, til, window, heads, tile)

    @pl.when(t == parts * steps - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# the dq kernel holds the step's heads of Q, dO, O and dq twice each
# beside f32 accumulators and statistics: 14.5 MB at 8 bf16 heads of 128,
# twice that in f32 — past the 16 MiB a kernel is given unasked
_PARAMS = dict(compiler_params=pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 << 20))


def _geometry(q, n_head, n_kv, window, block, sub):
    """``heads``: the query heads of a group that share a grid step, the
    most that divide it up to ``HEADS``; a larger group takes ``parts``
    steps a K/V block."""
    b, s, _ = q.shape
    group = n_head // n_kv
    heads = max(h for h in range(1, min(group, HEADS) + 1)
                if group % h == 0)
    til = pick_sub(pick_block(s, block), sub)
    return b, s, q.shape[-1] // n_head, heads, group // heads, til, \
        grid_steps(s, til.blk, window)


def _specs(d, blk, window, heads, parts):
    """Block specs of the forward and dq grids (b, heads' step, qi, t):
    the query block of the step's heads together, a K/V block, the
    heads' lane-dense rows of per-query statistics."""
    def kmap(b, hq, i, t):
        lo, hi = _key_range(i, blk, window)
        return b, jnp.minimum(lo + t, hi), hq // parts

    q = pl.BlockSpec((1, blk, heads * d), lambda b, hq, i, t: (b, i, hq))
    kv = pl.BlockSpec((1, blk, d), kmap)
    row = pl.BlockSpec((1, heads, 1, blk), lambda b, hq, i, t: (b, hq, 0, i))
    return q, kv, row


def _flash_fwd(q, k, v, n_head, n_kv, window, block, sub, interpret):
    b, s, d, heads, parts, til, steps = _geometry(q, n_head, n_kv, window,
                                                  block, sub)
    blk = til.blk
    qs, kvs, rows = _specs(d, blk, window, heads, parts)
    stats = pltpu.VMEM((heads, blk, til.lanes), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d), til=til,
                          window=window, steps=steps, heads=heads, d=d),
        grid=(b, n_head // heads, s // blk, steps),
        in_specs=[qs, kvs, kvs], out_specs=[qs, rows],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, n_head, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, heads * d), jnp.float32),
                        stats, stats],
        interpret=interpret, name="flash_gqa_fwd", **_PARAMS,
    )(q, k, v)


def _flash_bwd(q, k, v, o, lse, g, n_head, n_kv, window, block, sub,
               interpret):
    b, s, d, heads, parts, til, steps = _geometry(q, n_head, n_kv, window,
                                                  block, sub)
    blk, num_q = til.blk, s // til.blk
    scale = 1.0 / math.sqrt(d)
    qs, kvs, rows = _specs(d, blk, window, heads, parts)
    stats = pltpu.VMEM((heads, blk, til.lanes), jnp.float32)
    # the dq kernel also makes delta = rowsum(dO * O), as the lane-dense
    # rows the dk/dv kernel reads
    dq, delta = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, til=til,
                          window=window, steps=steps, heads=heads, d=d),
        grid=(b, n_head // heads, num_q, steps),
        in_specs=[qs, kvs, kvs, qs, qs, rows], out_specs=[qs, rows],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, heads * d), jnp.float32),
                        stats, stats],
        interpret=interpret, name="flash_gqa_bwd_dq", **_PARAMS,
    )(q, k, v, g, o, lse)

    # dk/dv grid (b, kv head, key block, parts x query steps): the
    # innermost axis walks the group's heads, a step's worth at a time,
    # and for each the query blocks that can see this key block
    def qmap(b_, hk, j, t):
        lo, hi = _query_range(j, blk, window, num_q)
        return b_, jnp.minimum(lo + t % steps, hi), hk * parts + t // steps

    def rowmap(b_, hk, j, t):
        lo, hi = _query_range(j, blk, window, num_q)
        return b_, hk * parts + t // steps, 0, \
            jnp.minimum(lo + t % steps, hi)

    qs_t = pl.BlockSpec((1, blk, heads * d), qmap)
    rows_t = pl.BlockSpec((1, heads, 1, blk), rowmap)
    kvs_t = pl.BlockSpec((1, blk, d), lambda b_, hk, j, t: (b_, j, hk))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, til=til,
                          window=window, steps=steps, heads=heads,
                          parts=parts, d=d, num_q=num_q),
        grid=(b, n_kv, num_q, parts * steps),
        in_specs=[qs_t, kvs_t, kvs_t, qs_t, rows_t, rows_t],
        out_specs=[kvs_t, kvs_t],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32),
                        pltpu.VMEM((blk, d), jnp.float32)],
        interpret=interpret, name="flash_gqa_bwd_dkv", **_PARAMS,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _make(n_head, n_kv, window, block, sub, interpret):
    """One traced and lowered body per variant, shared by the layers
    that use it (PERF.md, PR 28: Mosaic lowers a kernel at every
    ``lower()``)."""
    args = (n_head, n_kv, window, block, sub, interpret)

    @jax.custom_vjp
    def f(q, k, v):
        return _flash_fwd(q, k, v, *args)[0]

    def fwd(q, k, v):
        o, lse = _flash_fwd(q, k, v, *args)
        return o, (q, k, v, o, lse)

    def bwd(res, g):
        return _flash_bwd(*res, g, *args)

    f.defvjp(fwd, bwd)
    return jax.jit(f)


def supported(seq, head_dim, n_head, n_kv, backend=None):
    """(ok, reason): can the kernels tile this problem?"""
    if seq % 128:
        return False, f"seq:{seq}%128"
    if head_dim % 128:
        return False, f"head-dim:{head_dim}%128"
    if n_kv <= 0 or n_head % n_kv:
        return False, f"heads:{n_head}%{n_kv}"
    from . import is_tpu_backend
    if not is_tpu_backend(backend):
        return False, f"backend:{backend}"
    return True, ""


def flash_gqa_bsd(q, k, v, *, n_head, n_kv_head, window=None, block=None,
                  sub=None, interpret=False):
    """Causal attention.  q ``[B, S, H * D]``, k/v ``[B, S, Hkv * D]``;
    ``window``: position ``i`` also needs ``i - j < window``.  Returns
    ``[B, S, H * D]``.  ``block`` overrides the 512/256/128 tile choice
    and ``sub`` the sub-tile's (interpret-mode tests at small sizes)."""
    return _make(int(n_head), int(n_kv_head), int(window or 0),
                 block and int(block), sub or None, bool(interpret))(q, k, v)


def reference(q, k, v, *, n_head, n_kv_head, window=None):
    """jnp spec of the kernels: the same mathematics with the scores
    materialised."""
    b, s, _ = q.shape
    d = q.shape[-1] // n_head
    group = n_head // n_kv_head
    qh = q.reshape(b, s, n_kv_head, group, d)
    kh = k.reshape(b, s, n_kv_head, d)
    vh = v.reshape(b, s, n_kv_head, d)
    scores = jnp.einsum("bqkgd,btkd->bkgqt", qh, kh,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    rows = jnp.arange(s)[:, None]
    cols = jnp.arange(s)[None, :]
    ok = cols <= rows
    if window:
        ok = ok & (rows - cols < window)
    probs = jax.nn.softmax(jnp.where(ok, scores, NEG_INF), axis=-1)
    out = jnp.einsum("bkgqt,btkd->bqkgd", probs.astype(vh.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, n_head * d).astype(q.dtype)
