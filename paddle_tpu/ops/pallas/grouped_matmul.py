"""Grouped matrix products for dropless mixture-of-experts layers, as
Pallas TPU kernels.

Rows arrive SORTED BY GROUP (one group per expert held here) with every
group padded to whole row tiles, so a tile belongs to exactly one group:
no tile straddles two experts, the kernels need no row masks, and the
weight block of a group stays in VMEM while consecutive tiles of that
group stream past it (Pallas re-fetches a block only when its index
changes).  The buffer is sized for the worst case — every assignment of
every token lands on an expert held here — because nothing is ever
dropped; the tiles past the real total are skipped: their index maps
point at the last real tile, so they move no data, and their bodies do
not run.  The work follows the real counts up to the tile rounding.

* ``gmm(lhs [M, K], rhs [G, K, N]) -> [M, N]``: row tile ``t`` times the
  matrix of ``tile_group[t]`` (``transpose_rhs``: ``rhs [G, N, K]``);
* ``tgmm(lhs [M, K], rhs [M, N]) -> [G, K, N]``: per group the sum over
  its tiles of ``lhs_t^T rhs_t`` (the weights' gradient), accumulated in
  f32 in VMEM; a group with no rows is never visited and reads as zero.

Rows of skipped tiles are never written: they hold whatever the buffer
held, and every consumer reads real rows only (``moe_ops`` gathers by
row index with out-of-range rows filled with zero).

``tile_layout`` turns the per-group counts into the scalar-prefetch
arguments, on the device, without a host sync.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_M = 256
#: the weight block of one expert (4 MiB at Mellum's 2304 x 896 in bf16)
#: is double-buffered and the weight-gradient kernel keeps an f32
#: accumulator of that shape: past the 16 MiB default, well inside the
#: v5e's 128 MiB of VMEM
VMEM_LIMIT = 64 * 1024 * 1024


def row_tile(assignments: int, num_experts: int) -> int:
    """The row tile for ``assignments`` spread over ``num_experts``: the
    power of two from 16 (one bfloat16 sublane tile) up to TILE_M that
    first reaches four times an expert's share under balance.  Every
    group pads to a whole tile, so a decode step's few rows an expert
    (128 x 8 over 256: 16) must not pad to a training step's tile
    (8 192 x 8 over 64: 256)."""
    tile = 16
    while tile < TILE_M and tile * num_experts < 4 * assignments:
        tile *= 2
    return tile


def padded_rows(assignments: int, groups: int, tile_m: int = TILE_M) -> int:
    """Rows of the worst-case buffer: every assignment here, every group
    rounded up to a whole tile."""
    return -(-assignments // tile_m) * tile_m + groups * tile_m


def tile_layout(counts, tile_m: int, num_tiles: int):
    """``counts`` [G] int32 -> (first padded row of each group [G], group
    of each tile [num_tiles], number of real tiles [1])."""
    padded = (counts + tile_m - 1) // tile_m * tile_m
    ends = jnp.cumsum(padded)
    tile_group = jnp.searchsorted(
        ends, jnp.arange(num_tiles, dtype=jnp.int32) * tile_m, side="right")
    tile_group = jnp.minimum(tile_group, counts.shape[0] - 1)
    return (ends - padded).astype(jnp.int32), \
        tile_group.astype(jnp.int32), (ends[-1:] // tile_m).astype(jnp.int32)


def _real_tile(t, na_ref):
    return jnp.minimum(t, jnp.maximum(na_ref[0] - 1, 0))


def _gmm_kernel(tg_ref, na_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs,
                tile_axis=0):
    del tg_ref

    @pl.when(pl.program_id(tile_axis) < na_ref[0])
    def _body():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs \
            else (((1,), (0,)), ((), ()))
        out_ref[...] = lax.dot_general(
            lhs_ref[...], rhs_ref[0], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


#: the largest weight block ``gmm`` keeps whole (double-buffered in
#: VMEM); a wider expert is cut into column blocks
WEIGHT_BLOCK_BYTES = 8 * 1024 * 1024


def column_block(k: int, n: int, itemsize: int) -> int:
    """Columns of one weight block: all ``n`` where ``[k, n]`` fits
    WEIGHT_BLOCK_BYTES, else the largest lane-aligned divisor of ``n``
    that does."""
    if k * n * itemsize <= WEIGHT_BLOCK_BYTES or n % 128:
        return n
    fits = [c for c in range(128, n, 128)
            if n % c == 0 and k * c * itemsize <= WEIGHT_BLOCK_BYTES]
    return max(fits) if fits else 128


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "tile_m",
                                             "interpret"))
def gmm(lhs, rhs, tile_group, num_active, *, transpose_rhs=False,
        tile_m=TILE_M, interpret=False):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    num_tiles = m // tile_m
    tile_n = column_block(k, n, rhs.dtype.itemsize)
    if tile_n < n:
        return _gmm_by_columns(lhs, rhs, tile_group, num_active,
                               transpose_rhs, tile_m, tile_n, interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((tile_m, k),
                         lambda t, tg, na: (_real_tile(t, na), 0)),
            pl.BlockSpec((1,) + rhs.shape[1:],
                         lambda t, tg, na: (tg[_real_tile(t, na)], 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_m, n),
                               lambda t, tg, na: (_real_tile(t, na), 0)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * (k + n) + rhs.size) * lhs.dtype.itemsize),
        interpret=interpret,
        name="moe_gmm",
    )(tile_group, num_active, lhs, rhs)


def _gmm_by_columns(lhs, rhs, tile_group, num_active, transpose_rhs,
                    tile_m, tile_n, interpret):
    """``gmm`` for experts too wide for one VMEM block (7168 x 2048 in
    bf16 is 29 MB, twice that double buffered): the weight is cut into
    column blocks of ``tile_n``, the OUTER grid axis, and the row tiles
    stream past each column block, so a group's block is fetched once
    while its consecutive tiles use it and every hit expert's matrix is
    read exactly once — what a decode step's few rows an expert are
    bound by.  The row tiles are read once a column block: ``n /
    tile_n`` times, little beside the weights at any group size."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    rhs_block = (1, tile_n, k) if transpose_rhs else (1, k, tile_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n // tile_n, m // tile_m),
        in_specs=[
            pl.BlockSpec((tile_m, k),
                         lambda j, t, tg, na: (_real_tile(t, na), 0)),
            pl.BlockSpec(
                rhs_block,
                (lambda j, t, tg, na: (tg[_real_tile(t, na)], j, 0))
                if transpose_rhs else
                (lambda j, t, tg, na: (tg[_real_tile(t, na)], 0, j))),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n),
                               lambda j, t, tg, na: (_real_tile(t, na), j)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs,
                          tile_axis=1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * (k + n) + rhs.size) * lhs.dtype.itemsize),
        interpret=interpret,
        name="moe_gmm",
    )(tile_group, num_active, lhs, rhs)


def _tgmm_kernel(tg_ref, na_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                 num_tiles):
    t = pl.program_id(0)
    na = na_ref[0]
    g = tg_ref[t]
    first = (t == 0) | (tg_ref[jnp.maximum(t - 1, 0)] != g)
    last = (t == na - 1) | (tg_ref[jnp.minimum(t + 1, num_tiles - 1)] != g)

    @pl.when(t < na)
    def _body():
        @pl.when(first)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _store():
            out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("groups", "tile_m",
                                             "interpret"))
def tgmm(lhs, rhs, tile_group, num_active, counts, *, groups,
         tile_m=TILE_M, interpret=False):
    m, k = lhs.shape
    n = rhs.shape[1]
    num_tiles = m // tile_m
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((tile_m, k),
                         lambda t, tg, na: (_real_tile(t, na), 0)),
            pl.BlockSpec((tile_m, n),
                         lambda t, tg, na: (_real_tile(t, na), 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, k, n), lambda t, tg, na: (tg[_real_tile(t, na)], 0, 0)),
        scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_tgmm_kernel, num_tiles=num_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * (k + n) + groups * k * n)
            * lhs.dtype.itemsize),
        interpret=interpret,
        name="moe_gmm_wgrad",
    )(tile_group, num_active, lhs, rhs)
    # a group without rows was never visited: its block was never written
    return jnp.where(counts[:, None, None] > 0, out, 0)
