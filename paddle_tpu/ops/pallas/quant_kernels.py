"""Fused dequant-upcast-accumulate(-requantize) Pallas kernels for the
quantized-collective receive stage (ops/quantize_wire.py, EQuARX-style).

After the stage-1 ``all_to_all`` every rank holds n peer copies of ITS
shard at wire width (int8 payload, or int4 packed two-per-byte in an
int8 carrier, plus per-block f32 scales).  The jnp composition
dequantizes all n·shard bytes to f32 (n× the f32 shard materialised in
HBM), then sums, then — on the all-reduce path — re-reads the sum to
requantize: three-plus HBM passes over data whose useful output is one
f32 (or int8) shard.  These kernels do the whole receive stage in one
VMEM pass: the peer axis is the innermost grid dimension, each peer's
(BR, C) tile is dequantized and accumulated into an f32 scratch that
never leaves VMEM until the final peer, and the requantizing variant
derives the per-block amax/scale from the scratch and emits the int8
payload directly — the intermediate f32 sum never touches HBM.

Layout contract (matches quantize_blockwise): payload rows ARE
quantization blocks — ``q[(peer, block), :]`` carries ``block_size``
elements (int8) or ``block_size/2`` byte-packed pairs (int4); scales
arrive as (n·blocks, 1) f32 columns (row stats live as (rows, 1), the
same TPU-tiling idiom as the flash kernel's lse).

int4 nibbles are sign-extended in-kernel via arithmetic shifts on the
int32-widened byte (``(q << 28) >> 28`` / ``q >> 4``) but NOT
re-interleaved: the kernel emits separate even/odd-element sums (lo =
elements 0::2 of each block, hi = 1::2) and the host-side wrapper
interleaves the small f32 result —
one cheap stack/reshape on shard-sized data instead of a lane shuffle
inside the kernel.

Rounding in the requantizing variant is round-to-nearest-even
(jnp.round), matching quantize_blockwise exactly; stochastic rounding
needs the per-rank PRNG fold and stays on the jnp path (the route's
supported() gate rejects it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 8      # quant blocks per grid tile (f32 sublane multiple)

#: VMEM ceiling for one peer tile (bytes) — BR·C int8 + f32 scratch stay
#: far under the ~16 MB/core budget at the default 256-element blocks
_TILE_BYTES_MAX = 4 * 1024 * 1024


def _payload_cols(spec) -> int:
    """Bytes per payload row (= lane width of the kernel tiles)."""
    return spec.block_size // 2 if spec.dtype == "int4" else spec.block_size


def supported(n_peers: int, num_blocks: int, spec, backend=None):
    """Static gate: can the receive-stage kernel handle ``n_peers``
    contributions of ``num_blocks`` quantization blocks under
    ``spec``?  Returns (ok, reason) — mirrors exactly what the kernels
    reject, so routing dispatches without try/except."""
    from . import effective_backend, is_tpu_backend
    if spec.dtype not in ("int8", "int4"):
        return False, f"wire-dtype:{spec.dtype}"
    cols = _payload_cols(spec)
    if cols % 128:
        return False, f"block-size:{spec.block_size}%lanes"
    if n_peers is None or n_peers < 2:
        return False, "peers:unknown-or-single"
    if num_blocks is None or num_blocks < 1:
        return False, "blocks:unknown"
    if BLOCK_ROWS * cols * 5 > _TILE_BYTES_MAX:
        return False, f"tile-bytes:{BLOCK_ROWS * cols}"
    backend = backend or effective_backend()
    if not is_tpu_backend(backend):
        return False, f"backend:{backend}"
    return True, ""


def _dq_tile(q_ref, s_ref, *, int4):
    """Dequantize one (1, BR, C) payload tile against its (1, BR, 1)
    scales; int4 returns (lo, hi) element sub-tiles, int8 one tile."""
    q = q_ref[0]                                   # (BR, C) int8
    s = s_ref[0]                                   # (BR, 1) f32
    if int4:
        # shift in int32: Mosaic (jax 0.9.0) does not legalize shifts on
        # int8 vectors ('arith.shli' on vector<..xi8>, v5e)
        q = q.astype(jnp.int32)
        lo = ((q << 28) >> 28).astype(jnp.float32) * s
        hi = (q >> 4).astype(jnp.float32) * s
        return lo, hi
    return q.astype(jnp.float32) * s, None


def _dq_acc_kernel(q_ref, s_ref, o_ref, acc_ref, *, n_peers, int4):
    i = pl.program_id(1)                           # peer, innermost

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo, hi = _dq_tile(q_ref, s_ref, int4=int4)
    if int4:
        # acc layout [lo | hi]: even elements in the left half, odd in
        # the right — the host wrapper interleaves after the kernel
        acc_ref[...] += jnp.concatenate([lo, hi], axis=1)
    else:
        acc_ref[...] += lo

    @pl.when(i == n_peers - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


def _dq_acc_requant_kernel(q_ref, s_ref, qo_ref, so_ref, acc_ref, *,
                           n_peers, qmax):
    """int8-only: accumulate as _dq_acc_kernel, then requantize the
    reduced rows in the same pass (each row IS one quantization block,
    so the per-block amax is a row reduction over the scratch)."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += q_ref[0].astype(jnp.float32) * s_ref[0]

    @pl.when(i == n_peers - 1)
    def _emit():
        acc = acc_ref[...]
        amax = jnp.max(jnp.abs(acc), axis=1, keepdims=True)    # (BR, 1)
        scale = jnp.where(amax > 0, amax / qmax, 1.0)
        r = jnp.round(acc / scale)
        qo_ref[0] = jnp.clip(r, -qmax, qmax).astype(jnp.int8)
        so_ref[0] = scale


def _tiles(payload, scales, spec, n_peers):
    """Common reshape: (n·blocks, C) payload + (n·blocks,) scales →
    ((n, SB, C) int8, (n, SB, 1) f32, SB, C, BR, grid)."""
    cols = _payload_cols(spec)
    sb = payload.shape[0] // n_peers
    q3 = payload.reshape(n_peers, sb, cols)
    s3 = scales.reshape(n_peers, sb, 1).astype(jnp.float32)
    br = min(BLOCK_ROWS, sb)
    grid = (pl.cdiv(sb, br), n_peers)
    return q3, s3, sb, cols, br, grid


def dequant_accumulate(payload, scales, spec, n_peers, interpret=False):
    """Sum of ``n_peers`` dequantized contributions in one VMEM pass.

    ``payload``: (n·blocks, C) int8 rows as produced by
    quantize_blockwise + all_to_all; ``scales``: (n·blocks,) f32.
    Returns the f32 flat reduced shard (blocks · block_size elements) —
    the drop-in for ``dequantize_blockwise(...).reshape(n, -1).sum(0)``.
    """
    from jax.experimental.pallas import tpu as pltpu
    int4 = spec.dtype == "int4"
    q3, s3, sb, cols, br, grid = _tiles(payload, scales, spec, n_peers)
    out_cols = spec.block_size
    out = pl.pallas_call(
        functools.partial(_dq_acc_kernel, n_peers=n_peers, int4=int4),
        grid=grid,
        in_specs=[pl.BlockSpec((1, br, cols), lambda j, i: (i, j, 0)),
                  pl.BlockSpec((1, br, 1), lambda j, i: (i, j, 0))],
        out_specs=pl.BlockSpec((br, out_cols), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((sb, out_cols), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, out_cols), jnp.float32)],
        interpret=interpret,
        name="dequant_accumulate",
    )(q3, s3)
    if int4:
        # kernel emits [lo | hi] halves per block row; interleave the
        # shard-sized f32 result back to element order
        lo, hi = out[:, :cols], out[:, cols:]
        out = jnp.stack([lo, hi], axis=-1).reshape(sb, out_cols)
    return out.reshape(-1)


def dequant_accumulate_requant(payload, scales, spec, n_peers,
                               interpret=False):
    """int8 receive stage of the quantized all-reduce with the
    requantization fused: returns ``(q2, s2)`` — the rank's reduced
    shard already at wire width for the stage-2 all_gather, the f32 sum
    never materialising in HBM.  Round-to-nearest only (stochastic
    rounding stays on the jnp path)."""
    if spec.dtype != "int8":
        raise ValueError("fused requantize supports the int8 tier only")
    q3, s3, sb, cols, br, grid = _tiles(payload, scales, spec, n_peers)
    from jax.experimental.pallas import tpu as pltpu
    q2, s2 = pl.pallas_call(
        functools.partial(_dq_acc_requant_kernel, n_peers=n_peers,
                          qmax=float(spec.qmax)),
        grid=grid,
        in_specs=[pl.BlockSpec((1, br, cols), lambda j, i: (i, j, 0)),
                  pl.BlockSpec((1, br, 1), lambda j, i: (i, j, 0))],
        out_specs=[pl.BlockSpec((1, br, cols), lambda j, i: (0, j, 0)),
                   pl.BlockSpec((1, br, 1), lambda j, i: (0, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, sb, cols), jnp.int8),
                   jax.ShapeDtypeStruct((1, sb, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((br, cols), jnp.float32)],
        interpret=interpret,
        name="dequant_accumulate_requant",
    )(q3, s3)
    return q2.reshape(sb, cols), s2.reshape(sb)
