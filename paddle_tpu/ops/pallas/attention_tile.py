"""One-tile attention as Pallas TPU kernels: the lowering of
``fused_attention`` when the whole problem is a single (S, S) tile —
non-causal, ``Sq == Sk == TILE`` (BERT phase 1, sequence 128).

At one tile the blockwise kernel (flash_attention.py) has nothing to
block over and all of its machinery is cost: a grid step per (batch,
head) with a (128, 64) problem each, an online softmax with one key
tile, two backward kernels that each recompute the scores, the bias tile
fetched once per head, and head-split relayouts around every call.  On
the v5e that took 20.7 % of a BERT-base step for 2.6 % of its FLOPs
(PERF.md, PR 28).  These two kernels are the other design:

* operands stay in the ``(B, S, H*D)`` layout the op receives; a grid
  step takes ``rows`` whole batch rows, all heads, so there is no
  head split/merge outside and the ``(S, S)`` bias tile is fetched once
  per batch row;
* heads are taken 128 lanes at a time.  With ``D == 64`` a lane group
  holds two heads: the group's Q (or dO) is stacked head-over-head
  along the rows with the other head's lanes zeroed, ``(2S, 128)``, so
  ONE ``(2S, 128) x (128, S)`` product gives both heads' scores
  (contraction over a zeroed lane adds nothing), one ``(2S, S) x (S,
  128)`` product gives both contexts (each head's 64 valid lanes picked
  by a lane select) and the transposed products contract over the
  stacked rows, landing each head's dK / dV in its own lanes.  The MXU
  is 128 wide, so the zeroed half costs no pass a 64-wide product
  would not also pay;
* MXU operands keep the dtype they arrive in (bf16 under pure-bf16 AMP),
  accumulation, scores, softmax and its statistics are f32; ``p`` and
  ``ds`` are cast to the operand dtype for their products, as
  ``attention_ops.reference_attention`` casts ``probs``;
* plain softmax (one key tile: no running max, no rescale, no
  logsumexp residual); the ONE backward kernel recomputes ``s`` and
  ``p`` once, computes ``delta = rowsum(p * dp)`` itself and emits dq,
  dk, dv together.  Residuals are the inputs alone;
* dropout on the probabilities from the per-core PRNG, seeded per
  (batch row, lane group) by one injective index, so the backward
  regenerates the forward's keep-mask bit for bit.

Gradient w.r.t. the additive bias is zero by the same contract as the
blockwise kernel (mask bias, stop-gradiented).
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

from .flash_attention import _dropout_mask

TILE = 128
LANES = 128
#: batch rows per grid step: the largest count up to this that divides
#: the batch and keeps the backward's seven double-buffered blocks
#: inside VMEM_BLOCKS_BYTES (a step of one row is ~1 us of DMA,
#: comparable to the grid's per-step overhead)
MAX_ROWS = 4
VMEM_BLOCKS_BYTES = 12 << 20        # of the 16 MiB scoped-VMEM default
#: widest H*D the rule admits: one f32 row of the backward just fits
MAX_WIDTH = VMEM_BLOCKS_BYTES // (14 * TILE * 4)

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _head_masks(d):
    """Lane masks of the heads inside one 128-lane group: two heads at
    D == 64, the whole group (None) at D == 128."""
    if d == LANES:
        return (None,)
    lane = lax.broadcasted_iota(jnp.int32, (TILE, LANES), 1)
    return tuple((lane >= j * d) & (lane < (j + 1) * d)
                 for j in range(LANES // d))


def _stack(x2, masks):
    """(S, 128) group -> (heads*S, 128): head j's rows keep only head
    j's lanes."""
    if masks[0] is None:
        return x2
    zero = jnp.zeros_like(x2)
    return jnp.concatenate([jnp.where(m, x2, zero) for m in masks], axis=0)


def _unstack(y, masks):
    """(heads*S, 128) -> (S, 128): each head's valid lanes from its own
    row block."""
    if masks[0] is None:
        return y
    out = y[:TILE]
    for j in range(1, len(masks)):
        out = jnp.where(masks[j], y[j * TILE:(j + 1) * TILE], out)
    return out


def _probs(q_st, k2, bias_st, scale):
    s = _dot(q_st, k2, _NT) * scale
    if bias_st is not None:
        s = s + bias_st
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _bias_tile(b_ref, r, n_stack):
    """The row's additive bias, f32, stacked once for all lane groups:
    (heads*S, S), or (1, S) for a key-only mask (it broadcasts)."""
    b = b_ref[r, 0].astype(jnp.float32)
    if b.shape[0] == 1 or n_stack == 1:
        return b
    return jnp.concatenate([b] * n_stack, axis=0)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, o_ref, *, scale,
                d, rows, has_bias, rate):
    masks = _head_masks(d)
    groups = q_ref.shape[-1] // LANES
    for r in range(rows):
        row = pl.program_id(0) * rows + r
        bias = _bias_tile(b_ref, r, len(masks)) if has_bias else None
        for g in range(groups):
            sl = slice(g * LANES, (g + 1) * LANES)
            v2 = v_ref[r, :, sl]
            p = _probs(_stack(q_ref[r, :, sl], masks), k_ref[r, :, sl],
                       bias, scale)
            if rate:
                keep = _dropout_mask(seed_ref, row * groups + g, p.shape,
                                     rate)
                p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
            o_st = _dot(p.astype(v2.dtype), v2, _NN)
            o_ref[r, :, sl] = _unstack(o_st, masks).astype(o_ref.dtype)


def _bwd_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, dq_ref,
                dk_ref, dv_ref, *, scale, d, rows, has_bias, rate):
    masks = _head_masks(d)
    groups = q_ref.shape[-1] // LANES
    for r in range(rows):
        row = pl.program_id(0) * rows + r
        bias = _bias_tile(b_ref, r, len(masks)) if has_bias else None
        for g in range(groups):
            sl = slice(g * LANES, (g + 1) * LANES)
            k2 = k_ref[r, :, sl]
            v2 = v_ref[r, :, sl]
            q_st = _stack(q_ref[r, :, sl], masks)
            do_st = _stack(do_ref[r, :, sl], masks)
            p = _probs(q_st, k2, bias, scale)            # (heads*S, S)
            dp = _dot(do_st, v2, _NT)
            if rate:
                keep = _dropout_mask(seed_ref, row * groups + g, p.shape,
                                     rate)
                inv = 1.0 / (1.0 - rate)
                pd = jnp.where(keep, p * inv, 0.0)
                dp = jnp.where(keep, dp * inv, 0.0)
            else:
                pd = p
            # rowsum(p * dp) == rowsum(do * o), with dropout too
            ds = p * (dp - jnp.sum(p * dp, axis=-1, keepdims=True))
            ds = ds.astype(k2.dtype)
            # contraction over the stacked rows: head j's rows of do_st
            # / q_st are zero outside head j's lanes, so each head lands
            # in its own lanes
            dv_ref[r, :, sl] = _dot(pd.astype(v2.dtype), do_st,
                                    _TN).astype(dv_ref.dtype)
            dk_ref[r, :, sl] = (_dot(ds, q_st, _TN) * scale).astype(
                dk_ref.dtype)
            dq_ref[r, :, sl] = (_unstack(_dot(ds, k2, _NN), masks)
                                * scale).astype(dq_ref.dtype)


def _rows_per_step(b, row_bytes):
    return next(r for r in range(MAX_ROWS, 0, -1)
                if b % r == 0 and (r == 1 or 14 * r * row_bytes
                                   <= VMEM_BLOCKS_BYTES))


def _tile_call(kernel, name, operands, n_out, bias, seed, n_head, rate,
               interpret):
    """One pallas_call over the batch: ``operands`` (q, k, v[, do]) and
    the ``n_out`` outputs all move as (rows, S, H*D) blocks, the bias as
    the same rows' (rows, 1, S|1, S) tiles, the seed in SMEM."""
    q = operands[0]
    b, s, hd = q.shape
    d = hd // n_head
    rows = _rows_per_step(b, s * hd * q.dtype.itemsize)
    blk = pl.BlockSpec((rows, s, hd), lambda i: (i, 0, 0),
                       memory_space=pltpu.VMEM)
    if bias is None:
        bias = jnp.zeros((1, 1, 1, 1), jnp.float32)     # never read
        bspec = pl.BlockSpec((1, 1, 1, 1), lambda i: (0, 0, 0, 0),
                             memory_space=pltpu.VMEM)
        has_bias = False
    else:
        bspec = pl.BlockSpec((rows, 1) + bias.shape[2:],
                             lambda i: (i, 0, 0, 0),
                             memory_space=pltpu.VMEM)
        has_bias = True
    n_blocks = len(operands) + n_out
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)] * n_out
    # two MXU products a head forward (s, p@v), five backward
    products = 2 if n_out == 1 else 5
    return pl.pallas_call(
        functools.partial(kernel, scale=1.0 / math.sqrt(d), d=d, rows=rows,
                          has_bias=has_bias, rate=rate),
        grid=(b // rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk, blk, blk,
                  bspec] + [blk] * (len(operands) - 3),
        out_specs=[blk] * n_out if n_out > 1 else blk,
        out_shape=out_shape if n_out > 1 else out_shape[0],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * products * b * n_head * s * s * d,
            bytes_accessed=n_blocks * q.size * q.dtype.itemsize
            + bias.size * bias.dtype.itemsize,
            transcendentals=b * n_head * s * s),
        interpret=interpret,
        name=name,
    )(seed, *operands[:3], bias, *operands[3:])


@functools.lru_cache(maxsize=None)
def _make_tile(n_head, rate, has_bias, interpret):
    """custom_vjp'd one-tile attention specialised on its statics."""

    # jitted: a model's layers share shapes, so its step traces the
    # kernel body and lowers it to Mosaic once, not once a layer (the
    # body is unrolled over rows and lane groups: ~0.3 s a lowering,
    # paid by every process before the compile cache can answer)
    @jax.jit
    def tile_fwd(q, k, v, bias, seed):
        return _tile_call(_fwd_kernel, "attn_tile_fwd", (q, k, v), 1, bias,
                          seed, n_head, rate, interpret)

    @jax.jit
    def tile_bwd(q, k, v, bias, seed, g):
        return _tile_call(_bwd_kernel, "attn_tile_bwd", (q, k, v, g), 3,
                          bias, seed, n_head, rate, interpret)

    def fwd(q, k, v, bias, seed):
        return tile_fwd(q, k, v, bias, seed), (q, k, v, bias, seed)

    def bwd(res, g):
        q, k, v, bias, seed = res
        dq, dk, dv = tile_bwd(q, k, v, bias, seed, g)
        dbias = jnp.zeros_like(bias) if has_bias else None
        return dq, dk, dv, dbias, np.zeros(seed.shape, jax.dtypes.float0)

    f = jax.custom_vjp(tile_fwd)
    f.defvjp(fwd, bwd)
    return f


def tiles(s, sk, n_head, d, causal=False, bias_shape=None):
    """Static shape rule → (ok, reason): is this attention one tile the
    kernels take?  ``bias_shape`` is the additive bias's (B|1, 1, S|1,
    Sk) or None."""
    if s != TILE or sk != TILE:
        return False, f"one-tile:{s}x{sk}"
    if causal:
        return False, "one-tile:causal"
    if d not in (64, LANES) or (n_head * d) % LANES:
        return False, f"one-tile:head-dim:{d}x{n_head}"
    if n_head * d > MAX_WIDTH:
        return False, f"one-tile:width:{n_head * d}"
    if bias_shape is not None and (
            len(bias_shape) != 4 or bias_shape[1] != 1
            or bias_shape[2] not in (1, s) or bias_shape[3] != sk):
        return False, "one-tile:bias-shape"
    return True, ""


def attention_tile_bsd(q, k, v, bias=None, *, n_head, dropout_rate=0.0,
                       seed=None, interpret=False):
    """q/k/v: (B, S, H*D) with S == TILE; bias: additive (B|1, 1, S|1, S)
    mask bias or None; seed: int32 scalar/1-vector for the in-kernel
    dropout PRNG (required when dropout_rate > 0).  Returns the context,
    (B, S, H*D).  Raises ValueError for what tiles() rejects — call it
    first."""
    b, s, hd = q.shape
    ok, why = tiles(s, k.shape[1], n_head, hd // n_head,
                    bias_shape=None if bias is None else bias.shape)
    if not ok:
        raise ValueError(f"attention_tile: unsupported ({why})")
    if dropout_rate:
        if seed is None:
            raise ValueError("dropout_rate > 0 requires a seed")
        if interpret:
            raise ValueError(
                "dropout requires the hardware PRNG — unavailable in "
                "interpret mode")
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    seed = jnp.reshape(seed, (1,)).astype(jnp.int32)
    k, v = k.astype(q.dtype), v.astype(q.dtype)     # one MXU operand dtype
    if bias is not None:
        if bias.shape[0] != b:
            bias = jnp.broadcast_to(bias, (b,) + bias.shape[1:])
        bias = lax.stop_gradient(bias)
    fn = _make_tile(int(n_head), float(dropout_rate), bias is not None,
                    bool(interpret))
    return fn(q, k, v, bias, seed)
