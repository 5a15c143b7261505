"""Flash attention (online-softmax, blockwise) as Pallas TPU kernels.

The reference's fastest attention is a monolithic fused CUDA kernel
(ref: operators/fused/multihead_matmul_op.cu) that still materialises the
full (S, S) score matrix.  This kernel does more: O(S) memory via
online softmax, MXU-shaped (128x128) blocks, f32 accumulation, in-kernel
PRNG dropout (the reference's fused path has no dropout at all — its
dropout runs as a separate elementwise kernel over the (S, S) probs,
ref: operators/dropout_op.cu), and causal masking with true block
skipping (blocks above the diagonal never execute).

It is the lowering for what has something to block over or to skip:
causal attention, the cached (chunked-prefill) read, the ring step, any
sequence past one tile.  At Sq == Sk == 128, non-causal, the machinery
is pure cost — a grid step per (batch, head), one key tile's worth of
online softmax, two backward kernels each recomputing the scores, a
bias tile per head — and measured 3 % of the MXU's peak for a fifth of
a BERT-base step (PERF.md, PR 28); that shape is attention_tile.py's.

Layout: every kernel runs a 3-D grid with the KV (or Q, for dk/dv) axis
innermost and carries the online-softmax state in VMEM scratch.  K/V
arrive as (1, BLOCK, D) grid blocks, so VMEM holds O(BLOCK·D) regardless
of sequence length — Pallas double-buffers the HBM fetches between grid
steps, which is what makes long-context (ring-attention shard sizes)
viable where staging full K/V per step would overflow VMEM.

Forward: grid (batch*heads, q_blocks, kv_blocks); emits per-row
logsumexp as a (BH, Sq, 1) residual (row stats live as (rows, 1)
columns — TPU tiling requires block dim -2 divisible by 8, so a (BQ, 1)
block is legal where (1, BQ) is not).  Dropout draws uint32 bits from
the per-core PRNG seeded deterministically per (head, q-block, k-block)
so the backward kernels regenerate the identical mask without storing
it (hardware prng_seed takes at most 2 words → the grid coordinates
fold into one injective linear index).

Backward: two blockwise kernels (FlashAttention-2 style) —
  * dq: grid (bh, q_blocks, kv_blocks), dq accumulated in scratch;
  * dk/dv: grid (bh, kv_blocks, q_blocks), dk/dv accumulated in scratch;
both recompute p = exp(s - lse) in f32 and use the identity
rowsum(p * dp) == rowsum(do * o) (valid with dropout too) so only O(S)
residuals are ever materialised.

Gradient w.r.t. the additive bias is defined as zero: every call site in
this framework builds the bias from non-trainable padding masks and the
kernel wrapper stop-gradients it.  A learned attention bias must use the
jnp composition instead.
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

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30


def _dropout_mask(seed_ref, block_idx, shape, rate):
    """Regenerable keep-mask, seeded per (head, q-block, k-block).
    ``block_idx`` is the injective linear index (b*num_q + qi)*num_k + kj —
    hardware prng_seed takes at most 2 seed words."""
    pltpu.prng_seed(seed_ref[0], block_idx)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    threshold = np.uint32(min(int(rate * 2**32), 2**32 - 1))
    return bits >= threshold           # P(keep) = 1 - rate


def _causal_mask_block(qi, kj):
    """(BQ, BK) bool: row position >= col position for the (qi, kj) tile."""
    rows = qi * BLOCK_Q + lax.broadcasted_iota(jnp.int32,
                                               (BLOCK_Q, BLOCK_K), 0)
    cols = kj * BLOCK_K + lax.broadcasted_iota(jnp.int32,
                                               (BLOCK_Q, BLOCK_K), 1)
    return rows >= cols


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, num_q_blocks,
                num_k_blocks, has_bias, rate, causal):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    last_j = (jnp.minimum((qi + 1) * BLOCK_Q // BLOCK_K, num_k_blocks) - 1
              if causal else num_k_blocks - 1)
    run = (j <= last_j) if causal else True

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)           # (BQ, D)
        ks = k_ref[0].astype(jnp.float32)          # (BK, D)
        vs = v_ref[0]
        s = lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + b_ref[0].astype(jnp.float32)
        if causal:
            s = jnp.where(_causal_mask_block(qi, j), s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # l accumulates the UNdropped probs (the softmax denominator);
        # the mask applies to the numerator only, so acc/l == dropout(P)@V
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if rate:
            idx = (b * num_q_blocks + qi) * num_k_blocks + j
            keep = _dropout_mask(seed_ref, idx, p.shape, rate)
            p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == last_j)
    def _finalize():
        l = l_ref[...]
        m = m_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)
        # rows with no unmasked keys (l == 0) store +inf so the backward's
        # exp(s - lse) is exactly 0 there, not inf
        lse_ref[0] = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                               jnp.inf)


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, acc_ref, *, scale, num_q_blocks,
                   num_k_blocks, has_bias, rate, causal):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    last_j = (jnp.minimum((qi + 1) * BLOCK_Q // BLOCK_K, num_k_blocks) - 1
              if causal else num_k_blocks - 1)
    run = (j <= last_j) if causal else True

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                           # (BQ, 1)
        delta = delta_ref[0]
        ks = k_ref[0].astype(jnp.float32)
        vs = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + b_ref[0].astype(jnp.float32)
        if causal:
            s = jnp.where(_causal_mask_block(qi, j), s, NEG_INF)
        p = jnp.exp(s - lse)                       # (BQ, BK)
        dp = lax.dot_general(do, vs, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        if rate:
            idx = (b * num_q_blocks + qi) * num_k_blocks + j
            keep = _dropout_mask(seed_ref, idx, p.shape, rate)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - rate)), 0.0)
        ds = p * (dp - delta)
        acc_ref[...] += lax.dot_general(ds, ks, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(j == last_j)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                    num_q_blocks, num_k_blocks, has_bias, rate, causal):
    b = pl.program_id(0)
    kj = pl.program_id(1)
    i = pl.program_id(2)
    first_i = (kj * BLOCK_K) // BLOCK_Q if causal else 0
    run = (i >= first_i) if causal else True

    @pl.when(i == first_i)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(run)
    def _body():
        k = k_ref[0].astype(jnp.float32)           # (BK, D)
        v = v_ref[0].astype(jnp.float32)
        qs = q_ref[0].astype(jnp.float32)          # (BQ, D)
        dos = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                           # (BQ, 1)
        delta = delta_ref[0]
        s = lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + b_ref[0].astype(jnp.float32)
        if causal:
            s = jnp.where(_causal_mask_block(i, kj), s, NEG_INF)
        p = jnp.exp(s - lse)                       # (BQ, BK)
        dp = lax.dot_general(dos, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        if rate:
            idx = (b * num_q_blocks + i) * num_k_blocks + kj
            keep = _dropout_mask(seed_ref, idx, p.shape, rate)
            inv = 1.0 / (1.0 - rate)
            pd = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            pd = p
        dv_acc[...] += lax.dot_general(pd, dos, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[...] += lax.dot_general(ds, qs, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(i == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bias_spec(bh, bias, transpose=False):
    """BlockSpec + arg for the additive bias, folding a head-shared bias
    ((B, Sq, Sk) with BH = B*H) without materialising the broadcast —
    keeps HBM traffic at O(B*Sq*Sk), not O(B*H*Sq*Sk)."""
    if bias is not None:
        ratio = bh // bias.shape[0]
        if transpose:   # dkv grid is (b, kj, i)
            spec = pl.BlockSpec((1, BLOCK_Q, BLOCK_K),
                                lambda b, j, i: (b // ratio, i, j),
                                memory_space=pltpu.VMEM)
        else:
            spec = pl.BlockSpec((1, BLOCK_Q, BLOCK_K),
                                lambda b, i, j: (b // ratio, i, j),
                                memory_space=pltpu.VMEM)
        return spec, bias
    spec = pl.BlockSpec((1, 1, 1), lambda b, i, j: (0, 0, 0),
                        memory_space=pltpu.VMEM)
    return spec, jnp.zeros((1, 1, 1), jnp.float32)


def _flash_fwd(q, k, v, bias, seed, rate, causal, interpret):
    """q: (BH, Sq, D), k/v: (BH, Sk, D) flattened batch*heads;
    bias: (B|BH, Sq, Sk) or None.  Returns (out, lse)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    num_q = sq // BLOCK_Q
    num_k = sk // BLOCK_K
    scale = 1.0 / math.sqrt(d)
    has_bias = bias is not None

    qspec = pl.BlockSpec((1, BLOCK_Q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM)
    kvspec = pl.BlockSpec((1, BLOCK_K, d), lambda b, i, j: (b, j, 0),
                          memory_space=pltpu.VMEM)
    bspec, barg = _bias_spec(bh, bias)

    kernel = functools.partial(_fwd_kernel, scale=scale, num_q_blocks=num_q,
                               num_k_blocks=num_k, has_bias=has_bias,
                               rate=rate, causal=causal)
    flops = 4 * bh * sq * sk * d // (2 if causal else 1)
    return pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_k),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qspec, kvspec, kvspec, bspec],
        out_specs=[qspec,
                   pl.BlockSpec((1, BLOCK_Q, 1), lambda b, i, j: (b, i, 0),
                                memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((BLOCK_Q, d), jnp.float32),
                        pltpu.VMEM((BLOCK_Q, 1), jnp.float32),
                        pltpu.VMEM((BLOCK_Q, 1), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=flops, bytes_accessed=q.size * 4 * 3,
            transcendentals=bh * sq * sk),
        interpret=interpret,
        name="flash_fwd",
    )(seed, q, k, v, barg)


def _flash_bwd(q, k, v, bias, seed, o, lse, g, rate, causal, interpret,
               dlse=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    num_q = sq // BLOCK_Q
    num_k = sk // BLOCK_K
    scale = 1.0 / math.sqrt(d)
    has_bias = bias is not None
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)         # (BH, Sq, 1)
    if dlse is not None:
        # dL/ds_ij = p_ij·(dp_ij − delta_i) + dlse_i·p_ij — an lse
        # cotangent folds into the SAME kernels as delta' = delta − dlse
        # (the ring-attention merge differentiates through lse)
        delta = delta - dlse.astype(jnp.float32)

    qblk = pl.BlockSpec((1, BLOCK_Q, d), lambda b, i, j: (b, i, 0),
                        memory_space=pltpu.VMEM)
    kblk = pl.BlockSpec((1, BLOCK_K, d), lambda b, i, j: (b, j, 0),
                        memory_space=pltpu.VMEM)
    rowq = pl.BlockSpec((1, BLOCK_Q, 1), lambda b, i, j: (b, i, 0),
                        memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    bspec_q, barg = _bias_spec(bh, bias)

    flops = 4 * bh * sq * sk * d // (2 if causal else 1)
    common = dict(scale=scale, num_q_blocks=num_q, num_k_blocks=num_k,
                  has_bias=has_bias, rate=rate, causal=causal)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(bh, num_q, num_k),
        in_specs=[smem, qblk, kblk, kblk, bspec_q, qblk, rowq, rowq],
        out_specs=qblk,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((BLOCK_Q, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * flops, bytes_accessed=q.size * 4 * 4,
            transcendentals=bh * sq * sk),
        interpret=interpret,
        name="flash_bwd_dq",
    )(seed, q, k, v, barg, g, lse, delta)

    # dkv grid: (b, kv block, q block) — q axis innermost for accumulation
    qblk_t = pl.BlockSpec((1, BLOCK_Q, d), lambda b, j, i: (b, i, 0),
                          memory_space=pltpu.VMEM)
    kblk_t = pl.BlockSpec((1, BLOCK_K, d), lambda b, j, i: (b, j, 0),
                          memory_space=pltpu.VMEM)
    rowq_t = pl.BlockSpec((1, BLOCK_Q, 1), lambda b, j, i: (b, i, 0),
                          memory_space=pltpu.VMEM)
    bspec_t, barg_t = _bias_spec(bh, bias, transpose=True)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(bh, num_k, num_q),
        in_specs=[smem, qblk_t, kblk_t, kblk_t, bspec_t, qblk_t, rowq_t,
                  rowq_t],
        out_specs=[kblk_t, kblk_t],
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((BLOCK_K, d), jnp.float32),
                        pltpu.VMEM((BLOCK_K, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * flops, bytes_accessed=q.size * 4 * 4,
            transcendentals=bh * sq * sk),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(seed, q, k, v, barg_t, g, lse, delta)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _make_flash(rate, has_bias, causal, interpret, with_lse=False):
    """custom_vjp'd flash attention specialised on (dropout rate, bias
    presence, causal, interpret mode) — all static, so each variant
    traces once.  ``with_lse=True`` additionally returns the per-row
    logsumexp as a differentiable output (the ring-attention merge needs
    it); its cotangent folds into the existing backward kernels via
    delta' = delta − dlse."""

    @jax.custom_vjp
    def f(q, k, v, bias, seed):
        o, lse = _flash_fwd(q, k, v, bias, seed, rate, causal, interpret)
        return (o, lse) if with_lse else o

    def fwd(q, k, v, bias, seed):
        o, lse = _flash_fwd(q, k, v, bias, seed, rate, causal, interpret)
        return ((o, lse) if with_lse else o), (q, k, v, bias, seed, o, lse)

    def bwd(res, g):
        q, k, v, bias, seed, o, lse = res
        if with_lse:
            g, dlse = g
        else:
            dlse = None
        dq, dk, dv = _flash_bwd(q, k, v, bias, seed, o, lse, g, rate,
                                causal, interpret, dlse=dlse)
        # bias grad is zero by contract (mask bias, stop-gradiented at the
        # kernel wrapper); seed is integer → float0 cotangent
        dbias = jnp.zeros_like(bias) if has_bias else None
        dseed = np.zeros(seed.shape, jax.dtypes.float0)
        return dq, dk, dv, dbias, dseed

    f.defvjp(fwd, bwd)
    return f


def _reference(q, k, v, bias, causal=False):
    """jnp spec for the kernels (no dropout), used by tests."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bsd,btd->bst", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        b = bias
        if b.shape[0] != q.shape[0]:            # head-shared mask
            b = jnp.repeat(b, q.shape[0] // b.shape[0], axis=0)
        s = s + b.astype(s.dtype)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bst,btd->bsd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def supported(shape_bhsd, k_seq=None, backend=None):
    """Static gate: can the kernel tile this (B, H, Sq, D) problem (with
    key/value sequence length ``k_seq``, defaulting to Sq)?  Mirrors
    exactly what flash_attention_bshd would reject, so callers dispatch
    without try/except."""
    b, h, s, d = shape_bhsd
    k_seq = s if k_seq is None else k_seq
    if s % BLOCK_Q or k_seq % BLOCK_K:
        return False
    if d % 128 and d != 64:
        # lane dim must tile; 64 still packs efficiently as (8, 128)
        return False
    from . import is_tpu_backend
    return is_tpu_backend(backend)


def flash_attention_bshd(q, k, v, bias=None, dropout_rate=0.0, seed=None,
                         causal=False, interpret=False):
    """q: (B, H, Sq, D), k/v: (B, H, Sk, D); bias: broadcastable
    (B, 1|H, 1|Sq, Sk) or None; seed: int32 scalar/1-vector driving the
    in-kernel dropout PRNG (required when dropout_rate > 0); causal masks
    col > row WITH block skipping (above-diagonal tiles never run).
    Returns (B, H, Sq, D).  Raises ValueError for shapes the kernel does
    not tile — call supported() first."""
    b, h, s, d = q.shape
    sk = k.shape[2]
    if not supported((b, h, s, d), k_seq=sk,
                     backend="tpu" if interpret else None):
        raise ValueError(
            f"flash_attention: unsupported shape/backend (Sq={s} must "
            f"tile {BLOCK_Q}, Sk={sk} must tile {BLOCK_K}, D={d} must be "
            f"64 or a multiple of 128, backend must be TPU)")
    if causal and s != sk:
        raise ValueError("causal flash attention requires Sq == Sk")
    if dropout_rate:
        if seed is None:
            raise ValueError("dropout_rate > 0 requires a seed")
        if interpret:
            # the interpreter stubs prng_random_bits to zeros, which
            # would silently drop every element
            raise ValueError(
                "dropout requires the hardware PRNG — unavailable in "
                "interpret mode")
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    seed = jnp.reshape(seed, (1,)).astype(jnp.int32)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    bf = None
    if bias is not None:
        if bias.shape[2] == 1:                  # e.g. (B, 1, 1, Sk) mask
            bias = jnp.broadcast_to(bias, bias.shape[:2] + (s, sk))
        if bias.shape[1] == 1:
            bf = bias.reshape(b, s, sk)         # head-shared mask
        else:
            bf = jnp.broadcast_to(bias, (b, h, s, sk)).reshape(
                b * h, s, sk)
        bf = lax.stop_gradient(bf)
    fn = _make_flash(float(dropout_rate), bf is not None, bool(causal),
                     interpret)
    return fn(qf, kf, vf, bf, seed).reshape(b, h, s, d)


def flash_attention_with_lse(q, k, v, bias=None, interpret=False):
    """Blockwise attention over ONE K/V block with residuals: returns
    ``(out, lse)`` where ``lse`` is the per-row logsumexp, both
    differentiable — the building block ring attention merges across
    rotated KV shards with the standard online-softmax combine
    (exp(lse_i − m)·o_i accumulation).  q/k/v: (B, H, Sq, D); bias:
    broadcastable (B, 1|H, 1|Sq, Sk) additive mask bias (stop-gradiented
    by contract, same as flash_attention_bshd).  lse: (B, H, Sq) f32."""
    b, h, s, d = q.shape
    sk = k.shape[2]
    if not supported((b, h, s, d), k_seq=sk,
                     backend="tpu" if interpret else None):
        raise ValueError(
            f"flash_attention_with_lse: unsupported shape/backend "
            f"(Sq={s} must tile {BLOCK_Q}, Sk={sk} must tile {BLOCK_K}, "
            f"D={d} must be 64 or a multiple of 128)")
    seed = jnp.zeros((1,), jnp.int32)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    bf = None
    if bias is not None:
        if bias.shape[2] == 1:
            bias = jnp.broadcast_to(bias, bias.shape[:2] + (s, sk))
        if bias.shape[1] == 1:
            bf = bias.reshape(b, s, sk)
        else:
            bf = jnp.broadcast_to(bias, (b, h, s, sk)).reshape(b * h, s, sk)
        bf = lax.stop_gradient(bf)
    fn = _make_flash(0.0, bf is not None, False, interpret, with_lse=True)
    o, lse = fn(qf, kf, vf, bf, seed)
    return o.reshape(b, h, s, d), lse.reshape(b, h, s)
