"""Causal flash attention with grouped K/V heads and an optional sliding
window, as Pallas TPU kernels (forward, dq, dk/dv).

What flash_attention.py's kernels do not have, and a long-sequence
decoder needs:

* **grouped heads**: ``H`` query heads read ``Hkv`` K/V heads (query
  head ``h`` reads ``h // (H / Hkv)``) straight through the K/V block's
  index map — K and V are never repeated in memory; the dk/dv kernel
  sums over the group's query heads in its innermost grid axis;
* **a window**: position ``i`` sees ``j <= i`` with ``i - j < window``.
  Key blocks wholly outside the window (and above the diagonal) are
  SKIPPED, not masked: the key axis of the grid spans only the blocks a
  query block can see (3 of 16 at window 1024, blocks of 512), the index
  maps clamp to the last block in range so a step out of range moves no
  data, and its body does not run.  Only blocks that the diagonal or the
  window's edge crosses pay for a mask;
* **the op's own layout**: Q ``[B, S, H * D]``, K/V ``[B, S, Hkv * D]``
  and the output are blocked as they are (head ``h`` is lane block ``h``
  of the last axis), so there is no head split or merge around the
  kernels (PERF.md, PR 28: those relayouts cost more than the attention);
* MXU operands stay in their own dtype (bf16 under AMP) with f32
  accumulation; softmax statistics in f32.

No bias and no dropout: a decoder's causal mask and window are
arithmetic on positions.  Residuals are O(S): the output and the per-row
logsumexp ``[B, H, S, 1]``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK = 512


def pick_block(seq: int, block=None) -> int:
    """The largest of 512/256/128 that divides ``seq`` (a caller's
    ``block`` — tests in interpret mode — wins)."""
    if block:
        return int(block)
    for b in (BLOCK, 256, 128):
        if seq % b == 0:
            return b
    raise ValueError(f"flash_gqa: sequence {seq} is not a multiple of 128")


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


def _masked(s, qi, kj, blk, window):
    """Scores of tile (qi, kj) with what the diagonal and the window's
    edge hide set to -inf; a tile neither crosses is returned as it is."""
    row0, col0 = qi * blk, kj * blk
    inside = col0 + blk - 1 <= row0
    if window:
        inside = inside & (row0 + blk - 1 - col0 < window)

    def mask(s):
        rows = row0 + lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        cols = col0 + lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        ok = cols <= rows
        if window:
            ok = ok & (rows - cols < window)
        return jnp.where(ok, s, NEG_INF)

    return lax.cond(inside, lambda s: s, mask, s)


def _scores(q, k, scale):
    return lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32) * scale


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, blk, window, steps):
    qi, t = pl.program_id(2), pl.program_id(3)
    lo, hi = _key_range(qi, blk, window)
    kj = lo + t

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(kj <= hi)
    def _body():
        v = v_ref[0]
        s = _masked(_scores(q_ref[0], k_ref[0], scale), qi, kj, blk, window)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(t == steps - 1)
    def _finalize():
        # every row sees at least its own position, so l > 0
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_ref[...])


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, blk, window, steps):
    qi, t = pl.program_id(2), pl.program_id(3)
    lo, hi = _key_range(qi, blk, window)
    kj = lo + t

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kj <= hi)
    def _body():
        k, v = k_ref[0], v_ref[0]
        s = _masked(_scores(q_ref[0], k, scale), qi, kj, blk, window)
        p = jnp.exp(s - lse_ref[0, 0])
        dp = lax.dot_general(do_ref[0], v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0])).astype(k.dtype)
        acc_ref[...] += lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(t == steps - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                    dv_ref, dk_acc, dv_acc, *, scale, blk, window, steps, group,
                    num_q):
    kj, t = pl.program_id(2), pl.program_id(3)
    lo, hi = _query_range(kj, blk, window, num_q)
    qi = lo + t % steps

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(qi <= hi)
    def _body():
        q, do = q_ref[0], do_ref[0]
        s = _masked(_scores(q, k_ref[0], scale), qi, kj, blk, window)
        p = jnp.exp(s - lse_ref[0, 0])
        dp = lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0])).astype(q.dtype)
        dv_acc[...] += lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(t == group * steps - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _specs(d, blk, window, group):
    """Block specs of the forward and dq grids (b, h, qi, t)."""
    def kmap(b, h, i, t):
        lo, hi = _key_range(i, blk, window)
        return b, jnp.minimum(lo + t, hi), h // group

    q = pl.BlockSpec((1, blk, d), lambda b, h, i, t: (b, i, h))
    kv = pl.BlockSpec((1, blk, d), kmap)
    row = pl.BlockSpec((1, 1, blk, 1), lambda b, h, i, t: (b, h, i, 0))
    return q, kv, row


_PARAMS = dict(compiler_params=pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")))


def _flash_fwd(q, k, v, n_head, n_kv, window, block, interpret):
    b, s, _ = q.shape
    d = q.shape[-1] // n_head
    blk = pick_block(s, block)
    steps = grid_steps(s, blk, window)
    qs, kvs, rows = _specs(d, blk, window, n_head // n_kv)
    kernel = functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d),
                               blk=blk, window=window, steps=steps)
    return pl.pallas_call(
        kernel, grid=(b, n_head, s // blk, steps),
        in_specs=[qs, kvs, kvs], out_specs=[qs, rows],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, n_head, s, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32),
                        pltpu.VMEM((blk, 1), jnp.float32),
                        pltpu.VMEM((blk, 1), jnp.float32)],
        interpret=interpret, name="flash_gqa_fwd", **_PARAMS,
    )(q, k, v)


def _flash_bwd(q, k, v, o, lse, g, n_head, n_kv, window, block, interpret):
    b, s, _ = q.shape
    d = q.shape[-1] // n_head
    group = n_head // n_kv
    blk = pick_block(s, block)
    scale = 1.0 / math.sqrt(d)
    delta = jnp.sum((g.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(b, s, n_head, d), axis=-1) \
        .transpose(0, 2, 1)[..., None]                   # [B, H, S, 1]
    steps = grid_steps(s, blk, window)
    qs, kvs, rows = _specs(d, blk, window, group)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, blk=blk,
                          window=window, steps=steps),
        grid=(b, n_head, s // blk, steps),
        in_specs=[qs, kvs, kvs, qs, rows, rows], out_specs=qs,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
        interpret=interpret, name="flash_gqa_bwd_dq", **_PARAMS,
    )(q, k, v, g, lse, delta)

    # dk/dv grid (b, kv head, key block, group x query steps): the
    # innermost axis walks the group's query heads and, for each, the
    # query blocks that can see this key block
    num_q = s // blk

    def qmap(b_, hk, j, t):
        lo, hi = _query_range(j, blk, window, num_q)
        return b_, jnp.minimum(lo + t % steps, hi), hk * group + t // steps

    def rowmap(b_, hk, j, t):
        lo, hi = _query_range(j, blk, window, num_q)
        return b_, hk * group + t // steps, \
            jnp.minimum(lo + t % steps, hi), 0

    qs_t = pl.BlockSpec((1, blk, d), qmap)
    rows_t = pl.BlockSpec((1, 1, blk, 1), rowmap)
    kvs_t = pl.BlockSpec((1, blk, d), lambda b_, hk, j, t: (b_, j, hk))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, blk=blk,
                          window=window, steps=steps, group=group,
                          num_q=num_q),
        grid=(b, n_kv, num_q, group * steps),
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
def _make(n_head, n_kv, window, block, interpret):
    """One traced and lowered body per variant, shared by the layers
    that use it (PERF.md, PR 28: Mosaic lowers a kernel at every
    ``lower()``)."""
    args = (n_head, n_kv, window, block, interpret)

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
                  interpret=False):
    """Causal attention.  q ``[B, S, H * D]``, k/v ``[B, S, Hkv * D]``;
    ``window``: position ``i`` also needs ``i - j < window``.  Returns
    ``[B, S, H * D]``.  ``block`` overrides the 512/256/128 tile choice
    (interpret-mode tests at small sizes)."""
    return _make(int(n_head), int(n_kv_head), int(window or 0),
                 block and int(block), bool(interpret))(q, k, v)


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
