"""Absorbed latent-attention decode as one Pallas TPU kernel: a decode
step's one query token a row against the PAGED LATENT CACHE, read in
place through each row's block table as far as the row's live context.

Latent attention (MLA) keeps per token and layer one row of ``dc + dr``
values — the compressed K/V latent after its norm (``dc``) and the one
rotary key all heads share (``dr``) — where full K and V would keep
``heads * (d_qk + d_v)``.  With the K up-projection absorbed into the
query (``q~_h = q_nope_h W_uk_h^T``) every head scores the SAME row:

    score_h(j) = (q~_h . c_kv_j + q_rope_h . k_rope_j) * scale
    o_h        = sum_j softmax(score_h)_j c_kv_j         (then W_uv_h)

so a row's whole attention is two MXU products per page group,
``[heads, dc + dr] x [dc + dr, tokens]`` and ``[heads, tokens] x
[tokens, dc]`` — not the per-head VPU sum of ops/pallas/paged_attention.py,
whose pools hold one K and one V row per head.

* the pool stays ``[num_blocks, block_size, W]`` bfloat16 in HBM
  (``memory_space=pl.ANY``), ``W`` the row ``[c_kv | k_rope | 0]`` padded
  to whole 128-lane tiles (576 -> 640: the TPU's tiled HBM layout pads
  the last dimension to that anyway, and a DMA slices whole tiles);
  block table and ``ctx_len`` are scalar prefetched; one grid step a row; the kernel copies the row's live
  pages ``0 .. ceil(ctx_len / block_size) - 1`` with its own DMAs,
  ``pages_per_step`` pages a step into one of two buffers, the next
  step's copies in flight while this step's are scored, and fetches
  nothing past the row's last live page;
* scores, softmax state and the accumulator are float32; the products
  take bfloat16 operands (the cache's own precision) and accumulate in
  f32; the score is ONE contraction over ``W`` (the query's pad lanes are
  zero, the cache's are written as zero);
* positions ``>= ctx_len`` in the last live page and the dead pages of a
  row's last step (a reused block's leftovers, never-written slots, an
  earlier row's pages still in the buffer) are masked by a SELECT on
  position, on the score and on the latent row (``0 * NaN`` is NaN);
  table entries past the live pages are never fetched; a row with no
  live position reads page 0 and writes zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: pages one step copies and scores (512 positions at 16-token pages:
#: one MXU pass of 128 heads x 512 keys)
PAGES_PER_STEP = 32
EMPTY = -1e30
MASKED = -2e30


def supported(sq, n_head, latent_dim, rope_dim, block_size, width,
              dtype="bfloat16", has_qpos=False):
    """Static shape rule -> (ok, reason): is this cached latent read a
    decode step the kernel takes?"""
    if sq != 1:
        return False, f"mla-paged:sq:{sq}"
    if has_qpos:
        return False, "mla-paged:qpos"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return False, f"mla-paged:dtype:{jnp.dtype(dtype).name}"
    if block_size % 16:
        return False, f"mla-paged:block-size:{block_size}"
    if latent_dim % LANES or width % LANES \
            or width < latent_dim + rope_dim:
        return False, f"mla-paged:latent:{latent_dim}+{rope_dim}/{width}"
    if n_head % 8:
        return False, f"mla-paged:heads:{n_head}"
    return True, ""


def _kernel(ctx_ref, tbl_ref, q_ref, pool_hbm, o_ref, buf, sem, *, dc,
            scale, pps):
    b = pl.program_id(0)
    num_blocks, bs, width = pool_hbm.shape
    pages_per_seq = tbl_ref.shape[0] // pl.num_programs(0)
    rows = pps * bs                 # positions one step copies and scores
    ctx = ctx_ref[b]
    # live pages: at least page 0, at most the table
    pages = jnp.clip((ctx + bs - 1) // bs, 1, pages_per_seq)
    steps = (pages + pps - 1) // pps

    def copies(c, slot):
        out = []
        for j in range(pps):
            entry = tbl_ref[b * pages_per_seq
                            + jnp.minimum(c * pps + j, pages_per_seq - 1)]
            blk = jnp.clip(entry, 0, num_blocks - 1)
            out.append((c * pps + j < pages, pltpu.make_async_copy(
                pool_hbm.at[blk], buf.at[slot, pl.ds(j * bs, bs)],
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

    q = q_ref[0]                                    # [H, W] bf16
    n_head = q.shape[0]
    pos_s = lax.broadcasted_iota(jnp.int32, (n_head, rows), 1)
    pos_kv = lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    start(0, 0)

    def step(c, state):
        m, l, acc = state
        slot = c % 2

        @pl.when(c + 1 < steps)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        left = ctx - c * rows
        kv = buf[slot]
        kv = jnp.where(pos_kv < left, kv, jnp.zeros_like(kv))
        s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.where(pos_s < left, s * scale, MASKED)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(kv.dtype), kv[:, :dc],
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = lax.fori_loop(
        0, steps, step,
        (jnp.full((n_head, 1), EMPTY, jnp.float32),
         jnp.zeros((n_head, 1), jnp.float32),
         jnp.zeros((n_head, dc), jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("latent_dim", "scale",
                                             "pages_per_step", "interpret"))
def mla_paged_decode(q, pool, block_table, ctx_len, *, latent_dim, scale,
                     pages_per_step=PAGES_PER_STEP, interpret=False):
    """q: (B, H, W) bfloat16, the absorbed query beside its rotary part,
    zero in the pad lanes; pool: (num_blocks, block_size, W) bfloat16,
    rows ``[c_kv (latent_dim) | k_rope | 0]``; block_table:
    (B, max_blocks_per_seq) int32; ctx_len: (B,) int32.  Returns
    ``sum_j softmax_j c_kv_j`` per head, (B, H, dc) float32 — the value
    up-projection is the caller's.  Raises ValueError for what
    supported() rejects — call it first."""
    b, h, width = q.shape
    _, bs, _ = pool.shape
    pages_per_seq = block_table.shape[1]
    ok, why = supported(1, h, latent_dim, 0, bs, pool.shape[2],
                        pool.dtype)
    if ok and width != pool.shape[2]:
        ok, why = False, f"mla-paged:q-width:{width}!={pool.shape[2]}"
    if not ok:
        raise ValueError(f"mla_paged_decode: unsupported ({why})")
    pps = min(int(pages_per_step), pages_per_seq)
    live_bytes = b * pages_per_seq * bs * width * 2      # every page live
    return pl.pallas_call(
        functools.partial(_kernel, dc=latent_dim, scale=float(scale),
                          pps=pps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, h, width), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, latent_dim),
                                   lambda i, *_: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, pps * bs, width), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((b, h, latent_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * h * (width + latent_dim) * (live_bytes // (2 * width)),
            bytes_accessed=live_bytes,
            transcendentals=h * (live_bytes // (2 * width))),
        interpret=interpret,
        name="mla_paged_decode",
    )(ctx_len.astype(jnp.int32), block_table.reshape(-1).astype(jnp.int32),
      q.astype(pool.dtype), pool)
