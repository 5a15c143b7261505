"""Multi-head latent attention (MLA): attention whose K and V are
up-projections of ONE compressed latent a token, scored together with
one rotary key all heads share (no reference analog; re-derived from
the published equations of the DeepSeek-V2/V3 family,
benchmark/reference/deepseek_v3_jnp.py writes them out).

``mla_attention`` takes the query heads ``Q`` ``[B, Sq, H * (dn + dr)]``
(``[q_nope | q_rope]`` per head, the rotary part already rotated), the
K/V up-projection ``WKVB`` ``[dc, H * (dn + dv)]`` (``[k_nope | v]`` per
head) and the latents — ``c_kv`` after its norm beside ``k_rope`` after
its rotation, ``dc + dr`` values a token in a row of width ``W >= dc +
dr`` (the model pads the row with zeros to whole 128-lane tiles, which
the TPU's tiled HBM layout does to the pool anyway) — in one of three
forms, read
from shapes and inputs, never from a flag or a model name:

* **fresh** (``Latent`` ``[B, S, W]``, optional ``AttnBias``):
  packed prefill.  EXPANDED form: ``[k_nope_h | v_h] = c_kv W_kvb`` for
  every head, ``score_h(i, j) = (q_nope_h,i . k_nope_h,j + q_rope_h,i .
  k_rope_j) * scale`` for ``j <= i``, softmax in f32;
* **cached with ``QPos``** (``Pool`` ``[NB, BS, W]``,
  ``BlockTable``, ``CtxLen``, ``QPos`` ``[B, Sq]``): chunked prefill
  over the paged latent cache.  EXPANDED too: on a TPU the Pallas kernel
  of ops/pallas/mla_chunk.py (route ``mla_chunk_attn``), which reads the
  pool in place, up-projects each live key once a layer in VMEM and
  scores each query block to its causal frontier; else ``chunk_attention``
  below, by blocks of keys with an online softmax (a block's latents
  gathered through the table and up-projected where they are used, the
  loop stopping at the longest live context), which is also the spec the
  kernel is held to;
* **cached, one query token a row, no ``QPos``**: a decode step.
  ABSORBED form: ``q~_h = q_nope_h W_uk_h^T`` (``dc`` wide), every head
  scores the same latent row, ``o_h = (sum_j p_j c_kv_j) W_uv_h`` — on
  a TPU the paged kernel of ops/pallas/mla_paged.py (route
  ``mla_paged_decode``), which reads only each row's live pages; else
  the gather composition below, which is also the spec the kernel is
  held to.

Positions at or beyond ``CtxLen`` are masked by SELECT on the score and
on the latent row, so leftovers in reused blocks (NaN included) add
exactly nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .cache_ops import gather_cache
from .registry import pallas_route, register, x

MASKED = -1e30
#: keys one block of the chunked (cached, QPos) form gathers and scores
KEY_BLOCK = 512


def _dims(attrs, q, wkvb):
    h = int(attrs["n_head"])
    dn, dr, dv = (int(attrs[k]) for k in ("nope_dim", "rope_dim", "v_dim"))
    dc = int(wkvb.shape[0])
    if q.shape[-1] != h * (dn + dr) or wkvb.shape[1] != h * (dn + dv):
        raise ValueError(
            f"mla_attention: Q width {q.shape[-1]} / WKVB {wkvb.shape} do "
            f"not match {h} heads of {dn}+{dr} / {dn}+{dv}")
    return h, dn, dr, dv, dc


def _expand(c_kv, wkvb, h, dn):
    """[..., dc] latents -> (k_nope [..., H, dn], v [..., H, dv])."""
    kv = jnp.einsum("...c,chd->...hd", c_kv,
                    wkvb.reshape(wkvb.shape[0], h, -1),
                    preferred_element_type=jnp.float32).astype(c_kv.dtype)
    return kv[..., :dn], kv[..., dn:]


def _scores(q_nope, q_rope, k_nope, k_rope):
    """[B, Sq, H, *] x [B, T, H, dn] / [B, T, dr] -> [B, H, Sq, T] f32."""
    return jnp.einsum("bqhd,bthd->bhqt", q_nope, k_nope,
                      preferred_element_type=jnp.float32) \
        + jnp.einsum("bqhd,btd->bhqt", q_rope, k_rope,
                     preferred_element_type=jnp.float32)


def fresh_attention(q, latent, wkvb, bias, attrs):
    """Packed prefill: every row attends to its own fresh latents."""
    h, dn, dr, dv, dc = _dims(attrs, q, wkvb)
    b, s, _ = q.shape
    qh = q.reshape(b, s, h, dn + dr)
    k_nope, v = _expand(latent[..., :dc], wkvb, h, dn)
    sc = _scores(qh[..., :dn], qh[..., dn:], k_nope, latent[..., dc:dc + dr]) \
        * float(attrs["scale"])
    if bias is not None:
        sc = sc + bias.astype(sc.dtype)
    tri = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(tri[None, None], sc, MASKED)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhqt,bthd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype).reshape(b, s, h * dv)


def _key_block(pages_per_seq: int, block_size: int) -> int:
    """Pages a key block spans: the largest divisor of the table that
    keeps a block within KEY_BLOCK positions."""
    want = max(1, KEY_BLOCK // block_size)
    return max(p for p in range(1, min(want, pages_per_seq) + 1)
               if pages_per_seq % p == 0)


def chunk_attention(q, pool, table, ctx_len, q_pos, wkvb, attrs):
    """Chunked prefill over the paged latent cache, expanded by blocks
    of keys; the loop's trip count follows the longest live context."""
    h, dn, dr, dv, dc = _dims(attrs, q, wkvb)
    b, sq, _ = q.shape
    nb, bs, width = pool.shape
    pages_per_seq = table.shape[1]
    ppb = _key_block(pages_per_seq, bs)
    kb = ppb * bs
    qh = q.reshape(b, sq, h, dn + dr)
    q_nope, q_rope = qh[..., :dn], qh[..., dn:]
    table = table.astype(jnp.int32)
    ctx_len = ctx_len.astype(jnp.int32)
    q_pos = q_pos.astype(jnp.int32)
    scale = float(attrs["scale"])
    flat = pool.reshape(nb * bs, width)
    offs = jnp.arange(bs, dtype=jnp.int32)

    def block(i, state):
        m, l, acc = state
        pages = lax.dynamic_slice_in_dim(table, i * ppb, ppb, axis=1)
        idx = (pages[:, :, None] * bs + offs[None, None, :]).reshape(b, kb)
        t = i * kb + jnp.arange(kb, dtype=jnp.int32)
        live = t[None, :] < ctx_len[:, None]                    # [B, kb]
        lat = jnp.where(live[..., None], jnp.take(flat, idx, axis=0), 0)
        k_nope, v = _expand(lat[..., :dc], wkvb, h, dn)
        sc = _scores(q_nope, q_rope, k_nope, lat[..., dc:dc + dr]) * scale
        seen = live[:, None, :] & (t[None, None, :] <= q_pos[:, :, None])
        sc = jnp.where(seen[:, None], sc, MASKED)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bhqt,bthd->bhqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    blocks = jnp.clip((jnp.max(ctx_len) + kb - 1) // kb, 1,
                      pages_per_seq // ppb)
    m, l, acc = lax.fori_loop(
        0, blocks, block,
        (jnp.full((b, h, sq), MASKED, jnp.float32),
         jnp.zeros((b, h, sq), jnp.float32),
         jnp.zeros((b, h, sq, dv), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype).reshape(b, sq, h * dv)


def absorb_query(q, wkvb, attrs, width):
    """[B, 1, H * (dn + dr)] -> [B, H, width]: ``q~_h = q_nope_h
    W_uk_h^T`` beside the rotary part, zero in the cache row's pad
    lanes."""
    h, dn, dr, _, dc = _dims(attrs, q, wkvb)
    qh = q.reshape(q.shape[0], h, dn + dr)
    w_uk = wkvb.reshape(dc, h, -1)[..., :dn]
    q_abs = jnp.einsum("bhd,chd->bhc", qh[..., :dn], w_uk,
                       preferred_element_type=jnp.float32)
    pad = jnp.zeros(qh.shape[:2] + (width - dc - dr,), q.dtype)
    return jnp.concatenate([q_abs.astype(q.dtype), qh[..., dn:], pad],
                           axis=-1)


def project_value(o_lat, wkvb, attrs, dtype):
    """[B, H, dc] -> [B, 1, H * dv]: ``o_h = o_lat_h W_uv_h``."""
    h, dn = int(attrs["n_head"]), int(attrs["nope_dim"])
    w_uv = wkvb.reshape(wkvb.shape[0], h, -1)[..., dn:]
    out = jnp.einsum("bhc,chd->bhd", o_lat.astype(dtype), w_uv,
                     preferred_element_type=jnp.float32)
    return out.astype(dtype).reshape(o_lat.shape[0], 1, -1)


def gathered_decode(q_abs, pool, table, ctx_len, dc, scale):
    """The absorbed decode read by GATHER (CPU, tests, the paged
    kernel's spec): [B, H, dc + dr] x the whole table's latents ->
    ``sum_j softmax_j c_kv_j`` [B, H, dc] f32."""
    lat = gather_cache(pool, table)                       # [B, T, dc + dr]
    live = jnp.arange(lat.shape[1])[None, :] \
        < ctx_len.astype(jnp.int32)[:, None]
    lat = jnp.where(live[..., None], lat, 0)
    sc = jnp.einsum("bhc,btc->bht", q_abs, lat,
                    preferred_element_type=jnp.float32) * scale
    sc = jnp.where(live[:, None, :], sc, MASKED)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.where(live[:, None, :], jnp.exp(sc - m), 0.0)
    o = jnp.einsum("bht,btc->bhc", p.astype(lat.dtype), lat[..., :dc],
                   preferred_element_type=jnp.float32)
    return o / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)


def lower_mla_paged_decode(ctx, ins, attrs):
    """The ``mla_paged_decode`` Pallas route (pallas_route guarantees
    the shape rule before this is called)."""
    from .pallas.mla_paged import mla_paged_decode
    q, wkvb, pool = x(ins, "Q"), x(ins, "WKVB"), x(ins, "Pool")
    o_lat = mla_paged_decode(
        absorb_query(q, wkvb, attrs, pool.shape[-1]), pool,
        x(ins, "BlockTable"),
        x(ins, "CtxLen"), latent_dim=int(wkvb.shape[0]),
        scale=float(attrs["scale"]))
    return {"Out": project_value(o_lat, wkvb, attrs, q.dtype)}


def lower_mla_chunk_attn(ctx, ins, attrs):
    """The ``mla_chunk_attn`` Pallas route (pallas_route guarantees the
    shape rule before this is called)."""
    from .pallas.mla_chunk import mla_chunk_attention
    return {"Out": mla_chunk_attention(
        x(ins, "Q"), x(ins, "Pool"), x(ins, "BlockTable"), x(ins, "CtxLen"),
        x(ins, "QPos"), x(ins, "WKVB"), n_head=int(attrs["n_head"]),
        nope_dim=int(attrs["nope_dim"]), rope_dim=int(attrs["rope_dim"]),
        v_dim=int(attrs["v_dim"]), scale=float(attrs["scale"]))}


@register("mla_attention")
def _mla_attention(ctx, ins, attrs):
    q, wkvb, pool = x(ins, "Q"), x(ins, "WKVB"), x(ins, "Pool")
    if pool is None:
        return {"Out": fresh_attention(q, x(ins, "Latent"), wkvb,
                                       x(ins, "AttnBias"), attrs)}
    table, ctx_len = x(ins, "BlockTable"), x(ins, "CtxLen")
    q_pos = x(ins, "QPos")
    if q_pos is not None or q.shape[1] != 1:
        if q_pos is None:
            raise ValueError("mla_attention: a cached read of more than "
                             "one query token a row needs QPos")
        route, _ = pallas_route("mla_attention", ins, attrs,
                                kernel="mla_chunk_attn")
        if route is not None:
            return route.lower(ctx, ins, attrs)
        return {"Out": chunk_attention(q, pool, table, ctx_len, q_pos,
                                       wkvb, attrs)}
    route, _ = pallas_route("mla_attention", ins, attrs,
                            kernel="mla_paged_decode")
    if route is not None:
        return route.lower(ctx, ins, attrs)
    o_lat = gathered_decode(absorb_query(q, wkvb, attrs, pool.shape[-1]),
                            pool, table, ctx_len, int(wkvb.shape[0]),
                            float(attrs["scale"]))
    return {"Out": project_value(o_lat, wkvb, attrs, q.dtype)}
