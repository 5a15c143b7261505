"""Paged KV-cache ops for the autoregressive decode runtime.

The reference's generation path (`beam_search`, `sampling_id`, the
`sequence_*` family) re-runs the whole prefix through the scoring
program for every emitted token; its serving tier has no notion of a
persistent attention cache.  TPU-natively the decode hot loop is won or
lost on KV-cache residency, so the cache is first-class:

* the cache is a **preallocated pool of fixed-size blocks** — one
  persistable per layer per K/V, shaped ``[num_blocks, block_size,
  hidden]``, sized ONCE at engine start by the static memory analyzer
  (framework/memory_analysis.plan_cache_pool) — not a per-sequence
  tensor that reallocates as sequences grow;
* sequences own **block tables** (i32 feeds mapping their logical
  positions onto pool blocks), so a sequence's context can live in any
  scattered set of blocks and freed blocks are reusable immediately;
* :func:`cache_write` scatters freshly-projected K/V rows into pool
  slots through a host-computed flat **slot-index feed** (-1 drops the
  write), which keeps every position/block computation out of the
  traced program — one scatter serves packed multi-segment prefill and
  single-token decode alike;
* the cache READ side lives on ``fused_attention`` (attention_ops.py):
  a ``KPool``/``VPool``/``BlockTable``/``CtxLen`` input set selects the
  read-through-the-table variant.  On a TPU a decode step (one query
  token a row) reads the pools IN PLACE, page by page as far as each
  row's live context (ops/pallas/paged_attention.py); a query of more
  than one token, and every CPU run, gathers the whole table's
  ``[B, T, H]`` context with :func:`gather_cache` and masks it with
  :func:`ctx_len_bias` — the composition that is also the spec the
  paged kernel is held to.

The pool vars are the ONLY persistables a decode program may write —
``analysis.verify_decode`` enforces exactly that.
"""

from __future__ import annotations

import jax.numpy as jnp

from .registry import register, x


def flat_slots(kpool_shape):
    """Total writable slots of a pool ``[num_blocks, block_size, H]``."""
    return int(kpool_shape[0]) * int(kpool_shape[1])


@register("cache_write")
def _cache_write(ctx, ins, attrs):
    """Scatter per-token K/V rows into the paged pools.

    With ``KPool``/``K`` alone the op writes ONE pool (a latent cache
    keeps one tensor a layer).  Inputs: ``KPool``/``VPool``
    ``[NB, BS, H]`` (persistable, updated in place — under the donated
    prepared path the scatter aliases the pool buffer), ``K``/``V`` ``[B, S, H]`` fresh projections, ``Slots``
    ``[B, S]`` i32 flat slot ids (``block * BS + offset``; -1 = padding,
    dropped).  Outputs overwrite the pool vars.

    The drop semantics make one executable serve every occupancy: a
    packed prefill writes every valid prompt token, a decode step writes
    exactly one slot per live row, and warmup/pad rows write nothing —
    bitwise — so co-batched sequences can never disturb each other's
    blocks."""
    kpool, vpool = x(ins, "KPool"), x(ins, "VPool")
    k, v = x(ins, "K"), x(ins, "V")
    slots = x(ins, "Slots").astype(jnp.int32)
    nslots = flat_slots(kpool.shape)
    h = kpool.shape[-1]
    idx = slots.reshape(-1)
    # jax wraps negative indices; route the dropped (-1) writes out of
    # bounds instead so mode="drop" discards them
    idx = jnp.where(idx < 0, nslots, idx)
    flat_k = kpool.reshape(nslots, h)
    if vpool is None:
        # ONE pool a layer (a latent cache: ops/mla_ops.py)
        return {"KPoolOut": flat_k.at[idx].set(
            k.reshape(-1, h).astype(kpool.dtype), mode="drop")
            .reshape(kpool.shape)}
    flat_v = vpool.reshape(nslots, h)
    new_k = flat_k.at[idx].set(k.reshape(-1, h).astype(kpool.dtype),
                               mode="drop")
    new_v = flat_v.at[idx].set(v.reshape(-1, h).astype(vpool.dtype),
                               mode="drop")
    return {"KPoolOut": new_k.reshape(kpool.shape),
            "VPoolOut": new_v.reshape(vpool.shape)}


@register("window_ring")
def _window_ring(ctx, ins, attrs):
    """Where a sliding window's K/V live: a RING of ``ring_pages`` pages
    a sequence, in a pool of ``[slots * ring_pages, block_size, H]``
    (slot ``s``'s ring is pages ``s * ring_pages ..``).  Position ``p`` of
    the sequence in slot ``StateSlot`` lives in ring page ``p //
    block_size % ring_pages``, so a page is overwritten ``ring_pages``
    pages later; a ring of ``ceil((window + launch - 1) / block_size) +
    1`` pages holds every position a launch of ``launch`` tokens a row
    still reads (serving/decode.py sizes it).

    Outputs ``RingSlots`` ``[B, S]``: the flat slot each written token
    goes to (``-1`` where ``Slots`` is -1: padding stays dropped), and,
    with ``table_pages``, ``Table`` ``[B, table_pages]``: logical page
    ``j`` of each row -> its ring page, the block table a cached read of
    the window layers walks like any other."""
    slot = x(ins, "StateSlot").astype(jnp.int32)
    pos = x(ins, "Pos").astype(jnp.int32)
    valid = x(ins, "Slots").astype(jnp.int32).reshape(pos.shape) >= 0
    ring, bs = int(attrs["ring_pages"]), int(attrs["block_size"])
    base = slot[:, None] * ring
    out = {"RingSlots": jnp.where(
        valid, (base + pos // bs % ring) * bs + pos % bs, -1)}
    if attrs.get("table_pages"):
        pages = jnp.arange(int(attrs["table_pages"]), dtype=jnp.int32)
        out["Table"] = base + pages[None, :] % ring
    return out


def gather_cache(pool, block_table, block_size=None):
    """Gather a per-sequence context ``[B, T, H]`` out of the pool
    through the block table (``T = max_blocks_per_seq * block_size``).
    Shared by the einsum composition and the gather-then-flash route so
    both read the cache identically (gathered values for valid
    positions are bitwise the written rows — block identity is
    transparent, which is what makes block reuse parity-safe)."""
    nb, bs, h = pool.shape
    if block_size is None:
        block_size = bs
    table = block_table.astype(jnp.int32)
    b, nseq = table.shape
    offs = jnp.arange(block_size, dtype=jnp.int32)[None, None, :]
    idx = (table[:, :, None] * block_size + offs).reshape(b, -1)
    return jnp.take(pool.reshape(nb * bs, h), idx, axis=0)


def ctx_len_bias(ctx_len, total, dtype=jnp.float32):
    """Additive attention bias ``[B, 1, 1, T]`` masking positions at or
    beyond each row's valid context length with -1e9 (exact-zero softmax
    weight after the exp underflow, so gathered garbage from padded
    table entries or reused blocks contributes bitwise nothing)."""
    pos = jnp.arange(total, dtype=jnp.int32)[None, :]
    valid = pos < ctx_len.astype(jnp.int32)[:, None]
    return jnp.where(valid, 0.0, -1e9).astype(dtype)[:, None, None, :]


__all__ = ["gather_cache", "ctx_len_bias", "flat_slots"]
