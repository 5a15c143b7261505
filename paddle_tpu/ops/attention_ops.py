"""Fused attention op — the analog of the reference's fused multihead
attention kernels (ref: operators/fused/multihead_matmul_op.cu and
math/bert_encoder_functor.cu), TPU-native.

One op takes projected Q/K/V in (B, S, H*D) layout plus an additive
attention bias and produces the context in (B, S, H*D).  Keeping the whole
attention in a single op gives a clean seam to swap the implementation for
a Pallas kernel on TPU while the jnp composition remains the
CPU/interpret fallback: the one-tile kernels
(ops/pallas/attention_tile.py) when the whole problem is one tile —
non-causal, Sq == Sk == 128, which they take in this (B, S, H*D) layout —
and the blockwise flash kernel (ops/pallas/flash_attention.py, on
head-split operands) for causal, cached, ring and longer sequences; for
a decoder's grouped K/V heads and sliding window (attrs
``num_kv_heads``, ``window``) the kernels of ops/pallas/flash_gqa.py,
which also take the op's layout and skip the key blocks outside the
window; and for a decode step's one-token query over the KV pools the
paged kernel (ops/pallas/paged_attention.py), which reads the pools in
place through the block table as far as each row's live context.

Routing goes through the registry's Pallas channel
(``pallas_route("fused_attention", ...)`` — ops/op_specs.py registers the
``attention_tile``, ``flash_attention``, ``flash_gqa_attention``,
``paged_decode_attention_wide``, ``paged_decode_attention``,
``cached_flash_attention`` and ``ring_flash_attention`` routes; which one is read from shapes and attrs,
never from a flag or a model name), so the gate is
statically enumerable, every hit/fallback lands in
``observability.metrics`` counters labeled by op + reason, and fallback
warnings name the EFFECTIVE lowering backend (ops.pallas), not
``jax.default_backend()``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import pallas_route, register, x


def _split_heads(t, n_head):
    b, s, hd = t.shape
    return t.reshape(b, s, n_head, hd // n_head).transpose(0, 2, 1, 3)


def _merge_heads(t):
    b, h, s, d = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _resolve_heads(q, attrs):
    """tensor-parallel callers pass the GLOBAL head count + head_dim; the
    local head count follows from the traced width (hidden/tp inside
    shard_map, full hidden off-mesh) so one program is correct under
    both lowerings."""
    n_head = attrs["n_head"]
    head_dim = attrs.get("head_dim")
    if head_dim:
        n_head = max(1, int(q.shape[-1]) // int(head_dim))
    return n_head


def _attn_bias(ins):
    """The additive bias: explicit AttnBias, else derived from the
    [B, S] 0/1 valid-key KVMask."""
    bias = x(ins, "AttnBias")
    if bias is None:
        kv_mask = x(ins, "KVMask")
        if kv_mask is not None:
            bias = (1.0 - kv_mask.astype(jnp.float32))[:, None, None, :] \
                * -1e9
    return bias


def reference_attention(q, k, v, bias, n_head, dropout_rate, ctx,
                        is_test, causal=False):
    """Plain jnp attention, numerically the spec for the pallas kernel."""
    d_key = q.shape[-1] // n_head
    qh = _split_heads(q, n_head)
    kh = _split_heads(k, n_head)
    vh = _split_heads(v, n_head)
    scores = jnp.einsum("bhsd,bhtd->bhst", qh, kh,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / jnp.sqrt(d_key).astype(jnp.float32))
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    if causal:
        # mask from TRACED shapes (not a baked [S, S] constant) so one
        # program serves every bucketed sequence length
        sq, sk = scores.shape[-2], scores.shape[-1]
        tri = jnp.triu(jnp.full((sq, sk), -1e9, scores.dtype), k=1)
        scores = scores + tri
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate and not is_test:
        keep = jax.random.bernoulli(ctx.next_key(), 1.0 - dropout_rate,
                                    probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    ctxv = jnp.einsum("bhst,bhtd->bhsd", probs.astype(vh.dtype), vh,
                      preferred_element_type=jnp.float32).astype(vh.dtype)
    return _merge_heads(ctxv)


def _dropout_seed(ctx, attrs):
    """(rate, seed) of a training-mode attention: a per-step int32 seed
    from the program RNG, so the in-kernel PRNG mask changes every step
    but forward and backward agree."""
    is_test = attrs.get("is_test", False) or ctx.is_test
    rate = 0.0 if is_test else float(attrs.get("dropout_rate", 0.0))
    if not rate:
        return 0.0, None
    return rate, jax.random.randint(ctx.next_key(), (1,), 0,
                                    jnp.iinfo(jnp.int32).max,
                                    dtype=jnp.int32)


def lower_flash_attention(ctx, ins, attrs):
    """The ``flash_attention`` Pallas route: blockwise online-softmax
    kernel on head-split operands (pallas_route guarantees the shape
    tiles before this is called)."""
    from .pallas.flash_attention import flash_attention_bshd
    q, k, v = x(ins, "Q"), x(ins, "K"), x(ins, "V")
    n_head = _resolve_heads(q, attrs)
    rate, seed = _dropout_seed(ctx, attrs)
    out = flash_attention_bshd(
        _split_heads(q, n_head), _split_heads(k, n_head),
        _split_heads(v, n_head), _attn_bias(ins), dropout_rate=rate,
        seed=seed, causal=bool(attrs.get("causal", False)))
    return {"Out": _merge_heads(out)}


def lower_attention_tile(ctx, ins, attrs):
    """The ``attention_tile`` Pallas route: the whole problem is one
    (S, S) tile per head, so the kernels take Q/K/V and write the context
    in the (B, S, H*D) layout the op has them in — no head split or
    merge (pallas_route guarantees the shape rule before this is
    called)."""
    from .pallas.attention_tile import attention_tile_bsd
    q, k, v = x(ins, "Q"), x(ins, "K"), x(ins, "V")
    rate, seed = _dropout_seed(ctx, attrs)
    return {"Out": attention_tile_bsd(
        q, k, v, _attn_bias(ins), n_head=_resolve_heads(q, attrs),
        dropout_rate=rate, seed=seed)}


def gqa_attrs(attrs):
    """(window, K/V heads) when the op is a decoder's grouped-head or
    windowed attention, else None: the attrs alone decide (the plain,
    one-tile and flash routes never see such an op)."""
    window, n_kv = attrs.get("window"), attrs.get("num_kv_heads")
    if not window and not n_kv:
        return None
    return int(window or 0), int(n_kv or attrs["n_head"])


def lower_gqa_attention(ctx, ins, attrs, use_flash=True):
    """Causal attention over grouped K/V heads with an optional window:
    the ``flash_gqa_attention`` Pallas route, or the same mathematics
    with the scores materialised (CPU, tests)."""
    from .pallas import flash_gqa
    window, n_kv = gqa_attrs(attrs)
    if not attrs.get("causal") or x(ins, "AttnBias") is not None \
            or attrs.get("dropout_rate"):
        raise ValueError("fused_attention with num_kv_heads/window is "
                         "causal, without bias or dropout")
    fn = flash_gqa.flash_gqa_bsd if use_flash else flash_gqa.reference
    return {"Out": fn(x(ins, "Q"), x(ins, "K"), x(ins, "V"),
                      n_head=attrs["n_head"], n_kv_head=n_kv,
                      window=window)}


def lower_paged_decode_attention(ctx, ins, attrs, wide=False):
    """The ``paged_decode_attention`` Pallas routes: a decode step's
    cache read (one query token a row, no ``QPos``) straight from the
    pools, page by page through the block table as far as each row's
    ``CtxLen`` — no ``[B, T, H]`` copy of the cache exists
    (pallas_route guarantees the shape rule before this is called).
    ``wide``: the body for heads of whole lane tiles."""
    from .pallas import paged_attention
    kernel = paged_attention.paged_decode_attention_wide if wide \
        else paged_attention.paged_decode_attention
    q = x(ins, "Q")
    return {"Out": kernel(
        q, x(ins, "KPool"), x(ins, "VPool"), x(ins, "BlockTable"),
        x(ins, "CtxLen"), n_head=_resolve_heads(q, attrs))}


def lower_cached_attention(ctx, ins, attrs, use_flash=False):
    """Cache-read attention by GATHER: K/V come out of the block pools
    through the per-sequence block table as a ``[B, T, H]`` context
    (``T`` the whole table).  It serves every cached read the paged
    kernel does not take — a query of more than one token (chunked and
    prefix-hit prefill), odd shapes, every CPU run: the einsum
    composition is the CPU/tier-1 path and the spec the paged kernel is
    held to; ``use_flash=True`` (the ``cached_flash_attention`` Pallas
    route) runs the same gather and hands the gathered context to the
    blockwise flash kernel — both read the cache identically, so
    routing can never change which bytes attention sees.

    Positions at or beyond ``CtxLen`` (padded table entries, reused
    blocks carrying another sequence's leftovers) are masked to an
    EXACT-zero softmax weight, which is what makes co-batched and
    block-reuse results bitwise equal to a lone run.

    The optional ``QPos`` input ([B, Sq] absolute query positions —
    chunked prefill, serving/decode.py) adds a per-query causal term on
    top: key position t is visible to query position p iff ``t <= p``.
    Valid (query, key) pairs still get an EXACTLY-zero bias (0.0 + 0.0),
    so a prompt prefilled in chunks reads bitwise the same cache bytes
    a packed one-shot prefill reads; without QPos the decode-step bias
    is bitwise unchanged."""
    from .cache_ops import ctx_len_bias, gather_cache
    q = x(ins, "Q")
    kpool, vpool = x(ins, "KPool"), x(ins, "VPool")
    table, ctx_len = x(ins, "BlockTable"), x(ins, "CtxLen")
    n_head = _resolve_heads(q, attrs)
    keys = gather_cache(kpool, table)
    vals = gather_cache(vpool, table)
    bias = ctx_len_bias(ctx_len, keys.shape[1])
    q_pos = x(ins, "QPos")
    if q_pos is not None:
        tpos = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, None, :]
        causal = jnp.where(
            tpos <= q_pos.astype(jnp.int32)[:, :, None], 0.0, -1e9)
        # [B, 1, 1, T] + [B, 1, Sq, T] — both legs contribute exact
        # zeros on valid pairs, so the sum stays exactly zero there
        bias = bias + causal[:, None, :, :].astype(bias.dtype)
    if use_flash:
        from .pallas.flash_attention import flash_attention_bshd
        out = flash_attention_bshd(
            _split_heads(q, n_head), _split_heads(keys, n_head),
            _split_heads(vals, n_head), bias)
        return {"Out": _merge_heads(out)}
    return {"Out": reference_attention(q, keys, vals, bias, n_head,
                                       0.0, ctx, True, causal=False)}


def lower_ring_attention(ctx, ins, attrs, use_flash=False):
    """Sequence-parallel attention: ring over the sp axis, inner step
    either the Pallas blockwise flash kernel (the
    ``ring_flash_attention`` route) or the einsum composition."""
    from ..parallel.ring_attention import ring_attention
    q, k, v = x(ins, "Q"), x(ins, "K"), x(ins, "V")
    n_head = _resolve_heads(q, attrs)
    kv_mask = x(ins, "KVMask")
    out = ring_attention(
        _split_heads(q, n_head), _split_heads(k, n_head),
        _split_heads(v, n_head), attrs["_seq_axis"],
        causal=attrs.get("causal", False), kv_mask=kv_mask,
        use_flash=use_flash)
    return {"Out": _merge_heads(out)}


@register("fused_attention")
def _fused_attention(ctx, ins, attrs):
    q, k, v = x(ins, "Q"), x(ins, "K"), x(ins, "V")
    n_head = _resolve_heads(q, attrs)
    dropout_rate = attrs.get("dropout_rate", 0.0)
    is_test = attrs.get("is_test", False) or ctx.is_test
    # paged KV-cache read (serving/decode.py): K/V through the block
    # pools instead of fresh projections — a decode step's one-token
    # query by the paged kernel, a longer one by gather + flash
    if x(ins, "KPool") is not None:
        route, _ = pallas_route("fused_attention", ins, attrs,
                                kernel=("paged_decode_attention_wide",
                                        "paged_decode_attention",
                                        "cached_flash_attention"))
        if route is not None:
            return route.lower(ctx, ins, attrs)
        return lower_cached_attention(ctx, ins, attrs, use_flash=False)
    # sequence parallelism: attention rings over the sp axis (the q/k/v
    # entering here hold only this device's sequence shard)
    seq_axis = attrs.get("_seq_axis")
    if seq_axis and seq_axis in ctx.axis_names:
        route, _ = pallas_route("fused_attention", ins, attrs,
                                kernel="ring_flash_attention")
        if route is not None:
            return route.lower(ctx, ins, attrs)
        return lower_ring_attention(ctx, ins, attrs, use_flash=False)
    # a decoder's grouped K/V heads or window: its own kernels
    if gqa_attrs(attrs) is not None:
        route, _ = pallas_route("fused_attention", ins, attrs,
                                kernel="flash_gqa_attention")
        if route is not None:
            return route.lower(ctx, ins, attrs)
        return lower_gqa_attention(ctx, ins, attrs, use_flash=False)
    # one tile (Sq == Sk == 128, non-causal) has its own kernels; every
    # other shape the blockwise kernel tiles is the flash route's
    route, _ = pallas_route("fused_attention", ins, attrs,
                            kernel=("attention_tile", "flash_attention"))
    if route is not None:
        return route.lower(ctx, ins, attrs)
    return {"Out": reference_attention(q, k, v, _attn_bias(ins), n_head,
                                       dropout_rate, ctx, is_test,
                                       causal=bool(attrs.get("causal",
                                                             False)))}
