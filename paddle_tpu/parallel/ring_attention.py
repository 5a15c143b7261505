"""Ring attention — sequence/context parallelism over an ICI mesh axis.

NEW capability: the reference (2020) has no sequence parallelism
(SURVEY §5 "Long-context: Absent").  Design follows blockwise ring
attention: every device holds the full Q for its sequence shard and
rotates K/V shards around the `sp` ring with `lax.ppermute`, maintaining
numerically-stable online-softmax accumulators (m, l, acc) exactly like
flash attention — so the full S×S score matrix never materialises and
sequence length scales linearly with the number of devices.

Two inner-step implementations share the (m, l, acc) carry:

* the **Pallas blockwise flash kernel** (ops/pallas/flash_attention.py)
  on each rotated K/V shard — per-shard score blocks never materialise
  even LOCALLY (O(BLOCK·D) VMEM instead of an (S_loc, S_loc) HBM
  tensor), which is what makes sp-sharded long context actually O(S);
  the kernel returns (out, lse) with lse differentiable, and the carry
  merge is the standard logsumexp combine
  ``acc·exp(m−m') + o_blk·exp(lse−m')``;
* the **einsum composition** — the jnp fallback off-TPU / at shapes the
  kernel does not tile; XLA still overlaps the ppermute with the
  per-block matmuls.

Routing: the fused_attention op dispatches through the registry's
``ring_flash_attention`` Pallas route (ops/op_specs.py); direct callers
get the same gate via ``use_flash=None`` (auto).  Reverse-mode autodiff
of the scan gives the backward pass in both modes — the flash kernel's
custom_vjp folds the lse cotangent into its existing backward kernels.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


_NEG = -1e30


def _flash_auto(b, h, s_loc, d, bias, interpret) -> bool:
    """The auto gate for direct callers: flag + kernel tiling rules on
    the LOCAL shard shapes (the op-level path decides via
    pallas_route("fused_attention", ..., kernel="ring_flash_attention")
    and passes use_flash explicitly)."""
    if bias is not None:          # per-source-block bias semantics —
        return False              # einsum path only
    from ..flags import flag
    if not flag("use_flash_attention"):
        return False
    from ..ops.pallas.flash_attention import supported
    return supported((b, h, s_loc, d),
                     backend="tpu" if interpret else None)


def ring_attention(q, k, v, axis_name: str,
                   bias: Optional[jax.Array] = None,
                   causal: bool = False,
                   kv_mask: Optional[jax.Array] = None,
                   use_flash: Optional[bool] = None,
                   interpret: bool = False):
    """Blockwise ring attention.

    Args:
      q, k, v: [B, H, S_local, D] — this device's sequence shard.
      axis_name: the sp mesh axis to ring over.
      bias: optional additive bias for the LOCAL block grid, shape
        broadcastable to [B, H, S_local, S_local] applied per source block
        (rare; prefer kv_mask — forces the einsum inner step).
      causal: apply causal masking using global positions.
      kv_mask: [B, S_local] bool/0-1 — valid-key mask for the local shard;
        travels around the ring with K/V.
      use_flash: inner step on the Pallas flash kernel (None = auto:
        flag + shape gate); the causal/kv masks fold into the kernel's
        additive-bias input, built per rotated block from global
        positions.
      interpret: run the flash kernel in interpret mode (CPU parity
        tests).

    Returns [B, H, S_local, D].
    """
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]
    if use_flash is None:
        use_flash = _flash_auto(b, h, s_loc, d, bias, interpret)

    def _vary(t):
        # mark freshly-created accumulators as varying over the sp axis so
        # the scan carry types match (shard_map VMA tracking)
        return lax.pcast(t, (axis_name,), to="varying")

    m0 = _vary(jnp.full((b, h, s_loc), -jnp.inf, jnp.float32))
    l0 = _vary(jnp.zeros((b, h, s_loc), jnp.float32))
    acc0 = _vary(jnp.zeros((b, h, s_loc, d), jnp.float32))
    mask0 = kv_mask if kv_mask is not None else _vary(
        jnp.ones((b, s_loc), jnp.float32))
    q_pos = my_idx * s_loc + jnp.arange(s_loc)

    def _flash_block(k_blk, v_blk, msk, src):
        """(o_blk, lse) for one rotated K/V shard via the blockwise
        flash kernel — causal/key masks enter as an additive bias built
        from GLOBAL positions (the kernel's own causal flag assumes
        aligned blocks, which ring rotation breaks)."""
        from ..ops.pallas.flash_attention import flash_attention_with_lse
        blk_bias = (1.0 - msk.astype(jnp.float32))[:, None, None, :] * _NEG
        if causal:
            k_pos = src * s_loc + jnp.arange(s_loc)
            cm = q_pos[:, None] >= k_pos[None, :]
            blk_bias = blk_bias + jnp.where(cm, 0.0, _NEG)[None, None]
        return flash_attention_with_lse(q, k_blk, v_blk, blk_bias,
                                        interpret=interpret)

    def step(carry, i):
        k_blk, v_blk, msk, m, l, acc = carry
        src = (my_idx - i) % n                       # owner of this K/V block
        if use_flash:
            o_blk, lse = _flash_block(k_blk, v_blk, msk, src)
            # same online-softmax merge as the einsum path, with the
            # whole block's (o, lse) standing in for its score rows:
            # exp(lse) is the block's softmax mass, o its normalised sum
            m_new = jnp.maximum(m, lse)
            corr = jnp.exp(m - m_new)
            w = jnp.exp(lse - m_new)
            l_new = l * corr + w
            acc_new = acc * corr[..., None] + \
                o_blk.astype(jnp.float32) * w[..., None]
        else:
            # operand-dtype in, f32 accumulate: bf16 q/k ride the MXU at
            # the bf16 rate instead of being upcast (same numerics
            # contract as the flash kernel; identical math for f32)
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                           preferred_element_type=jnp.float32)
            s = s * scale
            if bias is not None:
                s = s + bias.astype(s.dtype)
            neg = jnp.asarray(_NEG, s.dtype)
            s = jnp.where(msk[:, None, None, :].astype(bool), s, neg)
            if causal:
                k_pos = src * s_loc + jnp.arange(s_loc)
                cm = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(cm[None, None], s, neg)
            blk_max = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m, blk_max)
            # renormalise previous accumulators to the new running max
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        msk = lax.ppermute(msk, axis_name, perm)
        return (k_blk, v_blk, msk, m_new, l_new, acc_new), None

    (_, _, _, m, l, acc), _ = lax.scan(
        step, (k, v, mask0, m0, l0, acc0), jnp.arange(n))
    # all-masked rows (fully padded) → zeros, not NaN
    safe_l = jnp.where(l > 0, l, 1.0)
    out = acc / safe_l[..., None]
    return out.astype(q.dtype)
