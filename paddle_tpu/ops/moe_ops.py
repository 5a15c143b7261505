"""Mixture-of-Experts ops — expert-parallel FFN (SURVEY §2.3 "Expert
parallel / MoE"; the reference has no MoE — this supersedes it with the
GShard/Switch formulation, which is the TPU-native design: routing is
expressed as dense one-hot einsums that land on the MXU, and expert
exchange is a single ``lax.all_to_all`` over the expert mesh axis).

Layout contract (enforced by parallel/moe.py):

- gate weight ``[M, E]`` is replicated;
- expert weights ``[E, M, H]`` / ``[E, H, M]`` carry
  ``dist_attr = (ep_axis, None, None)`` so shard_map hands each device its
  ``E/ep`` local experts;
- the expert axis is the BATCH axis (every device contributes tokens and
  owns experts — the GShard layout), so expert-weight grads arrive fully
  summed through the transposed all_to_all and must NOT be allreduced
  again (compiler._insert_grad_allreduce skips axes present in a param's
  dist_attr, but still applies the 1/n mean-loss scale).

Tokens are routed within fixed-size GROUPS (the GShard G dim): the
dispatch/combine one-hots are ``[G, S_g, E, C]`` with capacity
``C ∝ S_g/E``, so routing memory is linear in token count
(``N·cf·k·S_g``) instead of the quadratic ``N·cf·k·N`` a flat layout
would cost.  Routing math per group (top-k with capacity, GShard paper
§3.2 semantics, re-derived — no reference analog):

    gates   = softmax(x @ Wg)                         [G, S, E]
    k picks = iterated argmax with chosen column masked out
    pos     = running per-(group, expert) cumsum → slot within capacity
    disp    = Σ_k  keep_k ⊗ one_hot(pos_k, C)         [G, S, E, C]
    combine = Σ_k  gate_k · that                      [G, S, E, C]
    xe      = einsum('gsec,gsm->egcm', disp, x)  (dispatch — MXU)
    ye      = W2·act(W1·xe)  per expert          (batched matmul — MXU)
    out     = einsum('gsec,egcm->gsm', combine, ye)   (combine — MXU)

Tokens overflowing an expert's per-group capacity are dropped (their
combine weight is zero → they pass through the residual connection of
the surrounding block, Switch-Transformer semantics).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, x

_ACTS = {
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
    None: lambda a: a,
}


def _group_size(n: int, target: int = 256) -> int:
    """Largest divisor of n that is ≤ target (GShard group dim).  Keeps
    the [G, S_g, E, C] routing tensors ~n·cf·k·S_g elements."""
    for d in range(min(n, target), 0, -1):
        if n % d == 0:
            return d
    return 1


def _route(gates, top_k, capacity):
    """Top-k routing with per-(group, expert) capacity.

    gates [G, S, E] f32 → (dispatch [G, S, E, C], combine [G, S, E, C],
    me [E], ce [E]) where me/ce feed the load-balance aux loss."""
    g, s, e = gates.shape
    remaining = gates
    masks, gvals = [], []
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                # [G, S]
        m = jax.nn.one_hot(idx, e, dtype=gates.dtype)        # [G, S, E]
        gvals.append(jnp.sum(remaining * m, axis=-1))        # [G, S]
        remaining = remaining * (1.0 - m)
        masks.append(m)

    # slot position of each token within its (group, expert): running
    # cumsum over the group's tokens, earlier-k choices take priority
    # (GShard §3.2)
    dispatch = jnp.zeros((g, s, e, capacity), gates.dtype)
    combine = jnp.zeros((g, s, e, capacity), gates.dtype)
    offset = jnp.zeros((g, 1, e), gates.dtype)
    for m, gv in zip(masks, gvals):
        pos = jnp.cumsum(m, axis=1) - m + offset             # [G, S, E]
        offset = offset + jnp.sum(m, axis=1, keepdims=True)
        keep = m * (pos < capacity)                          # [G, S, E]
        slot = jax.nn.one_hot(
            jnp.sum(pos * m, axis=-1).astype(jnp.int32), capacity,
            dtype=gates.dtype)                               # [G, S, C]
        hot = keep[..., None] * slot[:, :, None, :]          # [G, S, E, C]
        dispatch = dispatch + lax.stop_gradient(hot)
        combine = combine + gv[..., None, None] * lax.stop_gradient(hot)

    me = jnp.mean(gates, axis=(0, 1))                        # softmax mass
    ce = jnp.mean(masks[0], axis=(0, 1))                     # top-1 traffic
    return dispatch, combine, me, ce


def moe_ffn_fn(xf, gate_w, w1, w2, b1=None, b2=None, *, top_k=2,
               capacity_factor=1.25, act="gelu", ep_axis=None, ep_size=1,
               group_size=0):
    """Functional MoE FFN on flattened tokens xf [N, M].

    w1/w2 hold the LOCAL expert shard [E_local, ...]; global expert count
    is E_local * ep_size.  Returns (out [N, M], aux_loss scalar)."""
    n, m = xf.shape
    e_local = w1.shape[0]
    e = e_local * ep_size
    sg = int(group_size) or _group_size(n)
    if n % sg:
        raise ValueError(f"group_size {sg} does not divide token count {n}")
    g = n // sg
    capacity = max(1, int(math.ceil(capacity_factor * top_k * sg / e)))

    xg = xf.reshape(g, sg, m)
    gates = jax.nn.softmax(
        jnp.einsum("gsm,me->gse", xg.astype(jnp.float32),
                   gate_w.astype(jnp.float32)), axis=-1)
    dispatch, combine, me, ce = _route(gates, top_k, capacity)
    aux = e * jnp.sum(me * ce)

    xe = jnp.einsum("gsec,gsm->egcm", dispatch.astype(xf.dtype), xg)
    if ep_axis is not None:
        # route each expert block to its owner; received leading dim
        # indexes the SOURCE shard
        xe = xe.reshape(ep_size, e_local, g, capacity, m)
        xe = lax.all_to_all(xe, ep_axis, split_axis=0, concat_axis=0,
                            tiled=False)
        xe = xe.transpose(1, 0, 2, 3, 4)          # [E_local, ep, G, C, M]
        xe = xe.reshape(e_local, ep_size * g * capacity, m)
    else:
        xe = xe.reshape(e, g * capacity, m)
    # expert FFN GEMMs are batched matmuls — route through the dtype-
    # aware path so bf16 MoE keeps bf16 operands in fwd AND bwd dots
    from .math_ops import _matmul_any
    h = _matmul_any(xe, w1)                        # esm,emh->esh
    if b1 is not None:
        h = h + b1[:, None, :]
    h = _ACTS[act](h)
    ye = _matmul_any(h, w2)                        # esh,ehm->esm
    if b2 is not None:
        ye = ye + b2[:, None, :]
    if ep_axis is not None:
        # per-source blocks back out front, exchange, leading dim becomes
        # the expert-OWNER shard → global expert order
        ye = ye.reshape(e_local, ep_size, g, capacity, m)
        ye = ye.transpose(1, 0, 2, 3, 4)
        ye = lax.all_to_all(ye, ep_axis, split_axis=0, concat_axis=0,
                            tiled=False)
        ye = ye.reshape(e, g, capacity, m)
    else:
        ye = ye.reshape(e, g, capacity, m)
    out = jnp.einsum("gsec,egcm->gsm", combine.astype(ye.dtype), ye)
    return out.reshape(n, m).astype(xf.dtype), aux.astype(jnp.float32)


def _moe_static_dims(x_shape, num_experts, top_k, capacity_factor,
                     group_size):
    """Static (N, G, S_g, C) for declared shapes / infer specs; -1 where
    the token count is unknown (dynamic leading dims).  Must mirror the
    runtime arithmetic in ``_moe_dispatch`` exactly — verify_program's
    ``moe-axis-capacity-mismatch`` diagnostic cross-checks the two."""
    lead = [int(d) for d in x_shape[:-1]]
    if lead and all(d > 0 for d in lead):
        n = 1
        for d in lead:
            n *= d
    else:
        n = -1
    e = int(num_experts)
    if n > 0:
        sg = int(group_size) or _group_size(n)
        g = n // sg if n % sg == 0 else -1
    else:
        sg = int(group_size) or -1
        g = -1
    if sg > 0:
        c = max(1, int(math.ceil(
            float(capacity_factor) * int(top_k) * sg / e)))
    else:
        c = -1
    return n, g, sg, c


# ---------------------------------------------------------------------------
# decomposed MoE pipeline: dispatch → c_expert_alltoall → expert FFN →
# c_expert_alltoall → combine.  Same math as the fused moe_ffn (bitwise,
# modulo reshape grouping) but the expert exchange is its own registry op,
# so the wire model prices it, spec_audit reconciles it against the
# StableHLO census, and the CompressionSpec quant ladder applies to it.
# ---------------------------------------------------------------------------


@register("moe_dispatch")
def _moe_dispatch(ctx, ins, attrs):
    """Route tokens into per-expert blocks.  Xe is laid out dest-major
    ([E_global, G·C, M]) so a leading-dim reshape is exactly the per-
    destination split the expert all_to_all needs."""
    a = x(ins, "X")
    gate_w = x(ins, "GateW")
    e = int(attrs["num_experts"])
    top_k = int(attrs.get("top_k", 2))
    cf = float(attrs.get("capacity_factor", 1.25))
    m = a.shape[-1]
    xf = a.reshape(-1, m)
    n = xf.shape[0]
    sg = int(attrs.get("group_size", 0)) or _group_size(n)
    if n % sg:
        raise ValueError(
            f"moe_dispatch: group_size {sg} does not divide token "
            f"count {n}")
    g = n // sg
    capacity = max(1, int(math.ceil(cf * top_k * sg / e)))
    xg = xf.reshape(g, sg, m)
    gates = jax.nn.softmax(
        jnp.einsum("gsm,me->gse", xg.astype(jnp.float32),
                   gate_w.astype(jnp.float32)), axis=-1)
    dispatch, combine, me, ce = _route(gates, top_k, capacity)
    aux = e * jnp.sum(me * ce)
    xe = jnp.einsum("gsec,gsm->egcm", dispatch.astype(a.dtype), xg)
    return {"Xe": xe.reshape(e, g * capacity, m),
            "Combine": combine.astype(jnp.float32),
            "AuxLoss": aux.astype(jnp.float32)}


def _expert_exchange(arr, axis, n, direction):
    """The expert all_to_all on a dest-major [E, B, M] block tensor.

    dispatch: [E_global, b, m] → [E/n, n·b, m] (each device keeps its
    E/n experts, receives every peer's token block for them); combine is
    the exact inverse.  Flattened-equivalent to the fused moe_ffn_fn
    sequences, so dispatch∘combine == identity — which is also why the
    VJP of one direction is the other direction applied to the
    cotangent."""
    if direction == "combine":
        e_l, bb, m = arr.shape
        arr = arr.reshape(e_l, n, bb // n, m).transpose(1, 0, 2, 3)
        arr = lax.all_to_all(arr, axis, split_axis=0, concat_axis=0,
                             tiled=False)
        return arr.reshape(n * e_l, bb // n, m)
    e, b, m = arr.shape
    arr = arr.reshape(n, e // n, b, m)
    arr = lax.all_to_all(arr, axis, split_axis=0, concat_axis=0,
                         tiled=False)
    return arr.transpose(1, 0, 2, 3).reshape(e // n, n * b, m)


def _quant_exchange_impl(arr, axis, n, direction, spec_key):
    """Blockwise-quantized expert exchange (EQuARX applied to a2a): each
    per-destination slice is padded to whole quantization blocks,
    quantized (payload + f32 scales), both ride ONE all_to_all each, and
    the receive side dequantizes (one contribution a slice, nothing to
    accumulate: the dequant-accumulate kernel takes two peers or more)."""
    from .quantize_wire import (CompressionSpec, dequantize_blockwise,
                                quantize_blockwise)
    spec = CompressionSpec(dtype=spec_key[0], block_size=spec_key[1])
    orig = arr.dtype
    if direction == "combine":
        e_l, bb, m = arr.shape
        parts = arr.reshape(e_l, n, bb // n, m).transpose(1, 0, 2, 3)
        recv_shape = (n, e_l, bb // n, m)
    else:
        e, b, m = arr.shape
        parts = arr.reshape(n, e // n, b, m)
        recv_shape = (n, e // n, b, m)
    parts = parts.reshape(n, -1)
    slice_numel = parts.shape[1]
    bs = spec.block_size
    k = -(-slice_numel // bs)                 # blocks per dest slice
    pad = k * bs - slice_numel
    pf = parts.astype(jnp.float32)
    if pad:
        # pad PER SLICE (not the flat whole): every destination's payload
        # must stay a whole number of blocks or the post-a2a rows would
        # straddle block boundaries
        pf = jnp.pad(pf, ((0, 0), (0, pad)))
    q, s = quantize_blockwise(pf.reshape(-1), spec)
    qx = lax.all_to_all(q.reshape(n, k, -1), axis, split_axis=0,
                        concat_axis=0)
    sx = lax.all_to_all(s.reshape(n, k), axis, split_axis=0,
                        concat_axis=0)
    full = dequantize_blockwise(qx.reshape(n * k, -1), sx.reshape(-1),
                                spec).reshape(n, k * bs)
    if pad:
        full = full[:, :slice_numel]
    recv = full.reshape(recv_shape)
    if direction == "combine":
        out = recv.reshape(n * recv_shape[1], recv_shape[2], recv.shape[3])
    else:
        out = recv.transpose(1, 0, 2, 3).reshape(
            recv_shape[1], n * recv_shape[2], recv.shape[3])
    return out.astype(orig)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _quant_expert_exchange(arr, axis, n, direction, spec_key):
    """custom_vjp wrapper: the exchange is a cross-device permutation, so
    its VJP is the opposite-direction exchange of the cotangent — also
    quantized, which is what makes the BACKWARD a2a ride the wire tier
    too.  Rounding is deterministic here (no stochastic-rounding key
    threading through custom_vjp); spec_key = (dtype, block_size)."""
    return _quant_exchange_impl(arr, axis, n, direction, spec_key)


def _quant_exchange_fwd(arr, axis, n, direction, spec_key):
    return _quant_expert_exchange(arr, axis, n, direction, spec_key), None


def _quant_exchange_bwd(axis, n, direction, spec_key, _res, ct):
    back = "combine" if direction == "dispatch" else "dispatch"
    return (_quant_expert_exchange(ct, axis, n, back, spec_key),)


_quant_expert_exchange.defvjp(_quant_exchange_fwd, _quant_exchange_bwd)


@register("c_expert_alltoall")
def _c_expert_alltoall(ctx, ins, attrs):
    """The expert exchange as a first-class collective op.  Identity off
    mesh / when the axis is absent (single-device run of an ep-stamped
    program).  ``direction`` ∈ {dispatch, combine}; an optional
    ``quant_spec`` attr rides the CompressionSpec ladder (bf16 = cast
    path, int8/int4 = blockwise payload + scales)."""
    a = x(ins, "X")
    ep_axis = attrs.get("_axis_name")
    if not ep_axis or not ctx.axis_names or ep_axis not in ctx.axis_names:
        return {"Out": a}
    n = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))[ep_axis]
    if n <= 1:
        return {"Out": a}
    direction = attrs.get("direction", "dispatch")
    from .quantize_wire import quant_spec_of
    spec = quant_spec_of(attrs)
    if spec is not None and jnp.issubdtype(a.dtype, jnp.floating):
        if spec.dtype == "bfloat16":
            out = _expert_exchange(a.astype(jnp.bfloat16), ep_axis, n,
                                   direction)
            return {"Out": out.astype(a.dtype)}
        out = _quant_expert_exchange(a, ep_axis, n, direction,
                                     (spec.dtype, spec.block_size))
        return {"Out": out}
    return {"Out": _expert_exchange(a, ep_axis, n, direction)}


@register("moe_expert_ffn")
def _moe_expert_ffn(ctx, ins, attrs):
    """Per-expert FFN on dispatched blocks [E_local, B, M] — batched
    matmuls through the dtype-aware path (bf16 operands stay bf16 in fwd
    AND bwd dots)."""
    xe = x(ins, "Xe")
    w1, w2 = x(ins, "W1"), x(ins, "W2")
    b1, b2 = x(ins, "B1"), x(ins, "B2")
    from .math_ops import _matmul_any
    h = _matmul_any(xe, w1)
    if b1 is not None:
        h = h + b1[:, None, :]
    h = _ACTS[attrs.get("act", "gelu")](h)
    ye = _matmul_any(h, w2)
    if b2 is not None:
        ye = ye + b2[:, None, :]
    return {"Out": ye}


@register("moe_combine")
def _moe_combine(ctx, ins, attrs):
    """Weighted un-route of expert outputs back to token order.  X is a
    shape/dtype reference only (no data copied) so the declared output
    matches the block input exactly."""
    ye = x(ins, "Ye")
    comb = x(ins, "Combine")
    ref = x(ins, "X")
    g, s, e, c = comb.shape
    ye = ye.reshape(e, g, c, ye.shape[-1])
    out = jnp.einsum("gsec,egcm->gsm", comb.astype(ye.dtype), ye)
    return {"Out": out.reshape(ref.shape).astype(ref.dtype)}


@register("moe_ffn")
def _moe_ffn(ctx, ins, attrs):
    a = x(ins, "X")
    gate_w = x(ins, "GateW")
    w1, w2 = x(ins, "W1"), x(ins, "W2")
    b1, b2 = x(ins, "B1"), x(ins, "B2")
    ep_axis = attrs.get("_axis_name")
    ep_size = 1
    if ep_axis and ctx.mesh is not None and ep_axis in ctx.axis_names:
        ep_size = dict(zip(ctx.mesh.axis_names,
                           ctx.mesh.devices.shape))[ep_axis]
    else:
        ep_axis = None
    shape = a.shape
    xf = a.reshape(-1, shape[-1])
    out, aux = moe_ffn_fn(
        xf, gate_w, w1, w2, b1, b2,
        top_k=int(attrs.get("top_k", 2)),
        capacity_factor=float(attrs.get("capacity_factor", 1.25)),
        act=attrs.get("act", "gelu"),
        ep_axis=ep_axis, ep_size=ep_size,
        group_size=int(attrs.get("group_size", 0)))
    return {"Out": out.reshape(shape), "AuxLoss": aux}
