"""Collective communication ops (ref: operators/collective/c_allreduce_op.h,
c_broadcast_op.h, c_allgather_op.h, c_reducescatter_op.h).

The reference implements these over NCCL comms keyed by ring_id, with
explicit stream-sync ops.  TPU-natively they are XLA collectives over ICI:
``ring_id`` maps to a mesh *axis name* and the ops lower to ``lax.psum`` /
``all_gather`` / ``psum_scatter`` / ``ppermute`` inside the shard_map the
executor wraps around data/model-parallel programs (executor.py).  Outside a
mapped axis (single device) they are identity — same as running the
reference single-rank.  No comm-init or stream ordering ops are needed: XLA
owns topology and scheduling (SURVEY §5 "Distributed communication backend"),
so ``c_comm_init``/``c_gen_nccl_id``/``c_sync_*_stream`` register as no-ops
for script compatibility.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register, x
from .quantize_wire import (CompressionSpec, dequantize_blockwise,
                            pad_to_blocks, quantize_blockwise)


def _ring_axis(ctx, attrs):
    """ring_id → mesh axis name(s); None when not running under shard_map.
    `_axis_name` may be a tuple (reduce over several axes at once — e.g.
    grad allreduce over (dp, sp))."""
    if not ctx.axis_names:
        return None
    ring_id = attrs.get("ring_id", 0)
    # the executor records the ring→axis mapping; default ring 0 = first axis
    mapping = attrs.get("_axis_name")
    if mapping:
        if isinstance(mapping, (tuple, list)):
            axes = tuple(a for a in mapping if a in ctx.axis_names)
            return axes or None
        return mapping if mapping in ctx.axis_names else None
    if isinstance(ring_id, int) and ring_id < len(ctx.axis_names):
        return ctx.axis_names[ring_id]
    return ctx.axis_names[0]


def _allreduce(reducer):
    def impl(ctx, ins, attrs):
        a = x(ins, "X")
        axis = _ring_axis(ctx, attrs)
        if axis is None:
            return {"Out": a}
        return {"Out": reducer(a, axis)}
    return impl


def _compressed(a, axis, compress_dtype):
    """Cast → psum → upcast: the quantized-AllReduce rewrite (EQuARX,
    arXiv:2506.17615, at bf16 granularity).  Halves collective bytes on
    ICI; numerics are bounded by the parity leg in test_grad_comm.py."""
    orig = a.dtype
    return lax.psum(a.astype(compress_dtype), axis).astype(orig)


def _c_allreduce_sum_impl(ctx, ins, attrs):
    a = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    if axis is None:
        return {"Out": a}
    comp = attrs.get("compress_dtype")
    if comp and jnp.issubdtype(a.dtype, jnp.floating):
        return {"Out": _compressed(a, axis, comp)}
    return {"Out": lax.psum(a, axis)}


register("c_allreduce_sum")(_c_allreduce_sum_impl)
register("c_allreduce_max")(_allreduce(lambda a, ax: lax.pmax(a, ax)))
register("c_allreduce_min")(_allreduce(lambda a, ax: lax.pmin(a, ax)))
def _psum_prod(a, ax):
    """Exact product-allreduce (ref semantics: ncclProd) — all_gather the
    shards and multiply.  exp∘psum∘log would break on zeros/negatives and
    rounds integers; a prod-allreduce is rare enough that the n× gather
    bandwidth is irrelevant."""
    gathered = lax.all_gather(a, ax)          # [n, ...] leading axis
    return jnp.prod(gathered, axis=0).astype(a.dtype)


register("c_allreduce_prod")(_allreduce(_psum_prod))


@register("c_fused_allreduce_sum")
def _c_fused_allreduce_sum(ctx, ins, attrs):
    """Bucketed gradient all-reduce (ref: details/fused_all_reduce_op_handle.cc
    + the fuse_all_reduce_op_pass the reference's
    BuildStrategy.fuse_all_reduce_ops enables): the per-leaf grads of one
    bucket are flattened into a single buffer, all-reduced ONCE, and split
    back.  One collective per bucket instead of one per gradient leaf —
    the latency win the reference measures on many small tensors.

    attrs: ``scale`` folds the 1/nranks mean-scale into the flat buffer
    (replacing the per-leaf ``scale`` ops); ``compress_dtype`` optionally
    runs the collective at bf16 (cast → all_reduce → upcast)."""
    xs = list(ins.get("X", []))
    if not xs:
        return {"Out": []}
    axis = _ring_axis(ctx, attrs)
    scale = attrs.get("scale")
    outs = xs
    if scale is not None:
        outs = [a * jnp.asarray(scale, a.dtype) for a in outs]
    if axis is None:
        return {"Out": outs}
    sizes = [int(np.prod(a.shape)) if a.ndim else 1 for a in outs]
    flat = jnp.concatenate([a.reshape(-1) for a in outs])
    comp = attrs.get("compress_dtype")
    if comp and jnp.issubdtype(flat.dtype, jnp.floating):
        flat = _compressed(flat, axis, comp)
    else:
        flat = lax.psum(flat, axis)
    pieces, off = [], 0
    for a, n in zip(outs, sizes):
        pieces.append(flat[off:off + n].reshape(a.shape))
        off += n
    return {"Out": pieces}


def _flat_pad(a, n, align=1):
    """Flatten and zero-pad to a multiple of n·align (n = shard count;
    align > 1 makes every shard a whole number of quantization blocks,
    the quant_reduce_scatter/zero_shard_slice layout contract)."""
    flat = a.reshape(-1)
    pad = (-flat.shape[0]) % (n * align)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


# ---------------------------------------------------------------------------
# quantized wire-compression collectives (EQuARX-style, quantize_wire.py)
# ---------------------------------------------------------------------------


def _quant_key(ctx, spec, ax):
    """Stochastic-rounding key, decorrelated per rank (the trace is SPMD
    so ctx.key alone is identical on every rank)."""
    if not spec.stochastic_rounding:
        return None
    k = ctx.next_key()
    return jax.random.fold_in(k, lax.axis_index(ax))


def _recv_use_kernel(spec, n, shard_blocks, use_kernel):
    """Per-axis re-check of the fused receive-stage kernel gate (the
    op-level pallas_route decided from the FIRST reduce axis; later axes
    of a dp×sp grid may differ in size)."""
    if not use_kernel:
        return False
    from .pallas.quant_kernels import supported
    ok, _ = supported(n, shard_blocks, spec, backend="tpu")
    return ok


def _recv_accumulate(qx, sx, spec, n, shard_blocks, use_kernel):
    """The receive stage: n peer contributions (wire-width payload +
    scales) → the local f32 reduced shard.  One fused VMEM pass when the
    dequant-accumulate kernel is routed, else the jnp multi-pass."""
    if _recv_use_kernel(spec, n, shard_blocks, use_kernel):
        from .pallas.quant_kernels import dequant_accumulate
        return dequant_accumulate(qx.reshape(n * shard_blocks, -1),
                                  sx.reshape(-1), spec, n)
    contrib = dequantize_blockwise(
        qx.reshape(n * shard_blocks, -1), sx.reshape(-1), spec)
    return contrib.reshape(n, -1).sum(axis=0)


def _quant_allreduce_axis(flat, ax, spec, ctx, use_kernel=False):
    """One reduce axis of the two-stage quantized all-reduce: quantize →
    all_to_all shards (wire-width payload + f32 scales) → dequant →
    upcast-accumulate → requantize → all_gather → dequant.  Returns the
    reduced f32 flat array at the input length.  With ``use_kernel``
    (the registry's dequant_accumulate pallas route) the receive stage
    runs as one fused VMEM pass — and for round-to-nearest int8 the
    requantization fuses too, so the local f32 sum never touches HBM."""
    n = lax.axis_size(ax)
    numel = flat.shape[0]
    bs = spec.block_size
    flat = pad_to_blocks(flat, n * bs)
    shard_blocks = flat.shape[0] // (n * bs)
    q, s = quantize_blockwise(flat, spec, key=_quant_key(ctx, spec, ax))
    # stage 1: each rank receives every peer's quantized shard-i and
    # reduces it locally at full precision
    qx = lax.all_to_all(q.reshape(n, shard_blocks, -1), ax,
                        split_axis=0, concat_axis=0)
    sx = lax.all_to_all(s.reshape(n, shard_blocks), ax,
                        split_axis=0, concat_axis=0)
    if (spec.dtype == "int8" and not spec.stochastic_rounding
            and _recv_use_kernel(spec, n, shard_blocks, use_kernel)):
        from .pallas.quant_kernels import dequant_accumulate_requant
        q2, s2 = dequant_accumulate_requant(
            qx.reshape(n * shard_blocks, -1), sx.reshape(-1), spec, n)
    else:
        local = _recv_accumulate(qx, sx, spec, n, shard_blocks,
                                 use_kernel)
        q2, s2 = quantize_blockwise(local, spec,
                                    key=_quant_key(ctx, spec, ax))
    # stage 2: rebuild the full reduced tensor — same bytes on every
    # rank, so local dequant cannot diverge across replicas
    qf = lax.all_gather(q2.reshape(-1), ax, axis=0, tiled=True)
    sf = lax.all_gather(s2, ax, axis=0, tiled=True)
    full = dequantize_blockwise(qf.reshape(n * shard_blocks, -1), sf, spec)
    return full[:numel], sf


def _quant_allreduce_flat(flat, axes, spec, ctx, use_kernel=False):
    """Sequential per-axis quantized all-reduce (dp×sp grids reduce one
    axis at a time; quantization error compounds per stage, the byte
    saving applies on every axis).  Returns (reduced flat f32, last
    stage-2 scale tensor)."""
    scales = None
    for ax in _axes_tuple(axes):
        flat, scales = _quant_allreduce_axis(flat, ax, spec, ctx,
                                             use_kernel=use_kernel)
    return flat, scales


def _quant_route(op_type, ins, attrs, axis):
    """Op-level pallas_route for a quantized collective's receive stage
    (counts the hit/fallback in observability.metrics)."""
    from .registry import pallas_route
    axis_sizes = {ax: lax.axis_size(ax) for ax in _axes_tuple(axis)}
    route, _ = pallas_route(op_type, ins, attrs, axis_sizes=axis_sizes)
    return route is not None


@register("c_quant_allreduce_sum")
def _c_quant_allreduce_sum(ctx, ins, attrs):
    """Per-leaf blockwise-quantized all-reduce (the int8/int4 tier of the
    wire-compression layer; bf16 stays on c_allreduce_sum's cast path).
    attrs: ``quant_spec`` (dict, see CompressionSpec), optional ``scale``
    folding the 1/nranks mean into the payload before quantization."""
    a = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    scale = attrs.get("scale")
    if scale is not None:
        a = a * jnp.asarray(scale, a.dtype)
    if axis is None:
        return {"Out": a}
    spec = CompressionSpec.from_attr(attrs["quant_spec"])
    orig = a.dtype
    use_kernel = _quant_route("c_quant_allreduce_sum", ins, attrs, axis)
    flat, _ = _quant_allreduce_flat(
        a.reshape(-1).astype(jnp.float32), axis, spec, ctx,
        use_kernel=use_kernel)
    return {"Out": flat.reshape(a.shape).astype(orig)}


@register("c_fused_quant_allreduce_sum")
def _c_fused_quant_allreduce_sum(ctx, ins, attrs):
    """Bucketed quantized all-reduce: the bucket's grads flatten into one
    buffer, ride the two-stage quantized collective ONCE, and split back
    — c_fused_allreduce_sum's latency win times the wire-byte win.  The
    per-bucket stage-2 scale tensor is exposed on the ``QScale`` slot
    (the compiler declares a var for it, so the static layer prices the
    scales that ride alongside the payload)."""
    xs = list(ins.get("X", []))
    if not xs:
        return {"Out": []}
    axis = _ring_axis(ctx, attrs)
    scale = attrs.get("scale")
    outs = xs
    if scale is not None:
        outs = [a * jnp.asarray(scale, a.dtype) for a in outs]
    if axis is None:
        return {"Out": outs}
    spec = CompressionSpec.from_attr(attrs["quant_spec"])
    sizes = [int(np.prod(a.shape)) if a.ndim else 1 for a in outs]
    flat = jnp.concatenate([a.reshape(-1) for a in outs])
    orig = flat.dtype
    use_kernel = _quant_route("c_fused_quant_allreduce_sum", ins, attrs,
                              axis)
    red, scales = _quant_allreduce_flat(
        flat.astype(jnp.float32), axis, spec, ctx, use_kernel=use_kernel)
    red = red.astype(orig)
    pieces, off = [], 0
    for a, n in zip(outs, sizes):
        pieces.append(red[off:off + n].reshape(a.shape))
        off += n
    result = {"Out": pieces}
    if scales is not None:
        result["QScale"] = scales
    return result


@register("quant_reduce_scatter")
def _quant_reduce_scatter(ctx, ins, attrs):
    """Quantized grad sync for the ZeRO-1 path: quantize → all_to_all
    (each rank receives every peer's quantized copy of ITS shard, at
    wire width) → dequant → upcast-accumulate.  The output is the
    rank's reduced f32 flat shard — consumed locally by the sharded
    optimizer update, so no stage-2 requantization is needed (the
    all_gather half of ZeRO-1 moves updated PARAMS, not grads, and
    stays full precision).

    attrs: ``quant_spec``, ``scale`` (mean fold), ``_axis_name``; with
    multiple reduce axes the scatter rides the FIRST axis and a psum
    folds the rest (matching zero_reduce_scatter).  The flat pad is
    aligned to n·block_size — zero_shard_slice must be given the same
    ``align`` so param and grad shards cover identical element ranges."""
    g = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    scale = attrs.get("scale")
    if scale is not None:
        g = g * jnp.asarray(scale, g.dtype)
    spec = CompressionSpec.from_attr(attrs["quant_spec"])
    if axis is None:
        return {"Out": g.reshape(-1)}
    axes = _axes_tuple(axis)
    scatter_ax, rest = axes[0], axes[1:]
    n = lax.axis_size(scatter_ax)
    orig = g.dtype
    flat = _flat_pad(g.astype(jnp.float32), n, align=spec.block_size)
    if rest:
        flat = lax.psum(flat, rest)
    shard_blocks = flat.shape[0] // (n * spec.block_size)
    q, s = quantize_blockwise(flat, spec,
                              key=_quant_key(ctx, spec, scatter_ax))
    qx = lax.all_to_all(q.reshape(n, shard_blocks, -1), scatter_ax,
                        split_axis=0, concat_axis=0)
    sx = lax.all_to_all(s.reshape(n, shard_blocks), scatter_ax,
                        split_axis=0, concat_axis=0)
    use_kernel = _quant_route("quant_reduce_scatter", ins, attrs, axes)
    out = _recv_accumulate(qx, sx, spec, n, shard_blocks, use_kernel)
    return {"Out": out.astype(orig)}


def _axes_tuple(axis):
    return axis if isinstance(axis, tuple) else (axis,)


@register("zero_reduce_scatter")
def _zero_reduce_scatter(ctx, ins, attrs):
    """Grad sync half of the ZeRO-1 sharded weight update (ref:
    "Automatic Cross-Replica Sharding of Weight Update", arXiv:2004.13336;
    Fleet's sharding stage-1): instead of all-reducing the full gradient,
    each replica receives only its 1/n flat shard via reduce-scatter —
    same bytes on the wire as one all-reduce direction, and the optimizer
    then updates only that shard.  ``scale`` folds the mean-scale;
    ``compress_dtype`` optionally runs the scatter at bf16.

    With multiple reduce axes (dp×sp grids) the scatter rides the FIRST
    axis and a psum folds the rest."""
    g = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    scale = attrs.get("scale")
    if scale is not None:
        g = g * jnp.asarray(scale, g.dtype)
    if axis is None:
        return {"Out": g.reshape(-1)}
    axes = _axes_tuple(axis)
    scatter_ax, rest = axes[0], axes[1:]
    n = lax.axis_size(scatter_ax)
    # ``align`` mirrors zero_shard_slice: the sharded optimizer pads
    # flat shards to the fused-Adam kernel's 128-lane layout, so grad
    # and param shards must cover identical element ranges
    flat = _flat_pad(g, n, align=attrs.get("align", 1))
    comp = attrs.get("compress_dtype")
    orig = flat.dtype
    if comp and jnp.issubdtype(orig, jnp.floating):
        flat = flat.astype(comp)
    if rest:
        flat = lax.psum(flat, rest)
    out = lax.psum_scatter(flat, scatter_ax, scatter_dimension=0, tiled=True)
    return {"Out": out.astype(orig)}


@register("zero_shard_slice")
def _zero_shard_slice(ctx, ins, attrs):
    """This replica's flat 1/n shard of a replicated tensor (the param
    slice the sharded update owns).  Local slice — no communication."""
    a = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    if axis is None:
        return {"Out": a.reshape(-1)}
    ax = _axes_tuple(axis)[0]
    n = lax.axis_size(ax)
    # ``align`` matches the flat pad of a quantized grad scatter so the
    # param shard covers the same element range as the grad shard
    flat = _flat_pad(a, n, align=attrs.get("align", 1))
    shard = flat.shape[0] // n
    return {"Out": lax.dynamic_slice_in_dim(
        flat, lax.axis_index(ax) * shard, shard)}


@register("zero_all_gather")
def _zero_all_gather(ctx, ins, attrs):
    """Rebuild the full replicated tensor from per-replica updated shards
    (the all-gather half of the ZeRO-1 rewrite).  attrs carry the original
    ``numel``/``shape`` so the flat pad is dropped."""
    sh = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    shape = tuple(attrs["shape"])
    numel = int(attrs["numel"])
    if axis is None:
        full = sh
    else:
        full = lax.all_gather(sh, _axes_tuple(axis)[0], axis=0, tiled=True)
    return {"Out": full[:numel].reshape(shape)}


@register("fsdp_all_gather")
def _fsdp_all_gather(ctx, ins, attrs):
    """ZeRO-3 on-demand parameter gather (framework/fsdp.py): the
    resident param is the 1/n shard along ``gather_dim`` over the fsdp
    axis; this op rebuilds the full tensor right before its first
    forward use, and the gathered temp dies at its last use (XLA frees
    at last-use — the discard-after-last-use half of ZeRO-3 needs no
    op).  Its autodiff TRANSPOSE is ``psum_scatter`` over the same axis,
    so the param's gradient arrives already reduce-scattered to the
    shard — ZeRO-3's grad sync over fsdp costs zero extra ops.

    Off-mesh (axis absent — a single-device parity run) it is identity,
    like every collective here."""
    a = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    if axis is None:
        return {"Out": a}
    dim = attrs.get("gather_dim", 0)
    if dim < 0:
        dim += a.ndim
    return {"Out": lax.all_gather(a, _axes_tuple(axis)[0], axis=dim,
                                  tiled=True)}


@register("c_broadcast")
def _c_broadcast(ctx, ins, attrs):
    a = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    if axis is None:
        return {"Out": a}
    root = attrs.get("root", 0)
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root, a, jnp.zeros_like(a))
    return {"Out": lax.psum(masked, axis)}


@register("c_allgather")
def _c_allgather(ctx, ins, attrs):
    a = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    if axis is None:
        return {"Out": a}
    dim = attrs.get("gather_dim", 0)
    if dim < 0:
        dim += a.ndim
    return {"Out": lax.all_gather(a, axis, axis=dim, tiled=True)}


@register("c_reducescatter")
def _c_reducescatter(ctx, ins, attrs):
    a = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    if axis is None:
        return {"Out": a}
    return {"Out": lax.psum_scatter(a, axis, scatter_dimension=0, tiled=True)}


@register("c_concat")
def _c_concat(ctx, ins, attrs):
    return _c_allgather(ctx, ins, attrs)


@register("c_split")
def _c_split(ctx, ins, attrs):
    a = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    if axis is None:
        return {"Out": a}
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    piece = a.shape[0] // n
    return {"Out": lax.dynamic_slice_in_dim(a, idx * piece, piece, axis=0)}


@register("alltoall")
def _alltoall(ctx, ins, attrs):
    a = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    if axis is None:
        return {"Out": a}
    n = lax.axis_size(axis)
    parts = a.reshape((n, a.shape[0] // n) + a.shape[1:])
    return {"Out": lax.all_to_all(parts, axis, split_axis=0, concat_axis=0)
            .reshape(a.shape)}


@register("c_embedding")
def _c_embedding(ctx, ins, attrs):
    """Vocab-sharded embedding lookup (model parallel)."""
    w, ids = x(ins, "W"), x(ins, "Ids")
    axis = _ring_axis(ctx, attrs)
    if "per_shard_rows" in attrs and axis is not None:
        start = lax.axis_index(axis) * attrs["per_shard_rows"]
    else:
        start = attrs.get("start_index", 0)
    local = ids.astype(jnp.int32) - start
    valid = (local >= 0) & (local < w.shape[0])
    out = jnp.take(w, jnp.clip(local, 0, w.shape[0] - 1), axis=0)
    out = jnp.where(valid[..., None], out, 0.0)
    if axis is not None:
        out = lax.psum(out, axis)
    return {"Out": out}


@register("c_identity")
def _c_identity(ctx, ins, attrs):
    return {"Out": x(ins, "X")}


@register("c_sync_calc_stream")
@register("c_sync_comm_stream")
def _c_sync_stream(ctx, ins, attrs):
    # XLA schedules collectives; stream ordering ops are identity
    return {"Out": x(ins, "X")}


def _noop(ctx, ins, attrs):
    return {}


register("c_comm_init")(_noop)
register("c_comm_init_all")(_noop)
register("c_gen_nccl_id")(_noop)
register("barrier")(_noop)


@register("pipe_stage_boundary")
def _pipe_stage_boundary(ctx, ins, attrs):
    """Stage-cut marker (framework/pipe.apply_pipeline): the live
    tensors crossing one pipeline cut.  As an OP it is the identity —
    the actual stage→stage+1 ``ppermute`` hop happens inside the
    executor's scheduled 1F1B scan, which partitions the op list AT
    these markers; running the ops sequentially (pipe = 1, or a mesh
    without the pipe axis) must be a no-op.  The op exists so the
    static layer sees the boundary: its ``wire()`` spec prices the
    per-step ppermute traffic (payload × 2 — forward boundary plus the
    backward cotangent hop) and the census reports per-cut bytes."""
    return {"Out": list(ins.get("X", []))}


@register("collective_permute")
def _collective_permute(ctx, ins, attrs):
    """Ring shift (used by pipeline/sequence parallelism)."""
    a = x(ins, "X")
    axis = _ring_axis(ctx, attrs)
    if axis is None:
        return {"Out": a}
    n = lax.axis_size(axis)
    shift = attrs.get("shift", 1)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return {"Out": lax.ppermute(a, axis, perm)}


@register("local_sgd_sync")
def _local_sgd_sync(ctx, ins, attrs):
    """k-periodic parameter averaging for LocalSGD (ref:
    transpiler/collective.py:270 LocalSGD, localsgd_optimizer.py).

    All params are averaged over the dp axis inside one ``lax.cond`` gated
    on the (replicated) step counter, so the AllReduce only executes on
    sync steps — the communication saving that is LocalSGD's whole point.
    Safe under shard_map because every device holds the same step value and
    takes the same branch."""
    step = x(ins, "Step").reshape(()).astype(jnp.float32)
    params = tuple(ins.get("Params", []))
    axis = _ring_axis(ctx, attrs)
    if axis is None and ctx.axis_names:
        # the configured axis name is not in this mesh (e.g. the mesh
        # calls its data axis "data", not "dp") — replicas would silently
        # never synchronize.  On a single-axis mesh that axis must be the
        # data axis, so fall back to it (matching the grad-allreduce
        # batch-axis fallback, compiler.py with_data_parallel).  On a
        # multi-axis mesh guessing could average tensor-parallel SHARDS
        # (different slices, not replicas) and destroy the model — refuse
        # loudly instead.
        if len(ctx.axis_names) == 1:
            axis = ctx.axis_names[0]
        else:
            raise ValueError(
                f"local_sgd_sync: configured axis "
                f"{attrs.get('_axis_name')!r} is not in the mesh axes "
                f"{ctx.axis_names}; pass axis_name=<your data axis> to "
                f"LocalSGDOptimizer")
    if axis is None or not params:
        return {"Out": list(params)}
    k = float(attrs.get("k_steps", 1))
    begin = float(attrs.get("begin_step", 1))
    do_sync = jnp.logical_and(jnp.mod(step, k) == 0.0, step >= begin)
    outs = lax.cond(
        do_sync,
        lambda ps: tuple(lax.pmean(p, axis) for p in ps),
        lambda ps: ps,
        params)
    return {"Out": list(outs)}


# ---------------------------------------------------------------------------
# trace-time collective telemetry (observability tentpole)
# ---------------------------------------------------------------------------

import contextlib as _contextlib


def maybe_trace_collective(op, ins, ctx):
    """Span for one collective op's lowering, or a null context for
    non-collectives.  Called from the executor's trace loop ONLY while
    tracing is enabled, so the cost is per-compile, never per-step: the
    resulting ``collective::<kind>`` spans put every collective dispatch
    on the merged timeline (correlated to the compiling step's id) with
    its mesh axis and — when the op_spec ``wire`` channel prices it —
    logical/wire payload bytes, mirrored into labeled metrics counters."""
    from .registry import OP_SPECS, VarSig
    spec = OP_SPECS.get(op.type)
    if spec is None or not spec.collective:
        return _contextlib.nullcontext()
    from ..observability import metrics
    from ..observability.tracing import Span
    attrs = {"kind": op.type,
             "axis": str(op.attrs.get("_axis_name") or
                         op.attrs.get("ring_id", 0))}
    # overlap-aware schedule correlation: ready-order buckets stamp
    # their index/rank so tools/timeline.py renders the interleaving
    # (which bucket fired where, in ready order) on the merged trace
    if "_bucket_index" in op.attrs:
        attrs["bucket_index"] = int(op.attrs["_bucket_index"])
    if "_ready_rank" in op.attrs:
        attrs["ready_rank"] = int(op.attrs["_ready_rank"])
    if "_overlap" in op.attrs:
        attrs["overlap"] = bool(op.attrs["_overlap"])
    wire_fn = getattr(spec, "wire", None)
    if wire_fn is not None:
        try:
            sigs = {slot: [VarSig(tuple(v.shape), str(v.dtype))
                           if hasattr(v, "shape") else None
                           for v in vals]
                    for slot, vals in ins.items()}
            axis_sizes = {}
            if ctx.mesh is not None:
                axis_sizes = {str(k): int(v)
                              for k, v in dict(ctx.mesh.shape).items()}
            priced = wire_fn(sigs, op.attrs, axis_sizes)
        except Exception:       # pricing must not break tracing
            priced = None
        if priced is not None:
            logical, wire = priced
            attrs["logical_bytes"] = int(logical)
            attrs["wire_bytes"] = int(wire)
            metrics.counter("collective_traced_wire_bytes",
                            kind=op.type).add(int(wire))
    metrics.counter("collective_traced", kind=op.type).add()
    return Span("collective::" + op.type, attrs)
