"""Static shape/dtype specs for the op registry — the InferShape library.

The reference implements per-op ``InferShape``/``InferVarType`` in C++
(ref: operators/*_op.cc InferShape methods, framework/shape_inference.h);
each spec here is the trace-free Python analog, registered through the
``op_spec`` channel next to the op's JAX impl and consumed by the static
verifier (framework/analysis.py).

Conventions:

* ``ins`` maps input slot → list of :class:`VarSig`; a dim of ``-1`` is
  unknown (batch), ``shape is None`` is fully unknown.
* An infer function returns ``{slot: [VarSig, ...]}`` for the output
  slots it has an opinion about (others are left to declared metadata),
  or ``None`` for "no opinion".
* Invalid input combinations raise :class:`SpecMismatch` with
  ``kind="shape"`` or ``kind="dtype"`` — the verifier turns that into an
  ``InvalidArgumentError`` diagnostic anchored at the op's creation site.

Long-tail ops register with ``infer=None``: they count as *specced* for
coverage purposes (the op is known to the static layer) without claiming
shape knowledge — the warn-don't-fail path for exotic ops.
"""

from __future__ import annotations

import functools
from typing import List, Optional

from .registry import (PallasLowering, SpecMismatch, VarSig, _shape_of,
                       op_spec)

_INT_DTYPES = ("int8", "uint8", "int16", "int32", "int64", "bool")


def _sig(ins, slot, i=0) -> Optional[VarSig]:
    v = ins.get(slot)
    if not v or i >= len(v):
        return None
    return v[i]


def _is_int(dtype: str) -> bool:
    return dtype in _INT_DTYPES


def _known(shape) -> bool:
    return shape is not None and all(int(d) >= 0 for d in shape)


def _numel(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _dim_join(a: int, b: int) -> Optional[int]:
    """Broadcast-join two dims; None signals a conflict."""
    a, b = int(a), int(b)
    if a == b:
        return a
    if a == 1:
        return b
    if b == 1:
        return a
    if a == -1 or b == -1:
        return -1
    return None


def broadcast_shapes(sx, sy, axis=-1, op_name=""):
    """Paddle elementwise broadcast: Y aligns into X at ``axis`` (trailing
    when -1).  Returns the output shape or raises SpecMismatch."""
    if sx is None or sy is None:
        return None
    big, small = (sx, sy) if len(sx) >= len(sy) else (sy, sx)
    if axis == -1 or len(sx) == len(sy):
        offset = len(big) - len(small)
    else:
        offset = int(axis)
        if offset < 0 or offset + len(small) > len(big):
            raise SpecMismatch(
                f"{op_name}: axis={axis} places Y{list(sy)} outside "
                f"X{list(sx)}", kind="shape")
    out = [int(d) for d in big]
    for i, d in enumerate(small):
        j = _dim_join(out[offset + i], d)
        if j is None:
            raise SpecMismatch(
                f"{op_name}: operands X{list(sx)} and Y{list(sy)} are not "
                f"broadcast-compatible at dim {offset + i} "
                f"({out[offset + i]} vs {int(d)})", kind="shape")
        out[offset + i] = j
    return tuple(out)


def _require_same_dtype(x, y, op_name):
    if x is not None and y is not None and x.dtype != y.dtype:
        raise SpecMismatch(
            f"{op_name}: operand dtypes differ — X is {x.dtype}, Y is "
            f"{y.dtype} (insert an explicit cast)", kind="dtype")


# ---------------------------------------------------------------------------
# generic infer builders
# ---------------------------------------------------------------------------


def same_as_input(slot="X", out_slot="Out"):
    """Unary shape/dtype-preserving op."""
    def infer(ins, attrs):
        v = _sig(ins, slot)
        if v is None:
            return None
        return {out_slot: [VarSig(v.shape, v.dtype)]}
    return infer


def elementwise(out_dtype=None, check_dtype=True):
    """Binary broadcast op; ``out_dtype`` overrides (comparison → bool)."""
    def infer(ins, attrs):
        xv, yv = _sig(ins, "X"), _sig(ins, "Y")
        if xv is None or yv is None:
            return None
        name = attrs.get("_op_type", "elementwise")
        if check_dtype and out_dtype is None:
            _require_same_dtype(xv, yv, name)
        shape = broadcast_shapes(xv.shape, yv.shape,
                                 attrs.get("axis", -1), name)
        return {"Out": [VarSig(shape, out_dtype or xv.dtype)]}
    return infer


def from_shape_attr(dtype_default="float32"):
    """Ops whose output shape/dtype come from attrs (fill_constant,
    random initializer ops)."""
    def infer(ins, attrs):
        shape = attrs.get("shape")
        if shape is None:
            return None
        dtype = attrs.get("dtype", dtype_default)
        try:
            from ..framework.core import convert_dtype
            dtype = convert_dtype(dtype)
        except Exception:
            dtype = dtype_default
        return {"Out": [VarSig(tuple(int(s) for s in shape), dtype)]}
    return infer


# ---------------------------------------------------------------------------
# math ops
# ---------------------------------------------------------------------------


def _infer_mul(ins, attrs):
    xv, yv = _sig(ins, "X"), _sig(ins, "Y")
    if xv is None or yv is None or xv.shape is None or yv.shape is None:
        return None
    _require_same_dtype(xv, yv, "mul")
    xn = int(attrs.get("x_num_col_dims", 1))
    yn = int(attrs.get("y_num_col_dims", 1))
    sx, sy = xv.shape, yv.shape
    if len(sx) < xn + 1 or len(sy) < yn + 1:
        raise SpecMismatch(
            f"mul: rank too small for x_num_col_dims={xn}/"
            f"y_num_col_dims={yn} — X{list(sx)}, Y{list(sy)}", kind="shape")
    k_x = sx[xn:]
    k_y = sy[:yn]
    if _known(k_x) and _known(k_y) and _numel(k_x) != _numel(k_y):
        raise SpecMismatch(
            f"mul: inner dims disagree — X{list(sx)} flattens to "
            f"[*, {_numel(k_x)}] but Y{list(sy)} flattens to "
            f"[{_numel(k_y)}, *]", kind="shape")
    out = tuple(sx[:xn]) + tuple(sy[yn:])
    return {"Out": [VarSig(out, xv.dtype)]}


def _infer_matmul(ins, attrs):
    xv, yv = _sig(ins, "X"), _sig(ins, "Y")
    if xv is None or yv is None or xv.shape is None or yv.shape is None:
        return None
    _require_same_dtype(xv, yv, "matmul")
    tx = bool(attrs.get("transpose_X", attrs.get("trans_x", False)))
    ty = bool(attrs.get("transpose_Y", attrs.get("trans_y", False)))
    sx, sy = list(xv.shape), list(yv.shape)
    if len(sx) < 2 or len(sy) < 2:
        return None                      # 1-D matmul forms: leave to jax
    mx, kx = (sx[-1], sx[-2]) if tx else (sx[-2], sx[-1])
    ky, ny = (sy[-1], sy[-2]) if ty else (sy[-2], sy[-1])
    if kx >= 0 and ky >= 0 and kx != ky:
        raise SpecMismatch(
            f"matmul: contracted dims disagree — X{list(xv.shape)}"
            f"{'^T' if tx else ''} × Y{list(yv.shape)}"
            f"{'^T' if ty else ''} contracts {kx} against {ky}",
            kind="shape")
    batch_x, batch_y = sx[:-2], sy[:-2]
    big, small = (batch_x, batch_y) if len(batch_x) >= len(batch_y) \
        else (batch_y, batch_x)
    batch = [int(d) for d in big]
    off = len(big) - len(small)
    for i, d in enumerate(small):
        j = _dim_join(batch[off + i], d)
        if j is None:
            raise SpecMismatch(
                f"matmul: batch dims disagree — X{list(xv.shape)} vs "
                f"Y{list(yv.shape)}", kind="shape")
        batch[off + i] = j
    return {"Out": [VarSig(tuple(batch) + (mx, ny), xv.dtype)]}


# -- GEMM FLOPs channel (observability/flops.py MFU numerator): forward
# FLOPs at 2 per MAC from the inferred signatures; None when any needed
# dim is unknown so the estimate stays a checked number, not a guess


def _flops_mul(ins, outs, attrs):
    xv, yv = _sig(ins, "X"), _sig(ins, "Y")
    if xv is None or yv is None or xv.shape is None or yv.shape is None:
        return None
    xn = int(attrs.get("x_num_col_dims", 1))
    yn = int(attrs.get("y_num_col_dims", 1))
    sx, sy = xv.shape, yv.shape
    if not _known(sx) or not _known(sy):
        return None
    return 2.0 * _numel(sx[:xn]) * _numel(sx[xn:]) * _numel(sy[yn:])


def _flops_matmul(ins, outs, attrs):
    xv, yv = _sig(ins, "X"), _sig(ins, "Y")
    if xv is None or yv is None or xv.shape is None or yv.shape is None \
            or len(xv.shape) < 2 or len(yv.shape) < 2:
        return None
    tx = bool(attrs.get("transpose_X", attrs.get("trans_x", False)))
    ty = bool(attrs.get("transpose_Y", attrs.get("trans_y", False)))
    sx, sy = list(xv.shape), list(yv.shape)
    m, k = (sx[-1], sx[-2]) if tx else (sx[-2], sx[-1])
    _, n = (sy[-1], sy[-2]) if ty else (sy[-2], sy[-1])
    batch_x, batch_y = sx[:-2], sy[:-2]
    batch = batch_x if len(batch_x) >= len(batch_y) else batch_y
    if not _known((m, k, n)) or not _known(batch):
        return None
    return 2.0 * _numel(batch) * m * k * n


def _flops_fused_attention(ins, outs, attrs):
    """QK^T and PV einsums: 2 GEMMs of [B,H,Sq,dh]x[B,H,dh,Sk] —
    4·B·Sq·Sk·hidden total (head split cancels)."""
    q, k = _sig(ins, "Q"), _sig(ins, "K")
    if q is None or q.shape is None or len(q.shape) < 3:
        return None
    b, sq, hidden = q.shape[0], q.shape[1], q.shape[-1]
    if _sig(ins, "KPool") is not None:
        sk = _cached_attn_total(ins)
        if sk is None:
            return None
    else:
        ksh = k.shape if k is not None and k.shape is not None else q.shape
        sk = ksh[1] if len(ksh) > 1 else sq
    if not _known((b, sq, sk, hidden)):
        return None
    return 4.0 * b * sq * sk * hidden


def _flops_conv2d(ins, outs, attrs):
    xv, wv = _sig(ins, "Input"), _sig(ins, "Filter")
    ov = _sig(outs, "Output") if outs else None
    if xv is None or wv is None or ov is None or xv.shape is None or \
            wv.shape is None or ov.shape is None or len(wv.shape) != 4:
        return None
    if not _known(ov.shape) or not _known(wv.shape):
        return None
    cout, cin_g, kh, kw = wv.shape
    return 2.0 * _numel(ov.shape) * cin_g * kh * kw


# -- elementwise/transcendental FLOPs (the non-GEMM tail): priced so the
# differential spec auditor (framework/spec_audit.py) can reconcile the
# program total against XLA cost_analysis; observability/flops.py keeps
# these OUT of the MFU numerator (NON_GEMM_FLOPS_OPS).  Counting
# convention matches the auditor's jaxpr prim table — ~1 FLOP per output
# element per arithmetic/transcendental prim, reductions at operand
# numel — so per-op attribution closes on the same model.


def _flops_elemwise(k, slot="X"):
    """``k`` FLOPs per element of input ``slot`` (prim-count
    calibrated: e.g. softmax = reduce_max + sub + exp + reduce_sum +
    div = 5 prims per logit element)."""
    def flops(ins, outs, attrs):
        v = _sig(ins, slot)
        if v is None or v.shape is None or not _known(v.shape):
            return None
        return float(k) * _numel(v.shape)
    return flops


def _flops_softmax_ce(ins, outs, attrs):
    """The fused loss materialises BOTH softmax and log_softmax over
    the logits (5 prims each) plus the label gather/mask tail —
    ~10 per logit element dominates."""
    v = _sig(ins, "Logits")
    if v is None or v.shape is None or not _known(v.shape):
        return None
    return 10.0 * _numel(v.shape)


def _flops_c_embedding(ins, outs, attrs):
    """Masked vocab-parallel lookup: shift/compare on Ids, clip +
    where over the [*, dim] gather result, and the psum add —
    ~2 per output element."""
    w, ids = _sig(ins, "W"), _sig(ins, "Ids")
    if w is None or ids is None or w.shape is None or ids.shape is None \
            or not _known(w.shape) or not _known(ids.shape):
        return None
    return 2.0 * _numel(ids.shape) * w.shape[-1]


def _infer_mean(ins, attrs):
    v = _sig(ins, "X")
    if v is None:
        return None
    return {"Out": [VarSig((), v.dtype)]}


def _infer_sum(ins, attrs):
    vs = ins.get("X") or []
    if not vs:
        return None
    base = vs[0]
    for v in vs[1:]:
        if v.shape is not None and base.shape is not None and \
                len(v.shape) == len(base.shape):
            for a, b in zip(v.shape, base.shape):
                if a >= 0 and b >= 0 and a != b:
                    raise SpecMismatch(
                        f"sum: operand shapes disagree — {list(base.shape)} "
                        f"vs {list(v.shape)}", kind="shape")
        if v.dtype != base.dtype:
            raise SpecMismatch(
                f"sum: operand dtypes disagree — {base.dtype} vs {v.dtype}",
                kind="dtype")
    return {"Out": [VarSig(base.shape, base.dtype)]}


def _infer_reduce(ins, attrs):
    v = _sig(ins, "X")
    if v is None or v.shape is None:
        return None
    if attrs.get("reduce_all") or attrs.get("dim") is None:
        dims = list(range(len(v.shape)))
    else:
        d = attrs["dim"]
        dims = [d] if isinstance(d, int) else list(d)
        dims = [x + len(v.shape) if x < 0 else x for x in dims]
    keep = bool(attrs.get("keep_dim", attrs.get("keepdim", False)))
    out = []
    for i, d in enumerate(v.shape):
        if i in dims:
            if keep:
                out.append(1)
        else:
            out.append(d)
    dtype = "bool" if attrs.get("_bool_out") else v.dtype
    return {"Out": [VarSig(tuple(out), dtype)]}


def _infer_scale(ins, attrs):
    return same_as_input()(ins, attrs)


def _infer_cast(ins, attrs):
    v = _sig(ins, "X")
    if v is None:
        return None
    dtype = attrs.get("out_dtype", attrs.get("dtype", "float32"))
    try:
        from ..framework.core import convert_dtype
        dtype = convert_dtype(dtype)
    except Exception:
        return None
    return {"Out": [VarSig(v.shape, dtype)]}


# ---------------------------------------------------------------------------
# nn ops
# ---------------------------------------------------------------------------


def _conv_out_dim(size, k, pad, stride, dilation=1):
    if size < 0:
        return -1
    eff = (k - 1) * dilation + 1
    return (size + 2 * pad - eff) // stride + 1


def _infer_conv2d(ins, attrs):
    iv, fv = _sig(ins, "Input"), _sig(ins, "Filter")
    if iv is None or fv is None or iv.shape is None or fv.shape is None:
        return None
    _require_same_dtype(iv, fv, "conv2d")
    if len(iv.shape) != 4 or len(fv.shape) != 4:
        raise SpecMismatch(
            f"conv2d: expects 4-D NCHW input and OIHW filter, got "
            f"Input{list(iv.shape)} Filter{list(fv.shape)}", kind="shape")
    n, c, h, w = iv.shape
    o, i, kh, kw = fv.shape
    groups = int(attrs.get("groups", 1) or 1)
    if c >= 0 and i >= 0 and c != i * groups:
        raise SpecMismatch(
            f"conv2d: input channels {c} != filter in-channels {i} × "
            f"groups {groups}", kind="shape")
    strides = list(attrs.get("strides", (1, 1)))
    pads = list(attrs.get("paddings", (0, 0)))
    dil = list(attrs.get("dilations", (1, 1)))
    ho = _conv_out_dim(h, kh, pads[0], strides[0], dil[0])
    wo = _conv_out_dim(w, kw, pads[1], strides[1], dil[1])
    return {"Output": [VarSig((n, o, ho, wo), iv.dtype)]}


def _infer_pool2d(ins, attrs):
    v = _sig(ins, "X")
    if v is None or v.shape is None or len(v.shape) != 4:
        return None
    n, c, h, w = v.shape
    if attrs.get("global_pooling") or attrs.get("adaptive"):
        ks = attrs.get("ksize", (1, 1))
        if attrs.get("global_pooling"):
            return {"Out": [VarSig((n, c, 1, 1), v.dtype)]}
        return {"Out": [VarSig((n, c, int(ks[0]), int(ks[1])), v.dtype)]}
    ks = list(attrs.get("ksize", (1, 1)))
    strides = list(attrs.get("strides", ks))
    pads = list(attrs.get("paddings", (0, 0)))
    ceil = bool(attrs.get("ceil_mode", False))

    def out_dim(size, k, p, s):
        if size < 0:
            return -1
        if ceil:
            return (size + 2 * p - k + s - 1) // s + 1
        return (size + 2 * p - k) // s + 1

    return {"Out": [VarSig((n, c, out_dim(h, ks[0], pads[0], strides[0]),
                            out_dim(w, ks[1], pads[1], strides[1])),
                           v.dtype)]}


def _infer_layer_norm(ins, attrs):
    v = _sig(ins, "X")
    if v is None:
        return None
    out = {"Y": [VarSig(v.shape, v.dtype)]}
    if v.shape is not None and len(v.shape) >= 1:
        # Mean/Variance are per-row statistics over the normalised axis
        stat = VarSig(tuple(v.shape[:-1]), "float32")
        out["Mean"] = [stat]
        out["Variance"] = [stat]
    return out


def _infer_dropout(ins, attrs):
    v = _sig(ins, "X")
    if v is None:
        return None
    # the impl materialises Mask as uint8 regardless of X's dtype
    # (caught by the differential spec auditor's shape channel)
    return {"Out": [VarSig(v.shape, v.dtype)],
            "Mask": [VarSig(v.shape, "uint8")]}


def _cached_attn_total(ins):
    """Gathered context length T = max_blocks_per_seq * block_size of
    the cache-read fused_attention variant, or None."""
    pool = _shape_of(_sig(ins, "KPool"))
    table = _shape_of(_sig(ins, "BlockTable"))
    if pool is None or table is None or len(pool) != 3 or len(table) != 2:
        return None
    if pool[1] < 0 or table[1] < 0:
        return None
    return table[1] * pool[1]


def _infer_fused_attention(ins, attrs):
    """Out mirrors Q ([B, Sq, hidden]); K/V must agree on the hidden
    width and on Sk between themselves.  The cache-read variant
    (KPool/VPool/BlockTable/CtxLen inputs — serving/decode.py) checks
    the pool hidden width against Q instead."""
    q = _sig(ins, "Q")
    if q is None or q.shape is None:
        return None
    kpool = _sig(ins, "KPool")
    if kpool is not None:
        if kpool.shape is not None and len(kpool.shape) == 3 and \
                kpool.shape[-1] >= 0 and q.shape[-1] >= 0 and \
                kpool.shape[-1] != q.shape[-1]:
            raise SpecMismatch(
                f"fused_attention: KPool hidden width {kpool.shape[-1]} "
                f"!= Q hidden width {q.shape[-1]}", kind="shape")
        qpos = _sig(ins, "QPos")
        if qpos is not None and qpos.shape is not None and \
                q.shape is not None and len(qpos.shape) == 2 and \
                all(d >= 0 for d in qpos.shape) and \
                all(d >= 0 for d in q.shape[:2]) and \
                tuple(qpos.shape) != tuple(q.shape[:2]):
            raise SpecMismatch(
                f"fused_attention: QPos {list(qpos.shape)} must match "
                f"Q's [B, Sq] = {list(q.shape[:2])} (per-query absolute "
                f"positions of the chunked-prefill causal mask)",
                kind="shape")
        return {"Out": [VarSig(q.shape, q.dtype)]}
    k, v = _sig(ins, "K"), _sig(ins, "V")
    # grouped K/V heads: K and V are num_kv_heads / n_head as wide as Q
    n_kv, n_head = attrs.get("num_kv_heads"), attrs.get("n_head") or 1
    want = q.shape[-1]
    if n_kv and want >= 0:
        if n_head % int(n_kv) or want % n_head:
            raise SpecMismatch(
                f"fused_attention: {n_head} query heads of width "
                f"{want} do not divide over {n_kv} K/V heads",
                kind="shape")
        want = want // n_head * int(n_kv)
    for other, nm in ((k, "K"), (v, "V")):
        if other is None or other.shape is None:
            continue
        if len(other.shape) == len(q.shape) and \
                other.shape[-1] >= 0 and want >= 0 and \
                other.shape[-1] != want:
            raise SpecMismatch(
                f"fused_attention: {nm} hidden width {other.shape[-1]} "
                f"!= {want} (Q's width"
                + (f" over {n_head} heads times {n_kv})" if n_kv else ")"),
                kind="shape")
    return {"Out": [VarSig(q.shape, q.dtype)]}


def _infer_cache_write(ins, attrs):
    """Pool outputs alias the pool inputs; K/V must agree with the pool
    hidden width and Slots with the K/V token count."""
    kpool, vpool = _sig(ins, "KPool"), _sig(ins, "VPool")
    k = _sig(ins, "K")
    if kpool is None or kpool.shape is None:
        return None
    if k is not None and k.shape is not None and \
            k.shape[-1] >= 0 and kpool.shape[-1] >= 0 and \
            k.shape[-1] != kpool.shape[-1]:
        raise SpecMismatch(
            f"cache_write: K hidden width {k.shape[-1]} != pool hidden "
            f"width {kpool.shape[-1]}", kind="shape")
    slots = _sig(ins, "Slots")
    if slots is not None and slots.shape is not None and \
            k is not None and k.shape is not None and \
            all(d >= 0 for d in slots.shape) and \
            all(d >= 0 for d in k.shape[:-1]):
        import numpy as _np
        if int(_np.prod(slots.shape)) != int(_np.prod(k.shape[:-1])):
            raise SpecMismatch(
                f"cache_write: Slots covers {list(slots.shape)} tokens "
                f"but K carries {list(k.shape[:-1])}", kind="shape")
    out = [VarSig(kpool.shape, kpool.dtype)]
    if vpool is None:           # one pool a layer (a latent cache)
        return {"KPoolOut": out}
    vout = [VarSig(vpool.shape, vpool.dtype)] if vpool.shape is not None \
        else out
    return {"KPoolOut": out, "VPoolOut": vout}


def _infer_decode_chain(ins, attrs):
    """The chained-decode marker op (executor.lower_decode_chain): Out
    is the packed ``[chain_length, B]`` emitted-token matrix the host
    fetches once per chain (-1 = row already finished)."""
    tok = _sig(ins, "TokenIds")
    if tok is None or tok.shape is None or len(tok.shape) != 1:
        return None
    length = int(attrs.get("chain_length", 0) or 0)
    if length < 1:
        raise SpecMismatch(
            f"decode_chain: chain_length={length} — the device chain "
            f"must run at least one step", kind="attr")
    b = tok.shape[0]
    steps = _sig(ins, "StepsLeft")
    if steps is not None and steps.shape is not None and \
            len(steps.shape) == 1 and steps.shape[0] >= 0 and b >= 0 and \
            steps.shape[0] != b:
        raise SpecMismatch(
            f"decode_chain: StepsLeft rows {steps.shape[0]} != TokenIds "
            f"rows {b}", kind="shape")
    out = {"Out": [VarSig((length, b), "int64")]}
    logits = _sig(ins, "Logits")
    if logits is not None and logits.shape is not None:
        # optional: every step's logits stacked (a request that asked
        # for its logits; fetched lazily, sliced on the device)
        out["LogitsOut"] = [VarSig((length,) + tuple(logits.shape),
                                   logits.dtype)]
    return out


def _attention_probs_bytes(ins, outs, attrs):
    """Backward residual the attention impl materialises internally:
    the pre-softmax logits + probability matrices [B, n_head, Sq, Sk]
    (never named Program vars — the op is one fused node)."""
    from .registry import dtype_nbytes
    q = _sig(ins, "Q")
    k = _sig(ins, "K") or q
    if q is None or q.shape is None or len(q.shape) < 3:
        return 0
    ksh = k.shape if k is not None and k.shape is not None else q.shape
    b, sq = int(q.shape[0]), int(q.shape[1])
    sk = int(ksh[1]) if len(ksh) > 1 else sq
    if min(b, sq, sk) < 0:
        return 0
    n_head = int(attrs.get("n_head", 1) or 1)
    head_dim = attrs.get("head_dim")
    if head_dim and q.shape[-1] > 0:
        n_head = max(1, int(q.shape[-1]) // int(head_dim))
    return 2 * b * n_head * sq * sk * dtype_nbytes(q.dtype)


def _softmax_ce_extra_bytes(ins, outs, attrs):
    """softmax-CE keeps the logit-sized softmax for backward, and its
    cotangent is logit-sized too — two full logit copies beyond the
    named Loss/Softmax outputs' alias classes."""
    lg = _sig(ins, "Logits")
    if lg is None or lg.shape is None or any(int(d) < 0 for d in lg.shape):
        return 0
    from .registry import dtype_nbytes
    n = 1
    for d in lg.shape:
        n *= int(d)
    return 2 * n * dtype_nbytes(lg.dtype)


def _infer_batch_norm(ins, attrs):
    v = _sig(ins, "X")
    if v is None:
        return None
    return {"Y": [VarSig(v.shape, v.dtype)]}


def _infer_lookup_table_v2(ins, attrs):
    w, ids = _sig(ins, "W"), _sig(ins, "Ids")
    if w is None or ids is None or w.shape is None:
        return None
    if not _is_int(ids.dtype):
        raise SpecMismatch(
            f"lookup_table_v2: Ids must be an integer tensor, got "
            f"{ids.dtype}", kind="dtype")
    if len(w.shape) != 2:
        raise SpecMismatch(
            f"lookup_table_v2: W must be 2-D [vocab, dim], got "
            f"{list(w.shape)}", kind="shape")
    if ids.shape is None:
        return None
    # the layer convention (layers/nn.py embedding) squeezes a declared
    # trailing 1 dim from Ids — mirror it so declared metadata agrees
    base = tuple(ids.shape[:-1]) if len(ids.shape) > 1 and \
        ids.shape[-1] == 1 else tuple(ids.shape)
    return {"Out": [VarSig(base + (w.shape[1],), w.dtype)]}


def _infer_lookup_table(ins, attrs):
    w, ids = _sig(ins, "W"), _sig(ins, "Ids")
    if w is None or ids is None or w.shape is None or ids.shape is None:
        return None
    if not _is_int(ids.dtype):
        raise SpecMismatch(
            f"lookup_table: Ids must be an integer tensor, got {ids.dtype}",
            kind="dtype")
    base = tuple(ids.shape[:-1]) if ids.shape and ids.shape[-1] == 1 \
        else tuple(ids.shape)
    return {"Out": [VarSig(base + (w.shape[1],), w.dtype)]}


def _infer_softmax_with_ce(ins, attrs):
    logits, label = _sig(ins, "Logits"), _sig(ins, "Label")
    if logits is None or logits.shape is None:
        return None
    if label is not None and not attrs.get("soft_label", False) and \
            not _is_int(label.dtype):
        raise SpecMismatch(
            f"softmax_with_cross_entropy: hard Label must be integer, got "
            f"{label.dtype}", kind="dtype")
    loss_shape = tuple(logits.shape[:-1]) + (1,)
    return {"Softmax": [VarSig(logits.shape, logits.dtype)],
            "Loss": [VarSig(loss_shape, logits.dtype)]}


def _infer_cross_entropy(ins, attrs):
    xv, label = _sig(ins, "X"), _sig(ins, "Label")
    if xv is None or xv.shape is None:
        return None
    if label is not None and not attrs.get("soft_label", False) and \
            not _is_int(label.dtype):
        raise SpecMismatch(
            f"cross_entropy: hard Label must be integer, got {label.dtype}",
            kind="dtype")
    return {"Y": [VarSig(tuple(xv.shape[:-1]) + (1,), xv.dtype)],
            "Out": [VarSig(tuple(xv.shape[:-1]) + (1,), xv.dtype)]}


# ---------------------------------------------------------------------------
# tensor manipulation
# ---------------------------------------------------------------------------


def _infer_reshape2(ins, attrs):
    v = _sig(ins, "X")
    target = attrs.get("shape")
    if v is None or target is None:
        return None
    out = []
    for i, d in enumerate(target):
        d = int(d)
        if d == 0:
            out.append(v.shape[i] if v.shape is not None and
                       i < len(v.shape) else -1)
        else:
            out.append(d)
    if v.shape is not None and _known(v.shape) and _known(out):
        if _numel(v.shape) != _numel(out):
            raise SpecMismatch(
                f"reshape2: cannot reshape {list(v.shape)} "
                f"({_numel(v.shape)} elements) into {list(out)} "
                f"({_numel(out)} elements)", kind="shape")
    if v.shape is not None and _known(v.shape) and out.count(-1) == 1:
        rest = 1
        for d in out:
            if d != -1:
                rest *= d
        if rest and _numel(v.shape) % rest == 0:
            out[out.index(-1)] = _numel(v.shape) // rest
    return {"Out": [VarSig(tuple(out), v.dtype)]}


def _infer_transpose2(ins, attrs):
    v = _sig(ins, "X")
    perm = attrs.get("axis")
    if v is None or v.shape is None or perm is None:
        return None
    if len(perm) != len(v.shape):
        raise SpecMismatch(
            f"transpose2: perm {list(perm)} rank != input rank "
            f"{len(v.shape)} ({list(v.shape)})", kind="shape")
    return {"Out": [VarSig(tuple(v.shape[int(p)] for p in perm), v.dtype)]}


def _infer_unsqueeze2(ins, attrs):
    v = _sig(ins, "X")
    axes = attrs.get("axes")
    if v is None or v.shape is None or axes is None:
        return None
    out = list(v.shape)
    for a in axes:
        a = int(a)
        if a < 0:
            a += len(out) + 1
        out.insert(a, 1)
    return {"Out": [VarSig(tuple(out), v.dtype)]}


def _infer_concat(ins, attrs):
    vs = ins.get("X") or []
    if not vs or any(v.shape is None for v in vs):
        return None
    axis = int(attrs.get("axis", 0))
    rank = len(vs[0].shape)
    if axis < 0:
        axis += rank
    for v in vs[1:]:
        if len(v.shape) != rank:
            raise SpecMismatch(
                f"concat: operand ranks differ — {list(vs[0].shape)} vs "
                f"{list(v.shape)}", kind="shape")
        if v.dtype != vs[0].dtype:
            raise SpecMismatch(
                f"concat: operand dtypes differ — {vs[0].dtype} vs "
                f"{v.dtype}", kind="dtype")
    out = list(vs[0].shape)
    total = 0
    for v in vs:
        d = v.shape[axis]
        if d < 0 or total < 0:
            total = -1
        else:
            total += d
    for i in range(rank):
        if i == axis:
            continue
        for v in vs[1:]:
            j = _dim_join(out[i], v.shape[i])
            if j is None:
                raise SpecMismatch(
                    f"concat: non-axis dim {i} differs — "
                    f"{list(vs[0].shape)} vs {list(v.shape)}", kind="shape")
            out[i] = j
    out[axis] = total
    return {"Out": [VarSig(tuple(out), vs[0].dtype)]}


def _infer_split(ins, attrs):
    v = _sig(ins, "X")
    if v is None or v.shape is None:
        return None
    axis = int(attrs.get("axis", 0))
    if axis < 0:
        axis += len(v.shape)
    sections = attrs.get("sections") or []
    num = int(attrs.get("num", 0) or 0)
    outs = []
    if sections:
        for s in sections:
            shp = list(v.shape)
            shp[axis] = int(s)
            outs.append(VarSig(tuple(shp), v.dtype))
    elif num:
        shp = list(v.shape)
        if shp[axis] >= 0:
            if shp[axis] % num != 0:
                raise SpecMismatch(
                    f"split: dim {axis} of {list(v.shape)} not divisible "
                    f"by num={num}", kind="shape")
            shp[axis] = shp[axis] // num
        outs = [VarSig(tuple(shp), v.dtype) for _ in range(num)]
    else:
        return None
    return {"Out": outs}


def _infer_top_k(ins, attrs):
    v = _sig(ins, "X")
    if v is None or v.shape is None:
        return None
    k = int(attrs.get("k", 1))
    out = tuple(v.shape[:-1]) + (k,)
    return {"Out": [VarSig(out, v.dtype)]}


def _infer_one_hot(ins, attrs):
    v = _sig(ins, "X")
    depth = attrs.get("depth")
    if v is None or v.shape is None or depth is None:
        return None
    base = tuple(v.shape[:-1]) if v.shape and v.shape[-1] == 1 \
        else tuple(v.shape)
    return {"Out": [VarSig(base + (int(depth),), "float32")]}


def _infer_fill_zeros_like(ins, attrs):
    return same_as_input()(ins, attrs)


def _infer_where(ins, attrs):
    xv = _sig(ins, "X")
    if xv is None:
        return None
    return {"Out": [VarSig(xv.shape, xv.dtype)]}


# ---------------------------------------------------------------------------
# optimizer / update ops
# ---------------------------------------------------------------------------


def _infer_opt_update(ins, attrs):
    p, g = _sig(ins, "Param"), _sig(ins, "Grad")
    if p is None:
        return None
    if g is not None and p.shape is not None and g.shape is not None and \
            _known(p.shape) and _known(g.shape) and \
            tuple(p.shape) != tuple(g.shape):
        raise SpecMismatch(
            f"optimizer update: Param{list(p.shape)} and Grad"
            f"{list(g.shape)} shapes disagree", kind="shape")
    return {"ParamOut": [VarSig(p.shape, p.dtype)]}


# ---------------------------------------------------------------------------
# collectives (flagged for the distributed-soundness checks)
# ---------------------------------------------------------------------------


def _infer_collective_same(ins, attrs):
    return same_as_input()(ins, attrs)


def _infer_pipe_boundary(ins, attrs):
    """Stage-cut marker: each crossing tensor passes through unchanged
    (X[i] → Out[i], slot-aligned — NOT the unary same_as_input, which
    would stamp every output with the first input's signature)."""
    xs = ins.get("X") or []
    if not xs or any(v is None for v in xs):
        return None
    return {"Out": [VarSig(v.shape, v.dtype) for v in xs]}


def _infer_argsort(ins, attrs):
    v = _sig(ins, "X")
    if v is None or v.shape is None:
        return None
    return {"Out": [VarSig(v.shape, v.dtype)],
            "Indices": [VarSig(v.shape, "int64")]}


# -- MoE decomposed pipeline (ops/moe_ops.py) -------------------------------
#
# The static dims mirror the runtime arithmetic in moe_dispatch exactly
# (same _moe_static_dims helper), so the shape ladder and the census
# price the capacity-factor geometry the kernels actually run.


def _moe_spec_dims(ins, attrs):
    """(n, g, sg, c, e, m) from the X/GateW sigs + attrs, or None."""
    xv, gw = _sig(ins, "X"), _sig(ins, "GateW")
    if xv is None or xv.shape is None or gw is None or gw.shape is None \
            or len(gw.shape) != 2:
        return None
    e = int(attrs.get("num_experts", gw.shape[1]))
    if gw.shape[1] != e and gw.shape[1] > 0:
        raise SpecMismatch(
            f"moe_dispatch: GateW expert dim {gw.shape[1]} != "
            f"num_experts attr {e}", kind="shape")
    m = xv.shape[-1]
    if m > 0 and gw.shape[0] > 0 and gw.shape[0] != m:
        raise SpecMismatch(
            f"moe_dispatch: GateW model dim {gw.shape[0]} != X last "
            f"dim {m}", kind="shape")
    from .moe_ops import _moe_static_dims
    n, g, sg, c = _moe_static_dims(
        xv.shape, e, attrs.get("top_k", 2),
        attrs.get("capacity_factor", 1.25), attrs.get("group_size", 0))
    return n, g, sg, c, e, m


def _infer_moe_dispatch(ins, attrs):
    dims = _moe_spec_dims(ins, attrs)
    if dims is None:
        return None
    n, g, sg, c, e, m = dims
    xv = _sig(ins, "X")
    gc = g * c if (g > 0 and c > 0) else -1
    return {"Xe": [VarSig((e, gc, m), xv.dtype)],
            "Combine": [VarSig((g, sg, e, c), "float32")],
            "AuxLoss": [VarSig((), "float32")]}


def _flops_moe_dispatch(ins, outs, attrs):
    """Gate GEMM (2·N·m·E) + the dispatch one-hot einsum
    (2·G·S·E·C·m = 2·N·E·C·m) — the capacity-factor geometry."""
    dims = _moe_spec_dims(ins, attrs)
    if dims is None:
        return None
    n, g, sg, c, e, m = dims
    if min(n, c, e, m) <= 0:
        return None
    return 2.0 * n * m * e + 2.0 * n * e * c * m


def _infer_moe_expert_ffn(ins, attrs):
    xe, w1, w2 = _sig(ins, "Xe"), _sig(ins, "W1"), _sig(ins, "W2")
    if xe is None or xe.shape is None:
        return None
    for w, tag in ((w1, "W1"), (w2, "W2")):
        if w is not None and w.shape is not None and len(w.shape) != 3:
            raise SpecMismatch(
                f"moe_expert_ffn: {tag} must be 3-D [E, in, out], got "
                f"{list(w.shape)}", kind="shape")
    return {"Out": [VarSig(xe.shape, xe.dtype)]}


def _flops_moe_expert_ffn(ins, outs, attrs):
    """Two batched GEMMs over the dispatched blocks: 4·E·B·m·h, where
    B = G·C carries the capacity factor."""
    xe, w1 = _sig(ins, "Xe"), _sig(ins, "W1")
    if xe is None or xe.shape is None or not _known(xe.shape) \
            or w1 is None or w1.shape is None or not _known(w1.shape):
        return None
    e, b, m = xe.shape
    h = w1.shape[-1]
    return 4.0 * e * b * m * h


def _infer_moe_topk_router(ins, attrs):
    """TopkWeight [N, k] float32 and TopkIndex [N, k] int32 over the
    flattened tokens; W's rows must match X's width."""
    xv, w = _sig(ins, "X"), _sig(ins, "W")
    if xv is None or xv.shape is None:
        return None
    if w is not None and w.shape is not None and len(w.shape) == 2 and \
            w.shape[0] >= 0 and xv.shape[-1] >= 0 and \
            w.shape[0] != xv.shape[-1]:
        raise SpecMismatch(
            f"moe_topk_router: W rows {w.shape[0]} != X width "
            f"{xv.shape[-1]}", kind="shape")
    lead = xv.shape[:-1]
    n = _numel(lead) if _known(lead) else -1
    k = int(attrs.get("top_k", 1))
    return {"TopkWeight": [VarSig((n, k), "float32")],
            "TopkIndex": [VarSig((n, k), "int32")]}


def _infer_lm_head_logits(ins, attrs):
    xv, w = _sig(ins, "X"), _sig(ins, "W")
    if xv is None or xv.shape is None or w is None or w.shape is None:
        return None
    if len(w.shape) == 2 and xv.shape[-1] >= 0 and w.shape[0] >= 0 and \
            xv.shape[-1] != w.shape[0]:
        raise SpecMismatch(
            f"lm_head_logits: W rows {w.shape[0]} != X width "
            f"{xv.shape[-1]}", kind="shape")
    return {"Out": [VarSig(tuple(xv.shape[:-1]) + (w.shape[-1],),
                           "float32")]}


def _infer_mla_attention(ins, attrs):
    """Out [B, Sq, n_head * v_dim] in Q's dtype; Q and WKVB must hold
    ``n_head`` heads of ``nope + rope`` and ``nope + v`` columns."""
    q, w = _sig(ins, "Q"), _sig(ins, "WKVB")
    if q is None or q.shape is None:
        return None
    h = int(attrs.get("n_head", 0))
    dn, dr, dv = (int(attrs.get(k, 0)) for k in
                  ("nope_dim", "rope_dim", "v_dim"))
    if q.shape[-1] >= 0 and q.shape[-1] != h * (dn + dr):
        raise SpecMismatch(
            f"mla_attention: Q width {q.shape[-1]} != {h} heads of "
            f"{dn}+{dr}", kind="shape")
    if w is not None and w.shape is not None and len(w.shape) == 2 and \
            w.shape[1] >= 0 and w.shape[1] != h * (dn + dv):
        raise SpecMismatch(
            f"mla_attention: WKVB columns {w.shape[1]} != {h} heads of "
            f"{dn}+{dv}", kind="shape")
    return {"Out": [VarSig(tuple(q.shape[:-1]) + (h * dv,), q.dtype)]}


def _pl_mla_paged_supported(ins, attrs, axis_sizes=None):
    """MLA paged decode route gate (ops/pallas/mla_paged.py): a cached
    read with a one-token query and no ``QPos`` over a bfloat16 latent
    pool in pages of a multiple of 16 tokens, the latent and the row in
    whole 128-lane tiles."""
    from .pallas.mla_paged import supported
    q = _shape_of(_sig(ins, "Q"))
    pool = _sig(ins, "Pool")
    pshape = _shape_of(pool)
    w = _shape_of(_sig(ins, "WKVB"))
    if pool is None:
        return False, "not-cached"
    if q is None or len(q) != 3 or pshape is None or len(pshape) != 3 \
            or w is None or min(q[1], pshape[1], pshape[2], w[0]) < 0:
        return False, "shape-unknown"
    return supported(q[1], int(attrs.get("n_head", 0)), w[0],
                     int(attrs.get("rope_dim", 0)), pshape[1], pshape[2],
                     pool.dtype, has_qpos=_sig(ins, "QPos") is not None)


def _lower_mla_paged_decode(ctx, ins, attrs):
    from .mla_ops import lower_mla_paged_decode
    return lower_mla_paged_decode(ctx, ins, attrs)


def _infer_gated_delta_rule(ins, attrs):
    """Out [B, S, n_head * d_v] in Q's dtype; Q / K hold ``n_head`` key
    heads, V ``n_head`` value heads; a StatePool is [slots, n_head, d_k,
    d_v] float32 and aliases its output."""
    q, v = _sig(ins, "Q"), _sig(ins, "V")
    if q is None or v is None or q.shape is None or v.shape is None:
        return None
    h = int(attrs.get("n_head", 0))
    for sig, nm in ((q, "Q"), (_sig(ins, "K"), "K"), (v, "V")):
        if sig is not None and sig.shape is not None \
                and sig.shape[-1] >= 0 and (h <= 0 or sig.shape[-1] % h):
            raise SpecMismatch(
                f"gated_delta_rule: {nm} width {sig.shape[-1]} does not "
                f"divide over {h} heads", kind="shape")
    out = {"Out": [VarSig(tuple(q.shape[:-1]) + (v.shape[-1],), q.dtype)]}
    pool = _sig(ins, "StatePool")
    if pool is not None:
        want = (h, q.shape[-1] // h, v.shape[-1] // h)
        if pool.shape is not None and len(pool.shape) == 4 and \
                min(want) >= 0 and tuple(pool.shape[1:]) != want:
            raise SpecMismatch(
                f"gated_delta_rule: StatePool {list(pool.shape)} does not "
                f"hold [slots, heads, d_k, d_v] = [*, {want[0]}, "
                f"{want[1]}, {want[2]}]", kind="shape")
        out["StatePoolOut"] = [VarSig(pool.shape, pool.dtype)]
    return out


def _infer_causal_conv1d(ins, attrs):
    xv, w, pool = _sig(ins, "X"), _sig(ins, "W"), _sig(ins, "TailPool")
    if xv is None or xv.shape is None:
        return None
    if w is not None and w.shape is not None and len(w.shape) == 2 and \
            min(w.shape[1], xv.shape[-1]) >= 0 and \
            w.shape[1] != xv.shape[-1]:
        raise SpecMismatch(
            f"causal_conv1d: W {list(w.shape)} is not [kernel, channels "
            f"= {xv.shape[-1]}]", kind="shape")
    out = {"Out": [VarSig(xv.shape, xv.dtype)]}
    if pool is not None:
        out["TailPoolOut"] = [VarSig(pool.shape, pool.dtype)]
    return out


def _gdn_dims(ins, attrs):
    """(S, heads, d_k, d_v, pool dtype or None) of a gated_delta_rule, or
    None where a shape is unknown."""
    q, v = _shape_of(_sig(ins, "Q")), _shape_of(_sig(ins, "V"))
    h = int(attrs.get("n_head", 0))
    if q is None or v is None or len(q) != 3 or h <= 0 \
            or min(q[1], q[2], v[2]) < 0:
        return None
    pool = _sig(ins, "StatePool")
    return q[1], h, q[2] // h, v[2] // h, \
        None if pool is None else pool.dtype


def _pl_gdn_decode_supported(ins, attrs, axis_sizes=None):
    """Recurrent route gate (ops/pallas/gated_delta.py): one token a row
    over a float32 state pool."""
    from .pallas.gated_delta import supported
    dims = _gdn_dims(ins, attrs)
    if dims is None:
        return False, "shape-unknown"
    s, h, dk, dv, pool_dtype = dims
    if pool_dtype is None or s != 1 or _sig(ins, "Fresh") is not None:
        return False, f"gdn:not-recurrent:sq:{s}"
    return supported(h, dk, dv, pool_dtype)


def _pl_gdn_chunk_supported(ins, attrs, axis_sizes=None):
    """Chunked route gate: any length (padded to whole sub-chunks), with
    or without a float32 state pool."""
    from .pallas.gated_delta import supported
    dims = _gdn_dims(ins, attrs)
    if dims is None:
        return False, "shape-unknown"
    _, h, dk, dv, pool_dtype = dims
    return supported(h, dk, dv, pool_dtype or "float32")


def _lower_gdn_decode(ctx, ins, attrs):
    from .linear_attn_ops import lower_gdn_decode
    return lower_gdn_decode(ctx, ins, attrs)


def _lower_gdn_chunk(ctx, ins, attrs):
    from .linear_attn_ops import lower_gdn_chunk
    return lower_gdn_chunk(ctx, ins, attrs)


def _infer_moe_grouped_ffn(ins, attrs):
    xv, wg = _sig(ins, "X"), _sig(ins, "WGate")
    if xv is None or xv.shape is None:
        return None
    for slot in ("WGate", "WUp", "WDown"):
        w = _sig(ins, slot)
        if w is not None and w.shape is not None and len(w.shape) != 3:
            raise SpecMismatch(
                f"moe_grouped_ffn: {slot} must be 3-D [E_local, in, out], "
                f"got {list(w.shape)}", kind="shape")
    out = {"Out": [VarSig(xv.shape, xv.dtype)]}
    if wg is not None and wg.shape is not None:
        out["ExpertCount"] = [VarSig((wg.shape[0],), "int32")]
    return out


def _flops_moe_grouped_ffn(ins, outs, attrs):
    """Three grouped products over the assignments EXPECTED here under
    uniform routing: 6 * N * k * (E_local / E) * d * f (the real count is
    the op's ExpertCount output)."""
    xv, wg = _sig(ins, "X"), _sig(ins, "WGate")
    idx = _sig(ins, "TopkIndex")
    if xv is None or wg is None or idx is None or not _known(xv.shape) \
            or not _known(wg.shape) or not _known(idx.shape):
        return None
    e_local, d, f = wg.shape
    share = e_local / float(attrs.get("num_experts") or e_local)
    return 6.0 * _numel(idx.shape) * share * d * f


def _infer_lm_head_loss(ins, attrs):
    xv, w, lab = _sig(ins, "X"), _sig(ins, "W"), _sig(ins, "Label")
    if xv is not None and w is not None and xv.shape is not None and \
            w.shape is not None and len(w.shape) == 2 and \
            xv.shape[-1] >= 0 and w.shape[0] >= 0 and \
            xv.shape[-1] != w.shape[0]:
        raise SpecMismatch(
            f"lm_head_loss: W rows {w.shape[0]} != X width "
            f"{xv.shape[-1]}", kind="shape")
    if lab is None or lab.shape is None:
        return None
    return {"Loss": [VarSig(lab.shape, "float32")]}


def _flops_lm_head_loss(ins, outs, attrs):
    lab, w = _sig(ins, "Label"), _sig(ins, "W")
    if lab is None or w is None or not _known(lab.shape) \
            or not _known(w.shape):
        return None
    return 2.0 * _numel(lab.shape) * w.shape[0] * w.shape[1]


def _pl_gmm_supported(ins, attrs, axis_sizes=None):
    """Grouped-matmul route gate (ops/pallas/grouped_matmul.py): whole
    weight blocks per expert, so both widths must be lane-aligned."""
    wg = _shape_of(_sig(ins, "WGate"))
    if wg is None or len(wg) != 3 or min(wg) < 0:
        return False, "shape-unknown"
    if wg[1] % 128 or wg[2] % 128:
        return False, f"widths:{wg[1]}x{wg[2]}%128"
    return True, ""


def _flops_moe_combine(ins, outs, attrs):
    """The combine einsum gsec,egcm→gsm: 2·G·S·E·C·m."""
    comb, xv = _sig(ins, "Combine"), _sig(ins, "X")
    if comb is None or comb.shape is None or not _known(comb.shape) \
            or xv is None or xv.shape is None or xv.shape[-1] <= 0:
        return None
    return 2.0 * _numel(comb.shape) * xv.shape[-1]


def _infer_c_embedding(ins, attrs):
    """Vocab-parallel embedding lookup: Out = Ids.shape + [dim] (the
    row dim is vocab-sharded; the psum restores the full [.., dim])."""
    w, ids = _sig(ins, "W"), _sig(ins, "Ids")
    if w is None or ids is None or w.shape is None or ids.shape is None:
        return None
    if len(w.shape) != 2:
        raise SpecMismatch(
            f"c_embedding: W must be 2-D [vocab_shard, dim], got "
            f"{list(w.shape)}", kind="shape")
    return {"Out": [VarSig(tuple(ids.shape) + (w.shape[1],), w.dtype)]}


# -- wire-byte accounting (the ``wire`` op_spec channel) --------------------
#
# Ring cost model over one reduce axis of size n (the standard
# bandwidth-optimal schedule XLA uses on ICI):
#
#   all_reduce       2·(n-1)/n · payload     (reduce-scatter + all-gather)
#   reduce_scatter     (n-1)/n · payload
#   all_gather         (n-1)/n · payload
#   all_to_all         (n-1)/n · payload
#
# ``logical_bytes`` prices the payload at the program dtype;
# ``wire_bytes`` prices it at the op's CompressionSpec tier (payload +
# per-block scales, quantize_wire.py) — for full-precision collectives
# the two are equal, ratio 1.0 (the census back-compat default).

_WIRE_DTYPE_BYTES = {"float64": 8, "int64": 8, "float32": 4, "int32": 4,
                     "bfloat16": 2, "float16": 2, "int16": 2, "int8": 1,
                     "uint8": 1, "bool": 1}


def _wire_width(dtype) -> int:
    """On-wire bytes per element.  Dtypes outside the fast table (e.g.
    float8 variants) price at their true canonical itemsize via
    registry.dtype_nbytes instead of silently defaulting to 4 — a
    non-default-dtype pipe boundary or collective must not be priced at
    fp32 width."""
    width = _WIRE_DTYPE_BYTES.get(str(dtype))
    if width is not None:
        return width
    try:
        from .registry import dtype_nbytes
        return dtype_nbytes(dtype)
    except Exception:
        return 4


def _ring_factor(attrs, axis_sizes, passes):
    """Σ over the op's reduce axes of passes·(n-1)/n; falls back to
    ``passes`` per axis when the mesh is unknown (n → ∞ bound).  With a
    KNOWN mesh, an axis absent from it (or of size 1) is an identity
    collective — zero wire, not the ∞ bound: pricing a tp-annotated
    program at tp = 1 must not carry phantom Megatron bytes (the
    exposed-comm ranking compares tp = 1 configs against real tp
    splits)."""
    axes = attrs.get("_axis_name") or ()
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    if not axes:
        axes = (None,)
    total = 0.0
    for ax in axes:
        n = (axis_sizes or {}).get(ax) if ax is not None else None
        if n is None and ax is not None and axis_sizes:
            continue                 # known mesh, axis not on it
        total += passes * ((n - 1) / n if n and n > 1 else
                           (0.0 if n == 1 else 1.0))
    return total


def _collective_wire(passes):
    """Build a ``wire`` accounting fn for a (possibly quantized) reduce
    collective moving its payload ``passes`` times per axis."""
    def wire(ins, attrs, axis_sizes=None):
        from .quantize_wire import quant_spec_of
        numel, width = 0, 4
        for sig in ins.get("X", []):
            if sig is None or sig.shape is None or not _known(sig.shape):
                return None              # dynamic payload — no claim
            numel += _numel(sig.shape)
            width = _wire_width(sig.dtype)
        if not numel:
            return None
        factor = _ring_factor(attrs, axis_sizes, passes)
        logical = int(numel * width * factor)
        spec = quant_spec_of(attrs)
        per_pass = spec.wire_bytes(numel) if spec is not None \
            else numel * width
        return logical, int(per_pass * factor)
    return wire


#: collective op type → its ``wire`` accounting fn (2 payload passes for
#: all-reduce shapes, 1 for scatter/gather halves).  Per-STEP training
#: cost: ops whose backward transposes to another collective price both
#: directions — fsdp_all_gather (fwd gather + bwd psum_scatter),
#: mp_allreduce_sum (fwd psum, bwd identity) and mp_copy (fwd identity,
#: bwd psum) each move the payload the listed number of passes so the
#: planner's ring-cost channel covers the Megatron f/g pair and the
#: ZeRO-3 gathers, not just the post-backward grad sync.
def _pipe_boundary_wire(ins, attrs, axis_sizes=None):
    """Per-STEP wire bytes of one pipeline stage cut: the boundary
    payload crosses the cut once per microbatch forward (ppermute hop to
    stage+1) and once per microbatch backward (the cotangent hop back),
    and the microbatch slices sum to the full batch — so per step the
    cut moves 2 × payload point-to-point, independent of the pipe
    degree.  Zero when the mesh is known and the pipe axis is absent or
    size 1 (the identity degenerate)."""
    numel_bytes = 0
    for sig in ins.get("X", []):
        if sig is None or sig.shape is None or not _known(sig.shape):
            return None
        numel_bytes += _numel(sig.shape) * _wire_width(sig.dtype)
    if not numel_bytes:
        return None
    ax = attrs.get("_axis_name")
    if axis_sizes is not None:
        n = (axis_sizes or {}).get(ax, 1)
        if not n or n <= 1:
            return 0, 0
    total = 2 * numel_bytes
    return total, total


def _c_embedding_wire(ins, attrs, axis_sizes=None):
    """Vocab-parallel embedding: the [*, dim] lookup result is psummed
    over the model axis in forward (the backward transpose is the
    identity), so the cut moves one ring all-reduce of the OUT payload
    — 2·(n-1)/n · ids_numel · dim · width."""
    w, ids = _sig(ins, "W"), _sig(ins, "Ids")
    if w is None or ids is None or w.shape is None or ids.shape is None \
            or not _known(w.shape) or not _known(ids.shape):
        return None
    numel = _numel(ids.shape) * w.shape[-1]
    factor = _ring_factor(attrs, axis_sizes, 2)
    total = int(numel * _wire_width(w.dtype) * factor)
    return total, total


_WIRE_SPECS = {
    "pipe_stage_boundary": _pipe_boundary_wire,
    # MoE/reshard dispatch: fwd a2a + the bwd a2a transpose, (n-1)/n each
    "alltoall": _collective_wire(2),
    # expert exchange (decomposed MoE): each of the dispatch/combine ops
    # moves its payload once forward and once in the backward transpose,
    # (n-1)/n each — the pair therefore prices 4 a2a passes per step.
    # quant_spec reprices the payload at the CompressionSpec tier.
    "c_expert_alltoall": _collective_wire(2),
    # init-time weight sync: one ring broadcast pass, no backward
    "c_broadcast": _collective_wire(1),
    "c_embedding": _c_embedding_wire,
    "c_allreduce_sum": _collective_wire(2),
    "c_fused_allreduce_sum": _collective_wire(2),
    "c_quant_allreduce_sum": _collective_wire(2),
    "c_fused_quant_allreduce_sum": _collective_wire(2),
    "zero_reduce_scatter": _collective_wire(1),
    "quant_reduce_scatter": _collective_wire(1),
    "c_reducescatter": _collective_wire(1),
    "zero_all_gather": _collective_wire(1),
    # Megatron forward gather: in the training step autodiff transposes
    # the all_gather into a reduce_scatter of the cotangent, so the
    # per-step wire is 2 ring passes (spec_audit compares each half
    # against its HLO kind)
    "c_allgather": _collective_wire(2),
    "fsdp_all_gather": _collective_wire(2),
    "mp_allreduce_sum": _collective_wire(2),
    "mp_copy": _collective_wire(2),
}


def collective_wire_bytes(op_type, ins, attrs, axis_sizes=None):
    """(logical_bytes, wire_bytes) for one collective op, or None when
    the op has no wire accounting or its payload is dynamic."""
    from .registry import OP_SPECS
    spec = OP_SPECS.get(op_type)
    fn = getattr(spec, "wire", None) if spec is not None else None
    if fn is None:
        return None
    return fn(ins, attrs, axis_sizes)


# ---------------------------------------------------------------------------
# Pallas lowering channel — the per-op custom-kernel tier
# ---------------------------------------------------------------------------
#
# Each PallasLowering below carries a TRACE-FREE supported() predicate
# mirroring exactly what its kernel rejects (flash tiling rules, the
# fused-Adam size/alignment floor, the dequant-accumulate block layout),
# so analysis.kernel_routing_report can state per program which ops WILL
# lower to a custom kernel at given shapes — and why the rest fall back —
# with zero compiles.  The predicates accept VarSig (static analysis) and
# traced jax arrays (op-impl dispatch) interchangeably via _shape_of.
# ``axis_sizes`` is the mesh map for GLOBAL (program-level) shapes; the
# trace-time convention is axis_sizes=None with shapes already
# device-local.


def _attn_heads(attrs, hidden):
    """The head count the op impl resolves (attention_ops._resolve_heads):
    ``n_head``, or ``hidden // head_dim`` when tensor-parallel callers
    stamp the head size."""
    head_dim = attrs.get("head_dim")
    if head_dim:
        return max(1, hidden // int(head_dim))
    return attrs.get("n_head", 1)


def _attn_bhsd(ins, attrs):
    """(b, h, s, sk, d) from the fused_attention Q/K/V slots, or None."""
    q = _shape_of(_sig(ins, "Q"))
    k = _shape_of(_sig(ins, "K"))
    if q is None or k is None or len(q) != 3:
        return None
    hd = q[-1]
    if hd < 0 or q[1] < 0 or k[1] < 0:
        return None
    n_head = _attn_heads(attrs, hd)
    if n_head <= 0 or hd % n_head:
        return None
    return q[0], n_head, q[1], k[1], hd // n_head


def _flash_tiles(s, sk, d, causal=False):
    """The flash kernel's static tiling rules → (ok, reason)."""
    if s % 128 or sk % 128:
        return False, f"seq:{s}x{sk}%128"
    if d % 128 and d != 64:
        return False, f"head-dim:{d}"
    if causal and s != sk:
        return False, "causal-rectangular"
    return True, ""


def _pl_flash_supported(ins, attrs, axis_sizes=None):
    dims = _attn_bhsd(ins, attrs)
    if dims is None:
        return False, "shape-unknown"
    b, h, s, sk, d = dims
    return _flash_tiles(s, sk, d, causal=bool(attrs.get("causal")))


def _pl_tile_supported(ins, attrs, axis_sizes=None):
    """One-tile route gate (ops/pallas/attention_tile.py): the whole
    attention is one (128, 128) tile per head — non-causal, Sq == Sk ==
    128, a head-shared additive bias (or none).  Everything else is the
    blockwise kernel's."""
    from .pallas.attention_tile import tiles
    dims = _attn_bhsd(ins, attrs)
    if dims is None:
        return False, "shape-unknown"
    b, h, s, sk, d = dims
    bias = _sig(ins, "AttnBias")
    # an unknown bias shape is () here: not 4-D, so the rule rejects it
    bias_shape = None if bias is None else _shape_of(bias) or ()
    return tiles(s, sk, h, d, causal=bool(attrs.get("causal")),
                 bias_shape=bias_shape)


def _pl_ring_supported(ins, attrs, axis_sizes=None):
    if _sig(ins, "AttnBias") is not None:
        return False, "ring-explicit-bias"
    dims = _attn_bhsd(ins, attrs)
    if dims is None:
        return False, "shape-unknown"
    b, h, s, sk, d = dims
    ax = attrs.get("_seq_axis")
    if axis_sizes is not None:
        # static view: program shapes are global — the ring step sees
        # the 1/sp sequence shard
        sp = axis_sizes.get(ax)
        if not sp:
            return False, f"sp-axis:{ax}-unknown"
        if s % sp or sk % sp:
            return False, f"seq:{s}%sp{sp}"
        s, sk = s // sp, sk // sp
    return _flash_tiles(s, sk, d)


def _gqa_stamped(attrs, axis_sizes=None):
    return bool(attrs.get("window") or attrs.get("num_kv_heads"))


def _pl_gqa_supported(ins, attrs, axis_sizes=None):
    """Grouped-head / windowed decoder attention gate
    (ops/pallas/flash_gqa.py): causal self-attention, no bias, the
    sequence in tiles of 128, heads of a multiple of 128 lanes."""
    from .pallas.flash_gqa import supported
    dims = _attn_bhsd(ins, attrs)
    if dims is None:
        return False, "shape-unknown"
    b, h, s, sk, d = dims
    if s != sk or not attrs.get("causal") or \
            _sig(ins, "AttnBias") is not None:
        return False, "gqa-needs-causal-self-attention-without-bias"
    return supported(s, d, h, int(attrs.get("num_kv_heads") or h),
                     backend="tpu")


def _lower_gqa_attention(ctx, ins, attrs):
    from .attention_ops import lower_gqa_attention
    return lower_gqa_attention(ctx, ins, attrs, use_flash=True)


def _ring_stamped(attrs, axis_sizes):
    ax = attrs.get("_seq_axis")
    return bool(ax) and (axis_sizes is None or ax in (axis_sizes or {}))


def _rows_last_dim(sig, bna):
    sh = _shape_of(sig)
    if sh is None or any(d < 0 for d in sh[bna:]):
        return None
    d = _numel(sh[bna:])
    r = -1 if any(x < 0 for x in sh[:bna]) else _numel(sh[:bna])
    return r, d


def _pl_ln_supported(ins, attrs, axis_sizes=None):
    if _sig(ins, "Scale") is None or _sig(ins, "Bias") is None:
        return False, "no-affine"
    rd = _rows_last_dim(_sig(ins, "X"), attrs.get("begin_norm_axis", 1))
    if rd is None:
        return False, "shape-unknown"
    from .pallas.fused_ops import LN_MAX_D
    _, d = rd
    if d % 128 or d > LN_MAX_D:
        return False, f"norm-dim:{d}"
    return True, ""


def _pl_add_ln_supported(ins, attrs, axis_sizes=None):
    if _sig(ins, "Residual") is None:
        return False, "no-residual"
    return _pl_ln_supported(ins, attrs, axis_sizes)


def _pl_bias_gelu_supported(ins, attrs, axis_sizes=None):
    functors = list(attrs.get("functor_list",
                              ["elementwise_add", "relu"]))
    if functors != ["elementwise_add", "gelu"]:
        return False, "functors:" + "+".join(functors)
    xs = _shape_of(_sig(ins, "X"))
    ys = _shape_of(_sig(ins, "Y"))
    if xs is None or ys is None:
        return False, "shape-unknown"
    if len(ys) != 1 or xs[-1] != ys[0]:
        return False, "bias-not-last-dim"
    axis = attrs.get("axis", -1)
    if axis not in (-1, len(xs) - 1):
        return False, f"axis:{axis}"
    d = xs[-1]
    if d < 0:
        return False, "shape-unknown"
    from .pallas.fused_ops import BG_MAX_D
    if d % 128 or d > BG_MAX_D:
        return False, f"dim:{d}"
    return True, ""


def _pl_mhm_supported(ins, attrs, axis_sizes=None):
    if attrs.get("dropout_rate") and not attrs.get("is_test"):
        return False, "dropout"
    q = _shape_of(_sig(ins, "Q"))
    k = _shape_of(_sig(ins, "K"))
    if q is None or k is None or len(q) != 4:
        return False, "shape-unknown"
    if q[2] < 0 or k[2] < 0:
        return False, "shape-unknown"
    return _flash_tiles(q[2], k[2], q[3])


def _quant_shard_blocks(ins, attrs, axis_sizes):
    """(n_peers, per-shard quant blocks, spec) for a quantized
    collective, or (None, None, spec) when the mesh/payload is
    unknown."""
    from .quantize_wire import CompressionSpec
    spec = CompressionSpec.from_attr(attrs.get("quant_spec"))
    axes = attrs.get("_axis_name") or ()
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    n = (axis_sizes or {}).get(axes[0]) if axes else None
    numel = 0
    for sig in ins.get("X", []):
        sh = _shape_of(sig)
        if sh is None or any(d < 0 for d in sh):
            return None, None, spec
        numel += _numel(sh)
    if not numel or not n:
        return n, None, spec
    pad = n * spec.block_size
    shard_blocks = (numel + pad - 1) // pad
    return n, shard_blocks, spec


def _pl_dequant_acc_supported(ins, attrs, axis_sizes=None):
    from .pallas import quant_kernels as qk
    n, shard_blocks, spec = _quant_shard_blocks(ins, attrs, axis_sizes)
    if spec is None:
        return False, "no-quant-spec"
    # backend is re-checked by pallas_route; pass a TPU backend so this
    # predicate reports only the shape/layout capability
    return qk.supported(n, shard_blocks, spec, backend="tpu")


def _lower_flash_attention(ctx, ins, attrs):
    from .attention_ops import lower_flash_attention
    return lower_flash_attention(ctx, ins, attrs)


def _lower_attention_tile(ctx, ins, attrs):
    from .attention_ops import lower_attention_tile
    return lower_attention_tile(ctx, ins, attrs)


def _lower_ring_flash_attention(ctx, ins, attrs):
    from .attention_ops import lower_ring_attention
    return lower_ring_attention(ctx, ins, attrs, use_flash=True)


def _lower_cached_flash_attention(ctx, ins, attrs):
    from .attention_ops import lower_cached_attention
    return lower_cached_attention(ctx, ins, attrs, use_flash=True)


def _lower_paged_decode_attention(ctx, ins, attrs, wide=False):
    from .attention_ops import lower_paged_decode_attention
    return lower_paged_decode_attention(ctx, ins, attrs, wide=wide)


def _pl_paged_supported(ins, attrs, axis_sizes=None, wide=False):
    """Paged decode route gate (ops/pallas/paged_attention.py): a
    cache read with a one-token query and no ``QPos``, float32 pools in
    pages of a multiple of 8 tokens or bfloat16 pools in pages of a
    multiple of 16, the hidden width in 128-lane tiles and a head size
    that divides 128 — or, ``wide``, that is a multiple of 128."""
    from .pallas import paged_attention
    supported = paged_attention.supported_wide if wide \
        else paged_attention.supported
    q = _shape_of(_sig(ins, "Q"))
    pool = _sig(ins, "KPool")
    pshape = _shape_of(pool)
    if pool is None:
        return False, "not-cached"
    if q is None or len(q) != 3 or pshape is None or len(pshape) != 3 \
            or min(q[1], q[2], pshape[1]) < 0:
        return False, "shape-unknown"
    return supported(q[1], q[2], _attn_heads(attrs, q[2]), pshape[1],
                     pool.dtype, has_qpos=_sig(ins, "QPos") is not None)


def _pl_cached_supported(ins, attrs, axis_sizes=None):
    """Gather-then-flash route gate: the gathered context hands the SAME
    blockwise flash kernel a (B, H, Sq, T) problem, so the kernel's
    tiling rules apply with Sk = the table-window length T.  It is the
    cached read's route for Sq > 1 (chunked and prefix-hit prefill); a
    decode step (Sq = 1) is the paged route's, and where that one's rule
    refuses it too, the gather + einsum composition's."""
    if _sig(ins, "KPool") is None:
        return False, "not-cached"
    q = _shape_of(_sig(ins, "Q"))
    t = _cached_attn_total(ins)
    if q is None or len(q) != 3 or t is None:
        return False, "shape-unknown"
    hd = q[-1]
    if hd < 0 or q[1] < 0:
        return False, "shape-unknown"
    n_head = _attn_heads(attrs, hd)
    if n_head <= 0 or hd % n_head:
        return False, "shape-unknown"
    return _flash_tiles(q[1], t, hd // n_head)


_FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")

#: the Pallas tier, one route table entry per op (kernel names are the
#: census contract: each must appear as a tpu_custom_call kernel_name in
#: the TPU-lowered module when the route reports a hit)
_PL_FLASH = PallasLowering(
    "flash_attention", flag="use_flash_attention", attr="use_flash",
    match=lambda attrs, ax: not _ring_stamped(attrs, ax)
    and not attrs.get("_cached") and not _gqa_stamped(attrs),
    supported=_pl_flash_supported, lower=_lower_flash_attention,
    kernels=_FLASH_KERNELS)
# a decoder's grouped K/V heads and sliding window: attrs alone pick the
# route, so no other attention (and neither cell that has one) sees it
_PL_GQA = PallasLowering(
    "flash_gqa_attention", flag="use_flash_attention", attr="use_flash",
    match=_gqa_stamped,
    supported=_pl_gqa_supported, lower=_lower_gqa_attention,
    kernels=("flash_gqa_fwd", "flash_gqa_bwd_dq", "flash_gqa_bwd_dkv"))
# the plain (no ring, no KV pool) fused_attention tries this route first:
# at one tile the blockwise kernel's machinery is pure cost (PERF.md,
# PR 28); any other shape falls through to _PL_FLASH
_PL_TILE = PallasLowering(
    "attention_tile", flag="use_flash_attention", attr="use_flash",
    match=_PL_FLASH.match,
    supported=_pl_tile_supported, lower=_lower_attention_tile,
    kernels=("attn_tile_fwd", "attn_tile_bwd"))
_PL_RING = PallasLowering(
    "ring_flash_attention", flag="use_flash_attention", attr="use_flash",
    match=_ring_stamped,
    supported=_pl_ring_supported, lower=_lower_ring_flash_attention,
    kernels=_FLASH_KERNELS)
_PL_CACHED = PallasLowering(
    "cached_flash_attention", flag="use_flash_attention",
    attr="use_flash",
    # applicability rides the builder-stamped `_cached` attr (match
    # cannot see the input slots): a non-cached fused_attention skips
    # this route SILENTLY instead of polluting its fallback reasons
    match=lambda attrs, ax: bool(attrs.get("_cached"))
    and not _ring_stamped(attrs, ax),
    supported=_pl_cached_supported, lower=_lower_cached_flash_attention,
    kernels=_FLASH_KERNELS)
# the cached read of a decode step (Sq == 1) is tried first: it reads the
# pools through the block table where _PL_CACHED gathers the whole table
_PL_PAGED = PallasLowering(
    "paged_decode_attention", flag="use_flash_attention",
    attr="use_flash", match=_PL_CACHED.match,
    supported=_pl_paged_supported, lower=_lower_paged_decode_attention,
    kernels=("paged_decode_attn",))
# heads that are whole lane tiles (a head's slab of a page is a slice the
# MXU takes as it lies) have a body of their own, tried first; narrower
# heads share a tile and keep _PL_PAGED's
_PL_PAGED_WIDE = PallasLowering(
    "paged_decode_attention_wide", flag="use_flash_attention",
    attr="use_flash", match=_PL_CACHED.match,
    supported=functools.partial(_pl_paged_supported, wide=True),
    lower=functools.partial(_lower_paged_decode_attention, wide=True),
    kernels=("paged_decode_attn_wide",))
# a decode step's read of the paged LATENT cache (mla_attention with a
# Pool, one query token a row): the absorbed form on the MXU
_PL_MLA_PAGED = PallasLowering(
    "mla_paged_decode", flag="use_flash_attention", attr="use_flash",
    match=lambda attrs, ax: bool(attrs.get("_cached")),
    supported=_pl_mla_paged_supported, lower=_lower_mla_paged_decode,
    kernels=("mla_paged_decode",))
# the gated delta rule's two serving forms (ops/linear_attn_ops.py picks
# by the query's length): one token a row against the state pool, and the
# chain over a chunk's sub-chunks
_PL_GDN_DECODE = PallasLowering(
    "gdn_decode", flag="use_pallas_fused",
    supported=_pl_gdn_decode_supported, lower=_lower_gdn_decode,
    kernels=("gdn_decode",))
_PL_GDN_CHUNK = PallasLowering(
    "gdn_chunk", flag="use_pallas_fused",
    supported=_pl_gdn_chunk_supported, lower=_lower_gdn_chunk,
    kernels=("gdn_chunk",))
_PL_GMM = PallasLowering(
    "moe_grouped_matmul", flag="use_pallas_fused",
    supported=_pl_gmm_supported,
    kernels=("moe_gmm", "moe_gmm_wgrad"))
_PL_LN = PallasLowering(
    "fused_layer_norm", flag="use_pallas_fused",
    supported=_pl_ln_supported,
    kernels=("fused_layer_norm_fwd", "fused_layer_norm_bwd"))
_PL_ADD_LN = PallasLowering(
    "fused_add_layer_norm", flag="use_pallas_fused",
    supported=_pl_add_ln_supported,
    kernels=("fused_add_layer_norm_fwd", "fused_add_layer_norm_bwd"))
_PL_BIAS_GELU = PallasLowering(
    "fused_bias_gelu", flag="use_pallas_fused",
    supported=_pl_bias_gelu_supported,
    kernels=("fused_bias_gelu_fwd", "fused_bias_gelu_bwd"))
_PL_MHM = PallasLowering(
    "flash_attention", flag="use_flash_attention",
    supported=_pl_mhm_supported,
    kernels=("flash_fwd",))
_PL_DEQUANT_ACC = PallasLowering(
    "dequant_accumulate", flag="use_pallas_fused",
    supported=_pl_dequant_acc_supported,
    kernels=("dequant_accumulate",))
_PL_DEQUANT_ACC_AR = PallasLowering(
    "dequant_accumulate", flag="use_pallas_fused",
    supported=_pl_dequant_acc_supported,
    kernels=("dequant_accumulate", "dequant_accumulate_requant"))


def register_default_specs():
    """Register the built-in spec library (idempotent).

    ``mem_transparent=True`` marks the fusible families for the memory
    analyzer's residual-class collapse (framework/memory_analysis.py):
    XLA assigns one buffer to a view/elementwise/activation chain, so
    these ops join their input's alias class instead of adding bytes.
    """
    # elementwise family (add/sub/mul fuse into their producer's buffer;
    # div/max/min keep both operands as backward residuals — opaque)
    for name in ("elementwise_add", "elementwise_sub", "elementwise_mul"):
        op_spec(name, infer=elementwise(), mem_transparent=True)
    for name in ("elementwise_div", "elementwise_max", "elementwise_min",
                 "elementwise_pow", "elementwise_mod",
                 "elementwise_floordiv"):
        op_spec(name, infer=elementwise())
    for name in ("equal", "not_equal", "less_than", "less_equal",
                 "greater_than", "greater_equal"):
        op_spec(name, infer=elementwise(out_dtype="bool", check_dtype=False),
                mem_transparent=True)
    for name in ("logical_and", "logical_or", "logical_xor"):
        op_spec(name, infer=elementwise(out_dtype="bool", check_dtype=False),
                mem_transparent=True)
    op_spec("logical_not", infer=same_as_input(), mem_transparent=True)

    # unary shape/dtype-preserving (all fusible elementwise)
    for name in ("relu", "relu6", "sigmoid", "tanh", "gelu",
                 "exp", "log", "sqrt", "rsqrt", "square",
                 "abs", "floor", "ceil", "round", "sign", "softplus",
                 "swish", "hard_swish", "hard_sigmoid", "leaky_relu",
                 "scale", "assign", "clip", "pow",
                 "softsign", "erf", "sin", "cos"):
        op_spec(name, infer=same_as_input(), mem_transparent=True)
    # softmax family carries the elementwise flops channel (5 prims per
    # logit element) so the spec auditor's XLA reconciliation closes on
    # attention-heavy programs; still fusible/transparent for memory
    for name in ("softmax", "log_softmax"):
        op_spec(name, infer=same_as_input(), mem_transparent=True,
                flops=_flops_elemwise(5))
    op_spec("dropout", infer=_infer_dropout, mem_transparent=True)

    # math
    op_spec("mul", infer=_infer_mul, flops=_flops_mul)
    op_spec("matmul", infer=_infer_matmul, flops=_flops_matmul)
    op_spec("matmul_v2", infer=_infer_matmul, flops=_flops_matmul)
    op_spec("mean", infer=_infer_mean)
    op_spec("sum", infer=_infer_sum)
    for name in ("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
                 "reduce_prod"):
        op_spec(name, infer=_infer_reduce)
    op_spec("reduce_all", infer=_infer_reduce)
    op_spec("reduce_any", infer=_infer_reduce)
    op_spec("cast", infer=_infer_cast, mem_transparent=True)

    # nn
    op_spec("conv2d", infer=_infer_conv2d, flops=_flops_conv2d)
    op_spec("depthwise_conv2d", infer=_infer_conv2d, flops=_flops_conv2d)
    op_spec("pool2d", infer=_infer_pool2d)
    op_spec("layer_norm", infer=_infer_layer_norm, pallas=(_PL_LN,))
    op_spec("batch_norm", infer=_infer_batch_norm)
    op_spec("lookup_table", infer=_infer_lookup_table)
    op_spec("lookup_table_v2", infer=_infer_lookup_table_v2)
    op_spec("softmax_with_cross_entropy", infer=_infer_softmax_with_ce,
            mem_backward_extra=_softmax_ce_extra_bytes,
            flops=_flops_softmax_ce)
    op_spec("cross_entropy", infer=_infer_cross_entropy,
            flops=_flops_elemwise(3))
    op_spec("cross_entropy2", infer=_infer_cross_entropy,
            flops=_flops_elemwise(3))
    op_spec("fused_attention", infer=_infer_fused_attention,
            mem_backward_extra=_attention_probs_bytes,
            flops=_flops_fused_attention,
            pallas=(_PL_RING, _PL_PAGED_WIDE, _PL_PAGED, _PL_CACHED,
                    _PL_GQA, _PL_TILE, _PL_FLASH))
    op_spec("cache_write", infer=_infer_cache_write)
    op_spec("decode_chain", infer=_infer_decode_chain)

    # tensor manipulation (views are pure aliases)
    op_spec("reshape2", infer=_infer_reshape2, mem_transparent=True)
    op_spec("reshape", infer=_infer_reshape2, mem_transparent=True)
    op_spec("transpose2", infer=_infer_transpose2)
    op_spec("transpose", infer=_infer_transpose2)
    op_spec("unsqueeze2", infer=_infer_unsqueeze2, mem_transparent=True)
    op_spec("squeeze2", infer=None, mem_transparent=True)
    op_spec("concat", infer=_infer_concat)
    op_spec("split", infer=_infer_split)
    op_spec("top_k", infer=_infer_top_k)
    op_spec("one_hot", infer=_infer_one_hot)
    # routing-primitive tail the MoE census exposes (_route lowers to
    # one_hot/cumsum/argsort-shaped HLO): shape-transparent scan and the
    # sort pair — specced so the SPEC_AUDIT coverage ratchet advances
    op_spec("cumsum", infer=same_as_input(), flops=_flops_elemwise(1))
    op_spec("argsort", infer=_infer_argsort)
    op_spec("fill_zeros_like", infer=_infer_fill_zeros_like)
    op_spec("where", infer=_infer_where)
    op_spec("fill_constant", infer=from_shape_attr())
    for name in ("gaussian_random", "uniform_random",
                 "truncated_gaussian_random"):
        op_spec(name, infer=from_shape_attr())

    # optimizer updates: elementwise compositions, fused by XLA in each
    # tensor's own layout (often into the gradient's producer)
    for name in ("sgd", "momentum", "adamax", "adagrad", "adam", "adamw",
                 "rmsprop", "lars_momentum", "lamb"):
        op_spec(name, infer=_infer_opt_update)

    # meta ops (known to the static layer, no shape opinion)
    for name in ("feed", "fetch", "backward", "pipeline", "assign_value",
                 "fill_constant_batch_size_like", "expand", "expand_as",
                 "slice", "strided_slice", "stack", "gather", "gather_nd",
                 "scatter", "arg_max", "arg_min", "shape",
                 "accuracy", "auc", "increment", "put_along_axis",
                 "take_along_axis", "tile", "range", "linspace",
                 "while_loop", "conditional_block", "switch_case",
                 "static_rnn", "py_func", "print", "beam_gather",
                 "gather_tree", "gather_tokens",
                 "fused_bn_activation",
                 "fused_embedding_eltwise_layernorm", "fc",
                 "affine_channel",
                 "uniform_random_batch_size_like", "seed"):
        op_spec(name, infer=None)
    # fused-pattern ops with Pallas routes (no shape opinion, but the
    # kernel tier gate is statically enumerable)
    op_spec("multihead_matmul", infer=None, pallas=(_PL_MHM,))
    op_spec("fused_elemwise_activation", infer=None,
            pallas=(_PL_BIAS_GELU,))
    op_spec("fused_add_layernorm", infer=None, pallas=(_PL_ADD_LN,))
    op_spec("flatten2", infer=None, mem_transparent=True)
    op_spec("flatten", infer=None, mem_transparent=True)

    # collectives — flagged so the distributed-soundness pass can find
    # them structurally (divergent control flow, sequence divergence)
    for name in ("c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
                 "c_allreduce_prod", "mp_allreduce_sum"):
        op_spec(name, infer=_infer_collective_same, collective=True,
                wire=_WIRE_SPECS.get(name))
    op_spec("c_quant_allreduce_sum", infer=_infer_collective_same,
            collective=True, wire=_WIRE_SPECS["c_quant_allreduce_sum"],
            pallas=(_PL_DEQUANT_ACC_AR,))
    op_spec("c_identity", infer=_infer_collective_same)
    op_spec("c_sync_calc_stream", infer=_infer_collective_same)
    op_spec("c_sync_comm_stream", infer=_infer_collective_same)
    for name in ("c_fused_allreduce_sum",
                 "c_broadcast", "c_allgather",
                 "c_reducescatter", "c_concat", "c_split", "alltoall",
                 "collective_permute", "zero_reduce_scatter",
                 "zero_all_gather", "zero_shard_slice",
                 "local_sgd_sync", "moe_ffn"):
        op_spec(name, infer=None, collective=True,
                wire=_WIRE_SPECS.get(name))
    # quantized collectives: the receive stage routes onto the fused
    # dequant-upcast-accumulate(-requantize) kernel
    op_spec("c_fused_quant_allreduce_sum", infer=None, collective=True,
            wire=_WIRE_SPECS["c_fused_quant_allreduce_sum"],
            pallas=(_PL_DEQUANT_ACC_AR,))
    op_spec("quant_reduce_scatter", infer=None, collective=True,
            wire=_WIRE_SPECS["quant_reduce_scatter"],
            pallas=(_PL_DEQUANT_ACC,))
    # decomposed MoE pipeline: the expert exchange is the collective
    # (global identity — a cross-device permutation, so its quantized
    # tier is sound blockwise); dispatch/ffn/combine are local compute
    # with the capacity-factor flops the planner prices
    op_spec("c_expert_alltoall", infer=_infer_collective_same,
            collective=True, wire=_WIRE_SPECS["c_expert_alltoall"])
    op_spec("moe_dispatch", infer=_infer_moe_dispatch,
            flops=_flops_moe_dispatch)
    op_spec("moe_expert_ffn", infer=_infer_moe_expert_ffn,
            flops=_flops_moe_expert_ffn)
    op_spec("moe_combine", infer=same_as_input(),
            flops=_flops_moe_combine)
    # the pre-norm rotary decoder with dropless experts
    # (ops/decoder_lm_ops.py)
    op_spec("rms_norm", infer=same_as_input("X", "Y"),
            flops=_flops_elemwise(4))
    op_spec("rotary_embedding", infer=same_as_input(),
            flops=_flops_elemwise(3))
    op_spec("moe_topk_router", infer=_infer_moe_topk_router)
    op_spec("lm_head_logits", infer=_infer_lm_head_logits)
    op_spec("mla_attention", infer=_infer_mla_attention,
            pallas=(_PL_MLA_PAGED,))
    # linear-attention layers of a served hybrid decoder
    # (ops/linear_attn_ops.py)
    op_spec("gated_delta_rule", infer=_infer_gated_delta_rule,
            pallas=(_PL_GDN_DECODE, _PL_GDN_CHUNK))
    op_spec("causal_conv1d", infer=_infer_causal_conv1d)
    op_spec("gated_rms_norm", infer=same_as_input())
    op_spec("moe_grouped_ffn", infer=_infer_moe_grouped_ffn,
            flops=_flops_moe_grouped_ffn, pallas=(_PL_GMM,))
    op_spec("moe_load_stats", infer=same_as_input("Acc", "AccOut"))
    op_spec("lm_head_loss", infer=_infer_lm_head_loss,
            flops=_flops_lm_head_loss)
    # vocab-parallel embedding: Out = Ids.shape + [dim] exactly like
    # lookup_table_v2 (the psum keeps the global [.., dim] width).
    # Without this the tp-BERT shape propagation stalled at op 0 and
    # the flops channel priced the whole encoder at 0 — the exposed-
    # comm roofline then had no compute term to hide wire under.
    op_spec("c_embedding", infer=_infer_c_embedding, collective=True,
            wire=_WIRE_SPECS.get("c_embedding"),
            flops=_flops_c_embedding)
    # Megatron f op: identity forward (psum transpose in backward)
    op_spec("mp_copy", infer=_infer_collective_same, collective=True,
            wire=_WIRE_SPECS.get("mp_copy"))
    # pipeline stage-cut marker (framework/pipe.py): identity op whose
    # wire spec prices the per-microbatch ppermute hops (fwd boundary +
    # bwd cotangent) the scheduled 1F1B lowering realises at the cut
    op_spec("pipe_stage_boundary", infer=_infer_pipe_boundary,
            collective=True, wire=_WIRE_SPECS["pipe_stage_boundary"])
    # ZeRO-3 on-demand parameter gather (framework/fsdp.py): metadata is
    # GLOBAL throughout, so Out mirrors X's declared signature
    op_spec("fsdp_all_gather", infer=_infer_collective_same,
            collective=True, wire=_WIRE_SPECS["fsdp_all_gather"])
    # zero_shard_slice/mp_copy are local ops but ride the collective
    # schedule (their placement must agree across ranks), so they are
    # flagged too.


register_default_specs()
