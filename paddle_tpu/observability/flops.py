"""Static per-step FLOPs (the MFU numerator) and device peak FLOPs (the
denominator).

The telemetry recorder needs the benchmark's analytic FLOP count
(``benchmark/flops.py``) *per program, statically, without a trace*: the
op-spec metadata channel (ops/registry.py ``op_spec(..., flops=...)``)
prices each GEMM-class op from its inferred input signatures —
``flops(ins, outs, attrs) -> float`` counting 2 FLOPs per MAC — and
:func:`estimate_step_flops` walks the program with the same shape
propagation the memory analyzer uses.  Backward GEMMs cost 2× forward
(dX and dW), so a program containing the ``backward`` meta-op prices at
3× its forward GEMM count — exactly the analytic model
``benchmark.flops.bert_flops_per_step`` uses
(tests/test_tpu_lowering.py brackets it with XLA's own count).

Peak FLOPs come from a device-kind table (bf16 dense peak per chip;
TPU generations the framework targets) with a CPU fallback, overridable
by ``flag("device_peak_flops")`` for exotic hosts.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

#: bf16 dense peak FLOP/s per chip, by device-kind substring (first
#: match wins; lowercase).  Sources: published TPU spec sheets.
DEVICE_PEAK_FLOPS = (
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
)

#: CPU fallback: an optimistic many-core AVX host peak.  MFU numbers on
#: CPU are only meaningful relative to each other; the fallback keeps
#: them finite and in (0, 1] for the framework-overhead regimes the CPU
#: benches run in.
CPU_FALLBACK_FLOPS = 5e11


def device_peak_flops(device=None) -> float:
    """Peak FLOP/s of ``device`` (default: jax.devices()[0]).
    ``flag("device_peak_flops")`` (> 0) overrides the table."""
    from ..flags import flag
    override = float(flag("device_peak_flops") or 0.0)
    if override > 0:
        return override
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = (getattr(device, "device_kind", "") or "").lower()
    platform = (getattr(device, "platform", "") or "").lower()
    if platform == "tpu" or "tpu" in kind:
        for sub, peak in DEVICE_PEAK_FLOPS:
            if sub in kind:
                return peak
        raise ValueError(
            f"no peak FLOP/s on file for TPU device_kind "
            f"{getattr(device, 'device_kind', None)!r} — add it to "
            f"DEVICE_PEAK_FLOPS with its source")
    return CPU_FALLBACK_FLOPS


def device_info(device=None) -> Dict[str, Any]:
    if device is None:
        import jax
        device = jax.devices()[0]
    return {"platform": getattr(device, "platform", None),
            "device_kind": getattr(device, "device_kind", None),
            "peak_flops": device_peak_flops(device)}


#: flops-specced ops whose count is elementwise/transcendental class,
#: NOT GEMM MACs — priced by the spec channel so the differential spec
#: auditor (framework/spec_audit.py) can reconcile the program total
#: against XLA cost_analysis, but EXCLUDED from the MFU numerator:
#: the MFU convention (benchmark.flops.bert_flops_per_step)
#: counts GEMMs only, and the telemetry band tests pin that ratio.
NON_GEMM_FLOPS_OPS = frozenset({
    "softmax", "log_softmax", "softmax_with_cross_entropy",
    "cross_entropy", "cross_entropy2", "c_embedding",
})


def estimate_step_flops(program, feed_shapes=None,
                        fetch_names: Iterable[str] = (),
                        unknown_dim: int = 1) -> Dict[str, Any]:
    """Static GEMM-class FLOPs for ONE step of ``program`` via the
    op-spec ``flops`` channel.

    Returns ``{"fwd_flops", "total_flops", "has_backward", "by_op",
    "unpriced"}``: ``total_flops`` applies the 3× fwd+bwd multiplier
    when the program carries a ``backward`` meta-op (GEMM backward =
    two GEMMs), else equals ``fwd_flops``.  ``unpriced`` lists op types
    that looked compute-bearing (matmul family) but had no priced spec
    or unknown shapes — a non-empty list means the estimate is a lower
    bound.

    Ops in :data:`NON_GEMM_FLOPS_OPS` are priced in ``by_op`` and the
    ``*_all`` fields (``fwd_flops_all``/``total_flops_all`` — what the
    spec auditor reconciles against XLA's count) but kept out of
    ``fwd_flops``/``total_flops`` so the MFU numerator stays the
    GEMM-only analytic model."""
    from ..ops.registry import OP_SPECS, VarSig
    from ..framework.analysis import VerifyResult, infer_shapes
    from ..framework.memory_analysis import _feed_sigs

    block = program.global_block()
    feed_sigs = _feed_sigs(program, feed_shapes, unknown_dim)
    scratch = VerifyResult(program)
    env = infer_shapes(program, scratch, feed_names=list(feed_sigs),
                       init_env=dict(feed_sigs))

    def sig_of(name):
        s = env.get(name)
        if s is not None and s.shape is not None:
            return s
        v = block._find_var_recursive(name)
        if v is None:
            return s
        return VarSig(tuple(v.shape) or None, v.dtype)

    fwd = 0.0
    fwd_non_gemm = 0.0
    by_op: Dict[str, float] = {}
    unpriced = []
    has_backward = False
    for op in block.ops:
        if op.type == "backward":
            has_backward = True
            continue
        spec = OP_SPECS.get(op.type)
        fn = getattr(spec, "flops", None) if spec is not None else None
        if fn is None:
            continue
        ins = {slot: [sig_of(n) for n in names]
               for slot, names in op.inputs.items()}
        outs = {slot: [sig_of(n) for n in names]
                for slot, names in op.outputs.items()}
        try:
            f = fn(ins, outs, op.attrs)
        except Exception:       # accounting must not kill telemetry
            f = None
        if f is None:
            unpriced.append(op.type)
            continue
        f = float(f)
        if op.type in NON_GEMM_FLOPS_OPS:
            fwd_non_gemm += f
        else:
            fwd += f
        by_op[op.type] = by_op.get(op.type, 0.0) + f
    total = 3.0 * fwd if has_backward else fwd
    fwd_all = fwd + fwd_non_gemm
    return {"fwd_flops": fwd, "total_flops": total,
            "fwd_flops_all": fwd_all,
            "total_flops_all": 3.0 * fwd_all if has_backward else fwd_all,
            "has_backward": has_backward, "by_op": by_op,
            "unpriced": sorted(set(unpriced))}


__all__ = ["device_peak_flops", "device_info", "estimate_step_flops",
           "DEVICE_PEAK_FLOPS", "CPU_FALLBACK_FLOPS",
           "NON_GEMM_FLOPS_OPS"]
