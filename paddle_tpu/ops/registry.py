"""JAX op registry — the analog of the reference's OpRegistry + kernel
dispatch (ref: framework/op_registry.h:223, operator.cc:1032 ChooseKernel).

In the reference every op carries per-(dtype, place, layout) kernels picked
at runtime.  Here there is exactly one implementation per op — a pure JAX
function — because XLA owns dtype/layout/device specialisation.  An op impl
has signature::

    fn(ctx, ins, attrs) -> {slot: array | [arrays]}

where ``ins`` maps input slot names → lists of jax arrays (the reference's
slot convention: "X", "Y", "Out", ...) and ``ctx`` provides PRNG-key
threading and lowering-time info (mesh, train/eval).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax

OPS: Dict[str, Callable] = {}

#: ops that perform host-side I/O (RPC) and must run outside jit — the
#: executor runs programs containing them in host-segmented mode
HOST_OPS: set = set()


def register(name: str):
    def deco(fn):
        if name in OPS:
            raise ValueError(f"op {name!r} registered twice")
        OPS[name] = fn
        return fn
    return deco


# ---------------------------------------------------------------------------
# op_spec — optional static shape/dtype metadata channel
# ---------------------------------------------------------------------------
# The reference runs C++ InferShape/InferVarType at every op insertion
# (ref: framework/op_desc.cc InferShape, shape_inference.h); this rebuild
# deliberately dropped that machinery, so a malformed program only fails
# deep inside jit tracing.  ``op_spec`` restores the metadata channel: an
# op may register, alongside its JAX impl, a trace-free ``infer`` function
# consumed by the static verifier (framework/analysis.py).
#
#     infer(ins, attrs) -> {slot: [VarSig, ...]}   # or None (no opinion)
#
# where ``ins`` maps input slot names → lists of VarSig (shape tuple with
# -1 for unknown dims, canonical dtype string).  An infer function raises
# ``SpecMismatch`` to report an invalid input combination (wrong rank,
# incompatible inner dims, conflicting dtypes); the verifier anchors the
# resulting diagnostic to the op's recorded user callstack.

OP_SPECS: Dict[str, "OpSpec"] = {}


class VarSig:
    """Static (shape, dtype) signature of a variable.  ``shape`` entries of
    -1 are unknown (batch dims); ``shape is None`` means fully unknown."""

    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape = None if shape is None else tuple(int(s) for s in shape)
        self.dtype = str(dtype)

    def __repr__(self):
        return f"VarSig(shape={self.shape}, dtype={self.dtype!r})"

    def __eq__(self, other):
        return (isinstance(other, VarSig) and self.shape == other.shape
                and self.dtype == other.dtype)


class SpecMismatch(Exception):
    """Raised by an ``infer`` function when the op's static inputs are
    inconsistent (the InferShape-failure analog).  ``kind`` distinguishes
    shape from dtype defects for diagnostics."""

    def __init__(self, message: str, kind: str = "shape"):
        super().__init__(message)
        self.kind = kind


class PallasLowering:
    """One Pallas kernel route for an op — the per-op custom-kernel
    lowering channel (``op_spec(name, pallas=[...])``).

    The reference links its fused CUDA kernels unconditionally and picks
    them in ChooseKernel; here every custom-kernel routing decision is a
    (flag, backend, shape) gate, so the gates live in ONE statically
    enumerable table instead of ad-hoc ``flag(...)`` call-sites buried in
    op impls.  Fields:

    * ``kernel`` — route name (``"flash_attention"``, ``"fused_layer_norm"``,
      ``"dequant_accumulate"``, ...), the unit the census reports on;
    * ``flag`` — the flags.py gate; ``attr`` optionally names an op attr
      that overrides the flag per-op (``use_flash``);
    * ``match(attrs, axis_sizes)`` — cheap applicability (is this route
      even in play for this op instance — e.g. the ring route only when
      ``_seq_axis`` is stamped); non-matching routes are skipped
      silently, they are not "fallbacks";
    * ``supported(ins, attrs, axis_sizes)`` → ``(ok, reason)`` — the
      static capability gate, trace-free: ``ins`` maps slots to lists of
      objects with ``.shape``/``.dtype`` (VarSig during static analysis,
      traced jax arrays during lowering — the predicate must accept
      both); ``axis_sizes`` maps mesh axis → size (None when shapes are
      already device-local, the trace-time convention);
    * ``lower(ctx, ins, attrs)`` — the trace-time lowering onto the
      Pallas kernel, same signature/contract as an op impl;
    * ``kernels`` — the Pallas kernel function names this route is
      expected to place in a TPU-lowered module (``kernel_name = ...``
      on the ``tpu_custom_call``) — the census contract.
    """

    __slots__ = ("kernel", "flag", "attr", "match", "supported", "lower",
                 "kernels")

    def __init__(self, kernel: str, flag: Optional[str] = None,
                 attr: Optional[str] = None,
                 match: Optional[Callable] = None,
                 supported: Optional[Callable] = None,
                 lower: Optional[Callable] = None,
                 kernels=()):
        self.kernel = kernel
        self.flag = flag
        self.attr = attr
        self.match = match
        self.supported = supported
        self.lower = lower
        self.kernels = tuple(kernels)


def _shape_of(sig):
    """Static shape tuple of a VarSig OR a traced array (None/-1 dims
    count as unknown), shared by PallasLowering predicates."""
    if sig is None:
        return None
    shape = getattr(sig, "shape", None)
    if shape is None:
        return None
    try:
        return tuple(int(s) for s in shape)
    except (TypeError, ValueError):
        return None


_PALLAS_WARNED: set = set()


def pallas_route(op_type: str, ins, attrs, axis_sizes=None, backend=None,
                 count: bool = True, kernel: Optional[str] = None):
    """Resolve the Pallas route for one op instance.

    Returns ``(route, reason)`` — ``route`` is the winning
    :class:`PallasLowering` (call ``route.lower(ctx, ins, attrs)``) or
    None with ``reason`` naming why every matching route fell back
    (``flag:...=off`` / ``backend:cpu`` / the shape reason).  With
    ``count=True`` (the trace-time default) hit/fallback counters land in
    ``observability.metrics`` labeled by op + kernel + reason, so tests
    and the census observe EVERY routing decision, not just the first;
    static callers (analysis.kernel_routing_report) pass ``count=False``.
    ``kernel`` filters to one named route, or a tuple of them tried in
    table order (op impls that already know which path they are on —
    e.g. fused_attention's ring branch, or its plain branch: the
    one-tile route, else the blockwise one)."""
    spec = OP_SPECS.get(op_type)
    routes = getattr(spec, "pallas", None) if spec is not None else None
    if not routes:
        return None, "no-pallas-channel"
    from . import pallas as _pallas
    if backend is None:
        backend = _pallas.effective_backend()
    names = (kernel,) if isinstance(kernel, str) else kernel
    reasons = []
    matched = []
    for route in routes:
        if names is not None and route.kernel not in names:
            continue
        if route.match is not None and not route.match(attrs, axis_sizes):
            continue
        matched.append(route.kernel)
        enabled = True
        if route.flag is not None:
            from ..flags import flag as _flag
            enabled = _flag(route.flag)
        if route.attr is not None and attrs.get(route.attr) is not None:
            enabled = attrs[route.attr]
        if not enabled:
            reasons.append(f"flag:{route.flag}=off")
            continue
        if not _pallas.is_tpu_backend(backend):
            reasons.append(f"backend:{backend}")
            continue
        ok, why = (True, "") if route.supported is None else \
            route.supported(ins, attrs, axis_sizes)
        if ok:
            if count:
                _pallas_count(op_type, route.kernel, "hit", "supported")
            return route, "supported"
        reasons.append(why)
    # routes sharing a gate (flag, backend) give its reason once
    reason = "; ".join(dict.fromkeys(reasons)) if reasons \
        else "no-matching-route"
    if count and routes:
        # filed under the LAST route in play — the general one (the
        # blockwise kernel behind the one-tile route), where the
        # fallbacks of an op were counted before it gained a special case
        kname = matched[-1] if matched else \
            (names[-1] if names else routes[0].kernel)
        _pallas_count(op_type, kname, "fallback", reason)
        _pallas_warn(op_type, kname, reason, backend)
    return None, reason


def _pallas_count(op_type: str, kernel: str, outcome: str, reason: str):
    try:
        from ..observability import metrics
        metrics.counter("pallas_routes", op=op_type, kernel=kernel,
                        outcome=outcome, reason=reason).add()
    except Exception:        # metrics must never break a trace
        pass


def _pallas_warn(op_type: str, kernel: str, reason: str, backend: str):
    """Log shape-capability fallbacks once per (op, reason) — flag-off
    and wrong-backend fallbacks are expected states, not surprises.
    Reports the EFFECTIVE lowering backend (ops.pallas), not
    jax.default_backend(): cross-lowering for TPU on a CPU host must
    name the platform the gates actually saw."""
    if reason.startswith(("flag:", "backend:")) or \
            (op_type, reason) in _PALLAS_WARNED:
        return
    _PALLAS_WARNED.add((op_type, reason))
    import logging
    logging.getLogger(__name__).warning(
        "%s: pallas kernel %r unavailable on backend %s — falling back "
        "to the jnp composition (%s)", op_type, kernel, backend, reason)


def pallas_table() -> Dict[str, tuple]:
    """The statically enumerable Pallas tier: op type → its registered
    route tuple (analysis/census consumers iterate this)."""
    out = {}
    for name, spec in OP_SPECS.items():
        routes = getattr(spec, "pallas", None)
        if routes:
            out[name] = tuple(routes)
    return out


class OpSpec:
    """Static metadata for one op type.

    Beyond shape/dtype inference (``infer``) and the collective flag, a
    spec may carry **byte accounting** consumed by the static memory
    analyzer (framework/memory_analysis.py):

    * ``mem_transparent`` — True for fusible ops (views, elementwise
      arithmetic, activations): XLA assigns the whole chain one buffer,
      so the op's output joins its input's residual alias class instead
      of opening a new one.  None (default) defers to the analyzer's
      built-in fallback set.
    * ``mem_backward_extra(ins, outs, attrs) -> bytes`` — op-internal
      values retained for the backward sweep that never appear as named
      Program vars (an attention impl's probability matrices, a fused
      loss's logit-sized softmax), where ``ins``/``outs`` map slots to
      lists of VarSig (or None when unknown).
    * ``wire(ins, attrs, axis_sizes) -> (logical_bytes, wire_bytes)`` —
      collective wire-byte accounting (ops/op_specs.py): the logical
      payload bytes the collective syncs vs the bytes it actually moves
      over ICI under its compression spec (ring cost model; axis_sizes
      maps mesh axis name → size, or None when the mesh is unknown).
      Consumed by the memory analyzer's wire summary and the
      quant-small-bucket lint.
    * ``flops(ins, outs, attrs) -> float`` — forward GEMM-class FLOPs
      (2 per MAC) from the op's inferred input/output signatures; None
      when shapes are unknown.  Consumed by the telemetry recorder's
      static MFU numerator
      (observability/flops.py estimate_step_flops).
    * ``pallas`` — tuple of :class:`PallasLowering` routes, the per-op
      custom-kernel lowering channel: op impls dispatch through
      :func:`pallas_route` and the static layer enumerates the table
      via :func:`pallas_table` / analysis.kernel_routing_report.
    """

    __slots__ = ("name", "infer", "collective", "mem_transparent",
                 "mem_backward_extra", "wire", "flops", "pallas")

    def __init__(self, name: str, infer: Optional[Callable] = None,
                 collective: bool = False,
                 mem_transparent: Optional[bool] = None,
                 mem_backward_extra: Optional[Callable] = None,
                 wire: Optional[Callable] = None,
                 flops: Optional[Callable] = None,
                 pallas=None):
        self.name = name
        self.infer = infer
        self.collective = collective
        self.mem_transparent = mem_transparent
        self.mem_backward_extra = mem_backward_extra
        self.wire = wire
        self.flops = flops
        self.pallas = tuple(pallas) if pallas else None


def op_spec(name: str, infer: Optional[Callable] = None,
            collective: bool = False,
            mem_transparent: Optional[bool] = None,
            mem_backward_extra: Optional[Callable] = None,
            wire: Optional[Callable] = None,
            flops: Optional[Callable] = None,
            pallas=None):
    """Register static metadata for op ``name`` (idempotent per name —
    re-registration replaces, so spec modules can be reloaded)."""
    spec = OpSpec(name, infer=infer, collective=collective,
                  mem_transparent=mem_transparent,
                  mem_backward_extra=mem_backward_extra, wire=wire,
                  flops=flops, pallas=pallas)
    OP_SPECS[name] = spec
    return spec


#: the auditable static channels of an OpSpec, in census order — the
#: spec_audit coverage ratchet reports one op-name list per entry
SPEC_CHANNELS = ("infer", "flops", "wire", "mem")


def spec_coverage() -> Dict[str, list]:
    """Census of which registered op types carry each static channel —
    the raw material of the spec-coverage ratchet (SPEC_AUDIT_r*.json):
    ``{"infer": [...], "flops": [...], "wire": [...], "mem": [...]}``,
    each list sorted.  "mem" counts an op that declares EITHER
    ``mem_transparent`` or ``mem_backward_extra`` (both are opinions the
    memory analyzer consumes; a None/None spec has no memory opinion).
    """
    cov = {ch: [] for ch in SPEC_CHANNELS}
    for name in sorted(OP_SPECS):
        spec = OP_SPECS[name]
        if spec.infer is not None:
            cov["infer"].append(name)
        if spec.flops is not None:
            cov["flops"].append(name)
        if spec.wire is not None:
            cov["wire"].append(name)
        if spec.mem_transparent is not None or \
                spec.mem_backward_extra is not None:
            cov["mem"].append(name)
    return cov


def get_op_spec(name: str) -> Optional[OpSpec]:
    return OP_SPECS.get(name)


def has_op_spec(name: str) -> bool:
    return name in OP_SPECS


def get_op(name: str) -> Callable:
    try:
        return OPS[name]
    except KeyError:
        raise NotImplementedError(
            f"op {name!r} has no JAX implementation registered "
            f"({len(OPS)} ops available)") from None


def has_op(name: str) -> bool:
    return name in OPS


class LoweringContext:
    """Threaded through one block lowering.

    Carries the PRNG key (functional analog of the per-device curand states
    the reference's dropout/random ops use) plus mesh/axis info for
    collective ops lowered under shard_map.
    """

    def __init__(self, key, mesh=None, axis_names=(), is_test=False):
        self.key = key
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.is_test = is_test

    def next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub


def x(ins, slot, i=0):
    """Fetch input ``slot[i]``, or None if absent/empty."""
    v = ins.get(slot)
    if not v:
        return None
    return v[i]


def canonical_dtype(dtype):
    """The dtype jax will actually use: int64 → int32 (float64 → float32)
    when x64 is disabled — WITHOUT the per-site truncation UserWarning an
    explicit ``astype(jnp.int64)`` fires on every trace.  Op impls that
    produce the reference's int64 outputs (indices, lengths, counts) must
    request dtypes through here so real warnings stay visible."""
    return jax.dtypes.canonicalize_dtype(dtype)


def i64():
    """Canonical wide int (the reference's int64 index/length dtype)."""
    return jax.dtypes.canonicalize_dtype("int64")


_DTYPE_NBYTES_CACHE: Dict[str, int] = {}


def dtype_nbytes(dtype) -> int:
    """On-device bytes per element of ``dtype`` AFTER canonicalisation
    (int64 → int32 / float64 → float32 when x64 is off) — the width the
    memory analyzer must price, since device_put canonicalises feeds.
    bfloat16 correctly prices at 2."""
    key = str(dtype)
    b = _DTYPE_NBYTES_CACHE.get(key)
    if b is None:
        import numpy as np
        b = int(np.dtype(jax.dtypes.canonicalize_dtype(key)).itemsize)
        _DTYPE_NBYTES_CACHE[key] = b
    return b
