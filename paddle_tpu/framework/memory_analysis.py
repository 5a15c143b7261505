"""Static liveness & peak-HBM analyzer over a verified Program.

The north-star workload (BERT-base pretrain on a v5e-32) is HBM-bound
long before it is FLOP-bound, yet an over-budget program previously
failed DEEP inside XLA — after a multi-minute trace+compile — with an
allocator error naming an HLO buffer, not a Program variable.  And the
PR 2 donation bug class (state buffers silently not aliased) showed up
only as 2× live-set growth at runtime.  This module turns PR 3's
op_spec shape/dtype inference into the missing memory model, entirely
statically (no trace, no device):

* **liveness** — per-block def/last-use intervals over the op list,
  recursing into Block-valued control-flow attrs (a read inside a while
  body is a use at the while op's index in the parent block);
  feed/fetch/persistable roots are pinned across the whole step;
* **per-device peak-HBM estimate** — every variable priced at its
  canonical on-device width (int64 → int32 under disabled x64, bf16/amp
  at 2 bytes — the op_spec dtype inference supplies true widths) and
  divided by its mesh sharding: persistables by their ``dist_attr``
  axes (ZeRO-1 flat state shards, tp-split weights), feeds/activations
  by the batch/sequence axes; donated state is counted ONCE (the arg
  aliases its output), non-donated written persistables twice;
* **lint profile** — donation gaps (a trainable persistable that
  receives a gradient but is never updated in place), fetch-induced
  retention (fetching an early activation pins it across the peak),
  and gradient-accumulation doubling (param-shaped persistable grad
  accumulators), each anchored to the op's recorded creation site.

The transient (XLA "temp") model is deliberately simple and validated
against ground truth rather than derived from a scheduler simulation
(``tools/mem_probe.py`` compares it to
``jit(...).lower().compile().memory_analysis()`` per leg; tier-1 holds
the smallest rung and the two mesh legs within ±15 %):

    transient = RESIDUAL_FACTOR × Σ residual classes
              + Σ op-internal backward extras      (op_spec mem channel)
              + grads                   (collectives' and updates' operands)

where a *residual class* is an alias set of forward intermediates
collapsed across fusible ops (views, elementwise chains, activations —
XLA assigns them one buffer), ``RESIDUAL_FACTOR = 1.5`` prices the
forward value plus the ~half of its cotangents in flight during the
reverse sweep, op-internal extras come from the op_spec byte-accounting
channel (attention probability matrices, softmax-CE logit copies — the
values an op impl materialises that never appear as named Program
vars), and the grad term counts each gradient buffer a grad-sync
collective or an optimizer update reads (XLA finishes the backward sweep
before the updates: the temp bytes of one program are the same under
sgd, momentum and adam).

Wired three ways: ``tools/proglint.py --memory`` prints the report;
``flag("hbm_budget_gb")`` makes ``Executor.prepare`` /
``CompiledProgram._variant_for`` / ``Executor._compile`` raise
``InvalidArgumentError`` BEFORE any XLA compile when the estimate
exceeds budget; ``tools/mem_probe.py`` validates the estimator.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

from .core import Block, Program
from .errors import InvalidArgumentError
from .analysis import (VerifyResult, _iter_sub_blocks, infer_shapes,
                       op_reads_recursive)

# lint codes (joins the analysis.py taxonomy; warning severity — memory
# lints are retention smells, not well-formedness errors)
DONATION_GAP = "donation-gap"
FETCH_RETENTION = "fetch-retention"
GRAD_ACCUM_DOUBLING = "grad-accum-doubling"

#: forward residual + in-flight cotangents during the reverse sweep,
#: per residual class (calibrated against XLA buffer assignment across
#: the transformer-bench ladder; see module docstring and mem_probe)
RESIDUAL_FACTOR = 1.5

_GIB = float(1 << 30)


# ---------------------------------------------------------------------------
# byte pricing
# ---------------------------------------------------------------------------


def sig_bytes(sig, unknown_dim: int = 1) -> int:
    """On-device bytes of one VarSig: canonical dtype width (int64→int32
    when x64 is off — feeds are canonicalised at device_put), unknown
    dims priced at ``unknown_dim``."""
    if sig is None or sig.shape is None:
        return 0
    from ..ops.registry import dtype_nbytes
    n = 1
    for d in sig.shape:
        d = int(d)
        n *= d if d > 0 else unknown_dim
    return n * dtype_nbytes(sig.dtype)


def _axis_divisor(axes, mesh_axes: Dict[str, int]) -> int:
    """Product of mesh-axis sizes over ``axes``; entries may be axis
    names, None, or nested tuples of names (a ShardSpec dim sharded over
    fsdp×tp, or a tuple batch_axis like ("dp", "fsdp"))."""
    from .mesh_layout import _flat_axes
    div = 1
    for a in _flat_axes(axes):
        div *= int(mesh_axes.get(a, 1))
    return div


def _var_sig(v):
    """Declared VarSig of a Variable (None-safe)."""
    if v is None:
        return None
    from ..ops.registry import VarSig
    return VarSig(tuple(v.shape) or None, v.dtype)


# ---------------------------------------------------------------------------
# 1. liveness (def / last-use intervals, sub-blocks recursed)
# ---------------------------------------------------------------------------


class Interval:
    """Liveness interval of one name inside one block: ``def_idx`` is the
    first producing op (None for roots that pre-exist the block — feeds,
    persistables, closure vars), ``last_use`` the last op reading it
    (uses inside a control-flow sub-block count at the PARENT op's
    index).  ``pinned`` roots (feeds / fetches / persistables) live
    across the whole block regardless of their last textual use."""

    __slots__ = ("name", "def_idx", "last_use", "pinned", "def_op")

    def __init__(self, name, def_idx=None, last_use=-1, pinned=False,
                 def_op=None):
        self.name = name
        self.def_idx = def_idx
        self.last_use = last_use
        self.pinned = pinned
        self.def_op = def_op           # Operator, for creation-site anchors

    def live_at(self, idx: int, end: int) -> bool:
        if self.pinned:
            return True
        lo = self.def_idx if self.def_idx is not None else 0
        return lo <= idx <= (end if self.last_use < 0 else self.last_use)

    def __repr__(self):
        return (f"Interval({self.name!r}, def={self.def_idx}, "
                f"last_use={self.last_use}, pinned={self.pinned})")


def block_liveness(block: Block, feed_names: Iterable[str] = (),
                   fetch_names: Iterable[str] = (),
                   pinned_extra: Iterable[str] = ()
                   ) -> Dict[str, Interval]:
    """Def/last-use intervals for every name touched in ``block``.

    A control-flow op (while_loop / conditional_block / ...) reads, at
    its own index, every name its sub-blocks read recursively (the
    closure contract ``Program._prune`` follows), so an outer var
    consumed only inside a loop body stays live through the loop op.
    Feed / fetch / persistable roots are pinned."""
    fetch = set(fetch_names)
    pinned = set(feed_names) | set(pinned_extra)
    out: Dict[str, Interval] = {}
    for idx, op in enumerate(block.ops):
        if op.type in ("feed", "fetch"):
            continue
        reads = set(op.input_names())
        for sub in _iter_sub_blocks(op):
            for sub_op in sub.ops:
                reads |= op_reads_recursive(sub_op)
        for n in reads:
            iv = out.get(n)
            if iv is None:
                iv = out[n] = Interval(n)
            iv.last_use = max(iv.last_use, idx)
        for n in op.output_names():
            iv = out.get(n)
            if iv is None:
                iv = out[n] = Interval(n)
            if iv.def_idx is None:
                iv.def_idx = idx
                iv.def_op = op
    for n, iv in out.items():
        v = block._find_var_recursive(n)
        if n in pinned or n in fetch or (
                v is not None and (v.persistable or v.is_data)):
            iv.pinned = True
    return out


def program_liveness(program: Program, feed_names: Iterable[str] = (),
                     fetch_names: Iterable[str] = ()
                     ) -> Dict[int, Dict[str, Interval]]:
    """Liveness per block index, sub-blocks included (each sub-block gets
    its OWN interval table; its closure reads also appear as uses in the
    parent table at the owning op's index)."""
    tables: Dict[int, Dict[str, Interval]] = {}

    def walk(block, feeds, fetches):
        tables[block.idx] = block_liveness(block, feeds, fetches)
        for op in block.ops:
            for sub in _iter_sub_blocks(op):
                if sub.idx not in tables:
                    walk(sub, (), ())
    walk(program.global_block(), feed_names, fetch_names)
    return tables


# ---------------------------------------------------------------------------
# 2. per-device peak-HBM estimate
# ---------------------------------------------------------------------------


class LiveTensor:
    """One entry of the top-k live set at the peak point."""

    __slots__ = ("name", "nbytes", "kind", "op_type", "callstack")

    def __init__(self, name, nbytes, kind, op_type=None, callstack=()):
        self.name = name
        self.nbytes = int(nbytes)
        self.kind = kind               # param|opt-state|feed|activation|...
        self.op_type = op_type
        self.callstack = list(callstack or ())

    def format(self) -> str:
        loc = f" (op {self.op_type!r})" if self.op_type else ""
        line = f"{self.nbytes / (1 << 20):9.3f} MiB  {self.kind:<10s} " \
               f"{self.name}{loc}"
        if self.callstack:
            line += "\n" + "\n".join(f"        {f}"
                                     for f in self.callstack[-2:])
        return line


class MemoryEstimate:
    """Per-device peak-HBM estimate + its components.

    ``peak_bytes = args_bytes + transient_bytes`` corresponds to XLA's
    ``argument_size_in_bytes + temp_size_in_bytes`` (donated outputs
    alias their args; non-aliased outputs are reported separately in
    ``output_bytes``)."""

    def __init__(self):
        self.feed_bytes = 0
        self.param_bytes = 0           # trainable persistables
        self.opt_state_bytes = 0       # non-trainable persistables
        self.rng_bytes = 8
        self.residual_bytes = 0        # Σ residual classes (pre-factor)
        self.internal_bytes = 0        # op_spec backward extras
        self.grad_bytes = 0            # collectives' and updates' operands
        self.output_bytes = 0          # non-aliased outputs (fetches, and
        self.transient_bytes = 0       # written state when not donated)
        # grad-sync collective wire accounting (the op_spec ``wire``
        # channel): logical payload bytes vs the bytes the ring schedule
        # actually moves over ICI under the ops' compression specs.
        # Reported, not part of peak (wire buffers are transient and
        # already inside the residual factor's slack).
        self.wire_logical_bytes = 0
        self.wire_bytes = 0
        self.peak_op_idx = None
        self.top_live: List[LiveTensor] = []
        self.mesh_axes: Dict[str, int] = {}
        self.notes: List[str] = []

    @property
    def args_bytes(self) -> int:
        return (self.feed_bytes + self.param_bytes + self.opt_state_bytes
                + self.rng_bytes)

    @property
    def state_bytes(self) -> int:
        return self.param_bytes + self.opt_state_bytes

    @property
    def peak_bytes(self) -> int:
        return self.args_bytes + self.transient_bytes

    @property
    def peak_gb(self) -> float:
        return self.peak_bytes / _GIB

    def as_dict(self) -> Dict[str, Any]:
        return {
            "peak_bytes": self.peak_bytes,
            "peak_gb": round(self.peak_gb, 6),
            "args_bytes": self.args_bytes,
            "feed_bytes": self.feed_bytes,
            "param_bytes": self.param_bytes,
            "opt_state_bytes": self.opt_state_bytes,
            "transient_bytes": self.transient_bytes,
            "residual_bytes": self.residual_bytes,
            "internal_bytes": self.internal_bytes,
            "grad_bytes": self.grad_bytes,
            "output_bytes": self.output_bytes,
            "wire_logical_bytes": self.wire_logical_bytes,
            "wire_bytes": self.wire_bytes,
            "wire_compression_ratio": round(
                self.wire_logical_bytes / self.wire_bytes, 3)
            if self.wire_bytes else 1.0,
            "mesh_axes": dict(self.mesh_axes),
            "peak_op_idx": self.peak_op_idx,
            "top_live": [{"name": t.name, "bytes": t.nbytes,
                          "kind": t.kind, "op_type": t.op_type}
                         for t in self.top_live],
            "notes": list(self.notes),
        }

    def report(self) -> str:
        mb = 1 << 20
        lines = [
            f"static per-device peak HBM estimate: "
            f"{self.peak_bytes / mb:.2f} MiB ({self.peak_gb:.4f} GiB)"
            + (f"  [mesh {self.mesh_axes}]" if self.mesh_axes else ""),
            f"  arguments  {self.args_bytes / mb:10.2f} MiB  "
            f"(feeds {self.feed_bytes / mb:.2f}, params "
            f"{self.param_bytes / mb:.2f}, opt state "
            f"{self.opt_state_bytes / mb:.2f})",
            f"  transient  {self.transient_bytes / mb:10.2f} MiB  "
            f"(residuals {self.residual_bytes / mb:.2f} ×"
            f"{RESIDUAL_FACTOR}, op-internal "
            f"{self.internal_bytes / mb:.2f}, grads "
            f"{self.grad_bytes / mb:.2f})",
            f"  outputs    {self.output_bytes / mb:10.2f} MiB  "
            f"(non-aliased)",
        ]
        if self.wire_logical_bytes:
            ratio = (self.wire_logical_bytes / self.wire_bytes
                     if self.wire_bytes else 1.0)
            lines.append(
                f"  grad-sync wire {self.wire_bytes / mb:6.2f} MiB on ICI "
                f"(logical {self.wire_logical_bytes / mb:.2f} MiB, "
                f"compression {ratio:.2f}x)")
        if self.top_live:
            lines.append(f"  top live tensors at the peak point"
                         + (f" (op #{self.peak_op_idx})"
                            if self.peak_op_idx is not None else "") + ":")
            lines.extend("    " + t.format() for t in self.top_live)
        for n in self.notes:
            lines.append(f"  note: {n}")
        return "\n".join(lines)


def _feed_sigs(program: Program, feed_shapes, unknown_dim: int):
    """Concrete (or declared-fallback) VarSigs for the feed roots."""
    from ..ops.registry import VarSig
    block = program.global_block()
    sigs: Dict[str, Any] = {}
    if feed_shapes:
        for name, v in feed_shapes.items():
            if hasattr(v, "shape") and hasattr(v, "dtype"):
                sigs[name] = VarSig(tuple(v.shape), str(v.dtype))
            else:
                shape, dtype = v
                sigs[name] = VarSig(tuple(shape), str(dtype))
    for name, v in block.vars.items():
        if v.is_data and name not in sigs:
            shape = tuple(int(d) if int(d) > 0 else unknown_dim
                          for d in v.shape)
            sigs[name] = VarSig(shape, v.dtype)
    return sigs


def _state_names(program: Program, fetch_names) -> Tuple[List[str],
                                                         List[str]]:
    """(state_in, written_state) exactly as Executor._compile resolves
    them: persistables read before being written, fetched never-written
    persistables, and persistables any op writes."""
    block = program.global_block()
    ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
    written: set = set()
    state_in: List[str] = []
    for op in ops:
        for n in op.input_names():
            if n in written or n in state_in:
                continue
            var = block._find_var_recursive(n)
            if var is not None and var.persistable:
                state_in.append(n)
        written |= set(op.output_names())
    for n in fetch_names:
        var = block._find_var_recursive(n)
        if var is not None and var.persistable and n not in written and \
                n not in state_in:
            state_in.append(n)
    written_state = []
    for op in ops:
        for n in op.output_names():
            var = block._find_var_recursive(n)
            if var is not None and var.persistable and \
                    n not in written_state:
                written_state.append(n)
    return state_in, written_state


#: fusible op families: XLA assigns one buffer to the whole chain, so
#: their outputs join their largest input's residual class instead of
#: opening a new one (views, elementwise arithmetic, activations whose
#: backward is recomputed inside the fusion)
_TRANSPARENT_FALLBACK = frozenset({
    "reshape2", "reshape", "squeeze2", "unsqueeze2", "flatten2", "flatten",
    "scale", "assign", "cast", "clip", "relu", "gelu", "tanh", "sigmoid",
    "dropout", "softmax", "elementwise_add", "elementwise_sub",
    "elementwise_mul",
})


def _op_transparent(op_type: str) -> bool:
    from ..ops.registry import OP_SPECS
    spec = OP_SPECS.get(op_type)
    if spec is not None and spec.mem_transparent is not None:
        return bool(spec.mem_transparent)
    return op_type in _TRANSPARENT_FALLBACK


def _op_backward_extra(op, env) -> int:
    """Op-internal bytes retained for backward beyond named vars (the
    op_spec byte-accounting channel — e.g. attention probability
    matrices)."""
    from ..ops.registry import OP_SPECS
    spec = OP_SPECS.get(op.type)
    fn = spec.mem_backward_extra if spec is not None else None
    if fn is None:
        return 0
    ins = {slot: [env.get(n) for n in names]
           for slot, names in op.inputs.items()}
    outs = {slot: [env.get(n) for n in names]
            for slot, names in op.outputs.items()}
    try:
        return int(fn(ins, outs, op.attrs) or 0)
    except Exception:       # an accounting bug must not kill the analyzer
        return 0


def mem_uncovered_suspects(program: Program) -> list:
    """Op types in ``program`` with NO memory opinion: neither a spec
    ``mem_transparent``/``mem_backward_extra`` channel nor membership in
    the transparent fallback set.  These are where a peak-HBM drift
    (``spec-drift-mem``) most plausibly originates — the attribution
    list the differential spec auditor (framework/spec_audit.py) names
    in its diagnostics, and the census the backfill ratchet consumes."""
    from ..framework.analysis import META_OPS
    from ..ops.registry import OP_SPECS
    out = set()
    for op in program.global_block().ops:
        if op.type in META_OPS or op.type in _TRANSPARENT_FALLBACK:
            continue
        spec = OP_SPECS.get(op.type)
        if spec is not None and (spec.mem_transparent is not None
                                 or spec.mem_backward_extra is not None):
            continue
        out.add(op.type)
    return sorted(out)


class _AliasSets:
    """Union-find over var names for residual-class collapse."""

    def __init__(self):
        self._parent: Dict[str, str] = {}

    def find(self, x: str) -> str:
        p = self._parent
        while p.get(x, x) != x:
            p[x] = p.get(p[x], p[x])
            x = p[x]
        return x

    def union(self, root: str, member: str):
        self._parent[self.find(member)] = self.find(root)


def analyze_memory(program: Program, feed_shapes=None,
                   fetch_names: Iterable[str] = (),
                   mesh_axes: Optional[Dict[str, int]] = None,
                   batch_axis: Optional[str] = None,
                   seq_axis: Optional[str] = None,
                   feed_specs: Optional[Dict[str, Any]] = None,
                   donate_state: bool = True, unknown_dim: int = 1,
                   top_k: int = 8) -> MemoryEstimate:
    """Static per-device peak-HBM estimate for one step of ``program``.

    ``feed_shapes`` maps feed names to arrays or ``(shape, dtype)``
    pairs; absent feeds fall back to declared metadata with unknown dims
    priced at ``unknown_dim`` (so a gate with no example feed is a lower
    bound).  ``mesh_axes`` maps axis name → size ({"dp": 8, "tp": 2});
    persistables divide by their ``dist_attr`` axes, feeds by their
    ``feed_specs`` entry (default: batch axis on dim 0), activations by
    the batch × sequence axes.
    """
    from ..ops.registry import VarSig

    mesh_axes = dict(mesh_axes or {})
    fetch_names = list(fetch_names)
    block = program.global_block()
    est = MemoryEstimate()
    est.mesh_axes = mesh_axes

    # -- shape env: feeds bound concretely, op_spec inference forward ----
    feed_sigs = _feed_sigs(program, feed_shapes, unknown_dim)
    scratch = VerifyResult(program)    # throwaway: bucket-vs-declared
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

    act_div = _axis_divisor((batch_axis, seq_axis), mesh_axes)

    def var_bytes(name, activation=False):
        v = block._find_var_recursive(name)
        b = sig_bytes(sig_of(name), unknown_dim)
        if not mesh_axes:
            return b
        if v is not None and getattr(v, "dist_attr", None):
            return b // _axis_divisor(v.dist_attr, mesh_axes)
        if name in feed_sigs:
            spec = (feed_specs or {}).get(name)
            axes = tuple(spec) if spec is not None else (batch_axis,)
            return b // _axis_divisor(axes, mesh_axes)
        if activation:
            return b // act_div
        return b

    # -- arguments (per device) ------------------------------------------
    state_in, written_state = _state_names(program, fetch_names)
    for n in feed_sigs:
        est.feed_bytes += var_bytes(n)
    for n in state_in:
        v = block._find_var_recursive(n)
        b = var_bytes(n)
        if v is not None and getattr(v, "trainable", False):
            est.param_bytes += b
        else:
            est.opt_state_bytes += b

    ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
    bw_idx = next((i for i, op in enumerate(ops)
                   if op.type == "backward"), None)
    liveness = block_liveness(block, feed_names=list(feed_sigs),
                              fetch_names=fetch_names)
    from ..ops.registry import OP_SPECS

    top: List[LiveTensor] = []

    def anchor(name):
        iv = liveness.get(name)
        op = iv.def_op if iv is not None else None
        return ((op.type if op is not None else None),
                getattr(op, "callstack", None) or ())

    if bw_idx is not None:
        # ---- training step: peak sits at the backward sweep ------------
        bw_attrs = ops[bw_idx].attrs
        checkpoints = set(bw_attrs.get("checkpoints") or ())
        pipe_S = int(bw_attrs.get("pipe_stages") or 1)
        pipe_M = int(bw_attrs.get("pipe_microbatches") or 1)
        aliases = _AliasSets()
        fwd_names: Dict[str, int] = {}
        def_pos: Dict[str, int] = {}
        last_read: Dict[str, int] = {}
        internal_per_op: List[int] = []
        internal = 0
        for idx, op in enumerate(ops[:bw_idx]):
            outs = op.output_names()
            for n in op_reads_recursive(op):
                last_read[n] = idx
            # a ZeRO-3 on-demand gather rebuilds the FULL parameter —
            # replicated across the batch axes, so never divided by the
            # activation (batch/seq) sharding
            is_gather = op.type == "fsdp_all_gather"
            for n in outs:
                def_pos.setdefault(n, idx)
                v = block._find_var_recursive(n)
                if v is not None and v.persistable:
                    continue
                fwd_names.setdefault(
                    n, var_bytes(n, activation=not is_gather))
            extra = _op_backward_extra(op, env) // act_div
            internal_per_op.append(extra)
            internal += extra
            ins = op.input_names()
            if outs and ins and _op_transparent(op.type):
                # ALL outputs join the input's class (a dropout's Out AND
                # Mask live in the one fused buffer region)
                big = max(ins, key=lambda n: fwd_names.get(
                    n, var_bytes(n, activation=True)))
                for o in outs:
                    aliases.union(big, o)
        classes: Dict[str, Tuple[int, str]] = {}
        for n, b in fwd_names.items():
            r = aliases.find(n)
            cur = classes.get(r)
            if cur is None or b > cur[0]:
                classes[r] = (b, n)
        if checkpoints:
            # recompute segments (jax.checkpoint over the op list,
            # executor._segment_at_checkpoints): what survives to the
            # backward sweep is each segment's INPUT live set — the
            # residual classes live across a segment boundary — plus the
            # checkpoint markers themselves; everything interior to a
            # segment re-materialises during its backward
            cuts = sorted({def_pos[c] + 1 for c in checkpoints
                           if c in def_pos})
            kept_roots = set()
            for n in fwd_names:
                d = def_pos.get(n)
                lu = last_read.get(n, -1)
                if n in checkpoints or (
                        d is not None and
                        any(d < c <= lu for c in cuts)):
                    kept_roots.add(aliases.find(n))
            kept = {r: v for r, v in classes.items() if r in kept_roots}
            dropped = sum(b for r, (b, n) in classes.items()
                          if r not in kept)
            est.notes.append(
                f"recompute checkpoints: {len(checkpoints)} boundaries, "
                f"{dropped / (1 << 20):.2f} MiB of residuals not retained")
            classes = kept or classes
            if cuts:
                # one segment's op-internal extras (attention probs, CE
                # logit copies) are live at a time during its recompute
                edges = [0] + cuts + [len(internal_per_op)]
                internal = max(
                    sum(internal_per_op[a:b])
                    for a, b in zip(edges, edges[1:])) if internal_per_op \
                    else 0
        est.residual_bytes = sum(b for b, _ in classes.values())
        est.internal_bytes = internal
        pipe_inflight = 0
        if pipe_S > 1 and pipe_M >= 1:
            # scheduled pipeline lowering: each backward tick recomputes
            # its stage's forward from the saved stage input, so
            # per-device residual state is the rank's virtual stages'
            # classes at ONE microbatch, plus the saved-input /
            # cotangent rings (sizes from the schedule simulation,
            # stamped as pipe_ring_slots) and the two in-transit carries
            pipe_v = int(bw_attrs.get("pipe_chunks") or 1)
            ranks = max(pipe_S // max(pipe_v, 1), 1)
            stage_bytes: Dict[int, int] = {}
            for r, (b, n) in classes.items():
                iv = liveness.get(n)
                op = iv.def_op if iv is not None else None
                s = int(op.attrs.get("_pipe_stage", 0)) \
                    if op is not None else 0
                stage_bytes[s] = stage_bytes.get(s, 0) + b
            # an interleaved rank r hosts virtual stages {r, r+ranks, …}
            # — its residual is their sum; take the worst rank
            rank_bytes = [0] * ranks
            for s, b in stage_bytes.items():
                rank_bytes[s % ranks] += b
            est.residual_bytes = max(rank_bytes) // pipe_M \
                if stage_bytes else 0
            est.internal_bytes = internal // pipe_M
            bnd = 0
            for names in bw_attrs.get("pipe_boundaries") or ():
                for n in names:
                    bnd += var_bytes(n, activation=True)
            ring = bw_attrs.get("pipe_ring_slots")
            slots = (int(ring[0]) + int(ring[1])) if ring else ranks
            pipe_inflight = (slots + 2) * bnd // max(pipe_M, 1)
            sched = bw_attrs.get("pipe_schedule") or "1f1b"
            est.notes.append(
                f"pipeline {sched} on {ranks} ranks x {pipe_v} chunks "
                f"x {pipe_M} microbatches: max-rank residual "
                f"{est.residual_bytes / (1 << 20):.2f} MiB per "
                f"microbatch + {pipe_inflight / (1 << 20):.2f} MiB "
                f"in-flight ring/boundary state")
        # grad-sync collectives after the backward op keep BOTH their
        # source and result buffers live (a psum cannot update in place;
        # a reduce_scatter's full-grad input coexists with its 1/n
        # shard).
        scatter_ops = {"zero_reduce_scatter", "quant_reduce_scatter",
                       "c_reducescatter", "reduce_scatter"}
        # each gradient buffer counts at most once as a collective
        # SOURCE and once as a RESULT across the whole grad-sync zone —
        # a chain of collectives over the same name (the pipe-axis sum
        # feeding the data-axis sync) reuses the same two buffers, it
        # does not stack a fresh pair per hop
        seen_in: set = set()
        seen_out: set = set()
        for op in ops[bw_idx + 1:]:
            spec = OP_SPECS.get(op.type)
            if spec is None or not spec.collective:
                continue
            axes = op.attrs.get("_axis_name")
            axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
            for n in op.input_names():
                if n in seen_in:
                    continue
                seen_in.add(n)
                v = block._find_var_recursive(n)
                if v is None or not v.persistable:
                    est.grad_bytes += var_bytes(n)
            for n in op.output_names():
                if n in seen_out:
                    continue
                seen_out.add(n)
                v = block._find_var_recursive(n)
                if v is None or not v.persistable:
                    b = var_bytes(n)
                    if op.type in scatter_ops:
                        # a reduce-scatter's result is physically the
                        # 1/n shard even though the var is declared at
                        # the full flat shape
                        b //= _axis_divisor(axes, mesh_axes)
                    est.grad_bytes += b
            # true wire accounting (the op_spec ``wire`` channel): what
            # this collective moves over ICI vs its logical payload —
            # quantized collectives additionally keep their wire-width
            # payload + scale staging buffers live during the exchange
            wb = None
            if getattr(spec, "wire", None) is not None:
                ins = {slot: [sig_of(n) for n in names]
                       for slot, names in op.inputs.items()}
                try:
                    wb = spec.wire(ins, op.attrs, mesh_axes)
                except Exception:   # accounting must not kill the analyzer
                    wb = None
            if wb is not None:
                logical, wire = wb
                est.wire_logical_bytes += logical
                est.wire_bytes += wire
        # an optimizer update (Param, Grad -> ParamOut) reads its gradient
        # as a whole buffer: XLA finishes the backward sweep before the
        # updates, so the gradient set is live beside the residuals
        # whatever the optimizer (sgd, momentum and adam show the same
        # temp bytes).  Gradients a collective above already counted are
        # the same buffers.
        for op in ops[bw_idx + 1:]:
            if "Grad" not in op.inputs or "ParamOut" not in op.outputs:
                continue
            for n in op.inputs["Grad"]:
                if n in seen_in or n in seen_out:
                    continue
                seen_in.add(n)
                v = block._find_var_recursive(n)
                if v is None or not v.persistable:
                    est.grad_bytes += var_bytes(n)
        est.transient_bytes = int(RESIDUAL_FACTOR * est.residual_bytes
                                  + est.internal_bytes + est.grad_bytes
                                  + pipe_inflight)
        est.peak_op_idx = bw_idx
        # top-k live at the peak: params/state + residual classes
        for n in state_in:
            t, cs = anchor(n)
            v = block._find_var_recursive(n)
            kind = "param" if (v is not None and
                               getattr(v, "trainable", False)) \
                else "opt-state"
            top.append(LiveTensor(n, var_bytes(n), kind, t, cs))
        for r, (b, n) in classes.items():
            t, cs = anchor(n)
            top.append(LiveTensor(n, int(b * RESIDUAL_FACTOR),
                                  "activation", t, cs))
        for n in feed_sigs:
            top.append(LiveTensor(n, var_bytes(n), "feed"))
    else:
        # ---- forward-only program: scan the live set over the op list --
        names = set(liveness)
        peak, peak_idx, peak_set = 0, 0, []
        end = len(block.ops) - 1
        cache: Dict[str, int] = {}

        def nb(n):
            if n not in cache:
                cache[n] = var_bytes(n, activation=True)
            return cache[n]

        sub_extra: Dict[int, int] = {}
        for idx, op in enumerate(block.ops):
            extra = 0
            for sub in _iter_sub_blocks(op):
                sl = block_liveness(sub)
                extra += sum(sig_bytes(sig_of(n), unknown_dim) // act_div
                             for n in sl
                             if block._find_var_recursive(n) is None
                             or not block._find_var_recursive(n).persistable)
            sub_extra[idx] = extra
        for idx, op in enumerate(block.ops):
            if op.type in ("feed", "fetch"):
                continue
            live = [n for n in names
                    if liveness[n].live_at(idx, end)
                    and not liveness[n].pinned]
            total = sum(nb(n) for n in live) + sub_extra.get(idx, 0)
            if total > peak:
                peak, peak_idx, peak_set = total, idx, live
        est.residual_bytes = peak
        est.transient_bytes = peak
        est.peak_op_idx = peak_idx
        for n in sorted(peak_set, key=nb, reverse=True)[:top_k]:
            t, cs = anchor(n)
            top.append(LiveTensor(n, nb(n), "activation", t, cs))
        for n in state_in:
            t, cs = anchor(n)
            top.append(LiveTensor(n, var_bytes(n), "param", t, cs))
        for n in feed_sigs:
            top.append(LiveTensor(n, var_bytes(n), "feed"))

    # -- outputs ---------------------------------------------------------
    for n in fetch_names:
        v = block._find_var_recursive(n)
        if v is None or not v.persistable:
            est.output_bytes += sig_bytes(sig_of(n), unknown_dim)
    if not donate_state:
        # read-only-state mode: written persistables come back as FRESH
        # buffers (no aliasing), so they are live twice at step end
        dbl = sum(var_bytes(n) for n in written_state)
        est.output_bytes += dbl
        est.transient_bytes += dbl
        if dbl:
            est.notes.append(
                f"donate_state=False: {len(written_state)} written "
                f"persistable(s) counted twice "
                f"(+{dbl / (1 << 20):.2f} MiB — no buffer aliasing)")

    top.sort(key=lambda t: -t.nbytes)
    est.top_live = top[:top_k]
    return est


# ---------------------------------------------------------------------------
# 3. memory lint profile
# ---------------------------------------------------------------------------


def lint_memory(program: Program, fetch_names: Iterable[str] = (),
                result: Optional[VerifyResult] = None) -> VerifyResult:
    """Memory-retention lints over one program (warning severity,
    creation-site anchored):

    * ``donation-gap`` — a trainable persistable receives a gradient
      (listed in the backward op's param_names) but NO op ever writes it:
      its update either never happened or landed in a separate buffer,
      so the stale param stays pinned next to the new value — the silent
      2× live-set growth of the PR 2 bug class;
    * ``fetch-retention`` — a fetched non-persistable whose last real
      consumer runs before the peak point (the backward op): the fetch
      pins an early activation across the whole step;
    * ``grad-accum-doubling`` — a param-shaped persistable accumulator
      summed from a gradient (``sum``/``elementwise_add`` writing back
      to a persistable input): doubles the per-device gradient live set;
      shard it (ZeRO-1) or accumulate in bf16.
    """
    from .core import GRAD_SUFFIX

    result = result or VerifyResult(program)
    block = program.global_block()
    ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
    bw_idx = next((i for i, op in enumerate(ops)
                   if op.type == "backward"), None)
    fetch = list(fetch_names)
    liveness = block_liveness(block, fetch_names=fetch)

    written: Dict[str, int] = {}
    for idx, op in enumerate(ops):
        for n in op.output_names():
            written.setdefault(n, idx)

    # (a) donation gap
    if bw_idx is not None:
        for pname in ops[bw_idx].attrs.get("param_names", ()):
            if pname in written:
                continue
            v = block._find_var_recursive(pname)
            if v is None or not v.persistable:
                continue
            reader_idx, reader = next(
                ((i, op) for i, op in enumerate(ops)
                 if pname in op.input_names()), (-1, None))
            b = sig_bytes(_var_sig(v))
            result.add(
                "warning", DONATION_GAP,
                f"trainable persistable {pname!r} receives a gradient but "
                f"is never updated in place — the update (if any) lives in "
                f"a separate buffer while the stale param stays pinned "
                f"(+{b / (1 << 20):.2f} MiB live-set growth); write the "
                f"optimizer output back to {pname!r} so its donated "
                f"buffer is reused",
                reader, block.idx, reader_idx)

    # (b) fetch-induced retention
    peak_idx = bw_idx if bw_idx is not None else len(ops) - 1
    for n in fetch:
        v = block._find_var_recursive(n)
        if v is not None and (v.persistable or v.is_data):
            continue
        iv = liveness.get(n)
        if iv is None or iv.def_idx is None:
            continue
        last_real = max((i for i, op in enumerate(ops)
                         if n in op.input_names()), default=-1)
        if last_real < peak_idx and iv.def_idx < peak_idx:
            b = sig_bytes(_var_sig(v))
            result.add(
                "warning", FETCH_RETENTION,
                f"fetch target {n!r} is produced at op #{iv.def_idx} and "
                f"last consumed at op #{last_real}, but the fetch pins it "
                f"across the peak point (op #{peak_idx})"
                + (f" — +{b / (1 << 20):.2f} MiB held through the "
                   f"backward sweep" if b else "")
                + "; fetch a reduced copy or move the fetch off the hot "
                  "step",
                iv.def_op, block.idx, iv.def_idx)

    # (c) gradient-accumulation doubling
    for idx, op in enumerate(ops):
        if op.type not in ("sum", "elementwise_add"):
            continue
        ins = op.input_names()
        outs = op.output_names()
        if not outs:
            continue
        acc = outs[0]
        if acc not in ins:
            continue
        v = block._find_var_recursive(acc)
        if v is None or not v.persistable:
            continue
        if not any(n.endswith(GRAD_SUFFIX) for n in ins if n != acc):
            continue
        b = sig_bytes(_var_sig(v))
        result.add(
            "warning", GRAD_ACCUM_DOUBLING,
            f"persistable gradient accumulator {acc!r} doubles the "
            f"per-device gradient live set (+{b / (1 << 20):.2f} MiB "
            f"pinned across every micro-step); shard it with ZeRO-1 "
            f"(strategy.sharded_update) or accumulate in bf16",
            op, block.idx, idx)
    return result


# ---------------------------------------------------------------------------
# 4. HBM budget gate (flag("hbm_budget_gb"))
# ---------------------------------------------------------------------------


def check_hbm_budget(program: Program, feed_shapes=None,
                     fetch_names: Iterable[str] = (),
                     mesh_axes: Optional[Dict[str, int]] = None,
                     batch_axis: Optional[str] = None,
                     seq_axis: Optional[str] = None,
                     feed_specs: Optional[Dict[str, Any]] = None,
                     donate_state: bool = True,
                     budget_gb: Optional[float] = None
                     ) -> Optional[MemoryEstimate]:
    """Raise ``InvalidArgumentError`` BEFORE any trace/compile when the
    static estimate exceeds ``flag("hbm_budget_gb")`` (0 = gate off).

    Replaces the reference's runtime allocator knobs
    (``fraction_of_gpu_memory_to_use`` / ``eager_delete_tensor_gb``,
    accepted as no-ops — XLA owns the allocator) with a STATIC pre-compile
    budget: an over-budget program is rejected in milliseconds with the
    top live tensors and their creation sites, not after a multi-minute
    XLA compile with an opaque HLO buffer name."""
    from ..flags import flag
    if budget_gb is None:
        budget_gb = float(flag("hbm_budget_gb") or 0.0)
    if not budget_gb or budget_gb <= 0:
        return None
    est = analyze_memory(program, feed_shapes=feed_shapes,
                         fetch_names=fetch_names, mesh_axes=mesh_axes,
                         batch_axis=batch_axis, seq_axis=seq_axis,
                         feed_specs=feed_specs, donate_state=donate_state)
    if est.peak_gb > budget_gb and flag("remat_on_reject"):
        # the rematerialization escape hatch (framework/pipe.py): insert
        # recompute checkpoints at the liveness-identified residual
        # minima instead of failing — the memory/compute trade is priced
        # (recompute FLOPs delta via the op_spec flops channel) and the
        # program only raises when even the deepest recompute plan still
        # exceeds the budget
        from .pipe import apply_remat, plan_remat
        plan = plan_remat(program, feed_shapes=feed_shapes,
                          fetch_names=fetch_names, mesh_axes=mesh_axes,
                          batch_axis=batch_axis, seq_axis=seq_axis,
                          budget_gb=budget_gb, donate_state=donate_state)
        if plan is not None and plan.fits:
            apply_remat(program, plan)
            est = analyze_memory(program, feed_shapes=feed_shapes,
                                 fetch_names=fetch_names,
                                 mesh_axes=mesh_axes,
                                 batch_axis=batch_axis, seq_axis=seq_axis,
                                 feed_specs=feed_specs,
                                 donate_state=donate_state)
            est.notes.append(
                f"remat_on_reject: inserted {len(plan.checkpoints)} "
                f"recompute checkpoint(s) "
                f"(+{plan.flops_delta / 1e9:.3f} GFLOP recompute) to fit "
                f"hbm_budget_gb={budget_gb:g}")
    if est.peak_gb > budget_gb:
        raise InvalidArgumentError(
            f"program exceeds hbm_budget_gb={budget_gb:g}: static "
            f"per-device peak estimate {est.peak_gb:.4f} GiB "
            f"({est.peak_bytes} bytes) — rejected before compile.\n"
            + est.report())
    return est


def estimate(program: Program, feed_shapes=None,
             fetch_names: Iterable[str] = (),
             mesh_axes: Optional[Dict[str, int]] = None,
             batch_axis: Optional[str] = None,
             seq_axis: Optional[str] = None,
             feed_specs: Optional[Dict[str, Any]] = None,
             donate_state: bool = True, unknown_dim: int = 1,
             top_k: int = 8) -> MemoryEstimate:
    """The admission-control entry point: one program's static per-device
    peak-HBM estimate at concrete feed shapes (an alias of
    :func:`analyze_memory` under the name the serving tier uses).

    ``ServingFleet`` prices each (model x bucket variant) with this —
    ``state_bytes`` is the model's resident weight footprint (shared by
    every bucket variant of one predictor) and ``peak_bytes -
    state_bytes`` the per-variant dynamic working set — and admits model
    sets under ``hbm_budget_gb`` BEFORE any compile is attempted."""
    return analyze_memory(program, feed_shapes=feed_shapes,
                          fetch_names=fetch_names, mesh_axes=mesh_axes,
                          batch_axis=batch_axis, seq_axis=seq_axis,
                          feed_specs=feed_specs, donate_state=donate_state,
                          unknown_dim=unknown_dim, top_k=top_k)


def plan_cache_pool(program: Program, feed_shapes=None,
                    fetch_names: Iterable[str] = (),
                    cache_vars: Iterable[str] = (),
                    block_bytes: int = 0,
                    budget_gb: Optional[float] = None,
                    min_blocks: int = 1,
                    reserve_blocks: int = 0) -> Dict[str, Any]:
    """Size a paged KV-cache pool at DECODE-ENGINE START — the
    generalization of ``ServingFleet``'s HBM admission from "one more
    bucket executable" to "one more cache block".

    ``program`` is the decode-step program built with a PROBE pool (any
    block count) at its largest batch bucket's ``feed_shapes``; the
    estimate splits into the pool persistables (``cache_vars``) vs
    everything else (weights + the variant working set), and the blocks
    affordable under ``budget_gb`` follow statically — no trace, no
    compile, no device allocation:

        blocks = (budget - (peak - probe_pool)) // block_bytes

    Returns ``{"blocks", "fixed_bytes", "block_bytes", "budget_bytes",
    "reserve_blocks", "estimate"}``; ``blocks`` is None when no budget
    applies (caller keeps its configured default).  Raises
    ``InvalidArgumentError`` when even ``min_blocks`` (one sequence's
    worth) plus ``reserve_blocks`` (headroom the caller pledges to the
    cross-request prefix cache so a full working set cannot starve it)
    cannot fit — at engine start, with the program's top live tensors
    in the message, instead of as a device OOM mid-traffic."""
    from ..flags import flag
    if budget_gb is None:
        budget_gb = float(flag("hbm_budget_gb") or 0.0)
    reserve_blocks = max(0, int(reserve_blocks))
    est = estimate(program, feed_shapes=feed_shapes,
                   fetch_names=fetch_names, donate_state=True)
    cache_vars = set(cache_vars)
    probe_pool = 0
    block = program.global_block()
    from ..ops.registry import dtype_nbytes
    for name in cache_vars:
        v = block.vars.get(name)
        if v is None or not v.shape:
            continue
        n = 1
        for d in v.shape:
            n *= int(d)
        probe_pool += n * dtype_nbytes(v.dtype)
    fixed = max(0, est.peak_bytes - probe_pool)
    out = {"blocks": None, "fixed_bytes": int(fixed),
           "block_bytes": int(block_bytes), "budget_bytes": None,
           "reserve_blocks": reserve_blocks, "estimate": est}
    if not budget_gb or budget_gb <= 0:
        return out
    budget = int(budget_gb * _GIB)
    out["budget_bytes"] = budget
    blocks = (budget - fixed) // max(1, int(block_bytes))
    if blocks < min_blocks + reserve_blocks:
        raise InvalidArgumentError(
            f"decode cache admission: hbm_budget_gb={budget_gb:g} leaves "
            f"{max(0, budget - fixed)} bytes for the KV-cache pool — "
            f"fewer than min_blocks={min_blocks} blocks (+ "
            f"reserve_blocks={reserve_blocks} prefix-cache headroom) of "
            f"{block_bytes} bytes (weights + decode working set cost "
            f"{fixed} bytes).  Rejected at engine start, before any "
            f"compile.\n" + est.report())
    out["blocks"] = int(blocks)
    return out


def collective_wire_summary(program: Program, feed_shapes=None,
                            fetch_names: Iterable[str] = (),
                            mesh_axes: Optional[Dict[str, int]] = None,
                            batch_axis=None,
                            seq_axis: Optional[str] = None,
                            feed_specs: Optional[Dict[str, Any]] = None,
                            unknown_dim: int = 1) -> Dict[str, Any]:
    """Whole-program per-STEP wire-byte summary over the op_spec
    ``wire`` channel — forward collectives included (Megatron f/g pair,
    ZeRO-3 ``fsdp_all_gather``), not just the post-backward grad-sync
    zone :func:`analyze_memory` reports.  This is the cost channel the
    shard planner ranks candidate layouts with.

    The ``wire`` fns price an op from its inputs' DECLARED (global)
    signatures; the actual traced payload is the local shard, so each
    op's bytes are divided by the payload's sharding over axes the op
    does NOT communicate over: a ``dist_attr``-sharded payload divides
    by its non-reduce axes (a ZeRO-3 grad reduced over dp divides by
    fsdp), activations divide by the batch×seq axes, feeds by their
    ``feed_specs`` entry.  Axes the op communicates over stay whole —
    an fsdp gather's ring cost is (n-1)/n of the FULL parameter.
    """
    from ..ops.registry import OP_SPECS
    from .mesh_layout import _flat_axes

    mesh_axes = dict(mesh_axes or {})
    block = program.global_block()
    feed_sigs = _feed_sigs(program, feed_shapes, unknown_dim)
    scratch = VerifyResult(program)
    env = infer_shapes(program, scratch, feed_names=list(feed_sigs),
                       init_env=dict(feed_sigs))

    def sig_of(name):
        from ..ops.registry import VarSig
        s = env.get(name)
        if s is not None and s.shape is not None:
            return s
        v = block._find_var_recursive(name)
        if v is None:
            return s
        return VarSig(tuple(v.shape) or None, v.dtype)

    batch_axes = _flat_axes(batch_axis) + tuple(
        a for a in (seq_axis,) if a)

    totals = {"wire_bytes": 0, "logical_bytes": 0,
              "grad_sync_wire_bytes": 0, "forward_wire_bytes": 0}
    bw_idx = next((i for i, op in enumerate(block.ops)
                   if op.type == "backward"), None)
    by_op: Dict[str, Dict[str, int]] = {}
    unpriced: List[str] = []
    for op_idx, op in enumerate(block.ops):
        spec = OP_SPECS.get(op.type)
        if spec is None or not spec.collective:
            continue
        fn = getattr(spec, "wire", None)
        if fn is None:
            if op.type not in ("zero_shard_slice", "mp_copy", "c_identity"):
                unpriced.append(op.type)
            continue
        ins = {slot: [sig_of(n) for n in names]
               for slot, names in op.inputs.items()}
        try:
            wb = fn(ins, op.attrs, mesh_axes)
        except Exception:       # accounting must not kill the planner
            wb = None
        if wb is None:
            unpriced.append(op.type)
            continue
        logical, wire = wb
        op_axes = op.attrs.get("_axis_name") or ()
        op_axes = set(_flat_axes(op_axes))
        # divide by the payload's sharding over NON-communicated axes
        div = None
        for n in op.input_names():
            v = block._find_var_recursive(n)
            da = tuple(getattr(v, "dist_attr", None) or ()) \
                if v is not None else ()
            if da:
                axes = tuple(a for a in _flat_axes(da) if a not in op_axes)
            elif n in feed_sigs:
                fspec = (feed_specs or {}).get(n)
                axes = tuple(a for a in _flat_axes(
                    tuple(fspec) if fspec is not None else batch_axes)
                    if a not in op_axes)
            elif v is not None and v.persistable:
                axes = ()
            else:           # activation: batch/seq sharded
                axes = tuple(a for a in batch_axes if a not in op_axes)
            d = _axis_divisor(axes, mesh_axes)
            div = d if div is None else min(div, d)
        div = div or 1
        logical, wire = int(logical // div), int(wire // div)
        row = by_op.setdefault(op.type, {"count": 0, "wire_bytes": 0,
                                         "logical_bytes": 0})
        row["count"] += 1
        row["wire_bytes"] += wire
        row["logical_bytes"] += logical
        totals["wire_bytes"] += wire
        totals["logical_bytes"] += logical
        # placement split for the exposed-comm roofline: collectives
        # after the backward op are grad sync (hideable under the
        # remaining backward compute when overlap-scheduled); an
        # fsdp_all_gather is priced for both directions, so half its
        # wire is its backward psum_scatter transpose (free overlap)
        # and half the forward gather
        if bw_idx is not None and op_idx > bw_idx:
            totals["grad_sync_wire_bytes"] += wire
        elif op.type == "fsdp_all_gather":
            totals["grad_sync_wire_bytes"] += wire // 2
            totals["forward_wire_bytes"] += wire - wire // 2
        elif op.type == "mp_copy":
            # fwd identity, bwd psum: all its priced wire is the
            # Megatron g-transpose riding the backward sweep
            totals["grad_sync_wire_bytes"] += wire
        else:
            totals["forward_wire_bytes"] += wire
    return {"wire_bytes": totals["wire_bytes"],
            "logical_bytes": totals["logical_bytes"],
            "grad_sync_wire_bytes": totals["grad_sync_wire_bytes"],
            "forward_wire_bytes": totals["forward_wire_bytes"],
            "by_op": by_op,
            "unpriced_collectives": sorted(set(unpriced))}


def exposed_comm_model(wire_summary, flops_total, num_devices=1,
                       overlap=False, has_backward=True,
                       ici_gbps=None, peak_flops=None,
                       bubble_frac=0.0) -> Dict[str, Any]:
    """Static step-time roofline for one program/config: how much
    collective wire time is EXPOSED (not hidden under compute).

    ``exposed_comm = forward_wire_time +
                     max(0, grad_sync_wire_time − overlappable_compute)``

    where ``overlappable_compute`` is the backward sweep's compute time
    — ``flag("overlap_compute_frac")`` of the 3× fwd+bwd GEMM total the
    PR 9 ``flops`` channel prices; the default 2/3 preserves the
    historical constant bit-for-bit, and the measured-cost calibration
    loop can refit it from telemetry — when the grad sync is
    overlap-scheduled (``strategy.overlap_grad_sync``), else 0 — a
    tail-fused schedule hides nothing.  Forward collectives (Megatron
    f/g, un-prefetched fsdp gathers) serialise with compute by data
    dependence and count exposed.  Wire time = bytes /
    (``flag("ici_gbps")`` · 1e9); peak FLOPs from the device table
    (``flag("device_peak_flops")`` override).

    ``bubble_frac`` prices a pipeline schedule's idle bubble — the
    EXACT per-tick bubble fraction of the chosen schedule family
    (``pipe.simulate_schedule``: 1F1B, interleaved, zero-bubble),
    replacing the old analytic ``(pipe − 1) / num_microbatches``: the
    model charges ``pipe_bubble_s = bubble_frac × (compute_s +
    exposed)`` on top, and the planner ranks by the total ``cost_s``.
    0 (the default, every non-pipelined config) leaves all historical
    rankings unchanged.  Only the RANKING between configs consumes this
    model, so ordering fidelity matters more than absolute accuracy."""
    from ..flags import flag
    from ..observability import flops as _flops
    bw = float(ici_gbps if ici_gbps is not None
               else flag("ici_gbps")) * 1e9
    peak = float(peak_flops) if peak_flops else _flops.device_peak_flops()
    per_dev = float(flops_total or 0.0) / max(int(num_devices or 1), 1)
    compute_s = per_dev / peak if peak > 0 else 0.0
    frac = float(flag("overlap_compute_frac"))
    bwd_compute_s = compute_s * frac if has_backward else 0.0
    grad_wire_s = wire_summary.get("grad_sync_wire_bytes", 0) / bw
    fwd_wire_s = wire_summary.get("forward_wire_bytes", 0) / bw
    hidden_s = min(grad_wire_s, bwd_compute_s) if overlap else 0.0
    exposed_s = fwd_wire_s + grad_wire_s - hidden_s
    bubble_s = float(bubble_frac or 0.0) * (compute_s + exposed_s)
    return {
        "ici_gbps": bw / 1e9,
        "peak_flops": peak,
        "compute_s": compute_s,
        "overlap_compute_frac": frac,
        "overlappable_compute_s": bwd_compute_s if overlap else 0.0,
        "wire_time_s": fwd_wire_s + grad_wire_s,
        "grad_sync_wire_s": grad_wire_s,
        "forward_wire_s": fwd_wire_s,
        "hidden_s": hidden_s,
        "exposed_comm_s": exposed_s,
        "bubble_frac": float(bubble_frac or 0.0),
        "pipe_bubble_s": bubble_s,
        "cost_s": exposed_s + bubble_s,
    }


def mesh_axes_of(mesh) -> Dict[str, int]:
    """{axis name: size} of a jax Mesh (None → {})."""
    if mesh is None:
        return {}
    return dict(zip(mesh.axis_names, mesh.devices.shape))


__all__ = [
    "DONATION_GAP", "FETCH_RETENTION", "GRAD_ACCUM_DOUBLING",
    "RESIDUAL_FACTOR", "Interval", "LiveTensor", "MemoryEstimate",
    "block_liveness", "program_liveness", "analyze_memory", "estimate",
    "lint_memory", "check_hbm_budget", "mesh_axes_of", "sig_bytes",
    "collective_wire_summary", "exposed_comm_model",
    "mem_uncovered_suspects",
]
