"""Static verifier + analysis-pass framework over Program/Block/Operator.

The reference front-loads correctness into C++ infrastructure this rebuild
deliberately dropped: ``InferShape``/``InferVarType`` run at every op
insertion (ref: framework/op_desc.cc, shape_inference.h) and
``PADDLE_ENFORCE`` guards every kernel, so a malformed ProgramDesc fails at
build time with the op named.  Here a malformed Program previously failed
deep inside jit tracing with a raw JAX traceback — and some defect classes
(a donated state var in the fetch list, a collective sequence that diverges
across mesh ranks) produced no error at all, just wrong results or a hang.

This module restores that safety net at trace-free cost:

* **structural verification** — use-before-def per block (recursing into
  control-flow sub-blocks via Block-valued attrs), undeclared inputs,
  duplicate/dangling writes, ops with no registry implementation,
  startup-vs-main parameter shape/dtype agreement;
* **static shape & dtype inference** — the ``op_spec`` metadata channel
  (ops/registry.py) propagates shapes/dtypes from feed vars and parameters
  through the op list, reporting mismatches as diagnostics anchored to the
  op's recorded user callstack (framework/errors.py) instead of an in-jit
  XLA error;
* **distributed soundness** — collectives under divergent control flow,
  inconsistent collective sequences across program clones, bf16-compressed
  collectives applied to integer gradients, donation/aliasing conflicts
  (the PR 2 silently-dropped-donation bug class);
* **pass-pipeline invariant checking** — ``apply_pass``/``PassBuilder``
  verify the program around each pass under ``flag("verify_passes")``,
  diffing defined-var and fetch-reachability sets at the pass boundary.

``Executor.prepare`` and ``CompiledProgram`` call :func:`verify_cached`,
which verifies each program at most once per ``(_uid, _version)`` (plus
feed/fetch signature); ``tools/proglint.py`` lints a serialized program
from the CLI.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .core import Block, Operator, Program, Variable
from .errors import Error, InvalidArgumentError

# defect-class codes (the lint taxonomy; see MIGRATION.md "Static analysis
# mapping" for the defect-class ↔ reference-enforcement table)
USE_BEFORE_DEF = "use-before-def"
UNDECLARED_INPUT = "undeclared-input"
DANGLING_WRITE = "dangling-write"
DUPLICATE_WRITE = "duplicate-write"
MISSING_OP_IMPL = "missing-op-impl"
SHAPE_MISMATCH = "shape-mismatch"
DTYPE_MISMATCH = "dtype-mismatch"
STARTUP_MAIN_MISMATCH = "startup-main-mismatch"
COLLECTIVE_DIVERGENT_CF = "collective-divergent-control-flow"
COLLECTIVE_SEQ_DIVERGENCE = "collective-sequence-divergence"
BF16_ALLREDUCE_INTEGER = "bf16-allreduce-integer"
QUANT_COLLECTIVE_INTEGER = "quant-collective-integer"
QUANT_NON_SUM = "quant-collective-non-sum"
QUANT_SMALL_BUCKET = "quant-small-bucket"
# overlap-aware collective scheduling soundness (the ready-order bucket
# pass — compiler.insert_grad_sync under strategy.overlap_grad_sync)
OVERLAP_SINGLE_BUCKET = "overlap-single-bucket"
OVERLAP_TAIL_SUNK = "overlap-tail-sunk"
DONATED_VAR_FETCHED = "donated-var-fetched"
READ_AFTER_DONATE = "read-after-donate"
# named-axis layout soundness (the MeshLayout/ShardSpec contract —
# framework/mesh_layout.py, stamped by the auto-shard planner)
SHARD_LAYOUT_UNKNOWN_AXIS = "shard-layout-unknown-axis"
SHARD_LAYOUT_COLLECTIVE_MISMATCH = "shard-layout-collective-mismatch"
# MoE expert-parallel soundness (the parallel/moe.py decomposed route
# moe_dispatch → c_expert_alltoall → moe_expert_ffn → moe_combine and the
# fused ops.moe_ffn fallback — both name the exchange axis statically)
MOE_AXIS_UNKNOWN = "moe-axis-unknown"
MOE_AXIS_CAPACITY_MISMATCH = "moe-axis-capacity-mismatch"
# pipeline/remat soundness (the stage-cut + recompute rewrites —
# framework/pipe.py, lowered by the executor's scheduled scan)
PIPE_COLLECTIVE_CROSSES_STAGE = "pipe-collective-crosses-stage"
PIPE_SCHEDULE_ORDER = "pipe-schedule-order"
PIPE_RING_OVERFLOW = "pipe-ring-overflow"
REMAT_RECOMPUTE_SIDE_EFFECT = "remat-recompute-side-effect"
UNSPECCED_OP = "unspecced-op"
PASS_INVARIANT = "pass-invariant"
# differential spec audit (framework/spec_audit.py): a static op_spec
# channel disagrees with the ONCE-lowered program's ground truth —
# shape/dtype vs jaxpr avals (always an error), flops vs XLA
# cost_analysis / wire vs the module's collective census / peak-HBM vs
# memory_analysis (errors outside the per-channel tolerance band
# recorded in SPEC_AUDIT_r*.json)
SPEC_DRIFT_SHAPE = "spec-drift-shape"
SPEC_DRIFT_FLOPS = "spec-drift-flops"
SPEC_DRIFT_WIRE = "spec-drift-wire"
SPEC_DRIFT_MEM = "spec-drift-mem"
# inference/serving profile (a SERVED program must be a pure read-only
# function of its feeds — see verify_inference)
INFERENCE_COLLECTIVE = "inference-collective"
INFERENCE_TRAINING_OP = "inference-training-op"
INFERENCE_STATE_WRITE = "inference-state-write"
INFERENCE_DONATED_READ = "inference-donated-read"
# decode profile (a decode-engine program may write ONLY its declared
# KV-cache pool persistables — see verify_decode)
DECODE_STATE_WRITE = "decode-state-write"
DECODE_CACHE_UNDECLARED = "decode-cache-undeclared"
DECODE_CHAIN_MISPLACED = "decode-chain-misplaced"
# launch audit (framework/launch_audit.py): per-rank collective
# timelines proven mutually compatible and deadlock-free, and launch
# fingerprints proven identical, before the first collective fires —
# the static answer to the silent pod-wide NCCL-style hang (see
# MIGRATION.md "Launch audit mapping")
LAUNCH_SCHEDULE_DIVERGENCE = "launch-schedule-divergence"
LAUNCH_DEADLOCK_CYCLE = "launch-deadlock-cycle"
LAUNCH_FINGERPRINT_DRIFT = "launch-fingerprint-drift"

#: meta-ops interpreted by the executor itself, not the registry
META_OPS = frozenset({"feed", "fetch", "backward", "pipeline"})


class PassInvariantError(Error):
    """A program pass broke a well-formedness invariant at the pass
    boundary (the analog of an ir::Graph pass failing its
    post-condition checks)."""
    code = "PASS_INVARIANT"


class Diagnostic:
    """One verifier finding, anchored (when possible) to the op's recorded
    user creation site — the op_call_stack.cc contract applied at static
    verification time instead of at kernel failure."""

    __slots__ = ("severity", "code", "message", "op_type", "block_idx",
                 "op_index", "callstack")

    def __init__(self, severity: str, code: str, message: str,
                 op: Optional[Operator] = None, block_idx: int = 0,
                 op_index: int = -1):
        self.severity = severity        # "error" | "warning"
        self.code = code
        self.message = message
        self.op_type = op.type if op is not None else None
        self.block_idx = block_idx
        self.op_index = op_index
        self.callstack = list(getattr(op, "callstack", None) or ())

    def format(self) -> str:
        loc = ""
        if self.op_type is not None:
            loc = (f" [operator < {self.op_type} > "
                   f"block {self.block_idx} op #{self.op_index}]")
        lines = [f"{self.severity.upper()} {self.code}{loc}: {self.message}"]
        if self.callstack:
            lines.append("  Python call stack (op creation site):")
            lines.extend(f"    {frame}" for frame in self.callstack)
        return "\n".join(lines)

    def __repr__(self):
        return f"Diagnostic({self.severity}, {self.code}, {self.op_type})"


class VerifyResult:
    """Collected diagnostics + the unspecced-op census for one program."""

    def __init__(self, program: Optional[Program] = None):
        self.program = program
        self.diagnostics: List[Diagnostic] = []
        self.unspecced_ops: Dict[str, int] = {}

    # -- collection ------------------------------------------------------
    def add(self, severity, code, message, op=None, block_idx=0,
            op_index=-1):
        self.diagnostics.append(
            Diagnostic(severity, code, message, op, block_idx, op_index))

    def merge(self, other: "VerifyResult"):
        self.diagnostics.extend(other.diagnostics)
        for k, v in other.unspecced_ops.items():
            self.unspecced_ops[k] = self.unspecced_ops.get(k, 0) + v

    # -- queries ---------------------------------------------------------
    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == "error"]

    def warnings(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == "warning"]

    def by_code(self, code: str) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.code == code]

    @property
    def ok(self) -> bool:
        return not self.errors()

    def raise_on_error(self):
        errs = self.errors()
        if errs:
            raise InvalidArgumentError(
                "program verification failed with "
                f"{len(errs)} error(s):\n" +
                "\n".join(d.format() for d in errs))
        return self

    def report(self) -> str:
        lines = [f"program verification: {len(self.errors())} error(s), "
                 f"{len(self.warnings())} warning(s)"]
        for d in self.diagnostics:
            lines.append(d.format())
        if self.unspecced_ops:
            lines.append(
                "unspecced ops (no op_spec registered — shape/dtype "
                "inference skipped):")
            for name, count in sorted(self.unspecced_ops.items()):
                lines.append(f"  {name}: {count} op(s)")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# helpers shared by the checks
# ---------------------------------------------------------------------------


def _iter_sub_blocks(op: Operator):
    """Block-valued attrs of a control-flow op (single or list-valued)."""
    for v in op.attrs.values():
        if isinstance(v, Block):
            yield v
        elif isinstance(v, (list, tuple)):
            for item in v:
                if isinstance(item, Block):
                    yield item


def _attr_name_lists(op: Operator) -> Set[str]:
    """Names carried by string-list attrs (x_names/closure_names/...):
    the in-block bindings a control-flow op seeds its sub-blocks with."""
    out: Set[str] = set()
    for k, v in op.attrs.items():
        if isinstance(v, (list, tuple)) and v and \
                all(isinstance(item, str) for item in v):
            out.update(v)
        elif isinstance(v, str) and k.endswith(("_out", "_name")):
            out.add(v)
    return out


def op_reads_recursive(op: Operator) -> Set[str]:
    """All names ``op`` reads, including reads made inside its control-flow
    sub-blocks (recursively) — the closure an interpreter-style prune must
    treat as live (satellite fix consumed by ``Program._prune``)."""
    reads = set(op.input_names())
    for sub in _iter_sub_blocks(op):
        for sub_op in sub.ops:
            reads |= op_reads_recursive(sub_op)
    return reads


def _collective_types() -> Set[str]:
    from ..ops.registry import OP_SPECS
    return {name for name, spec in OP_SPECS.items() if spec.collective}


def _seed_available(block: Block, feed_names: Iterable[str],
                    scope_names: Iterable[str]) -> Set[str]:
    """Names readable before any op of ``block`` runs: feeds, data vars,
    persistables (startup-initialised), initializer-carrying vars, plus
    anything already materialised in the scope."""
    avail = set(feed_names) | set(scope_names)
    b: Optional[Block] = block
    while b is not None:
        for name, v in b.vars.items():
            if v.persistable or v.is_data or v.initializer is not None:
                avail.add(name)
        b = b.parent_block
    return avail


# ---------------------------------------------------------------------------
# 1. structural verification
# ---------------------------------------------------------------------------


def verify_structure(program: Program, result: VerifyResult,
                     feed_names: Iterable[str] = (),
                     scope_names: Iterable[str] = ()):
    """Use-before-def / undeclared inputs / duplicate+dangling writes /
    missing registry impls, recursing into control-flow sub-blocks."""
    from ..ops.registry import has_op

    produced_anywhere: Set[str] = set()
    for b in program.blocks:
        for op in b.ops:
            produced_anywhere |= set(op.output_names())

    def check_block(block: Block, available: Set[str], top_level: bool):
        defined = set(available)
        writer_index: Dict[str, int] = {}
        read_since_write: Set[str] = set()
        for idx, op in enumerate(block.ops):
            if op.type not in META_OPS and not has_op(op.type):
                result.add(
                    "error", MISSING_OP_IMPL,
                    f"op {op.type!r} has no JAX implementation in the "
                    f"registry — it will fail at lowering",
                    op, block.idx, idx)
            for slot, names in op.inputs.items():
                for n in names:
                    read_since_write.add(n)
                    if n in defined:
                        continue
                    declared = block._find_var_recursive(n) is not None
                    if not declared and n not in produced_anywhere:
                        # warning, not error: a name declared nowhere can
                        # still be a scope-resident var another program
                        # initialised (e.g. a decode program reusing the
                        # train program's weights by name)
                        result.add(
                            "warning", UNDECLARED_INPUT,
                            f"op {op.type!r} input {slot}={n!r} is not "
                            f"declared in any reachable block and no op "
                            f"produces it",
                            op, block.idx, idx)
                    else:
                        result.add(
                            "error" if top_level else "warning",
                            USE_BEFORE_DEF,
                            f"op {op.type!r} reads {slot}={n!r} before any "
                            f"op defines it (not a feed/data var, not "
                            f"persistable, no initializer)",
                            op, block.idx, idx)
                    defined.add(n)      # report each name once per block
            # recurse into control-flow sub-blocks: outer defs so far plus
            # the op's declared in-block bindings are visible inside
            sub_avail = defined | _attr_name_lists(op)
            for sub in _iter_sub_blocks(op):
                check_block(sub, sub_avail, top_level=False)
            for slot, names in op.outputs.items():
                for n in names:
                    var = block._find_var_recursive(n)
                    if var is None:
                        result.add(
                            "warning", DANGLING_WRITE,
                            f"op {op.type!r} writes {slot}={n!r} but the "
                            f"variable is not declared in any reachable "
                            f"block",
                            op, block.idx, idx)
                    prev = writer_index.get(n)
                    if prev is not None and n not in read_since_write and \
                            n not in op.input_names() and \
                            (var is None or not var.persistable):
                        result.add(
                            "warning", DUPLICATE_WRITE,
                            f"op {op.type!r} overwrites {n!r} (first "
                            f"written by op #{prev}) before any op read "
                            f"it — the first value is dead",
                            op, block.idx, idx)
                    writer_index[n] = idx
                    read_since_write.discard(n)
                    defined.add(n)

    top = program.global_block()
    check_block(top, _seed_available(top, feed_names, scope_names),
                top_level=True)


def verify_startup_agreement(main: Program, startup: Program,
                             result: VerifyResult):
    """Persistables declared in both programs must agree on shape/dtype —
    the startup program materialises the buffers the main program will
    lower against (ref contract: the two-program convention of
    framework.py default_main_program/default_startup_program)."""
    sb = startup.global_block()
    for name, v in main.global_block().vars.items():
        if not v.persistable:
            continue
        sv = sb.vars.get(name)
        if sv is None:
            continue
        if tuple(sv.shape) != tuple(v.shape) and sv.shape and v.shape:
            result.add(
                "error", STARTUP_MAIN_MISMATCH,
                f"parameter {name!r}: startup declares shape "
                f"{list(sv.shape)} but main declares {list(v.shape)}")
        elif str(sv.dtype) != str(v.dtype):
            result.add(
                "error", STARTUP_MAIN_MISMATCH,
                f"parameter {name!r}: startup declares dtype {sv.dtype} "
                f"but main declares {v.dtype}")


# ---------------------------------------------------------------------------
# 2. static shape & dtype inference
# ---------------------------------------------------------------------------


def _declared_sig(block: Block, name: str):
    from ..ops.registry import VarSig
    v = block._find_var_recursive(name)
    if v is None:
        return None
    shape = tuple(v.shape)
    # a declared () is ambiguous (scalar OR "shape not filled in") —
    # treat it as unknown so it never fights real inference
    return VarSig(shape if shape else None, v.dtype)


def _merge_sig(declared, inferred):
    from ..ops.registry import VarSig
    if declared is None or declared.shape is None:
        return inferred
    if inferred.shape is None:
        return VarSig(declared.shape, inferred.dtype)
    if len(declared.shape) != len(inferred.shape):
        return inferred
    shape = tuple(d if i < 0 else i
                  for d, i in zip(declared.shape, inferred.shape))
    return VarSig(shape, inferred.dtype)


def _shapes_conflict(declared, inferred) -> bool:
    if declared is None or inferred is None:
        return False
    if declared.shape is None or inferred.shape is None:
        return False
    if len(declared.shape) != len(inferred.shape):
        return True
    return any(d >= 0 and i >= 0 and d != i
               for d, i in zip(declared.shape, inferred.shape))


def infer_shapes(program: Program, result: VerifyResult,
                 feed_names: Iterable[str] = (),
                 init_env: Optional[Dict[str, Any]] = None):
    """Propagate static (shape, dtype) signatures through the global
    block's op list via the ``op_spec`` infer channel, reporting
    mismatches against declared variable metadata.  Ops without a spec
    pass their declared output metadata through and are counted in the
    unspecced census (the warn-don't-fail long-tail path).

    ``init_env`` seeds the propagation environment with concrete
    signatures (name → VarSig) — the memory analyzer binds the actual
    feed shapes here so batch/seq dims declared ``-1`` resolve to real
    extents instead of staying unknown."""
    from ..ops.registry import OP_SPECS, SpecMismatch, VarSig

    block = program.global_block()
    env: Dict[str, Any] = dict(init_env or {})

    def sig_of(name: str):
        if name in env:
            return env[name]
        return _declared_sig(block, name)

    for idx, op in enumerate(block.ops):
        if op.type in META_OPS:
            # the backward meta-op defines grads shaped like their params
            if op.type == "backward":
                for pname in op.attrs.get("param_names", ()):
                    from .core import grad_var_name
                    g = grad_var_name(pname)
                    psig = sig_of(pname)
                    if psig is not None:
                        env[g] = psig
            continue
        spec = OP_SPECS.get(op.type)
        if spec is None:
            result.unspecced_ops[op.type] = \
                result.unspecced_ops.get(op.type, 0) + 1
            for n in op.output_names():
                d = _declared_sig(block, n)
                if d is not None:
                    env[n] = d
            continue
        if spec.infer is None:
            for n in op.output_names():
                d = _declared_sig(block, n)
                if d is not None:
                    env[n] = d
            continue
        ins = {slot: [sig_of(n) or VarSig(None, "float32") for n in names]
               for slot, names in op.inputs.items()}
        try:
            out = spec.infer(ins, op.attrs)
        except SpecMismatch as e:
            code = DTYPE_MISMATCH if e.kind == "dtype" else SHAPE_MISMATCH
            result.add("error", code, str(e), op, block.idx, idx)
            out = None
        except Exception as e:          # an infer bug must not kill lint
            result.add(
                "warning", UNSPECCED_OP,
                f"op_spec infer for {op.type!r} failed "
                f"({type(e).__name__}: {e}) — treating as unspecced",
                op, block.idx, idx)
            out = None
        if not out:
            for n in op.output_names():
                d = _declared_sig(block, n)
                if d is not None:
                    env[n] = d
            continue
        for slot, sigs in out.items():
            names = op.outputs.get(slot, [])
            for n, inferred in zip(names, sigs):
                declared = _declared_sig(block, n)
                if _shapes_conflict(declared, inferred):
                    result.add(
                        "error", SHAPE_MISMATCH,
                        f"op {op.type!r} output {slot}={n!r}: inferred "
                        f"shape {list(inferred.shape)} conflicts with "
                        f"declared {list(declared.shape)}",
                        op, block.idx, idx)
                env[n] = _merge_sig(declared, inferred)
        # outputs in slots the spec had no opinion about
        for slot, names in op.outputs.items():
            if slot in out:
                continue
            for n in names:
                d = _declared_sig(block, n)
                if d is not None:
                    env[n] = d
    return env


# ---------------------------------------------------------------------------
# 3. distributed soundness
# ---------------------------------------------------------------------------


def verify_distributed(program: Program, result: VerifyResult,
                       fetch_names: Iterable[str] = ()):
    """Collective & donation soundness over one program."""
    from ..ops.registry import OP_SPECS

    collectives = _collective_types()
    fetch = set(fetch_names)
    block = program.global_block()

    # (a) collectives under divergent control flow: a collective inside a
    # conditional_block/switch_case/while_loop sub-block executes a
    # data-dependent number of times — mesh ranks disagree and the program
    # hangs (the reference cannot express this; our sub-block lowering can)
    def scan_cf(parent_op, blk, depth):
        for idx, op in enumerate(blk.ops):
            if op.type in collectives:
                result.add(
                    "error", COLLECTIVE_DIVERGENT_CF,
                    f"collective op {op.type!r} appears inside the "
                    f"sub-block of control-flow op {parent_op.type!r} — "
                    f"collectives under divergent control flow deadlock "
                    f"when ranks disagree on the branch/trip count",
                    op, blk.idx, idx)
            for sub in _iter_sub_blocks(op):
                scan_cf(op, sub, depth + 1)

    # the pipeline mega-op's stage blocks run under a rank-STATIC
    # schedule (every rank executes the same switch sequence), so
    # collectives inside its stages are sound — exempt
    cf_exempt = {"pipeline"}
    for op in block.ops:
        if op.type in cf_exempt:
            continue
        for sub in _iter_sub_blocks(op):
            scan_cf(op, sub, 1)

    # (b) bf16-compressed collectives on integer tensors: the cast →
    # psum → upcast rewrite silently truncates integer payloads
    for idx, op in enumerate(block.ops):
        comp = op.attrs.get("compress_dtype")
        if not comp or op.type not in collectives:
            continue
        for n in op.input_names():
            v = block._find_var_recursive(n)
            if v is not None and str(v.dtype) in (
                    "int8", "uint8", "int16", "int32", "int64", "bool"):
                result.add(
                    "error", BF16_ALLREDUCE_INTEGER,
                    f"collective {op.type!r} compresses {n!r} "
                    f"({v.dtype}) to {comp} — integer payloads must not "
                    f"ride compressed collectives",
                    op, block.idx, idx)

    # (b2) quantized wire-compression collectives (ops/quantize_wire.py):
    # blockwise amax-scaling is only meaningful on float payloads that
    # are SUMMED — integer payloads would be truncated twice (quantize +
    # dequant-accumulate), and a non-sum reduction (max/min/prod, raw
    # gather/permute) has no dequant-accumulate stage for the per-block
    # scales to cancel in.  Also: the quant-small-bucket lint — a payload
    # under flag("quant_min_bucket_kb") pays more in scale-tensor and
    # extra-collective overhead than the narrower dtype saves.
    _INT_DTYPES = ("int8", "uint8", "int16", "int32", "int64", "bool")
    _QUANT_SUM_OPS = {"c_quant_allreduce_sum", "c_fused_quant_allreduce_sum",
                      "quant_reduce_scatter", "c_allreduce_sum",
                      "c_fused_allreduce_sum", "zero_reduce_scatter",
                      "c_reducescatter"}
    # quantized PERMUTES are also sound: an all_to_all only re-routes the
    # payload — every receive slice is dequantized whole (a degenerate
    # one-operand accumulate), so the per-block scales never have to
    # cancel across ranks.  The integer-payload check below still applies.
    _QUANT_PERMUTE_OPS = {"c_expert_alltoall"}
    from ..flags import flag
    min_bucket = float(flag("quant_min_bucket_kb")) * 1024.0
    for idx, op in enumerate(block.ops):
        quantized = op.type in ("c_quant_allreduce_sum",
                                "c_fused_quant_allreduce_sum",
                                "quant_reduce_scatter") or \
            op.attrs.get("quant_spec") is not None
        if not quantized or op.type not in collectives:
            continue
        if op.type not in _QUANT_SUM_OPS and \
                op.type not in _QUANT_PERMUTE_OPS:
            result.add(
                "error", QUANT_NON_SUM,
                f"collective {op.type!r} carries a quant_spec but is not "
                f"a summing reduction — blockwise dequant-accumulate-"
                f"requant is only sound for '+' (use the full-precision "
                f"op, or c_quant_allreduce_sum for sums)",
                op, block.idx, idx)
            continue
        payload, payload_known = 0, True
        for n in op.input_names():
            v = block._find_var_recursive(n)
            if v is None:
                payload_known = False
                continue
            if str(v.dtype) in _INT_DTYPES:
                result.add(
                    "error", QUANT_COLLECTIVE_INTEGER,
                    f"quantized collective {op.type!r} would blockwise-"
                    f"quantize {n!r} ({v.dtype}) — integer payloads must "
                    f"ride full-precision collectives (amax/qmax scaling "
                    f"truncates them silently)",
                    op, block.idx, idx)
                payload_known = False
                continue
            shape = tuple(v.shape)
            if not shape or any(int(d) < 0 for d in shape):
                payload_known = False
                continue
            width = {"float64": 8, "float32": 4, "bfloat16": 2,
                     "float16": 2}.get(str(v.dtype), 4)
            numel = 1
            for d in shape:
                numel *= int(d)
            payload += numel * width
        if payload_known and min_bucket > 0 and payload < min_bucket:
            result.add(
                "warning", QUANT_SMALL_BUCKET,
                f"quantized collective {op.type!r} moves only "
                f"{payload} payload bytes "
                f"({sorted(op.input_names())}) < quant_min_bucket_kb = "
                f"{min_bucket / 1024:.0f} KiB — per-block scale tensors "
                f"and the extra collective stage outweigh the byte "
                f"saving; raise fuse_grad_size_in_MB or leave this "
                f"bucket full-precision",
                op, block.idx, idx)

    # (b3) overlap-aware grad-sync soundness (compiler.insert_grad_sync
    # ready-order buckets).  Two misuse classes: (i) overlap requested
    # but a (dtype, axes) group coalesced into ONE bucket — a single
    # collective has no peer to interleave with, so nothing can hide
    # (raise overlap_min_buckets / shrink overlap_bucket_size_in_MB);
    # (ii) a ready-ordered collective with no usable hook position —
    # the lowering cannot fire it inside the backward sweep, so it
    # sinks to the program tail with no backward compute after it.
    ov_groups: Dict[Any, List[int]] = {}
    for idx, op in enumerate(block.ops):
        if not op.attrs.get("_overlap") or op.type not in collectives:
            continue
        dt = None
        for n in op.input_names():
            v = block._find_var_recursive(n)
            if v is not None:
                dt = str(v.dtype)
                break
        key = (dt, str(op.attrs.get("_axis_name") or
                       op.attrs.get("ring_id", 0)))
        ov_groups.setdefault(key, []).append(idx)
        if op.attrs.get("_overlap_hook_pos") is None:
            result.add(
                "warning", OVERLAP_TAIL_SUNK,
                f"ready-ordered collective {op.type!r} "
                f"({sorted(op.input_names())}) has no overlap hook "
                f"position — its bucket's params have no recorded "
                f"forward use, so the collective traces at the program "
                f"tail with no backward compute left to hide it",
                op, block.idx, idx)
    for (dt, axes), idxs in sorted(ov_groups.items(),
                                   key=lambda kv: kv[1][0]):
        if len(idxs) == 1:
            idx = idxs[0]
            op = block.ops[idx]
            result.add(
                "warning", OVERLAP_SINGLE_BUCKET,
                f"overlap_grad_sync requested but the ({dt}, {axes}) "
                f"gradient group coalesced into ONE bucket "
                f"({op.type!r}) — a lone collective cannot interleave "
                f"with later backward compute, so nothing hides; "
                f"shrink overlap_bucket_size_in_MB or raise "
                f"overlap_min_buckets",
                op, block.idx, idx)

    # (c) donation/aliasing conflicts (the PR 2 bug class).  State vars
    # (persistables written by the program) are donated on the jit
    # boundary; a fetch of the same name aliases a buffer the NEXT step's
    # dispatch will donate away, so the handle dies under the reader.
    donated_state = set()
    for op in block.ops:
        for n in op.output_names():
            v = block._find_var_recursive(n)
            if v is not None and v.persistable:
                donated_state.add(n)
    for n in sorted(donated_state & fetch):
        writer = next((op for op in block.ops if n in op.output_names()),
                      None)
        result.add(
            "error", DONATED_VAR_FETCHED,
            f"fetch target {n!r} is a donated state var (persistable, "
            f"updated in-program) — the fetched handle aliases a buffer "
            f"the next step donates away; fetch a copy (assign) or sync "
            f"the scope instead",
            writer, block.idx,
            block.ops.index(writer) if writer is not None else -1)

    # (d) explicit donation annotations: an op that declares it consumes
    # (donates) an input buffer — attrs["_donated_inputs"] — must be the
    # LAST reader of those names
    for idx, op in enumerate(block.ops):
        donated = op.attrs.get("_donated_inputs")
        if not donated:
            continue
        for later_idx in range(idx + 1, len(block.ops)):
            later = block.ops[later_idx]
            hit = set(donated) & set(later.input_names())
            for n in sorted(hit):
                result.add(
                    "error", READ_AFTER_DONATE,
                    f"op {later.type!r} reads {n!r} after op "
                    f"{op.type!r} (op #{idx}) donated its buffer",
                    later, block.idx, later_idx)
        for n in sorted(set(donated) & fetch):
            result.add(
                "error", DONATED_VAR_FETCHED,
                f"fetch target {n!r} is donated by op {op.type!r} "
                f"(op #{idx}) — the fetched handle would alias a "
                f"consumed buffer",
                op, block.idx, idx)


#: gathers whose INPUT must be sharded over the gather axis (the op
#: rebuilds a full tensor from per-rank shards — feeding it a var whose
#: stamped spec does not cover the axis means the layout and the
#: collective schedule disagree)
_SHARD_GATHER_OPS = frozenset({"fsdp_all_gather", "zero_all_gather"})
#: summing reductions whose reduce axes must be DISJOINT from the
#: payload's sharded axes (reducing over an axis the payload is already
#: sharded on double-counts shards that hold different slices)
_SHARD_REDUCE_OPS = frozenset({
    "c_allreduce_sum", "c_fused_allreduce_sum", "c_quant_allreduce_sum",
    "c_fused_quant_allreduce_sum", "zero_reduce_scatter",
    "quant_reduce_scatter", "c_reducescatter", "mp_allreduce_sum"})


def verify_shard_layout(program: Program, result: VerifyResult):
    """Named-axis layout soundness over one program (the shard-layout-*
    diagnostic codes):

    * ``shard-layout-unknown-axis`` — a var's stamped ``dist_attr``
      references a mesh axis that does not exist in the program's
      :class:`~.mesh_layout.MeshLayout` (checked only when a layout is
      stamped — hand-annotated programs without a layout keep the old
      dangling-axes-replicate behavior);
    * ``shard-layout-collective-mismatch`` — a per-var spec disagrees
      with an op's collective schedule: a shard gather
      (``fsdp_all_gather``/``zero_all_gather``) whose input is NOT
      sharded over the gather axis, or a summing reduction whose reduce
      axes intersect the payload's sharded axes (each rank holds a
      DIFFERENT slice — summing them is not a replica reduction).

    Diagnostics are anchored to the op's recorded creation site (for
    unknown axes: the first op touching the var)."""
    from .mesh_layout import _flat_axes

    block = program.global_block()
    layout = getattr(program, "_mesh_layout", None)

    if layout is not None:
        layout_axes = set(layout.axis_names)
        for name, v in block.vars.items():
            da = tuple(getattr(v, "dist_attr", None) or ())
            bad = [a for a in _flat_axes(da) if a not in layout_axes]
            if not bad:
                continue
            idx, op = next(
                ((i, op) for i, op in enumerate(block.ops)
                 if name in op.input_names() or name in op.output_names()),
                (-1, None))
            result.add(
                "error", SHARD_LAYOUT_UNKNOWN_AXIS,
                f"var {name!r} dist_attr {tuple(da)!r} references mesh "
                f"axis(es) {bad} that do not exist in the program's "
                f"MeshLayout {dict(layout.sizes)} — the stamp would "
                f"silently replicate on the real mesh; fix the spec or "
                f"the layout",
                op, block.idx, idx)

    for idx, op in enumerate(block.ops):
        axes = op.attrs.get("_axis_name") or ()
        op_axes = set(_flat_axes(axes))
        if not op_axes:
            continue
        if op.type in _SHARD_GATHER_OPS:
            for n in op.input_names():
                v = block._find_var_recursive(n)
                da = set(_flat_axes(tuple(
                    getattr(v, "dist_attr", None) or ()))) \
                    if v is not None else set()
                missing = op_axes - da
                if missing:
                    result.add(
                        "error", SHARD_LAYOUT_COLLECTIVE_MISMATCH,
                        f"shard gather {op.type!r} rebuilds {n!r} over "
                        f"axis(es) {sorted(missing)} but the var's "
                        f"dist_attr {tuple(getattr(v, 'dist_attr', None) or ()) if v is not None else None!r} "
                        f"does not shard over them — gathering a "
                        f"replicated tensor would tile duplicate copies",
                        op, block.idx, idx)
        elif op.type in _SHARD_REDUCE_OPS:
            for n in op.input_names():
                v = block._find_var_recursive(n)
                if v is None:
                    continue
                da = set(_flat_axes(tuple(
                    getattr(v, "dist_attr", None) or ())))
                overlap = op_axes & da
                if overlap:
                    result.add(
                        "error", SHARD_LAYOUT_COLLECTIVE_MISMATCH,
                        f"collective {op.type!r} sum-reduces {n!r} over "
                        f"axis(es) {sorted(overlap)} that its dist_attr "
                        f"{tuple(getattr(v, 'dist_attr', None) or ())!r} "
                        f"already shards — each rank holds a DIFFERENT "
                        f"slice there, so the reduction double-counts; "
                        f"reduce only over the axes the payload is "
                        f"replicated on",
                        op, block.idx, idx)


_MOE_AXIS_OPS = ("c_expert_alltoall", "moe_ffn")


def verify_moe(program: Program, result: VerifyResult):
    """MoE expert-parallel soundness (parallel/moe.py's decomposed route
    moe_dispatch → c_expert_alltoall → moe_expert_ffn → moe_combine, and
    the fused ops-level moe_ffn fallback — both name the exchange axis
    statically via ``_axis_name``).

    Two misuse classes, both anchored to the offending op:

    * **moe-axis-unknown** — the op names a mesh axis the stamped
      :class:`MeshLayout` does not carry.  At run time the impl resolves
      ``axis_name`` against the live mesh, finds nothing, and silently
      degrades to the identity: every rank keeps its own tokens and the
      experts on the other ranks never see a single one — training
      "works" with 1/ep of the intended expert capacity;
    * **moe-axis-capacity-mismatch** — the static expert count does not
      divide the named axis's size, so ranks would hold ragged expert
      slices and the dispatch/combine all_to_all pair reassembles tokens
      against the wrong expert offsets."""
    from .mesh_layout import _flat_axes

    block = program.global_block()
    layout = getattr(program, "_mesh_layout", None)
    if layout is None:
        return
    layout_axes = set(layout.axis_names)
    sizes = dict(layout.sizes)

    for idx, op in enumerate(block.ops):
        if op.type not in _MOE_AXIS_OPS:
            continue
        axes = tuple(_flat_axes(op.attrs.get("_axis_name") or ()))
        if not axes:
            continue
        unknown = [a for a in axes if a not in layout_axes]
        if unknown:
            result.add(
                "error", MOE_AXIS_UNKNOWN,
                f"MoE op {op.type!r} routes its expert exchange over "
                f"axis(es) {unknown} that do not exist in the program's "
                f"MeshLayout {sizes} — the exchange would silently "
                f"degrade to the identity (each rank keeps its own "
                f"tokens; remote experts never fire); pass the layout's "
                f"expert axis (axis_name={layout.expert_axis!r}) or "
                f"build dense and let the planner stamp it",
                op, block.idx, idx)
            continue
        ep = 1
        for a in axes:
            ep *= int(sizes.get(a, 1))
        if ep <= 1:
            continue
        # static expert count: fused op carries it as an attr; the
        # exchange op's payload Xe is [E, G*C, M] dest-major, so dim 0
        # of its input is E in the (global-shape) dense build.
        e = int(op.attrs.get("num_experts", 0) or 0)
        if not e:
            for n in op.input_names():
                v = block._find_var_recursive(n)
                shape = tuple(getattr(v, "shape", ()) or ()) \
                    if v is not None else ()
                if len(shape) >= 1 and int(shape[0]) > 0:
                    e = int(shape[0])
                    break
        if e and e % ep != 0:
            result.add(
                "error", MOE_AXIS_CAPACITY_MISMATCH,
                f"MoE op {op.type!r} shards {e} experts over axis(es) "
                f"{list(axes)} of total size {ep} — {e} % {ep} != 0, so "
                f"ranks would hold ragged expert slices and the "
                f"dispatch/combine all_to_all pair reassembles tokens "
                f"against wrong expert offsets; pick an expert count "
                f"divisible by the exchange axis (or a smaller "
                f"ep_degree)",
                op, block.idx, idx)


def _collective_sig_ops(program: Program
                        ) -> List[Tuple[Tuple, Operator, int, int]]:
    """(signature, op, block idx, op idx) per collective op of the
    global block — the anchored form of :func:`collective_signature`."""
    collectives = _collective_types()
    block = program.global_block()
    out: List[Tuple[Tuple, Operator, int, int]] = []
    for idx, op in enumerate(block.ops):
        if op.type not in collectives:
            continue
        axes = op.attrs.get("_axis_name")
        if isinstance(axes, (list, tuple)):
            axes = tuple(axes)
        perm = op.attrs.get("perm")
        if perm:
            perm = tuple(tuple(int(x) for x in p) for p in perm)
        elif op.type == "collective_permute":
            perm = ("shift", int(op.attrs.get("shift", 1)))
        elif op.type == "pipe_stage_boundary":
            perm = ("cut", int(op.attrs.get("_pipe_cut", 0)))
        else:
            perm = None
        groups = op.attrs.get("replica_groups") \
            or op.attrs.get("rank_groups")
        if groups:
            groups = tuple(tuple(int(r) for r in g) for g in groups)
        sig = (op.type, axes, op.attrs.get("ring_id", 0),
               tuple(op.input_names()), perm, groups or None)
        out.append((sig, op, block.idx, idx))
    return out


def collective_signature(program: Program) -> List[Tuple]:
    """The ordered collective schedule of a program: (op type, reduce
    axes, ring id, operand names, permutation table, replica groups)
    per collective op.  Operand names are part of the schedule — a
    bucketing pass that splits or reorders the same grads differently
    on one rank deadlocks the mesh even though the op kinds agree; so
    are the ppermute permutation table and replica groups — ranks that
    agree on kind and order but disagree on WHO exchanges with whom
    (a pipe-hop reorder, a regrouped reduce) rendezvous mismatched
    peers.  Two clones of one program running on different ranks MUST
    have identical signatures."""
    return [s for s, _op, _b, _i in _collective_sig_ops(program)]


def check_collective_consistency(programs: Sequence[Program],
                                 result: Optional[VerifyResult] = None
                                 ) -> VerifyResult:
    """Compare the collective schedules of program clones (one per rank /
    per pass variant).  Divergence — different op order, bucket split,
    reduce axes, ppermute permutation table or replica groups — is the
    cross-rank deadlock class the runtime cannot detect (every rank
    blocks in a different collective).  The diagnostic is anchored to
    the diverging op's creation site."""
    result = result or VerifyResult()
    if len(programs) < 2:
        return result
    base = _collective_sig_ops(programs[0])
    base_sig = [s for s, _op, _b, _i in base]
    for i, p in enumerate(programs[1:], start=1):
        sig_ops = _collective_sig_ops(p)
        sig = [s for s, _op, _b, _i in sig_ops]
        if sig != base_sig:
            # find the first divergence point for the message
            j = 0
            while j < min(len(base_sig), len(sig)) \
                    and base_sig[j] == sig[j]:
                j += 1
            a = base_sig[j] if j < len(base_sig) else "<end of schedule>"
            b = sig[j] if j < len(sig) else "<end of schedule>"
            anchor = sig_ops[j] if j < len(sig_ops) \
                else (base[j] if j < len(base) else None)
            op, bidx, oidx = (anchor[1], anchor[2], anchor[3]) \
                if anchor is not None else (None, 0, -1)
            result.add(
                "error", COLLECTIVE_SEQ_DIVERGENCE,
                f"program clone #{i} diverges from clone #0 at collective "
                f"#{j}: {a} vs {b} ({len(base_sig)} vs {len(sig)} "
                f"collectives total) — ranks would deadlock mid-step",
                op, bidx, oidx)
    return result


# ---------------------------------------------------------------------------
# top-level entry points
# ---------------------------------------------------------------------------


def verify_pipeline(program: Program,
                    result: Optional[VerifyResult] = None) -> VerifyResult:
    """Pipeline/remat soundness over a rewritten program
    (framework/pipe.py):

    * ``pipe-collective-crosses-stage`` (error) — a forward collective
      reads a value produced in a DIFFERENT pipeline stage.  Under the
      1F1B lowering each pipe rank executes only its own stage's
      branch, and cross-stage values arrive via the scheduled ppermute
      at a different tick: a collective fed across a cut would
      rendezvous its mesh peers against mismatched schedules.  The
      stage-cut planner refuses such positions; a hand-stamped or
      pass-mutated program is caught here.
    * ``pipe-schedule-order`` (error) — the stamped
      ``pipe_schedule_order`` tick table violates pipeline dataflow: a
      unit runs before the unit that produces its input (a forward
      before its upstream forward, a backward before its own forward or
      its downstream backward, a zero-bubble W before the B that
      stashed its cotangent).  The executor's scan consumes these
      static tables verbatim — a hand-mutated or stale table would read
      a ring slot before anything arrived in it.
    * ``pipe-ring-overflow`` (error) — the stamped ``pipe_ring_slots``
      are smaller than the maximum in-flight saved-input / cotangent
      count the stamped order actually reaches: slot ``mb % slots``
      would be overwritten while a live microbatch still needs it.
    * ``remat-recompute-side-effect`` (warning) — a recompute segment
      (between ``backward.checkpoints`` boundaries) contains an
      RNG-drawing op with no ``_folded_key``/``fix_seed`` marker: the
      segment re-executes during the backward sweep, and randomness not
      derived from the replayed segment key would redraw, making the
      recomputed forward disagree with the original (wrong gradients).
      The executor's ``jax.checkpoint`` lowering threads the segment
      key explicitly — ``pipe.apply_remat`` stamps ``_folded_key`` after
      that audit; hand-set checkpoints get the warning."""
    result = result or VerifyResult(program)
    block = program.global_block()
    ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
    bw_idx = next((i for i, op in enumerate(ops)
                   if op.type == "backward"), None)
    if bw_idx is None:
        return result
    bw = ops[bw_idx]
    fwd_ops = ops[:bw_idx]

    if bw.attrs.get("pipe_stages"):
        from ..ops.registry import OP_SPECS
        def_stage: Dict[str, Any] = {}
        for op in fwd_ops:
            s = op.attrs.get("_pipe_stage")
            for n in op.output_names():
                def_stage.setdefault(n, s)
        for idx, op in enumerate(fwd_ops):
            spec = OP_SPECS.get(op.type)
            if spec is None or not getattr(spec, "collective", False) \
                    or op.type == "pipe_stage_boundary":
                continue
            s = op.attrs.get("_pipe_stage")
            for n in op.input_names():
                ds = def_stage.get(n)
                if ds is not None and s is not None and ds != s:
                    result.add(
                        "error", PIPE_COLLECTIVE_CROSSES_STAGE,
                        f"collective op {op.type!r} in pipeline stage "
                        f"{s} reads {n!r} produced in stage {ds} — a "
                        f"collective fed across a stage cut would "
                        f"rendezvous against mismatched 1F1B schedules "
                        f"(move the cut, or keep the collective with "
                        f"its producers)",
                        op, block.idx, idx)

        order = bw.attrs.get("pipe_schedule_order") or ()
        if order:
            V = int(bw.attrs.get("pipe_stages") or 1)
            ftick: Dict[Any, int] = {}
            btick: Dict[Any, int] = {}
            wtick: Dict[Any, int] = {}
            for t, k, ph, m in order:
                {"F": ftick, "B": btick, "W": wtick}[ph][(k, m)] = t

            def bad(msg):
                result.add("error", PIPE_SCHEDULE_ORDER,
                           f"pipe_schedule_order: {msg} — the "
                           f"executor's scan replays this table "
                           f"verbatim, so a dataflow-violating order "
                           f"reads ring slots before their arrival "
                           f"(restamp via pipe.apply_pipeline)",
                           bw, block.idx, bw_idx)

            for (k, m), t in ftick.items():
                if k > 0 and ftick.get((k - 1, m), t) >= t:
                    bad(f"F(stage {k}, mb {m}) at tick {t} does not "
                        f"follow F(stage {k - 1}, mb {m})")
            for (k, m), t in btick.items():
                if ftick.get((k, m), t) >= t:
                    bad(f"B(stage {k}, mb {m}) at tick {t} does not "
                        f"follow its own forward")
                if k < V - 1 and (k + 1, m) in btick \
                        and btick[(k + 1, m)] >= t:
                    bad(f"B(stage {k}, mb {m}) at tick {t} does not "
                        f"follow B(stage {k + 1}, mb {m})")
            for (k, m), t in wtick.items():
                dep = btick.get((k, m)) if k > 0 else btick.get((1, m))
                if dep is not None and dep >= t:
                    bad(f"W(stage {k}, mb {m}) at tick {t} does not "
                        f"follow the B that stashed its cotangent")

            ring = bw.attrs.get("pipe_ring_slots")
            if ring:
                M = int(bw.attrs.get("pipe_microbatches") or 1)

                def need(arrive):
                    peak = 0
                    for k in range(V):
                        events = [iv for iv in
                                  (arrive(k, m) for m in range(M))
                                  if iv is not None]
                        for a, r in events:
                            live = sum(1 for a2, r2 in events
                                       if a2 <= a <= r2)
                            peak = max(peak, live)
                    return peak

                def f_iv(k, m):
                    if k == 0 or (k - 1, m) not in ftick:
                        return None
                    rel = max(btick.get((k, m), 0), wtick.get((k, m), 0))
                    return (ftick[(k - 1, m)] + 1, rel)

                def c_iv(k, m):
                    if k >= V - 1 or (k + 1, m) not in btick:
                        return None
                    rel = max(btick.get((k, m), 0), wtick.get((k, m), 0))
                    return (btick[(k + 1, m)] + 1, rel)

                w_f, w_c = int(ring[0]), int(ring[1])
                need_f, need_c = need(f_iv), need(c_iv)
                if need_f > w_f or need_c > w_c:
                    result.add(
                        "error", PIPE_RING_OVERFLOW,
                        f"pipe_ring_slots {ring!r} smaller than the "
                        f"stamped order's in-flight peak (saved-input "
                        f"{need_f}, cotangent {need_c}): slot mb % "
                        f"slots would be overwritten while a live "
                        f"microbatch still reads it — restamp via "
                        f"pipe.apply_pipeline",
                        bw, block.idx, bw_idx)

    checkpoints = set(bw.attrs.get("checkpoints") or ())
    if checkpoints:
        # the recompute region = every op up to the LAST checkpoint
        # marker's producer (the final segment is never re-executed)
        last_seg_start = -1
        remaining = set(checkpoints)
        for idx, op in enumerate(fwd_ops):
            produced = set(op.output_names()) & remaining
            if produced:
                remaining -= produced
                last_seg_start = idx
        from .pipe import RNG_OP_TYPES
        for idx, op in enumerate(fwd_ops[:last_seg_start + 1]):
            if op.type not in RNG_OP_TYPES:
                continue
            if op.type == "dropout" and op.attrs.get("is_test"):
                continue
            if op.attrs.get("_folded_key") or op.attrs.get("fix_seed"):
                continue
            result.add(
                "warning", REMAT_RECOMPUTE_SIDE_EFFECT,
                f"RNG op {op.type!r} sits inside a recompute segment "
                f"(backward checkpoints re-execute it during the "
                f"reverse sweep) with no folded key: if its randomness "
                f"is not derived from the replayed segment key, the "
                f"recomputed forward diverges from the original and "
                f"the gradients are wrong — stamp `_folded_key` after "
                f"auditing (pipe.apply_remat does), or set fix_seed",
                op, block.idx, idx)
    return result


def verify_program(program: Program, startup: Optional[Program] = None,
                   feed_names: Iterable[str] = (),
                   fetch_names: Iterable[str] = (),
                   scope_names: Iterable[str] = ()) -> VerifyResult:
    """Run every static check over ``program``; returns the collected
    :class:`VerifyResult` (caller decides whether to raise)."""
    result = VerifyResult(program)
    verify_structure(program, result, feed_names, scope_names)
    if startup is not None:
        verify_startup_agreement(program, startup, result)
    infer_shapes(program, result, feed_names)
    verify_distributed(program, result, fetch_names)
    verify_shard_layout(program, result)
    verify_moe(program, result)
    verify_pipeline(program, result)
    # launch audit (framework/launch_audit.py): pipelined programs get
    # their stamped schedule expanded into per-rank timelines and proven
    # compatible + deadlock-free; collectives under divergent control
    # flow get their hang proven in the wait-for game
    from .launch_audit import verify_launch
    verify_launch(program, result)
    return result


def verify_inference(program: Program, feed_names: Iterable[str] = (),
                     fetch_names: Iterable[str] = (),
                     scope_names: Iterable[str] = ()) -> VerifyResult:
    """Inference/serving verification profile: everything
    :func:`verify_program` checks, plus rejections specific to a SERVED
    program.  A served program must be a pure read-only function of its
    feeds — it runs on a single replica (no mesh peers to rendezvous
    with), under the predictor's read-only-state fast path (weights
    device-resident, never donated), on arbitrary request streams:

    * **collectives** anywhere in the program deadlock a single serving
      replica (there is no peer to complete the rendezvous);
    * **backward/grad ops** mean the training graph leaked through the
      ``save_inference_model`` prune;
    * **persistable writes** would mutate (and, under the training fast
      path, donate) the shared weight buffers request-to-request — a
      served program must not update state;
    * **donation annotations** (``_donated_inputs``) consume buffers the
      next request still needs.

    Wired at :class:`AnalysisPredictor` load under
    ``flag("verify_programs")`` and exposed as
    ``tools/proglint.py --inference``."""
    result = verify_program(program, feed_names=feed_names,
                            fetch_names=fetch_names,
                            scope_names=scope_names)
    collectives = _collective_types()

    def scan(block: Block):
        for idx, op in enumerate(block.ops):
            if op.type in collectives:
                result.add(
                    "error", INFERENCE_COLLECTIVE,
                    f"served program contains collective op {op.type!r} — "
                    f"a single serving replica has no mesh peers and "
                    f"deadlocks at the rendezvous",
                    op, block.idx, idx)
            if op.type == "backward" or op.type.endswith("_grad"):
                result.add(
                    "error", INFERENCE_TRAINING_OP,
                    f"served program contains training op {op.type!r} — "
                    f"the backward graph leaked through the inference "
                    f"prune (save_inference_model)",
                    op, block.idx, idx)
            if op.attrs.get("_donated_inputs"):
                result.add(
                    "error", INFERENCE_DONATED_READ,
                    f"op {op.type!r} donates inputs "
                    f"{sorted(op.attrs['_donated_inputs'])} — a served "
                    f"program must not consume buffers; the next request "
                    f"reads the same weights",
                    op, block.idx, idx)
            for n in op.output_names():
                v = block._find_var_recursive(n)
                if v is not None and v.persistable:
                    result.add(
                        "error", INFERENCE_STATE_WRITE,
                        f"served program writes persistable {n!r} (op "
                        f"{op.type!r}) — inference state is read-only; a "
                        f"write would mutate weights request-to-request",
                        op, block.idx, idx)
            for sub in _iter_sub_blocks(op):
                scan(sub)

    scan(program.global_block())
    return result


def verify_decode(program: Program, feed_names: Iterable[str] = (),
                  fetch_names: Iterable[str] = (),
                  scope_names: Iterable[str] = (),
                  cache_vars: Iterable[str] = ()) -> VerifyResult:
    """Decode-engine verification profile (the autoregressive serving
    runtime, paddle_tpu/serving/decode.py): the inference rules with ONE
    carve-out — a decode program is a read-only function of its feeds
    AND ITS KV-CACHE POOLS, which it appends to in place:

    * **collectives** / **training ops** are rejected exactly as in
      :func:`verify_inference` (a decode replica is a single serving
      process);
    * **persistable writes** are allowed ONLY to the declared
      ``cache_vars`` (the paged pool the engine owns the lifecycle of);
      any other persistable write (``decode-state-write``) would mutate
      weights token-to-token;
    * every declared cache var must actually exist in the program
      (``decode-cache-undeclared``) — a typo'd pool name would silently
      re-enable the weight-write hole;
    * the ``decode_chain`` marker op (the device-chained decode scan,
      executor.lower_decode_chain) must be UNIQUE and the program's
      LAST op (``decode-chain-misplaced``): the executor lowers exactly
      one marker over everything before it, so a second marker or an op
      after the marker would silently escape the chained scan.

    Wired at :class:`DecodeEngine` start under
    ``flag("verify_programs")`` for every engine program (prefill,
    decode step, each chained executable, chunked prefill)."""
    result = verify_program(program, feed_names=feed_names,
                            fetch_names=fetch_names,
                            scope_names=scope_names)
    collectives = _collective_types()
    cache_vars = set(cache_vars)
    declared = set(program.global_block().vars)
    for name in sorted(cache_vars - declared):
        result.add(
            "error", DECODE_CACHE_UNDECLARED,
            f"decode cache var {name!r} is not declared in the program — "
            f"the write allow-list would not cover anything", None, 0, -1)

    gb = program.global_block()
    chain_at = [i for i, op in enumerate(gb.ops)
                if op.type == "decode_chain"]
    for i in chain_at[1:]:
        result.add(
            "error", DECODE_CHAIN_MISPLACED,
            f"decode program carries {len(chain_at)} decode_chain "
            f"markers — the executor lowers exactly ONE chain per "
            f"program; a second marker would never run",
            gb.ops[i], gb.idx, i)
    if chain_at and chain_at[0] != len(gb.ops) - 1 and \
            len(chain_at) == 1:
        result.add(
            "error", DECODE_CHAIN_MISPLACED,
            f"decode_chain marker at op {chain_at[0]} of "
            f"{len(gb.ops)} — the marker must be the LAST op: "
            f"everything before it is the scanned step body, and an op "
            f"AFTER it would silently escape the device chain",
            gb.ops[chain_at[0]], gb.idx, chain_at[0])

    def scan(block: Block):
        for idx, op in enumerate(block.ops):
            if op.type == "decode_chain" and block is not gb:
                result.add(
                    "error", DECODE_CHAIN_MISPLACED,
                    f"decode_chain marker inside sub-block {block.idx} "
                    f"— the executor only lowers a chain at the top "
                    f"level of the step program",
                    op, block.idx, idx)
            if op.type in collectives:
                result.add(
                    "error", INFERENCE_COLLECTIVE,
                    f"decode program contains collective op {op.type!r} — "
                    f"a single decode replica has no mesh peers and "
                    f"deadlocks at the rendezvous",
                    op, block.idx, idx)
            if op.type == "backward" or op.type.endswith("_grad"):
                result.add(
                    "error", INFERENCE_TRAINING_OP,
                    f"decode program contains training op {op.type!r} — "
                    f"the backward graph leaked into the serving path",
                    op, block.idx, idx)
            for n in op.output_names():
                if n in cache_vars:
                    continue
                v = block._find_var_recursive(n)
                if v is not None and v.persistable:
                    result.add(
                        "error", DECODE_STATE_WRITE,
                        f"decode program writes persistable {n!r} (op "
                        f"{op.type!r}) outside the declared cache pool "
                        f"{sorted(cache_vars)} — only the KV-cache may "
                        f"be appended to; anything else mutates weights "
                        f"token-to-token",
                        op, block.idx, idx)
            for sub in _iter_sub_blocks(op):
                scan(sub)

    scan(program.global_block())
    return result


#: verification cache — a program is verified at most once per
#: (_uid, _version, feeds, fetches); ``stats`` is asserted by tier-1
_VERIFY_CACHE: Dict[Tuple, VerifyResult] = {}
_VERIFY_CACHE_CAP = 256
VERIFY_STATS = {"runs": 0, "hits": 0}


def verify_cached(program: Program, feed_names: Iterable[str] = (),
                  fetch_names: Iterable[str] = (),
                  scope_names: Iterable[str] = (),
                  startup: Optional[Program] = None,
                  raise_on_error: bool = True) -> VerifyResult:
    """Cached :func:`verify_program` — the Executor/CompiledProgram wiring
    point.  The full-program walk runs once per program version; repeat
    ``prepare``/``run`` calls hit the cache."""
    # the mesh layout participates in the key: the SAME program verified
    # under a different MeshLayout (e.g. replanned after an elastic
    # restore) must not reuse the stale verdict — the shard-layout and
    # collective-axis checks read axis sizes
    layout = getattr(program, "_mesh_layout", None)
    mesh_axes = tuple(sorted(layout.sizes.items())) \
        if layout is not None else ()
    # the pipe schedule participates for the same reason: a replanner
    # that restamps the schedule family or microbatch count on the
    # backward op (without bumping the program version) changes the
    # per-rank collective timelines — the launch audit must re-prove
    # them, not reuse the stale verdict
    bw = next((op for op in program.global_block().ops
               if op.type == "backward"), None)
    pipe_key = (bw.attrs.get("pipe_schedule"),
                bw.attrs.get("pipe_microbatches"),
                bw.attrs.get("pipe_stages")) if bw is not None else ()
    key = (program._uid, program._version,
           tuple(sorted(feed_names)), tuple(fetch_names), mesh_axes,
           pipe_key)
    result = _VERIFY_CACHE.get(key)
    if result is None:
        VERIFY_STATS["runs"] += 1
        result = verify_program(program, startup=startup,
                                feed_names=feed_names,
                                fetch_names=fetch_names,
                                scope_names=scope_names)
        if len(_VERIFY_CACHE) >= _VERIFY_CACHE_CAP:
            _VERIFY_CACHE.pop(next(iter(_VERIFY_CACHE)))
        _VERIFY_CACHE[key] = result
    else:
        VERIFY_STATS["hits"] += 1
    if raise_on_error:
        result.raise_on_error()
    return result


def clear_verify_cache():
    _VERIFY_CACHE.clear()
    VERIFY_STATS["runs"] = 0
    VERIFY_STATS["hits"] = 0


# ---------------------------------------------------------------------------
# 4. pass-pipeline invariant checking
# ---------------------------------------------------------------------------


def _defined_names(program: Program) -> Set[str]:
    """Names either declared or produced somewhere in the program."""
    out: Set[str] = set()
    for b in program.blocks:
        out |= set(b.vars)
        for op in b.ops:
            out |= set(op.output_names())
    return out


def _producible_names(program: Program, feed_names=()) -> Set[str]:
    """Names a lowering could materialise: feeds, data/persistable/
    initializer vars, and every op output."""
    out = set(feed_names)
    for b in program.blocks:
        for name, v in b.vars.items():
            if v.persistable or v.is_data or v.initializer is not None:
                out.add(name)
        for op in b.ops:
            out |= set(op.output_names())
    return out


def pass_snapshot(program: Program, fetch_names: Iterable[str] = ()
                  ) -> Dict[str, Any]:
    """Pre-pass state consumed by :func:`check_pass_invariants`."""
    return {
        "defined": _defined_names(program),
        "producible": _producible_names(program),
        "fetch_names": tuple(fetch_names),
        "op_count": sum(len(b.ops) for b in program.blocks),
    }


def check_pass_invariants(program: Program, pass_name: str,
                          snapshot: Dict[str, Any],
                          fetch_names: Iterable[str] = ()):
    """Post-pass invariant check (ref: the reference's per-pass graph
    validity checks in framework/ir/pass.cc ApplyImpl wrappers): the
    rewritten program must still be structurally well-formed, and every
    fetch target that was producible before the pass must remain
    producible after it.  Raises :class:`PassInvariantError` naming the
    pass, with the defined-var diff — so a fusion pass that breaks
    well-formedness is caught at the pass boundary, not at compile."""
    fetch_names = tuple(fetch_names) or snapshot.get("fetch_names", ())
    result = VerifyResult(program)
    verify_structure(program, result)
    problems = [d for d in result.errors()
                if d.code in (USE_BEFORE_DEF, UNDECLARED_INPUT,
                              MISSING_OP_IMPL)]
    producible = _producible_names(program)
    lost_fetches = [n for n in fetch_names
                    if n in snapshot["producible"] and n not in producible]
    if not problems and not lost_fetches:
        return
    defined_now = _defined_names(program)
    dropped = sorted(snapshot["defined"] - defined_now)
    added = sorted(defined_now - snapshot["defined"])
    lines = [f"pass {pass_name!r} broke program invariants "
             f"(ops {snapshot['op_count']} → "
             f"{sum(len(b.ops) for b in program.blocks)}):"]
    if lost_fetches:
        lines.append(f"  fetch targets no longer producible: {lost_fetches}")
    for d in problems:
        lines.append("  " + d.format().replace("\n", "\n  "))
    if dropped:
        lines.append(f"  defined-var set dropped: {dropped[:20]}"
                     + (" ..." if len(dropped) > 20 else ""))
    if added:
        lines.append(f"  defined-var set added: {added[:20]}"
                     + (" ..." if len(added) > 20 else ""))
    raise PassInvariantError("\n".join(lines))


# ---------------------------------------------------------------------------
# Pallas kernel-routing report (the custom-kernel tier, statically)
# ---------------------------------------------------------------------------


def kernel_routing_report(program: Program, feed_shapes=None,
                          backend: str = "tpu", mesh_axes=None,
                          fetch_names: Iterable[str] = ()) -> Dict:
    """Per-program Pallas routing, with ZERO compiles and zero traces.

    For every op in the global block that carries a ``pallas`` channel
    (ops/op_specs.py), evaluate the route's flag/backend/shape gates at
    the op's statically inferred signatures — answering "which ops WILL
    lower to a custom kernel at these shapes on ``backend``, and why do
    the rest fall back".  Shapes come from the op_spec ``infer`` channel
    seeded with ``feed_shapes`` (name → shape tuple), exactly like the
    memory analyzer; ``mesh_axes`` (axis → size) defaults to the
    program's stamped :class:`MeshLayout` and scopes the routes that
    depend on device-local shards (the ring route divides the sequence
    by the sp size; the dequant-accumulate route needs the peer count).

    Returns ``{"backend", "rows": [{op, index, route, kernel, reason,
    kernels}], "summary": {kernel: {"pallas": n, "fallback": n}}}`` —
    the report tools/proglint.py prints under ``--kernels``."""
    from ..ops.registry import OP_SPECS, VarSig, pallas_route
    from .memory_analysis import _feed_sigs

    if mesh_axes is None:
        layout = getattr(program, "_mesh_layout", None)
        if layout is not None:
            mesh_axes = {a: s for a, s in layout.sizes.items()}
    result = VerifyResult()
    init_env = _feed_sigs(program, feed_shapes, unknown_dim=-1) \
        if feed_shapes else None
    env = infer_shapes(program, result, init_env=init_env)
    block = program.global_block()
    rows: List[Dict] = []
    summary: Dict[str, Dict[str, int]] = {}
    for idx, op in enumerate(block.ops):
        spec = OP_SPECS.get(op.type)
        if spec is None or not getattr(spec, "pallas", None):
            continue
        ins = {slot: [env.get(n) or _declared_sig(block, n)
                      or VarSig(None, "float32") for n in names]
               for slot, names in op.inputs.items()}
        route, reason = pallas_route(op.type, ins, op.attrs,
                                     axis_sizes=mesh_axes,
                                     backend=backend, count=False)
        if route is not None:
            row = {"op": op.type, "index": idx, "route": "pallas",
                   "kernel": route.kernel, "reason": reason,
                   "kernels": list(route.kernels)}
        else:
            matching = [r for r in spec.pallas
                        if r.match is None or r.match(op.attrs, mesh_axes)]
            # same label pallas_route's fallback counter takes: the
            # last (most general) route in play
            label = (matching or spec.pallas[:1])[-1].kernel
            row = {"op": op.type, "index": idx, "route": "fallback",
                   "kernel": label, "reason": reason,
                   "kernels": []}
        rows.append(row)
        s = summary.setdefault(row["kernel"],
                               {"pallas": 0, "fallback": 0})
        s["pallas" if route is not None else "fallback"] += 1
    return {"backend": backend,
            "mesh_axes": dict(mesh_axes or {}),
            "rows": rows, "summary": summary}


# ---------------------------------------------------------------------------
# reshard-plan validation (elastic restore: framework/reshard.py)
# ---------------------------------------------------------------------------

#: anchored diagnostic codes for resharding-restore plans
RESHARD_INDIVISIBLE = "reshard-indivisible"
RESHARD_AXIS_DANGLING = "reshard-axis-dangling"
RESHARD_FLAT_SHAPE = "reshard-flat-shape"
RESHARD_UNKNOWN_STEP = "reshard-unknown-step"
RESHARD_UNLOWERABLE = "reshard-unlowerable-step"
RESHARD_DIVS_UNRESOLVED = "reshard-divs-unresolved"
RESHARD_NEGATIVE_WIRE = "reshard-negative-wire"
RESHARD_CANDIDATE_ORDER = "reshard-candidate-order"
RESHARD_NOOP = "reshard-noop"


def verify_reshard(plan, result: Optional[VerifyResult] = None
                   ) -> VerifyResult:
    """Validate a :class:`~.reshard.ReshardPlan` before anything moves:
    schedule well-formedness (every step lowers to a registered op, the
    step chain lands exactly on the destination shard counts), byte
    accounting sanity (no negative wire, the chosen candidate is the
    cheapest priced), plus the per-var planning issues (indivisible
    dims, dangling axes, flat-shard metadata mismatches) as anchored
    ``reshard-*`` diagnostics.  Zero compiles — pure plan inspection."""
    from ..ops.registry import OP_SPECS
    from .reshard import STEP_LOWERING

    result = result or VerifyResult()
    for sev, code, msg in plan.issues():
        result.add(sev, code, msg)
    if plan.identity and plan.transfers:
        src = plan.src_layout.sizes if plan.src_layout else None
        dst = plan.dst_layout.sizes if plan.dst_layout else None
        if src == dst:
            result.add("warning", RESHARD_NOOP,
                       f"reshard plan {src} -> {dst} moves nothing — "
                       f"the layouts are identical")
    local_ops = {"slice", "concat", "reshape", "c_identity"}
    for t in plan.transfers.values():
        if t.identity:
            continue
        cur = list(t.src_divs)
        for s in t.steps:
            if s.kind not in STEP_LOWERING:
                result.add("error", RESHARD_UNKNOWN_STEP,
                           f"persistable {t.name!r}: step kind "
                           f"{s.kind!r} has no lowering")
                continue
            for op in s.lowers_to:
                if op not in OP_SPECS and op not in local_ops:
                    result.add(
                        "error", RESHARD_UNLOWERABLE,
                        f"persistable {t.name!r}: step {s.kind!r} "
                        f"lowers to unregistered op {op!r}")
            if s.wire_bytes < 0:
                result.add("error", RESHARD_NEGATIVE_WIRE,
                           f"persistable {t.name!r}: step {s.kind!r} "
                           f"prices negative wire ({s.wire_bytes})")
            if s.kind != "repad" and s.dim < len(cur):
                if cur[s.dim] != s.src_parts:
                    result.add(
                        "error", RESHARD_DIVS_UNRESOLVED,
                        f"persistable {t.name!r}: step {s.kind!r} on "
                        f"dim {s.dim} expects {s.src_parts} source "
                        f"part(s), chain has {cur[s.dim]}")
                cur[s.dim] = s.dst_parts
            elif s.kind == "repad":
                cur = list(t.dst_divs)
        if t.flat is None and cur != list(t.dst_divs):
            result.add("error", RESHARD_DIVS_UNRESOLVED,
                       f"persistable {t.name!r}: schedule ends at shard "
                       f"counts {cur}, destination needs {t.dst_divs}")
        if t.candidates:
            chosen = [c for c in t.candidates if c.get("chosen")]
            if len(chosen) != 1:
                result.add("error", RESHARD_CANDIDATE_ORDER,
                           f"persistable {t.name!r}: "
                           f"{len(chosen)} chosen candidate(s), want 1")
            elif any(c["wire_bytes"] < chosen[0]["wire_bytes"]
                     for c in t.candidates):
                result.add(
                    "error", RESHARD_CANDIDATE_ORDER,
                    f"persistable {t.name!r}: a rejected candidate is "
                    f"cheaper than the chosen schedule "
                    f"({t.candidates})")
    return result


__all__ = [
    "Diagnostic", "VerifyResult", "PassInvariantError",
    "QUANT_COLLECTIVE_INTEGER", "QUANT_NON_SUM", "QUANT_SMALL_BUCKET",
    "OVERLAP_SINGLE_BUCKET", "OVERLAP_TAIL_SUNK",
    "SHARD_LAYOUT_UNKNOWN_AXIS", "SHARD_LAYOUT_COLLECTIVE_MISMATCH",
    "MOE_AXIS_UNKNOWN", "MOE_AXIS_CAPACITY_MISMATCH", "verify_moe",
    "PIPE_COLLECTIVE_CROSSES_STAGE", "PIPE_SCHEDULE_ORDER",
    "PIPE_RING_OVERFLOW", "REMAT_RECOMPUTE_SIDE_EFFECT",
    "verify_program", "verify_inference", "verify_decode",
    "verify_cached", "verify_pipeline",
    "DECODE_STATE_WRITE", "DECODE_CACHE_UNDECLARED",
    "DECODE_CHAIN_MISPLACED",
    "clear_verify_cache",
    "verify_structure", "verify_startup_agreement", "infer_shapes",
    "verify_distributed", "verify_shard_layout", "collective_signature",
    "check_collective_consistency", "pass_snapshot",
    "check_pass_invariants", "op_reads_recursive", "VERIFY_STATS",
    "kernel_routing_report", "verify_reshard",
    "RESHARD_INDIVISIBLE", "RESHARD_AXIS_DANGLING", "RESHARD_FLAT_SHAPE",
    "RESHARD_UNKNOWN_STEP", "RESHARD_UNLOWERABLE",
    "RESHARD_DIVS_UNRESOLVED", "RESHARD_NEGATIVE_WIRE",
    "RESHARD_CANDIDATE_ORDER", "RESHARD_NOOP",
    "SPEC_DRIFT_SHAPE", "SPEC_DRIFT_FLOPS", "SPEC_DRIFT_WIRE",
    "SPEC_DRIFT_MEM",
    "LAUNCH_SCHEDULE_DIVERGENCE", "LAUNCH_DEADLOCK_CYCLE",
    "LAUNCH_FINGERPRINT_DRIFT",
]
