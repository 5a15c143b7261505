"""Program IR for the TPU-native framework.

The reference (PaddlePaddle Fluid v1.8) describes computation as a
``ProgramDesc{BlockDesc{VarDesc, OpDesc}}`` protobuf built from Python and
interpreted op-by-op by a C++ executor (ref: framework/framework.proto:211,
python/paddle/fluid/framework.py:3857).  This rebuild keeps the *contract* —
a serializable, Python-built static program with named variables and ops —
but the execution model is trace → XLA-compile → execute: an entire block
lowers to ONE jitted JAX function instead of an op-by-op interpreter loop
(see executor.py).  Ops therefore carry no kernels here; they are symbolic
nodes resolved against the JAX op registry (paddle_tpu/ops/registry.py) at
lowering time.
"""

from __future__ import annotations

import contextlib
import copy
import itertools
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import unique_name

# ---------------------------------------------------------------------------
# dtype handling
# ---------------------------------------------------------------------------

_DTYPE_ALIASES = {
    "float32": "float32", "fp32": "float32", np.float32: "float32",
    "float64": "float64", "fp64": "float64", np.float64: "float64",
    "float16": "float16", "fp16": "float16", np.float16: "float16",
    "bfloat16": "bfloat16", "bf16": "bfloat16",
    "int8": "int8", np.int8: "int8",
    "uint8": "uint8", np.uint8: "uint8",
    "int16": "int16", np.int16: "int16",
    "int32": "int32", np.int32: "int32",
    "int64": "int64", np.int64: "int64",
    "bool": "bool", np.bool_: "bool", bool: "bool",
    float: "float32", int: "int64",
}


def convert_dtype(dtype) -> str:
    """Normalise any dtype spelling to a canonical string."""
    if isinstance(dtype, str) and dtype in _DTYPE_ALIASES:
        return _DTYPE_ALIASES[dtype]
    if dtype in _DTYPE_ALIASES:
        return _DTYPE_ALIASES[dtype]
    try:
        return np.dtype(dtype).name
    except TypeError:
        pass
    # jax dtypes (e.g. jnp.bfloat16) expose a name
    name = getattr(dtype, "name", None) or getattr(dtype, "__name__", None)
    if name in ("bfloat16", "float32", "float64", "float16", "int8", "uint8",
                "int16", "int32", "int64", "bool"):
        return name
    raise ValueError(f"unsupported dtype: {dtype!r}")


# ---------------------------------------------------------------------------
# Variable / Parameter
# ---------------------------------------------------------------------------


class Variable:
    """A named tensor slot in a Block (ref: fluid framework.py:834).

    Unlike the reference there is no LoD machinery on device — ragged
    sequences are handled on the host by bucketing/padding (SURVEY §5
    "long-context").  ``shape`` may contain -1 (unknown/batch dims); concrete
    shapes are bound at executor lowering time from the feeds.
    """

    def __init__(self, block: "Block", name: str, shape: Sequence[int] = (),
                 dtype="float32", persistable: bool = False,
                 stop_gradient: bool = True, trainable: bool = False,
                 is_data: bool = False, initializer=None):
        self.block = block
        self.name = name
        self.shape = tuple(int(s) for s in shape)
        self.dtype = convert_dtype(dtype)
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.trainable = trainable
        self.is_data = is_data
        self.initializer = initializer
        # Optional jax.sharding.PartitionSpec-like annotation used by the
        # distributed lowering (parallel/); None means replicated/auto.
        self.sharding = None
        self._dist_attr = None

    @property
    def dist_attr(self):
        """Distributed layout of this var: a canonical
        :class:`~.mesh_layout.ShardSpec` (PartitionSpec over named mesh
        axes), or None for replicated/auto.  The setter coerces the
        legacy bare-tuple spelling (``w.dist_attr = (None, "tp")``) —
        ShardSpec subclasses tuple, so every old consumer keeps
        working."""
        d = self.__dict__
        if "_dist_attr" in d:
            return d["_dist_attr"]
        return d.get("dist_attr")      # pre-property pickles

    @dist_attr.setter
    def dist_attr(self, value):
        from .mesh_layout import ShardSpec
        self.__dict__["_dist_attr"] = ShardSpec.coerce(value)

    # -- python sugar mirroring the reference's Variable operators --------
    def _elementwise(self, other, op):
        from ..layers import math_ops
        return math_ops._binary(op, self, other)

    def __add__(self, other):
        return self._elementwise(other, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, other):
        return self._elementwise(other, "elementwise_sub")

    def __rsub__(self, other):
        from ..layers import math_ops
        return math_ops._binary("elementwise_sub", other, self)

    def __mul__(self, other):
        return self._elementwise(other, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._elementwise(other, "elementwise_div")

    def __matmul__(self, other):
        from ..layers import math_ops
        return math_ops.matmul(self, other)

    def __neg__(self):
        from ..layers import math_ops
        return math_ops.scale(self, scale=-1.0)

    @property
    def grad_name(self) -> str:
        return grad_var_name(self.name)

    def astype(self, dtype):
        from ..layers import tensor_ops
        return tensor_ops.cast(self, dtype)

    def __repr__(self):
        return (f"Variable(name={self.name!r}, shape={self.shape}, "
                f"dtype={self.dtype}, persistable={self.persistable})")

    __str__ = __repr__


class Parameter(Variable):
    """A trainable persistable Variable (ref: framework.py:5100)."""

    def __init__(self, block, name, shape, dtype="float32", initializer=None,
                 regularizer=None, need_clip=True, trainable=True,
                 is_distributed=False):
        super().__init__(block, name, shape, dtype, persistable=True,
                         stop_gradient=not trainable, trainable=trainable,
                         initializer=initializer)
        self.regularizer = regularizer
        self.need_clip = need_clip
        self.is_distributed = is_distributed
        self.optimize_attrs = {"learning_rate": 1.0}


GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


# ---------------------------------------------------------------------------
# Operator
# ---------------------------------------------------------------------------


class Operator:
    """Symbolic op node (ref: framework.py:1821 / framework.proto:42 OpDesc).

    ``inputs``/``outputs`` map slot names → lists of variable *names* (same
    slot convention as the reference: "X", "Y", "Out", ...).  The callable
    semantics live in the JAX op registry keyed by ``type``.
    """

    def __init__(self, block: "Block", type: str,
                 inputs: Optional[Dict[str, Any]] = None,
                 outputs: Optional[Dict[str, Any]] = None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.block = block
        self.type = type
        self.inputs = {k: _to_name_list(v) for k, v in (inputs or {}).items()}
        self.outputs = {k: _to_name_list(v) for k, v in (outputs or {}).items()}
        self.attrs = dict(attrs or {})
        # user creation site, attached to runtime errors (ref:
        # framework/op_call_stack.cc InsertCallStackInfo)
        from .errors import capture_user_callstack
        self.callstack = capture_user_callstack()

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    def input_names(self):
        return [n for ns in self.inputs.values() for n in ns]

    def output_names(self):
        return [n for ns in self.outputs.values() for n in ns]

    def __repr__(self):
        ins = {k: v for k, v in self.inputs.items()}
        outs = {k: v for k, v in self.outputs.items()}
        return f"Op({self.type}, in={ins}, out={outs})"


_device_guard_stack: List[str] = []


@contextlib.contextmanager
def device_guard(device: Optional[str] = None):
    """Pipeline stage annotation (ref: fluid.device_guard — consumed by
    PipelineOptimizer._split_program, optimizer.py:3751).  Accepts
    "tpu:k"/"gpu:k" — k is the pipeline stage index."""
    _device_guard_stack.append(device)
    try:
        yield
    finally:
        _device_guard_stack.pop()


def _to_name_list(v) -> List[str]:
    if v is None:
        return []
    if isinstance(v, (Variable, str)):
        v = [v]
    return [x.name if isinstance(x, Variable) else str(x) for x in v]


# ---------------------------------------------------------------------------
# Block / Program
# ---------------------------------------------------------------------------


class Block:
    """Ordered op list + var scope (ref: framework.py:2395, BlockDesc)."""

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    def var(self, name: str) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError(f"variable {name!r} not found in block {self.idx}")
        return v

    def has_var(self, name: str) -> bool:
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name: str) -> Optional[Variable]:
        b: Optional[Block] = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent_block
        return None

    def create_var(self, name=None, shape=None, dtype=None,
                   persistable=False, stop_gradient=True, is_data=False,
                   initializer=None, **kw) -> Variable:
        if name is None:
            name = unique_name.generate("tmp")
        if name in self.vars:
            # re-declaration returns the existing var — but only when the
            # requested metadata agrees with it.  Silently handing back a
            # conflicting declaration masks real layer bugs (ref:
            # framework.py Block.create_var raises on VarDesc mismatch);
            # a () shape or omitted dtype means "unspecified" and never
            # conflicts.
            existing = self.vars[name]
            from .errors import InvalidArgumentError
            if shape and existing.shape and \
                    tuple(int(s) for s in shape) != tuple(existing.shape):
                raise InvalidArgumentError(
                    f"create_var({name!r}): requested shape "
                    f"{list(shape)} conflicts with existing declaration "
                    f"{list(existing.shape)}")
            if dtype is not None and \
                    convert_dtype(dtype) != existing.dtype:
                raise InvalidArgumentError(
                    f"create_var({name!r}): requested dtype "
                    f"{convert_dtype(dtype)} conflicts with existing "
                    f"declaration {existing.dtype}")
            return existing
        v = Variable(self, name, shape if shape is not None else (),
                     dtype if dtype is not None else "float32",
                     persistable=persistable,
                     stop_gradient=stop_gradient, is_data=is_data,
                     initializer=initializer)
        self.vars[name] = v
        self.program._bump_version()
        return v

    def create_parameter(self, name, shape, dtype="float32", initializer=None,
                         regularizer=None, trainable=True, need_clip=True,
                         is_distributed=False) -> Parameter:
        if name in self.vars:
            existing = self.vars[name]
            assert isinstance(existing, Parameter)
            return existing
        p = Parameter(self, name, shape, dtype, initializer=initializer,
                      regularizer=regularizer, trainable=trainable,
                      need_clip=need_clip, is_distributed=is_distributed)
        self.vars[name] = p
        self.program._bump_version()
        return p

    def append_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        if _device_guard_stack and "op_device" not in op.attrs:
            op.attrs["op_device"] = _device_guard_stack[-1]
        self.ops.append(op)
        self.program._bump_version()
        return op

    def _insert_op(self, index: int, type: str, inputs=None, outputs=None,
                   attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(index, op)
        self.program._bump_version()
        return op

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def __repr__(self):
        return f"Block(idx={self.idx}, ops={len(self.ops)}, vars={len(self.vars)})"


def _clone_attrs(attrs, new_program):
    """Copy op attrs for Program.clone, remapping Block references into the
    cloned program (everything else is deep-copied)."""
    out = {}
    for k, v in attrs.items():
        if isinstance(v, Block):
            out[k] = new_program.blocks[v.idx]
        elif isinstance(v, (list, tuple)) and any(
                isinstance(x, Block) for x in v):
            out[k] = type(v)(new_program.blocks[x.idx]
                             if isinstance(x, Block) else copy.deepcopy(x)
                             for x in v)
        else:
            out[k] = copy.deepcopy(v)
    return out


class Program:
    """A whole training/inference program (ref: framework.py:3857).

    Two implicit global programs exist at any time, exactly like the
    reference: the *main* program (compute) and the *startup* program
    (parameter initialisation) — see ``default_main_program()``.
    """

    _uid_counter = itertools.count()

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._version = 0          # bumped on mutation; keys executor caches
        # monotonic identity for executor caches: id() can be reused by a
        # new Program after this one is GC'd, which would serve a stale
        # executable
        self._uid = next(Program._uid_counter)
        self._is_test = False
        # distributed annotations filled by parallel/ transforms
        self._mesh = None
        self._dist_attrs: Dict[str, Any] = {}
        # canonical named-axis layout (mesh_layout.MeshLayout) stamped by
        # the shard planner / fleet; carries the mesh axis SIZES so a
        # saved program reloads with its layout intact
        self._mesh_layout = None

    def __setstate__(self, state):
        # unpickled programs get a fresh cache identity — the serialized
        # uid may collide with a live program's
        self.__dict__.update(state)
        self._uid = next(Program._uid_counter)
        # programs pickled before these fields existed
        self.__dict__.setdefault('_is_test', False)
        self.__dict__.setdefault('_mesh', None)
        self.__dict__.setdefault('_dist_attrs', {})
        self.__dict__.setdefault('_mesh_layout', None)

    # -- structure -------------------------------------------------------
    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx=None) -> Block:
        parent_idx = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent_idx)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def _bump_version(self):
        self._version += 1

    # -- queries ---------------------------------------------------------
    def all_parameters(self) -> List[Parameter]:
        out = []
        for b in self.blocks:
            out.extend(b.all_parameters())
        return out

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    # -- cloning (ref: framework.py:4202 Program.clone) ------------------
    def clone(self, for_test: bool = False) -> "Program":
        p = Program.__new__(Program)
        p.blocks = []
        p.current_block_idx = self.current_block_idx
        p.random_seed = self.random_seed
        p._version = 0
        p._uid = next(Program._uid_counter)
        p._is_test = for_test or self._is_test
        p._mesh = self._mesh
        p._dist_attrs = dict(self._dist_attrs)
        p._mesh_layout = self._mesh_layout
        # two passes so sub-block attrs (control-flow ops) can be remapped to
        # the cloned program's blocks by index (the reference stores sub-block
        # *indices* in OpDesc attrs for the same reason, ref:
        # framework.proto:42 BLOCK attr type)
        for b in self.blocks:
            p.blocks.append(Block(p, b.idx, b.parent_idx))
        for b, nb in zip(self.blocks, p.blocks):
            for name, v in b.vars.items():
                nv = copy.copy(v)
                nv.block = nb
                nb.vars[name] = nv
            for op in b.ops:
                nop = Operator(nb, op.type, dict(op.inputs), dict(op.outputs),
                               _clone_attrs(op.attrs, p))
                nb.ops.append(nop)
        if for_test:
            p._set_test_mode()
        return p

    def _set_test_mode(self):
        for b in self.blocks:
            for op in b.ops:
                if "is_test" in _TEST_MODE_OPS.get(op.type, ()):
                    op.attrs["is_test"] = True
        self._bump_version()

    # -- pruning (ref: framework.py:4399 _prune) -------------------------
    def _prune(self, targets: Sequence[Variable]) -> "Program":
        """Return a clone keeping only ops needed to compute ``targets``.

        An op's read set includes reads made inside its control-flow
        sub-blocks (while/cond bodies close over outer vars through the
        Block-valued attrs): scanning only global-block op inputs would
        prune away the producers a loop body depends on."""
        p = self.clone()
        target_names = {t.name if isinstance(t, Variable) else str(t)
                        for t in targets}
        blk = p.global_block()
        needed = set(target_names)
        kept = []

        def op_reads(op):
            reads = set(op.input_names())
            for attr in op.attrs.values():
                subs = attr if isinstance(attr, (list, tuple)) else (attr,)
                for sub in subs:
                    if isinstance(sub, Block):
                        for sub_op in sub.ops:
                            reads |= op_reads(sub_op)
            return reads

        for op in reversed(blk.ops):
            if set(op.output_names()) & needed:
                kept.append(op)
                needed |= op_reads(op)
        blk.ops = list(reversed(kept))
        p._bump_version()
        return p

    def __repr__(self):
        return f"Program(blocks={len(self.blocks)}, version={self._version})"


# ops whose behavior flips in eval mode
_TEST_MODE_OPS = {
    "dropout": ("is_test",),
    "batch_norm": ("is_test",),
}


# ---------------------------------------------------------------------------
# global program state (ref: framework.py default_main_program etc.)
# ---------------------------------------------------------------------------

_main_program = Program()
_startup_program = Program()


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


def switch_main_program(p: Program) -> Program:
    global _main_program
    old, _main_program = _main_program, p
    return old


def switch_startup_program(p: Program) -> Program:
    global _startup_program
    old, _startup_program = _startup_program, p
    return old


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)


def reset_default_programs():
    """Fresh global programs (used by tests)."""
    global _main_program, _startup_program
    _main_program = Program()
    _startup_program = Program()
    unique_name.reset()


# ---------------------------------------------------------------------------
# Places — TPU is first-class (ref: platform/place.h:79)
# ---------------------------------------------------------------------------


class EOFException(Exception):
    """Raised when a started py_reader's pass is exhausted
    (ref: fluid.core.EOFException; paddle/fluid/framework/reader.h) —
    catch it and call ``reader.reset()`` to begin the next pass."""


class Place:
    _kind = "undefined"

    def __eq__(self, other):
        return type(self) is type(other) and getattr(self, "device_id", 0) == \
            getattr(other, "device_id", 0)

    def __hash__(self):
        return hash((self._kind, getattr(self, "device_id", 0)))

    def __repr__(self):
        return f"{type(self).__name__}({getattr(self, 'device_id', '')})"


class CPUPlace(Place):
    _kind = "cpu"


class TPUPlace(Place):
    """First-class TPU device (the rebuild's analog of CUDAPlace)."""
    _kind = "tpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id


# CUDAPlace kept as an alias for script compatibility; maps to the
# accelerator backend jax exposes (TPU here).
CUDAPlace = TPUPlace


def _jax_device_for(place: Place):
    """``TPUPlace(i)`` is device ``i`` of JAX's DEFAULT backend — the
    chip on a TPU host, a host device under ``JAX_PLATFORMS=cpu`` (the
    whole CPU test suite builds ``TPUPlace(0)``).  The place does not
    check the platform; the chip entry points (chip_smoke.py,
    benchmark/run.py) do, and fail without a TPU.  An index past the last device is an
    error, never an alias of another chip."""
    import jax
    if isinstance(place, CPUPlace):
        return jax.devices("cpu")[0]
    devs = jax.devices()
    idx = getattr(place, "device_id", 0)
    if not 0 <= idx < len(devs):
        raise ValueError(
            f"{place!r}: the {devs[0].platform} backend has "
            f"{len(devs)} device(s)")
    return devs[idx]


def is_compiled_with_tpu() -> bool:
    import jax
    return any(d.platform == "tpu" for d in jax.devices())


def require_tpu() -> dict:
    """The device check of the chip entry points (chip_smoke.py,
    benchmark/run.py): returns ``{"platform", "kind", "count"}`` as JAX
    reports the default backend, and exits non-zero before any model is
    built when that backend is not the TPU — a device number is never
    taken from a CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"no TPU: JAX's default backend is {devs[0].platform!r} "
            f"({len(devs)} device(s)) — this entry point runs on the "
            f"chip only")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
