"""Static SPMD launch auditor: prove N ranks will not deadlock BEFORE
the first collective fires.

Crossing the host boundary changes the dominant failure class: a wrong
program no longer produces a wrong answer, it produces a silent pod-wide
hang — every rank blocked inside a different collective, no diagnostic,
no owner.  The reference ecosystem debugs these post-hoc with NCCL
timeout dumps; nothing in either stack proves *ahead of launch* that the
per-rank programs are mutually compatible.  This module is that proof,
built from artifacts the static layer already has:

* a **collective timeline** per rank — the ordered
  collective/ppermute/pipe-boundary events a rank will issue, with kind,
  mesh axes, ring id, operand names, permutation table, replica groups
  and payload bytes (priced via the op_spec ``wire`` channel).  Flat
  SPMD programs yield one shared timeline; pipelined programs are
  expanded through the stamped 1F1B/interleaved/zero-bubble schedule
  table (``pipe_schedule_order``) into per-pipe-rank, per-tick
  timelines, including the stage→stage+1 ppermute hops the executor's
  scheduled scan will issue;
* **pairwise schedule compatibility** — for every communicator, all
  participating ranks must issue matching events in matching order
  (kind, operands, permutation tables, replica groups; payload shapes
  may legally differ — multi-step reshard decompositions are per-rank).
  Divergence is an anchored ``launch-schedule-divergence`` naming both
  ranks' op callstacks;
* **deadlock-freedom** — a progress game over the timelines: an event
  completes only when every participant's head matches it; when no rank
  can advance, the wait-for graph over (rank, tick, channel) edges is
  extracted and its cycle (or the starved edge to an exhausted rank)
  reported as ``launch-deadlock-cycle``.  This catches the classic
  classes statically: a collective under divergent control flow, a
  collective spanning a stage cut, interleaved ppermute rings with
  inconsistent hop order, mismatched warm-up depth across 1F1B-family
  schedules;
* **launch-identity agreement** — a canonical rank fingerprint
  (content-hashed program desc + MeshLayout + lowering-relevant flags +
  jax/jaxlib versions + the collective schedule) and a
  :func:`verify_rank_agreement` rendezvous helper on the gloo substrate:
  ranks all-gather fingerprints before the first device collective and
  abort with a named divergence (exit code
  :data:`EXIT_LAUNCH_DIVERGENCE`) instead of hanging at step 0.

Everything here is trace-free: 0 compiles, 0 live device collectives.
Wired into ``verify_program`` (pipelined/multi-rank profiles),
``tools/proglint.py --launch``, and the ``tools/launch_probe.py`` census,
which seeds every class above and proves it caught.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from .core import Block, Operator, Program
from .errors import Error

# anchored diagnostic codes (declared in the analysis taxonomy; see
# MIGRATION.md "Launch audit mapping" for the NCCL-hang failure-mode
# table)
from .analysis import (LAUNCH_DEADLOCK_CYCLE, LAUNCH_FINGERPRINT_DRIFT,
                       LAUNCH_SCHEDULE_DIVERGENCE)

#: process exit code for a named launch divergence (the rendezvous abort
#: path) — distinct from 42 (guardrail abort) and 66 (watchdog abort)
EXIT_LAUNCH_DIVERGENCE = 43

#: flags that change what the lowering emits — part of the rank
#: fingerprint; a rank launched with a different value compiles a
#: different program and must not join the mesh
LOWERING_FLAGS = (
    "use_flash_attention", "use_pallas_fused", "overlap_lowering",
    "guard_nonfinite", "guard_loss_scale", "remat_on_reject",
    "quant_min_bucket_kb",
)


class LaunchDivergenceError(Error):
    """Ranks disagree at rendezvous — program, mesh, flags, versions or
    collective schedule.  Carries :data:`EXIT_LAUNCH_DIVERGENCE` so
    launchers abort with a named divergence instead of hanging."""
    code = "LAUNCH_DIVERGENCE"
    exit_code = EXIT_LAUNCH_DIVERGENCE


# ---------------------------------------------------------------------------
# 1. collective timelines
# ---------------------------------------------------------------------------


class CollEvent:
    """One collective issue point in a rank's timeline.

    ``channel`` identifies the communicator — (mesh axes, ring id) —
    the granularity at which the runtime rendezvouses.  ``group`` names
    the participating modeled ranks (None = every rank); ``perm`` is
    the ppermute source→target table; ``groups`` the replica groups of
    a grouped collective.  ``key()`` is the compatibility identity two
    ranks must agree on; payload bytes are informational (per-rank
    reshard decompositions may legally differ in shape)."""

    __slots__ = ("kind", "axes", "ring_id", "operands", "payload_bytes",
                 "perm", "groups", "group", "tick", "op_type",
                 "block_idx", "op_index", "callstack", "detail")

    def __init__(self, kind: str, axes: Tuple[str, ...] = (),
                 ring_id: int = 0, operands: Tuple[str, ...] = (),
                 payload_bytes: Optional[int] = None,
                 perm: Optional[Tuple[Tuple[int, int], ...]] = None,
                 groups: Optional[Tuple[Tuple[int, ...], ...]] = None,
                 group: Optional[Tuple[int, ...]] = None,
                 tick: int = 0, op: Optional[Operator] = None,
                 block_idx: int = 0, op_index: int = -1,
                 detail: str = ""):
        self.kind = kind
        self.axes = tuple(axes or ())
        self.ring_id = int(ring_id or 0)
        self.operands = tuple(operands or ())
        self.payload_bytes = payload_bytes
        self.perm = tuple(tuple(p) for p in perm) if perm else None
        self.groups = tuple(tuple(g) for g in groups) if groups else None
        self.group = tuple(group) if group is not None else None
        self.tick = int(tick)
        self.op_type = op.type if op is not None else kind
        self.block_idx = block_idx
        self.op_index = op_index
        self.callstack = list(getattr(op, "callstack", None) or ())
        self.detail = detail

    @property
    def channel(self) -> Tuple:
        return (self.axes, self.ring_id)

    def key(self) -> Tuple:
        """The cross-rank compatibility identity: everything two ranks
        must agree on for the rendezvous to complete correctly."""
        return (self.kind, self.axes, self.ring_id, self.operands,
                self.perm, self.groups)

    def participates(self, rank: int) -> bool:
        return self.group is None or rank in self.group

    def describe(self) -> str:
        ax = ",".join(self.axes) or "-"
        s = f"{self.kind}[{ax}]#{self.ring_id}({','.join(self.operands)})"
        if self.perm:
            s += " perm=" + ";".join(f"{a}->{b}" for a, b in self.perm)
        if self.groups:
            s += " groups=" + ";".join(
                ",".join(map(str, g)) for g in self.groups)
        return s

    def as_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "axes": list(self.axes),
                "ring_id": self.ring_id, "operands": list(self.operands),
                "payload_bytes": self.payload_bytes,
                "perm": [list(p) for p in self.perm] if self.perm else None,
                "groups": [list(g) for g in self.groups]
                if self.groups else None,
                "group": list(self.group) if self.group is not None
                else None,
                "tick": self.tick, "op_type": self.op_type,
                "detail": self.detail}

    def __repr__(self):
        return f"CollEvent({self.describe()} @t{self.tick})"


def _axis_sizes(program: Optional[Program], layout=None) -> Dict[str, int]:
    layout = layout if layout is not None \
        else getattr(program, "_mesh_layout", None)
    return dict(layout.sizes) if layout is not None else {}


def _norm_axes(op: Operator) -> Tuple[str, ...]:
    axes = op.attrs.get("_axis_name")
    if axes is None:
        return ()
    if isinstance(axes, (list, tuple)):
        return tuple(axes)
    return (axes,)


def _op_perm(op: Operator, axis_sizes: Dict[str, int]):
    """The ppermute source→target table an op will issue, when static."""
    perm = op.attrs.get("perm")
    if perm:
        return tuple((int(a), int(b)) for a, b in perm)
    if op.type == "collective_permute":
        axes = _norm_axes(op)
        n = axis_sizes.get(axes[0]) if axes else None
        if n:
            shift = int(op.attrs.get("shift", 1))
            return tuple((i, (i + shift) % n) for i in range(n))
        return ((-1, int(op.attrs.get("shift", 1))),)   # symbolic
    if op.type == "pipe_stage_boundary":
        cut = int(op.attrs.get("_pipe_cut", op.attrs.get("_pipe_stage", 0)))
        axes = _norm_axes(op)
        S = axis_sizes.get(axes[0]) if axes else None
        if S:
            return ((cut % S, (cut + 1) % S),)
        return ((cut, cut + 1),)
    return None


def _op_groups(op: Operator):
    g = op.attrs.get("replica_groups") or op.attrs.get("rank_groups")
    if g:
        return tuple(tuple(int(r) for r in grp) for grp in g)
    return None


def _wire_of(block: Block, op: Operator,
             axis_sizes: Dict[str, int]) -> Optional[int]:
    """Wire bytes via the op_spec wire channel, when the payload is
    statically priceable (declared shapes); None otherwise."""
    from ..ops.op_specs import collective_wire_bytes
    from ..ops.registry import VarSig
    ins: Dict[str, List[Any]] = {}
    try:
        for slot, names in op.inputs.items():
            sigs = []
            for n in names:
                v = block._find_var_recursive(n) \
                    if hasattr(block, "_find_var_recursive") \
                    else block.vars.get(n)
                if v is None or v.shape is None:
                    return None
                sigs.append(VarSig(tuple(v.shape), v.dtype or "float32"))
            ins[slot] = sigs
        priced = collective_wire_bytes(op.type, ins, op.attrs, axis_sizes)
    except Exception:   # noqa: BLE001 — pricing is best-effort metadata
        return None
    if priced is None:
        return None
    return int(priced[1])


def extract_collective_timeline(program: Program, layout=None
                                ) -> List[CollEvent]:
    """The ordered collective schedule of one flat SPMD program: one
    event per collective/ppermute/pipe-boundary op of the global block,
    ticked by program order.  All mesh peers execute this same timeline
    (the SPMD contract) — per-rank divergence enters via clones, pipe
    expansion, or control flow (see the deadlock modeling in
    :func:`verify_launch`)."""
    from .analysis import _collective_types
    collectives = _collective_types()
    axis_sizes = _axis_sizes(program, layout)
    block = program.global_block()
    out: List[CollEvent] = []
    for idx, op in enumerate(block.ops):
        if op.type not in collectives:
            continue
        out.append(CollEvent(
            op.type, _norm_axes(op), op.attrs.get("ring_id", 0),
            tuple(op.input_names()), _wire_of(block, op, axis_sizes),
            perm=_op_perm(op, axis_sizes), groups=_op_groups(op),
            tick=len(out), op=op, block_idx=block.idx, op_index=idx))
    return out


def expand_pipe_timelines(program: Program, layout=None
                          ) -> Dict[int, List[CollEvent]]:
    """Expand a pipelined program into per-pipe-rank, per-tick
    collective timelines via the stamped schedule table.

    ``apply_pipeline`` stamps the backward op with the full
    ``pipe_schedule_order`` tick table ([tick, vstage, phase, mb]) and
    every forward op with its ``_pipe_stage``; virtual stage ``k`` lives
    on pipe rank ``k % S``.  For each F unit the owning rank issues its
    stage's collectives (stage-local communicators — orthogonal axes,
    so they do not synchronize pipe ranks) followed by the boundary
    ppermute hop to stage k+1's rank; each B unit issues the cotangent
    hop back to stage k-1's rank.  Tail grad-sync collectives (after
    the backward op) are SPMD across the pipe axis and appear on every
    rank.  The result is exactly the per-rank issue order the
    executor's scheduled scan will replay — auditable for deadlock
    with zero compiles."""
    from .analysis import _collective_types
    collectives = _collective_types()
    axis_sizes = _axis_sizes(program, layout)
    block = program.global_block()
    ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
    bw_idx = next((i for i, op in enumerate(ops)
                   if op.type == "backward"), None)
    if bw_idx is None:
        return {0: extract_collective_timeline(program, layout)}
    bw = ops[bw_idx]
    order = bw.attrs.get("pipe_schedule_order") or ()
    if not order:
        return {0: extract_collective_timeline(program, layout)}
    V = int(bw.attrs.get("pipe_stages") or 1)
    v = int(bw.attrs.get("pipe_chunks") or 1)
    S = max(1, V // max(1, v))
    pipe_axis = bw.attrs.get("pipe_axis") or "pipe"

    # per-virtual-stage collective ops (excluding the boundary markers,
    # which the schedule expansion re-issues per tick)
    stage_colls: Dict[int, List[Tuple[Operator, int]]] = {}
    boundary_ops: Dict[int, Tuple[Operator, int]] = {}
    def_stage: Dict[str, int] = {}
    for op in ops[:bw_idx]:
        s = op.attrs.get("_pipe_stage")
        if s is None:
            continue
        for n in op.output_names():
            def_stage.setdefault(n, int(s))
    # a collective whose input comes from a DIFFERENT stage spans the
    # cut: both stages' ranks must rendezvous it, each at its own F
    # tick — the deadlock the wait-for game must surface.  xstage maps
    # producer stage -> [(op, idx, owner stage)]
    xstage: Dict[int, List[Tuple[Operator, int, int]]] = {}
    for idx, op in enumerate(ops[:bw_idx]):
        if op.type == "pipe_stage_boundary":
            cut = int(op.attrs.get("_pipe_cut", 0))
            boundary_ops[cut] = (op, idx)
            continue
        if op.type in collectives:
            s = int(op.attrs.get("_pipe_stage", 0) or 0)
            stage_colls.setdefault(s, []).append((op, idx))
            for n in op.input_names():
                d = def_stage.get(n)
                if d is not None and d != s and d % S != s % S:
                    xstage.setdefault(d, []).append((op, idx, s))
                    break

    cross_of: Dict[int, int] = {}
    for d, lst in xstage.items():
        for op, _idx, _s in lst:
            cross_of[id(op)] = d

    timelines: Dict[int, List[CollEvent]] = {r: [] for r in range(S)}

    def _boundary_event(cut: int, tick: int, mb: int, back: bool):
        src = cut % S
        dst = (cut + 1) % S
        if back:
            src, dst = dst, src
        op, idx = boundary_ops.get(cut, (None, -1))
        wire = _wire_of(block, op, axis_sizes) if op is not None else None
        kind = "pipe_ppermute_bwd" if back else "pipe_ppermute_fwd"
        ev = CollEvent(
            kind, (pipe_axis,), ring_id=cut,
            operands=tuple(op.input_names()) if op is not None else (),
            payload_bytes=wire, perm=((src, dst),),
            group=(src, dst), tick=tick, op=op,
            block_idx=block.idx, op_index=idx,
            detail=f"mb {mb} cut {cut}")
        timelines[src].append(ev)
        if dst != src:
            timelines[dst].append(ev)

    for unit in sorted(order, key=lambda u: (u[0], u[1])):
        t, k, ph, m = int(unit[0]), int(unit[1]), unit[2], int(unit[3])
        r = k % S
        if ph == "F":
            for op, idx in stage_colls.get(k, ()):
                d = cross_of.get(id(op))
                group = (r,) if d is None \
                    else tuple(sorted({r, d % S}))
                timelines[r].append(CollEvent(
                    op.type, _norm_axes(op), op.attrs.get("ring_id", 0),
                    tuple(op.input_names()),
                    _wire_of(block, op, axis_sizes),
                    perm=_op_perm(op, axis_sizes), groups=_op_groups(op),
                    group=group, tick=t, op=op, block_idx=block.idx,
                    op_index=idx, detail=f"stage {k} mb {m}"))
            # producer side of a cross-stage collective: this rank must
            # also rendezvous it, at ITS OWN forward tick — before the
            # boundary hop the consumer stage is still waiting on
            for op, idx, s in xstage.get(k, ()):
                timelines[r].append(CollEvent(
                    op.type, _norm_axes(op), op.attrs.get("ring_id", 0),
                    tuple(op.input_names()),
                    _wire_of(block, op, axis_sizes),
                    perm=_op_perm(op, axis_sizes), groups=_op_groups(op),
                    group=tuple(sorted({r, s % S})), tick=t, op=op,
                    block_idx=block.idx, op_index=idx,
                    detail=f"stage {s} span from {k} mb {m}"))
            if k < V - 1:
                _boundary_event(k, t, m, back=False)
        elif ph == "B" and k > 0:
            _boundary_event(k - 1, t, m, back=True)

    # tail collectives (grad sync over the pipe axis) — SPMD, every rank
    last_tick = max((int(u[0]) for u in order), default=0) + 1
    for idx, op in enumerate(ops[bw_idx + 1:], start=bw_idx + 1):
        if op.type not in collectives:
            continue
        ev = CollEvent(
            op.type, _norm_axes(op), op.attrs.get("ring_id", 0),
            tuple(op.input_names()), _wire_of(block, op, axis_sizes),
            perm=_op_perm(op, axis_sizes), groups=_op_groups(op),
            group=None, tick=last_tick, op=op,
            block_idx=block.idx, op_index=idx, detail="grad-sync tail")
        last_tick += 1
        for r in range(S):
            timelines[r].append(ev)
    return timelines


# ---------------------------------------------------------------------------
# 2. pairwise schedule compatibility
# ---------------------------------------------------------------------------


def check_timeline_compatibility(timelines: Dict[int, List[CollEvent]],
                                 result=None):
    """Prove every pair of ranks issues matching events in matching
    order on every communicator they share.

    For ranks (a, b): the subsequence of a's events in which b
    participates must equal — by :meth:`CollEvent.key` (kind, axes,
    ring id, operands, perm table, replica groups) — the subsequence of
    b's events in which a participates.  Payload bytes are exempt:
    multi-step reshard decompositions legally differ per rank.  The
    first mismatch is an anchored ``launch-schedule-divergence`` naming
    both ranks' ops and creation callstacks."""
    from .analysis import VerifyResult
    result = result if result is not None else VerifyResult()
    ranks = sorted(timelines)
    for i, a in enumerate(ranks):
        for b in ranks[i + 1:]:
            pa = [e for e in timelines[a] if e.participates(b)]
            pb = [e for e in timelines[b] if e.participates(a)]
            n = min(len(pa), len(pb))
            j = 0
            while j < n and pa[j].key() == pb[j].key():
                j += 1
            if j == n and len(pa) == len(pb):
                continue
            ea = pa[j] if j < len(pa) else None
            eb = pb[j] if j < len(pb) else None
            da = ea.describe() if ea else "<end of schedule>"
            db = eb.describe() if eb else "<end of schedule>"
            anchor = ea or eb
            peer_stack = ""
            if eb is not None and eb is not anchor and eb.callstack:
                peer_stack = ("; rank %d op creation site: %s"
                              % (b, " | ".join(eb.callstack[-2:])))
            result.add(
                "error", LAUNCH_SCHEDULE_DIVERGENCE,
                f"rank {a} and rank {b} diverge at shared collective "
                f"#{j}: rank {a} issues {da} (tick "
                f"{ea.tick if ea else '-'}) but rank {b} issues {db} "
                f"(tick {eb.tick if eb else '-'}) — the mesh would "
                f"deadlock at this rendezvous"
                f"{peer_stack}",
                _AnchorOp(anchor) if anchor is not None else None,
                anchor.block_idx if anchor else 0,
                anchor.op_index if anchor else -1)
    return result


class _AnchorOp:
    """Adapter letting a CollEvent anchor a Diagnostic (op_type +
    callstack) without holding the Operator alive past extraction."""

    __slots__ = ("type", "callstack")

    def __init__(self, ev: CollEvent):
        self.type = ev.op_type
        self.callstack = list(ev.callstack)


# ---------------------------------------------------------------------------
# 3. deadlock-freedom (the wait-for progress game)
# ---------------------------------------------------------------------------


def check_deadlock_freedom(timelines: Dict[int, List[CollEvent]],
                           result=None):
    """Simulate the rendezvous progress game and prove every rank
    drains its timeline.

    An event at a rank's head completes only when every participant's
    head is a matching event on the same channel; completion advances
    all participants at once (the collective rendezvous semantics).
    When no head can complete, the launch hangs: the wait-for graph
    over (rank, tick, channel) edges is extracted and its cycle — or
    the starved edge to a rank that already drained its schedule —
    reported as an anchored ``launch-deadlock-cycle``."""
    from .analysis import VerifyResult
    result = result if result is not None else VerifyResult()
    ranks = sorted(timelines)
    ptr = {r: 0 for r in ranks}

    def head(r):
        tl = timelines[r]
        return tl[ptr[r]] if ptr[r] < len(tl) else None

    def matches(e: CollEvent, f: CollEvent) -> bool:
        return e.channel == f.channel and e.kind == f.kind \
            and e.operands == f.operands and e.perm == f.perm \
            and e.groups == f.groups

    total = sum(len(tl) for tl in timelines.values())
    for _ in range(total + 1):
        if all(ptr[r] >= len(timelines[r]) for r in ranks):
            return result                       # every rank drained
        progressed = False
        for r in ranks:
            e = head(r)
            if e is None:
                continue
            members = list(ranks) if e.group is None \
                else [m for m in ranks if m in e.group]
            ok = True
            for m in members:
                if m == r:
                    continue
                f = head(m)
                if f is None or not matches(e, f):
                    ok = False
                    break
            if ok:
                for m in members:
                    if head(m) is not None:
                        ptr[m] += 1
                progressed = True
                break
        if not progressed:
            break

    # stuck: extract the wait-for graph among blocked ranks
    edges: Dict[int, List[Tuple[int, CollEvent]]] = {}
    for r in ranks:
        e = head(r)
        if e is None:
            continue
        members = list(ranks) if e.group is None else list(e.group)
        for m in members:
            if m == r:
                continue
            f = head(m)
            if f is None or not matches(e, f):
                edges.setdefault(r, []).append((m, e))

    # DFS for a cycle
    def find_cycle():
        color: Dict[int, int] = {}
        stack: List[Tuple[int, CollEvent]] = []

        def dfs(u):
            color[u] = 1
            for (w, ev) in edges.get(u, ()):
                if color.get(w, 0) == 1:
                    stack.append((u, ev))
                    return w
                if color.get(w, 0) == 0:
                    stack.append((u, ev))
                    hit = dfs(w)
                    if hit is not None:
                        return hit
                    stack.pop()
            color[u] = 2
            return None

        for u in list(edges):
            if color.get(u, 0) == 0:
                start = dfs(u)
                if start is not None:
                    i = next(i for i, (n, _) in enumerate(stack)
                             if n == start)
                    return stack[i:]
        return None

    cyc = find_cycle()
    if cyc:
        desc = " -> ".join(
            f"(rank {r}, tick {ev.tick}, "
            f"chan {','.join(ev.axes) or '-'}#{ev.ring_id})"
            for r, ev in cyc) + f" -> (rank {cyc[0][0]}, ...)"
        anchor = cyc[0][1]
        result.add(
            "error", LAUNCH_DEADLOCK_CYCLE,
            f"static wait-for cycle — the launch deadlocks before any "
            f"rank completes: {desc}; first blocked event: "
            f"{anchor.describe()}", _AnchorOp(anchor),
            anchor.block_idx, anchor.op_index)
    elif edges:
        # no cycle: a blocked rank starves on a peer — prefer the edge
        # to a peer that already drained its schedule for the message
        pick = None
        for rr, lst in edges.items():
            for (mm, evv) in lst:
                if ptr[mm] >= len(timelines[mm]):
                    pick = (rr, mm, evv)
                    break
            if pick is not None:
                break
        if pick is None:
            rr = next(iter(edges))
            mm, evv = edges[rr][0]
            pick = (rr, mm, evv)
        r, m, ev = pick
        drained = ptr[m] >= len(timelines[m])
        result.add(
            "error", LAUNCH_DEADLOCK_CYCLE,
            f"rank {r} blocks forever at tick {ev.tick} on "
            f"{ev.describe()}: peer rank {m} "
            + ("has already drained its schedule without issuing it"
               if drained else "is issuing a different collective")
            + " — the launch hangs with no diagnostic at runtime",
            _AnchorOp(ev), ev.block_idx, ev.op_index)
    return result


# ---------------------------------------------------------------------------
# 4. launch-identity fingerprints + rendezvous agreement
# ---------------------------------------------------------------------------


def _digest(obj: Any) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True, default=str).encode()
    ).hexdigest()


def rank_fingerprint(program: Optional[Program] = None, layout=None,
                     timeline: Optional[Sequence[CollEvent]] = None,
                     extra: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
    """The canonical launch identity of this rank: component digests
    over (program desc, MeshLayout, lowering-relevant flags, jax/jaxlib
    versions) plus the readable collective schedule, and one top-level
    digest over all of it.  Component-level digests let the rendezvous
    name WHICH component drifted; the schedule rides as event strings
    so a schedule divergence names the exact op."""
    from .. import flags as _flags
    components: Dict[str, Any] = {}
    if program is not None:
        from .serialization import program_to_desc
        components["program"] = _digest(program_to_desc(program))
        if layout is None:
            layout = getattr(program, "_mesh_layout", None)
    components["mesh"] = layout.to_desc() if layout is not None else None
    fl = {}
    for name in LOWERING_FLAGS:
        try:
            fl[name] = _flags.flag(name)
        except Exception:   # noqa: BLE001 — unregistered flag: skip
            pass
    components["flags"] = fl
    try:
        import jax
        import jaxlib
        components["versions"] = {"jax": jax.__version__,
                                  "jaxlib": jaxlib.version.__version__}
    except Exception:   # noqa: BLE001 — gated dep
        components["versions"] = {}
    if timeline is None and program is not None:
        timeline = extract_collective_timeline(program, layout)
    schedule = [e.describe() for e in (timeline or ())]
    if extra:
        components["extra"] = dict(extra)
    fp = {"components": components, "schedule": schedule}
    fp["component_digests"] = {k: _digest(v)
                               for k, v in components.items()}
    fp["digest"] = _digest([fp["component_digests"], schedule])
    return fp


def fingerprint_divergence(fingerprints: Sequence[Dict[str, Any]]
                           ) -> Optional[Dict[str, Any]]:
    """First divergence across gathered rank fingerprints, or None when
    all ranks agree.  Names the diverging rank, the drifted component,
    and — for schedule drift — the first differing collective event."""
    if not fingerprints:
        return None
    base = fingerprints[0]
    for r, fp in enumerate(fingerprints[1:], start=1):
        if fp.get("digest") == base.get("digest"):
            continue
        bd = base.get("component_digests", {})
        rd = fp.get("component_digests", {})
        drifted = sorted(set(k for k in set(bd) | set(rd)
                             if bd.get(k) != rd.get(k)))
        sa, sb = base.get("schedule", []), fp.get("schedule", [])
        ev = None
        if sa != sb:
            drifted.append("schedule")
            j = 0
            while j < min(len(sa), len(sb)) and sa[j] == sb[j]:
                j += 1
            ev = {"index": j,
                  "rank0": sa[j] if j < len(sa) else "<end of schedule>",
                  f"rank{r}": sb[j] if j < len(sb)
                  else "<end of schedule>"}
        return {"rank": r, "components": drifted, "event": ev}
    return None


def check_fingerprint_agreement(fingerprints: Sequence[Dict[str, Any]],
                                result=None):
    """Diagnostic form of :func:`fingerprint_divergence`: an anchored
    ``launch-fingerprint-drift`` error naming the diverging rank, the
    drifted components, and (for schedule drift) the first diverging
    collective — the proglint/census counterpart of the rendezvous
    abort."""
    from .analysis import VerifyResult
    result = result if result is not None else VerifyResult()
    div = fingerprint_divergence(list(fingerprints))
    if div is not None:
        ev = div.get("event")
        at = f"; first diverging collective #{ev['index']}: {ev}" \
            if ev else ""
        result.add(
            "error", LAUNCH_FINGERPRINT_DRIFT,
            f"rank {div['rank']} launch fingerprint disagrees with rank "
            f"0 on {div['components']}{at} — the ranks would compile "
            f"different programs and hang at the first collective")
    return result


def _publish_endpoint(endpoint_file: str, endpoint: str):
    tmp = endpoint_file + ".tmp"
    with open(tmp, "w") as f:
        f.write(endpoint)
    os.replace(tmp, endpoint_file)      # atomic publish


def _await_endpoint(endpoint_file: str, timeout: float) -> str:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(endpoint_file):
            ep = open(endpoint_file).read().strip()
            if ep:
                return ep
        time.sleep(0.02)
    raise TimeoutError(
        f"launch rendezvous: endpoint file {endpoint_file!r} not "
        f"published within {timeout}s")


def verify_rank_agreement(endpoint_file: str, rank: int, world_size: int,
                          program: Optional[Program] = None,
                          fingerprint: Optional[Dict[str, Any]] = None,
                          layout=None, timeout: float = 60.0
                          ) -> Dict[str, Any]:
    """Rendezvous-time launch-identity proof on the gloo substrate.

    Rank 0 binds an ephemeral hub port and atomically publishes the
    resolved endpoint to ``endpoint_file``; every rank all-gathers its
    :func:`rank_fingerprint` BEFORE the first device collective.  Any
    divergence — program content, MeshLayout, lowering flags, jax
    version, or collective schedule — raises
    :class:`LaunchDivergenceError` naming the rank, the component, and
    (for schedule drift) the first diverging op, so the launcher aborts
    with exit code :data:`EXIT_LAUNCH_DIVERGENCE` instead of hanging at
    step 0.  Crosses the ``rank_divergence`` faultline seam: an armed
    drill perturbs THIS rank's fingerprint symbolically (e.g. a
    divergent bucket reorder) to prove the abort path end-to-end with
    no real divergent program build."""
    from ..testing import faultline
    from ..distributed.gloo import GlooContext
    if fingerprint is None:
        fingerprint = rank_fingerprint(program, layout=layout)
    spec = faultline.crossing("rank_divergence", rank=rank)
    if spec is not None:
        mode = spec.params.get("mode", "bucket_reorder")
        fingerprint = dict(fingerprint)
        schedule = list(fingerprint.get("schedule", ()))
        if mode == "bucket_reorder" and len(schedule) >= 2:
            schedule[0], schedule[1] = schedule[1], schedule[0]
        elif mode == "flag_flip":
            comps = dict(fingerprint.get("components", {}))
            fl = dict(comps.get("flags", {}))
            if fl:
                k = sorted(fl)[0]
                fl[k] = not fl[k] if isinstance(fl[k], bool) \
                    else (fl[k] or 0) + 1
            comps["flags"] = fl
            fingerprint["components"] = comps
            fingerprint["component_digests"] = {
                k: _digest(v) for k, v in comps.items()}
        fingerprint["schedule"] = schedule
        fingerprint["digest"] = _digest(
            [fingerprint.get("component_digests", {}), schedule])

    if rank == 0:
        ctx = GlooContext(0, world_size, "127.0.0.1:0", timeout=timeout)
        _publish_endpoint(endpoint_file, ctx.endpoint)
    else:
        ep = _await_endpoint(endpoint_file, timeout)
        ctx = GlooContext(rank, world_size, ep, timeout=timeout)
    try:
        gathered = ctx.all_gather(fingerprint)
        div = fingerprint_divergence(gathered)
        if div is not None:
            ev = div.get("event")
            at = f" at collective #{ev['index']}: {ev}" if ev else ""
            raise LaunchDivergenceError(
                f"launch fingerprint divergence at rendezvous: rank "
                f"{div['rank']} disagrees with rank 0 on "
                f"{div['components']}{at} — aborting before the first "
                f"collective (exit {EXIT_LAUNCH_DIVERGENCE}) instead "
                f"of deadlocking the mesh")
        return {"agreed": True, "digest": fingerprint["digest"],
                "world_size": world_size, "rank": rank}
    finally:
        try:
            ctx.close()
        except Exception:   # noqa: BLE001 — best-effort teardown
            pass


# ---------------------------------------------------------------------------
# 5. verify_program wiring + the audit report
# ---------------------------------------------------------------------------


def _cf_branch_events(program: Program, layout=None
                      ) -> List[Tuple[CollEvent, int]]:
    """Collectives reachable only through a control-flow branch of the
    global block: (event, position-among-main-block-collectives)."""
    from .analysis import _collective_types
    collectives = _collective_types()
    axis_sizes = _axis_sizes(program, layout)
    block = program.global_block()
    out: List[Tuple[CollEvent, int]] = []
    n_main = 0
    for idx, op in enumerate(block.ops):
        if op.type in collectives:
            n_main += 1
            continue
        if op.type == "pipeline":        # exempt: all ranks iterate alike
            continue
        for attr in op.attrs.values():
            if not isinstance(attr, Block):
                continue
            for sidx, sop in enumerate(attr.ops):
                if sop.type in collectives:
                    out.append((CollEvent(
                        sop.type, _norm_axes(sop),
                        sop.attrs.get("ring_id", 0),
                        tuple(sop.input_names()),
                        perm=_op_perm(sop, axis_sizes),
                        groups=_op_groups(sop), tick=n_main,
                        op=sop, block_idx=attr.idx, op_index=sidx,
                        detail=f"under {op.type!r}"), n_main))
    return out


def verify_launch(program: Program, result=None, layout=None):
    """The ``verify_program`` wiring: launch-audit the profiles that can
    statically diverge per rank.

    * **pipelined programs** — expand the stamped schedule into
      per-pipe-rank timelines and prove compatibility +
      deadlock-freedom of the exact issue order the scheduled scan
      replays;
    * **collectives under divergent control flow** — model the two
      hypothetical ranks (branch taken / not taken) and prove the hang
      in the wait-for game, so the divergent-CF warning class also
      carries its deadlock proof as an anchored
      ``launch-deadlock-cycle``."""
    from .analysis import VerifyResult
    result = result if result is not None else VerifyResult(program)
    block = program.global_block()
    bw = next((op for op in block.ops if op.type == "backward"), None)
    if bw is not None and bw.attrs.get("pipe_schedule_order"):
        timelines = expand_pipe_timelines(program, layout)
        check_timeline_compatibility(timelines, result)
        check_deadlock_freedom(timelines, result)

    branch = _cf_branch_events(program, layout)
    if branch:
        common = extract_collective_timeline(program, layout)
        taken: List[CollEvent] = list(common)
        for ev, pos in branch:
            ev = _with_group(ev, (0, 1))
            taken.insert(min(pos, len(taken)), ev)
        for e in common:
            e.group = (0, 1) if e.group is None else e.group
        check_deadlock_freedom({0: taken, 1: list(common)}, result)
    return result


def _with_group(ev: CollEvent, group) -> CollEvent:
    ev.group = tuple(group)
    return ev


class LaunchAuditReport:
    """One launch audit: the verdict + the evidence (per-rank timeline
    census, channels, fingerprint) — the ``proglint --launch`` and
    ``launch_probe`` payload."""

    def __init__(self, program: Optional[Program], result,
                 timelines: Dict[int, List[CollEvent]],
                 fingerprint: Dict[str, Any]):
        self.program = program
        self.result = result
        self.timelines = timelines
        self.fingerprint = fingerprint

    @property
    def ok(self) -> bool:
        return self.result.ok

    def as_dict(self) -> Dict[str, Any]:
        channels = sorted({
            f"{','.join(e.axes) or '-'}#{e.ring_id}"
            for tl in self.timelines.values() for e in tl})
        return {
            "ok": self.ok,
            "ranks": {str(r): len(tl)
                      for r, tl in sorted(self.timelines.items())},
            "channels": channels,
            "events": {str(r): [e.as_dict() for e in tl]
                       for r, tl in sorted(self.timelines.items())},
            "fingerprint_digest": self.fingerprint.get("digest"),
            "diagnostics": [
                {"severity": d.severity, "code": d.code,
                 "op_type": d.op_type, "message": d.message}
                for d in self.result.diagnostics],
        }

    def report(self) -> str:
        lines = [f"launch audit: {'OK' if self.ok else 'FAIL'} — "
                 f"{len(self.timelines)} rank timeline(s), "
                 f"fingerprint {self.fingerprint.get('digest', '')[:12]}"]
        for r, tl in sorted(self.timelines.items()):
            lines.append(f"  rank {r}: {len(tl)} collective event(s)")
        for d in self.result.diagnostics:
            lines.append("  " + d.format().splitlines()[0])
        return "\n".join(lines)


def audit_launch(program: Program, layout=None,
                 peer_programs: Sequence[Program] = ()
                 ) -> LaunchAuditReport:
    """Full static launch audit of one program (plus optional per-rank
    peer clones): timelines, compatibility, deadlock-freedom,
    fingerprint.  0 compiles, 0 live collectives."""
    from .analysis import VerifyResult
    result = VerifyResult(program)
    bw = next((op for op in program.global_block().ops
               if op.type == "backward"), None)
    if peer_programs:
        # per-rank clone comparison: every rank runs a full flat SPMD
        # program, so all ranks participate in every channel
        timelines = {0: extract_collective_timeline(program, layout)}
        for r, p in enumerate(peer_programs, start=1):
            timelines[r] = extract_collective_timeline(p, layout)
    elif bw is not None and bw.attrs.get("pipe_schedule_order"):
        timelines = expand_pipe_timelines(program, layout)
    else:
        timelines = {0: extract_collective_timeline(program, layout)}
    check_timeline_compatibility(timelines, result)
    check_deadlock_freedom(timelines, result)
    verify_launch(program, result, layout)
    fp = rank_fingerprint(program, layout=layout)
    return LaunchAuditReport(program, result, timelines, fp)


__all__ = [
    "LAUNCH_SCHEDULE_DIVERGENCE", "LAUNCH_DEADLOCK_CYCLE",
    "LAUNCH_FINGERPRINT_DRIFT", "EXIT_LAUNCH_DIVERGENCE",
    "LaunchDivergenceError", "CollEvent", "extract_collective_timeline",
    "expand_pipe_timelines", "check_timeline_compatibility",
    "check_deadlock_freedom", "rank_fingerprint",
    "fingerprint_divergence", "check_fingerprint_agreement",
    "verify_rank_agreement", "verify_launch",
    "audit_launch", "LaunchAuditReport", "LOWERING_FLAGS",
]
