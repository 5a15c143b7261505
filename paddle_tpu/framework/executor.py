"""Executor: lowers a whole Program block to ONE jitted XLA function.

The reference executes programs with a per-op interpreter hot loop
(ref: framework/executor.cc:465-472) and a multi-device SSA-graph executor
(ref: framework/details/fast_threaded_ssa_graph_executor.h:32).  On TPU the
idiomatic equivalent is: trace every op symbolically over JAX values,
``jax.jit`` the resulting function once per (program-version, feed-signature)
— the cache plays the role of ``ExecutorPrepareContext`` caching
(ref: executor.py:1084 _run_impl's ctx cache) — and let XLA fuse/schedule.

Static-graph mutation semantics (persistable vars updated across ``run()``
calls, ref: framework/scope.h:46) are preserved by an explicit VarStore: the
Scope holds device arrays; each compiled step is a pure function
``(feeds, state) -> (fetches, state')`` whose state buffers are donated, so
parameter updates are in-place at the XLA level — the analog of the
reference's inplace/memory-reuse passes (ref: framework/ir/memory_optimize_pass/).

The ``backward`` meta-op (inserted by backward.append_backward) is lowered
with ``jax.value_and_grad`` over the forward segment — replacing the
reference's per-op GradOpMaker machinery (ref: framework/grad_op_desc_maker.h,
python backward.py:1215) with XLA-native autodiff.  Recompute checkpoints
map to ``jax.checkpoint`` over op segments (ref: backward.py:629).
"""

from __future__ import annotations

import collections
import contextlib
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .core import (Program, Variable, Place, TPUPlace, CPUPlace,
                   default_main_program, _jax_device_for, grad_var_name)
from ..ops.registry import get_op, LoweringContext
# hot-loop observability hooks, bound once at import: one fused call per
# prepared step (run-level step-id bump + flight-recorder breadcrumb).
# Module-level names so the overhead test can swap them for no-ops to
# measure the delta.
from ..observability.tracing import (is_enabled as _tracing_enabled,
                                     next_step_id as _next_step_id)
from ..observability.flight import step_breadcrumb as _step_breadcrumb
from ..observability import flight as _flight
# hang-watchdog progress beacons (observability/watchdog.py): one
# begin/end pair brackets each prepared step so a stalled
# dispatch/collective is detectable; bound once like the breadcrumb
from ..observability.watchdog import (begin as _wd_begin, end as _wd_end,
                                      ensure_started as _wd_ensure)
# deterministic fault-injection seams (testing/faultline.py); _FL_ARMED
# is the live armed-spec dict — its truthiness gates every hot-path
# crossing down to one dict test
from ..testing import faultline as _faultline
from ..testing.faultline import _ARMED as _FL_ARMED, _EPOCH as _FL_EPOCH
from . import guardrails as _guardrails

_RNG_VAR = "@RNG_STATE@"

#: guardrail host-poll cadence: decode the (cumulative) guard counters
#: from the newest completed step every N prepared steps.  Budget
#: escalation therefore lags a NaN burst by at most N + the in-flight
#: window; every blocking sync point (wait, guard_info(sync=True),
#: telemetry reads) decodes immediately.
_GUARD_DECODE_EVERY = 16
_GUARD_PENDING_CAP = 64


class Scope:
    """Name → device-array store (ref: framework/scope.h:46).

    ``_version`` counts writes so the prepared fast path (PreparedStep,
    which keeps state device-resident OUTSIDE the scope between explicit
    sync points) can detect external writes — load_persistables, a plain
    ``Executor.run``, user ``set_var`` — and re-pull state instead of
    reusing donated-away buffers.  ``_prepared`` holds the live
    PreparedSteps bound to this scope so direct readers can flush them
    first (``sync_prepared_state``)."""

    def __init__(self):
        self.vars: Dict[str, Any] = {}
        self._version = 0
        self._prepared: "weakref.WeakSet" = weakref.WeakSet()

    def var_names(self):
        return list(self.vars)

    def find_var(self, name):
        return self.vars.get(name)

    def set_var(self, name, value):
        self.vars[name] = value
        self._version += 1

    def drop_all(self):
        self.vars.clear()
        self._version += 1
        # a dropped scope invalidates any prepared state bound to it —
        # unregister so a later checkpoint can't flush stale params back
        self._prepared = weakref.WeakSet()


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


@contextlib.contextmanager
def scope_guard(scope: Scope):
    global _global_scope
    old, _global_scope = _global_scope, scope
    try:
        yield
    finally:
        _global_scope = old


def sync_prepared_state(scope: Scope):
    """Flush every live PreparedStep's device-resident state back into
    ``scope`` (cheap dict writes — no device sync) so direct scope readers
    (a plain ``Executor.run``, io.save_*, the param-swap optimizers) never
    observe values that are stale behind the prepared fast path."""
    for ps in list(getattr(scope, "_prepared", ()) or ()):
        ps.sync_scope()


# ---------------------------------------------------------------------------
# symbolic block interpretation
# ---------------------------------------------------------------------------


def _gather_inputs(op, env):
    ins = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if n not in env:
                raise KeyError(
                    f"op {op.type!r} input {slot}={n!r} not computed/fed; "
                    f"known vars: {sorted(list(env))[:20]}...")
            vals.append(env[n])
        ins[slot] = vals
    return ins


def _scatter_outputs(op, outs, env):
    for slot, names in op.outputs.items():
        if slot not in outs:
            continue
        vals = outs[slot]
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        for n, v in zip(names, vals):
            env[n] = v


def run_ops(ops, env, ctx):
    """Interpret a straight-line op list symbolically (the trace loop — the
    analog of the reference's hot loop at executor.cc:465, but traced once).

    A failing op raises EnforceNotMet carrying the op type and the USER
    call site that created it (ref: op_call_stack.cc — the reference
    attaches the Python stack to op errors the same way)."""
    from .errors import EnforceNotMet
    traced = _tracing_enabled()
    for op in ops:
        if op.type in ("feed", "fetch"):
            continue
        try:
            impl = get_op(op.type)
            ins = _gather_inputs(op, env)
            if _FL_ARMED:
                # trace-time injection seam: a drill can make a chosen
                # op's lowering raise (spec match={"op": <type>}) —
                # wrapped below into the same EnforceNotMet a real
                # lowering failure produces
                _faultline.crossing("collective_impl", op=op.type)
            # the op's type on the name stack (the analog of the
            # reference's RecordEvent(Type()) in OperatorBase::Run):
            # every HLO instruction's op_name then says which Fluid op
            # it came from.  Trace time only.
            with jax.named_scope(op.type):
                if traced:
                    # trace-time collective spans (once per compile,
                    # zero steady-state cost): kind/axis/wire bytes land
                    # on the timeline correlated to the compiling step's
                    # id
                    from ..ops.collective_ops import maybe_trace_collective
                    with maybe_trace_collective(op, ins, ctx):
                        outs = impl(ctx, ins, op.attrs)
                else:
                    outs = impl(ctx, ins, op.attrs)
        except EnforceNotMet:
            raise
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:
            raise EnforceNotMet(op.type, e,
                                getattr(op, "callstack", None)) from e
        _scatter_outputs(op, outs, env)
    return env


def lower_decode_chain(ops, chain_idx, env, ctx, pool_names):
    """Device-chained decode: scan the program body ``chain_length``
    times entirely on device (serving/decode.py's fast path v2).

    The ``decode_chain`` marker op sits LAST in its program; its input
    slots name the per-step vars the chain drives (token/position/slot/
    ctx-len feeds are shadowed per iteration; the body's ``next_tokens``
    / ``next_logits`` close the loop) and its ``Out`` is the packed
    ``[chain_length, B]`` token matrix — ONE host fetch per chain
    instead of one per token.  Everything the single decode step did on
    the host moves into the carry:

    * slot/ctx computation — ``slot = table[pos // bs] * bs + pos % bs``
      (bitwise the engine's host arithmetic, so a chain of L steps
      writes exactly the slots L single steps would);
    * the next-token feedback edge — greedy rows ride the body's own
      argmax (bit parity with the single-step program); sampling rows
      re-draw from ``next_logits`` (ops/sampling_ops.py);
    * per-row EOS / length masks — finished rows freeze (position and
      carry token stop advancing), write nothing (slot -1 is the
      cache_write drop lane) and emit -1, which the host unpacker
      treats as "row already done".

    The KV pools thread through the scan carry, so the donated state
    chain is preserved — a chain program is state-compatible with the
    prefill/chunk executables sharing its scope."""
    chain_op = ops[chain_idx]
    body = ops[:chain_idx] + ops[chain_idx + 1:]
    attrs = chain_op.attrs
    length = int(attrs["chain_length"])
    bs = int(attrs["block_size"])
    with_sampling = bool(attrs.get("with_sampling"))

    def in0(slot):
        return chain_op.input(slot)[0]

    tok_v, pos_v = in0("TokenIds"), in0("PosIds")
    slot_v, ctxl_v = in0("SlotIds"), in0("CtxLen")
    logits_v, tokens_v = in0("Logits"), in0("Tokens")
    out_v = chain_op.output("Out")[0]
    # optional second output: every step's logits, stacked (a program
    # whose marker declares LogitsOut; the host slices the rows of the
    # requests that asked on the device and fetches only those)
    logits_out = (chain_op.outputs.get("LogitsOut") or [None])[0]
    # native integer dtypes throughout (no forced int64 — x64 is
    # usually disabled and an explicit widening astype warns)
    table = env[in0("BlockTable")].astype(jnp.int32)
    eos = env[in0("EosIds")].astype(jnp.int32)
    if with_sampling:
        from ..ops.sampling_ops import sample_chain_tokens
        temp = env[in0("Temperature")].astype(jnp.float32)
        top_k = env[in0("TopK")].astype(jnp.int32)
        top_p = env[in0("TopP")].astype(jnp.float32)
        seeds = env[in0("Seeds")].astype(jnp.int32)

    pools = [n for op in body for n in op.output_names()
             if n in pool_names]
    pools = list(dict.fromkeys(pools))

    def one_step(carry, _):
        tok, pos, left, done, pool_vals = carry
        blk_idx = (pos // bs).astype(jnp.int32)
        blk = jnp.take_along_axis(table, blk_idx[:, None], axis=1)[:, 0]
        slot = jnp.where(done, jnp.int32(-1),
                         blk * bs + (pos % bs).astype(jnp.int32))
        e = dict(env)
        for n, v in zip(pools, pool_vals):
            e[n] = v
        e[tok_v] = tok
        e[pos_v] = pos
        e[slot_v] = slot[:, None]
        e[ctxl_v] = (pos + 1).astype(jnp.int32)
        e = run_ops(body, e, ctx)
        nxt = e[tokens_v].reshape(-1).astype(tok.dtype)
        if with_sampling:
            nxt = sample_chain_tokens(e[logits_v], nxt, temp, top_k,
                                      top_p, seeds,
                                      pos).astype(tok.dtype)
        emitted = jnp.where(done, jnp.full_like(nxt, -1), nxt)
        left2 = jnp.where(done, left, left - 1)
        done2 = done | (left2 <= 0) | ((eos >= 0) & (nxt == eos))
        tok2 = jnp.where(done, tok, nxt)
        pos2 = jnp.where(done, pos, pos + 1)
        ys = emitted if logits_out is None else (emitted, e[logits_v])
        return (tok2, pos2, left2, done2,
                tuple(e[n] for n in pools)), ys

    left0 = env[in0("StepsLeft")].astype(jnp.int32)
    carry0 = (env[tok_v].astype(jnp.int32), env[pos_v].astype(jnp.int32),
              left0, left0 <= 0, tuple(env[n] for n in pools))
    carry, ys = jax.lax.scan(one_step, carry0, None, length=length)
    out = dict(env)
    for n, v in zip(pools, carry[4]):
        out[n] = v
    if logits_out is None:
        out[out_v] = ys
    else:
        out[out_v], out[logits_out] = ys
    return out


def _segment_at_checkpoints(ops, checkpoint_names):
    """Split ops into segments ending right after each checkpoint var is
    produced (for jax.checkpoint, ref: backward.py:629 recompute segments)."""
    if not checkpoint_names:
        return [list(ops)]
    remaining = set(checkpoint_names)
    segments, cur = [], []
    for op in ops:
        cur.append(op)
        produced = set(op.output_names()) & remaining
        if produced:
            remaining -= produced
            segments.append(cur)
            cur = []
    if cur:
        segments.append(cur)
    return segments


def _live_names_after(segments, seg_idx, always_live):
    live = set(always_live)
    for seg in segments[seg_idx + 1:]:
        for op in seg:
            live |= set(op.input_names())
    return live


def _make_overlap_hook(op, ctx, bucket_seed):
    """Identity custom-vjp hook over one ready-order bucket's params
    whose TRANSPOSE runs the bucket's (possibly quantized) fused grad
    collective — the overlap-aware scheduling rewrite: applied right
    before the bucket's earliest forward use, the hook's backward fires
    in the reverse sweep exactly when every member's cotangent is final,
    so the collective lands after its last contributing backward op in
    the lowered module instead of sinking to the program tail, and its
    wire time hides under the remaining backward compute.

    The cotangents pass through an ``optimization_barrier`` first, which
    pins the bucket together against XLA re-fusing it across buckets
    (the latency-hiding scheduler flags in ``flags.OVERLAP_XLA_FLAGS``
    keep the async collective where the trace put it on TPU).  A
    quantized bucket's stochastic-rounding key derives from a fixed
    per-bucket seed (the outer RNG chain is not threadable through a
    custom-vjp transpose)."""
    impl = get_op(op.type)
    mesh, axis_names, is_test = ctx.mesh, ctx.axis_names, ctx.is_test
    attrs = op.attrs

    @jax.custom_vjp
    def hook(*params):
        return params

    def h_fwd(*params):
        return params, None

    def h_bwd(_, cots):
        cots = list(jax.lax.optimization_barrier(tuple(cots)))
        hctx = LoweringContext(jax.random.PRNGKey(bucket_seed), mesh,
                               axis_names, is_test)
        ins = {"X": cots}
        if _tracing_enabled():
            from ..ops.collective_ops import maybe_trace_collective
            with maybe_trace_collective(op, ins, hctx):
                outs = impl(hctx, ins, attrs)
        else:
            outs = impl(hctx, ins, attrs)
        res = outs.get("Out", cots)
        if not isinstance(res, (list, tuple)):
            res = [res]
        return tuple(res)

    hook.defvjp(h_fwd, h_bwd)
    return hook


def _overlap_schedule(fwd_ops, tail_ops, param_names):
    """Resolve the ready-order hooks for this lowering: for each
    overlap-annotated grad-sync op in the tail, the bucket's param
    names and the hook position (min first forward use over members,
    recomputed HERE against the op list actually being lowered so
    clones/prunes can never leave a stale position behind).  Returns
    ``[(pos, pnames, op), ...]`` sorted by position."""
    from .analysis import op_reads_recursive
    from .core import grad_var_name as gvn
    overlap_ops = [op for op in tail_ops
                   if op.attrs.get("_overlap")
                   and op.attrs.get("_overlap_hook_pos") is not None]
    if not overlap_ops:
        return []
    grad_to_param = {gvn(n): n for n in param_names}
    first_use: Dict[str, int] = {}
    want = set(param_names)
    for i, op in enumerate(fwd_ops):
        for n in (op_reads_recursive(op) & want):
            first_use.setdefault(n, i)
    hooks = []
    for op in overlap_ops:
        pnames = [grad_to_param.get(g) for g in op.inputs.get("X", ())]
        if not pnames or any(p is None or p not in first_use
                             for p in pnames):
            continue            # falls back to tail placement
        hooks.append((min(first_use[p] for p in pnames), pnames, op))
    hooks.sort(key=lambda t: t[0])
    return hooks


def _microbatch_feeds(feeds, M):
    """Split every feed [B, ...] → [M, B/M, ...] (dim-0 microbatching —
    the gradient-merge substrate the pipeline loop rides)."""
    out = {}
    for n, v in feeds.items():
        if v.shape[0] % M:
            raise ValueError(
                f"pipeline microbatching: feed {n!r} batch {v.shape[0]} "
                f"not divisible by num_microbatches={M}")
        out[n] = v.reshape((M, v.shape[0] // M) + tuple(v.shape[1:]))
    return out


def _check_pipe_fetches(env, fetch_names, what):
    missing = [n for n in fetch_names if n not in env]
    if missing:
        from .errors import InvalidArgumentError
        raise InvalidArgumentError(
            f"{what}: fetch target(s) {missing} are per-microbatch "
            f"forward intermediates — under the microbatched/pipelined "
            f"lowering only the loss, persistables and update-zone "
            f"values are fetchable")


def _lower_microbatched(ops, env, ctx, bw_idx, fetch_names,
                        state_out_names):
    """Microbatch-accumulation lowering (pipe_microbatches > 1, no pipe
    mesh axis): scan the feeds in M slices through the whole forward,
    differentiate the mean of the per-microbatch losses — grads come out
    as ``(1/M) Σ_m g_m``, arithmetic-identical to
    ``GradientMergeOptimizer`` accumulating the same microbatch stream
    (bitwise at M = 2, where two-term addition order commutes exactly).
    This is also the pipe = 1 degenerate of the 1F1B lowering: stage
    cuts lower as identity, so the SAME pipelined program is its own
    non-pipelined parity baseline."""
    bw_op = ops[bw_idx]
    fwd_ops = [op for op in ops[:bw_idx]]
    tail_ops = ops[bw_idx + 1:]
    attrs = bw_op.attrs
    param_names = list(attrs["param_names"])
    loss_name = attrs["loss_name"]
    loss_scale = attrs.get("loss_scale", 1.0)
    M = int(attrs["pipe_microbatches"])
    feed_names = [n for n in attrs.get("pipe_feed_names", ()) if n in env]

    pvals = {n: env[n] for n in param_names}
    feeds = {n: env[n] for n in feed_names}
    base_env = {k: v for k, v in env.items()
                if k not in pvals and k not in feeds}
    mb_feeds = _microbatch_feeds(feeds, M)

    def fwd(p, key):
        def body(k, mb):
            k_step, k_next = jax.random.split(k)
            sub = LoweringContext(k_step, ctx.mesh, ctx.axis_names,
                                  ctx.is_test)
            e = dict(base_env)
            e.update(p)
            e.update(mb)
            e = run_ops(fwd_ops, e, sub)
            return k_next, (jnp.sum(e[loss_name]) * loss_scale,
                            e[loss_name])
        k_final, (totals, losses) = jax.lax.scan(body, key, mb_feeds)
        return jnp.mean(totals), (jnp.mean(losses, axis=0), k_final)

    (_, (loss_val, new_key)), grads = jax.value_and_grad(
        fwd, has_aux=True)(pvals, ctx.key)
    ctx.key = new_key
    env2 = dict(base_env)
    env2.update(feeds)
    env2.update(pvals)
    env2[loss_name] = loss_val
    for n in param_names:
        env2[grad_var_name(n)] = grads[n]
    env2[grad_var_name(loss_name)] = jnp.ones_like(loss_val)
    _guardrails.stash_probe(env2, loss_name,
                            [grad_var_name(n) for n in param_names], ctx)
    env2 = run_ops(tail_ops, env2, ctx)
    _check_pipe_fetches(env2, fetch_names, "microbatched lowering")
    return env2


# primitives that move/alias bytes but execute no arithmetic — the
# complete set a true no-op schedule branch may lower to (the idle-tick
# census asserts the idle branch jaxpr stays inside this set)
_ZERO_FLOP_PRIMS = frozenset({
    "broadcast_in_dim", "reshape", "convert_element_type", "transpose",
    "squeeze", "slice", "concatenate", "copy", "stop_gradient", "pjit",
})

# census of the most recent scheduled pipeline lowering (family, tick
# tables, idle accounting, weight-sharding summary) — read by
# tests/test_pipeline.py and the telemetry recorder
_LAST_PIPE_REPORT: Dict[str, Any] = {}


def last_pipeline_report() -> Dict[str, Any]:
    """The census of the most recent scheduled pipeline lowering."""
    return dict(_LAST_PIPE_REPORT)


def _jaxpr_prims(fn, *abstract_args):
    """Flat primitive inventory of ``fn``'s jaxpr (sub-jaxprs included);
    None if tracing fails."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            out.append(eqn.primitive.name)
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", None)
                if inner is not None:
                    walk(inner)
                elif hasattr(p, "eqns"):
                    walk(p)
    try:
        walk(jax.make_jaxpr(fn)(*abstract_args).jaxpr)
    except Exception:
        return None
    return out


def _lower_pipelined_schedule(ops, env, ctx, bw_idx, fetch_names,
                              state_out_names):
    """Scheduled pipeline lowering over the ``pp`` mesh axis — one
    ``lax.scan`` over the static per-tick tables of the stamped schedule
    family (``pipe.simulate_schedule``): non-interleaved 1F1B,
    interleaved (virtual-stage) 1F1B, or the zero-bubble B/W split.

    The program's forward was partitioned by framework/pipe.py into
    ``V = S·chunks`` virtual-stage segments separated by
    ``pipe_stage_boundary`` markers; virtual stage ``k`` lives on rank
    ``k % S`` as chunk ``k // S``.  Every tick, each rank runs an outer
    per-rank ``lax.switch`` branch that (a) performs the masked
    saved-input / cotangent ring stores for whatever arrived on the
    wire this tick (pure data movement — byte copies, no FLOPs), then
    (b) inner-switches on the tick's unit kind: a TRUE no-op branch for
    idle ticks (XLA conditionals execute only the selected branch, so
    idle-tick stage compute is exactly zero — the masked idle half-tick
    PR 13 carried is gone), F (stage forward), B (backward), or — zero
    bubble — B (activation grad only, the cotangent hop) and W (weight
    grad only, deferred into bubbles).  Boundary activations hop
    rank→rank+1 and cotangents rank→rank−1 with one wrapping
    ``lax.ppermute`` each per tick (the wrap link carries the
    chunk-transition hop for interleaved and zeros otherwise).

    A backward-kind tick RECOMPUTES its stage's forward from the saved
    stage input (``jax.vjp`` at the tick), so per-device in-flight
    state is the saved-input ring + the cotangent stash ring (sizes
    from the schedule simulation) + one stage's residuals.  Parameter
    cotangents accumulate into per-rank buffers; replicated params get
    the pipe-axis fused all-reduce in the tail, while pipe-SHARDED
    params (``apply_pipe_weight_sharding``) are all-gathered once
    before the scan and their grads reduce-scattered once after it —
    the scatter performing the cross-stage sum."""
    bw_op = ops[bw_idx]
    attrs = bw_op.attrs
    V = int(attrs["pipe_stages"])
    chunks = int(attrs.get("pipe_chunks") or 1)
    family = attrs.get("pipe_schedule") or "1f1b"
    S = V // max(chunks, 1)
    M = int(attrs["pipe_microbatches"])
    axis = attrs.get("pipe_axis", "pp")
    boundaries = [list(b) for b in attrs["pipe_boundaries"]]
    param_names = list(attrs["param_names"])
    sharded_params = dict(attrs.get("pipe_sharded_params") or {})
    loss_name = attrs["loss_name"]
    loss_scale = attrs.get("loss_scale", 1.0)
    feed_names = [n for n in attrs.get("pipe_feed_names", ()) if n in env]
    tail_ops = ops[bw_idx + 1:]

    n_pp = jax.lax.axis_size(axis)
    if n_pp != S:
        raise ValueError(
            f"pipelined program has {S} ranks ({V} virtual stages x "
            f"{chunks} chunks) but the {axis!r} mesh axis has size "
            f"{n_pp}")

    segments = [[] for _ in range(V)]
    for op in ops[:bw_idx]:
        if op.type == "pipe_stage_boundary":
            continue
        segments[int(op.attrs.get("_pipe_stage", 0))].append(op)
    b_union: List[str] = []
    for names in boundaries:
        for n in names:
            if n not in b_union:
                b_union.append(n)

    pvals = {n: env[n] for n in param_names}
    feeds = {n: env[n] for n in feed_names}
    base_env = {k: v for k, v in env.items()
                if k not in pvals and k not in feeds}
    mb_feeds = _microbatch_feeds(feeds, M)
    mb0 = {n: v[0] for n, v in mb_feeds.items()}
    base_key = ctx.key

    # pipe-sharded weights: gather the 1/S shards ONCE before the tick
    # scan — every stage body sees full values; the matching
    # psum_scatter after the scan returns shard grads already summed
    # across stages
    full_pvals = dict(pvals)
    for n, dim in sharded_params.items():
        full_pvals[n] = jax.lax.all_gather(
            pvals[n], axis, axis=int(dim), tiled=True)

    def stage_fn(k, p, f, bnd_in, key):
        """One virtual stage's segment on one microbatch: (boundary
        out, loss seed, loss var) — loss only materialises on the last
        virtual stage."""
        e = dict(base_env)
        e.update(p)
        e.update(f)
        for n in (boundaries[k - 1] if k > 0 else ()):
            e[n] = bnd_in[n]
        sub = LoweringContext(key, ctx.mesh, ctx.axis_names, ctx.is_test)
        e = run_ops(segments[k], e, sub)
        out = {n: e[n] for n in (boundaries[k] if k < V - 1 else ())}
        if k == V - 1:
            lvar = e[loss_name]
            total = jnp.sum(lvar) * loss_scale
        else:
            lvar, total = None, jnp.asarray(0.0, jnp.float32)
        return out, total, lvar

    # boundary/loss buffer shapes: abstract-eval one microbatch through
    # the whole forward (no compile, no device work)
    def probe(p, f, key):
        e = dict(base_env)
        e.update(p)
        e.update(f)
        sub = LoweringContext(key, ctx.mesh, ctx.axis_names, ctx.is_test)
        for seg in segments:
            e = run_ops(seg, e, sub)
        return {n: e[n] for n in b_union}, e[loss_name]

    bshapes, lshape = jax.eval_shape(probe, full_pvals, mb0, base_key)

    def zeros_of(sd):
        return jnp.zeros(sd.shape, sd.dtype)

    from .pipe import KIND_B, KIND_F, simulate_schedule
    sch = simulate_schedule(family, S, M, chunks=chunks)
    W_f = int(sch["slots"])
    W_c = int(sch["ct_slots"])
    T = int(sch["ticks"])
    has_w = family == "zero_bubble"
    # inner branch index per (tick, rank): 0 = idle, else
    # 1 + chunk·KPC + {F: 0, B: 1, W: 2}
    KPC = 3 if has_w else 2
    code_rows = [[0] * S for _ in range(T)]
    for t in range(T):
        for r in range(S):
            kind = sch["kind"][t][r]
            if kind:
                c = sch["vstage"][t][r] // S
                code_rows[t][r] = 1 + c * KPC + (
                    0 if kind == KIND_F else (1 if kind == KIND_B else 2))
    code_tbl = jnp.asarray(np.array(code_rows, dtype=np.int32))
    mb_tbl = jnp.asarray(np.array(sch["mb"], dtype=np.int32))
    fac_tbl = jnp.asarray(np.array(sch["arr_c"], dtype=np.int32))
    fam_tbl = jnp.asarray(np.array(sch["arr_mb"], dtype=np.int32))
    cac_tbl = jnp.asarray(np.array(sch["ct_arr_c"], dtype=np.int32))
    cam_tbl = jnp.asarray(np.array(sch["ct_arr_mb"], dtype=np.int32))

    def mb_key(i, k):
        # deterministic per (microbatch, virtual stage): a backward
        # tick's recompute replays the forward tick's randomness
        return jax.random.fold_in(jax.random.fold_in(base_key, i), k)

    def zero_sends():
        return ({n: zeros_of(bshapes[n]) for n in b_union},
                {n: zeros_of(bshapes[n]) for n in b_union})

    def make_noop():
        def noop(saved_f, saved_ct, acc, lvar_sum, mb):
            bnd_send, ct_send = zero_sends()
            return acc, lvar_sum, bnd_send, ct_send
        return noop

    def make_f(r, c):
        k = c * S + r
        seg_in = boundaries[k - 1] if k > 0 else []
        last = k == V - 1

        def f_unit(saved_f, saved_ct, acc, lvar_sum, mb):
            jj = jnp.clip(mb, 0, M - 1)
            f_j = {n: v[jj] for n, v in mb_feeds.items()}
            bnd_j = {n: saved_f[n][jj % W_f] for n in seg_in}
            out, _, lvar_i = stage_fn(k, full_pvals, f_j, bnd_j,
                                      mb_key(jj, k))
            bnd_send, ct_send = zero_sends()
            for n, v in out.items():
                bnd_send[n] = v.astype(bshapes[n].dtype)
            if last:
                lvar_sum = lvar_sum + lvar_i.astype(lvar_sum.dtype)
            return acc, lvar_sum, bnd_send, ct_send
        return f_unit

    def make_b(r, c, weight_grads=True, act_grads=True):
        k = c * S + r
        seg_in = boundaries[k - 1] if k > 0 else []
        seg_out = boundaries[k] if k < V - 1 else []
        last = k == V - 1

        def b_unit(saved_f, saved_ct, acc, lvar_sum, mb):
            jj = jnp.clip(mb, 0, M - 1)
            f_j = {n: v[jj] for n, v in mb_feeds.items()}
            bnd_j = {n: saved_f[n][jj % W_f] for n in seg_in}
            ct_j = {n: saved_ct[n][jj % W_c].astype(bshapes[n].dtype)
                    for n in seg_out}
            seed = jnp.asarray(1.0 / M, jnp.float32) if last \
                else jnp.asarray(0.0, jnp.float32)
            bnd_send, ct_send = zero_sends()
            if weight_grads and act_grads:
                def f_vjp(p_, bnd_):
                    out, total, _ = stage_fn(k, p_, f_j, bnd_,
                                             mb_key(jj, k))
                    return {n: out[n] for n in seg_out}, total
                _, vjp_fn = jax.vjp(f_vjp, full_pvals, bnd_j)
                dp, dbnd = vjp_fn((ct_j, seed))
            elif act_grads:
                # zero-bubble B: activation grad only — params are
                # constants, the weight grad waits for the W tick
                def f_vjp(bnd_):
                    out, total, _ = stage_fn(k, full_pvals, f_j, bnd_,
                                             mb_key(jj, k))
                    return {n: out[n] for n in seg_out}, total
                _, vjp_fn = jax.vjp(f_vjp, bnd_j)
                (dbnd,) = vjp_fn((ct_j, seed))
                dp = None
            else:
                # zero-bubble W: weight grad only — the saved input is
                # a constant, the cotangent was stashed by the B tick
                def f_vjp(p_):
                    out, total, _ = stage_fn(k, p_, f_j, bnd_j,
                                             mb_key(jj, k))
                    return {n: out[n] for n in seg_out}, total
                _, vjp_fn = jax.vjp(f_vjp, full_pvals)
                (dp,) = vjp_fn((ct_j, seed))
                dbnd = None
            if dp is not None:
                acc = {n: acc[n] + dp[n].astype(acc[n].dtype)
                       for n in acc}
            if dbnd is not None:
                for n in seg_in:
                    if n in dbnd:
                        ct_send[n] = dbnd[n].astype(bshapes[n].dtype)
            return acc, lvar_sum, bnd_send, ct_send
        return b_unit

    def make_rank_branch(r):
        # per-chunk arrival bookkeeping + the inner unit switch.  The
        # ring stores are uniform masked byte copies (zero FLOPs) so an
        # idle tick still files whatever landed on the wire; the unit
        # compute itself runs ONLY in the selected inner branch.
        inner = [make_noop()]
        for c in range(chunks):
            k = c * S + r
            inner.append(make_f(r, c))
            if has_w:
                # B = activation grad only (never scheduled at k = 0);
                # W = weight grad only (at k = 0 it IS the whole
                # backward — no upstream to feed)
                inner.append(make_b(r, c, weight_grads=False))
                inner.append(make_b(r, c, act_grads=False))
            else:
                inner.append(make_b(r, c))

        def branch(carry, code_row, mb_row, fac, fam, cac, cam):
            saved_f, saved_ct, bnd_in, ct_in, acc, lvar_sum = carry
            saved_f, saved_ct = dict(saved_f), dict(saved_ct)
            for c in range(chunks):
                k = c * S + r
                if k > 0:
                    hit = jnp.logical_and(fac[r] == c, fam[r] >= 0)
                    slot = jnp.clip(fam[r], 0, M - 1) % W_f
                    for n in boundaries[k - 1]:
                        saved_f[n] = jnp.where(
                            hit,
                            jax.lax.dynamic_update_index_in_dim(
                                saved_f[n], bnd_in[n], slot, 0),
                            saved_f[n])
                if k < V - 1:
                    hit = jnp.logical_and(cac[r] == c, cam[r] >= 0)
                    slot = jnp.clip(cam[r], 0, M - 1) % W_c
                    for n in boundaries[k]:
                        saved_ct[n] = jnp.where(
                            hit,
                            jax.lax.dynamic_update_index_in_dim(
                                saved_ct[n], ct_in[n], slot, 0),
                            saved_ct[n])
            acc, lvar_sum, bnd_send, ct_send = jax.lax.switch(
                jnp.clip(code_row[r], 0, len(inner) - 1), inner,
                saved_f, saved_ct, acc, lvar_sum, mb_row[r])
            return saved_f, saved_ct, acc, lvar_sum, bnd_send, ct_send
        return branch

    branches = [make_rank_branch(r) for r in range(S)]
    idx = jax.lax.axis_index(axis)
    # wrapping rings: the S−1 → 0 link carries the interleaved
    # chunk-transition hop (and zeros for v = 1, which the arrival
    # tables never file)
    perm_down = [(i, (i + 1) % S) for i in range(S)]
    perm_up = [(i, (i - 1) % S) for i in range(S)]

    def tick(carry, rows):
        code_row, mb_row, fac, fam, cac, cam = rows
        saved_f, saved_ct, acc, lvar_sum, bnd_send, ct_send = \
            jax.lax.switch(idx, branches, carry, code_row, mb_row,
                           fac, fam, cac, cam)
        bnd_in = {n: jax.lax.ppermute(bnd_send[n], axis, perm_down)
                  for n in b_union}
        ct_in = {n: jax.lax.ppermute(ct_send[n], axis, perm_up)
                 for n in b_union}
        return (saved_f, saved_ct, bnd_in, ct_in, acc, lvar_sum), None

    init = (
        {n: jnp.zeros((W_f,) + tuple(bshapes[n].shape),
                      bshapes[n].dtype) for n in b_union},
        {n: jnp.zeros((W_c,) + tuple(bshapes[n].shape),
                      bshapes[n].dtype) for n in b_union},
        {n: zeros_of(bshapes[n]) for n in b_union},
        {n: zeros_of(bshapes[n]) for n in b_union},
        {n: jnp.zeros(v.shape, v.dtype) for n, v in full_pvals.items()},
        jnp.zeros(lshape.shape, lshape.dtype),
    )
    (_, _, _, _, acc, lvar_sum), _ = jax.lax.scan(
        tick, init, (code_tbl, mb_tbl, fac_tbl, fam_tbl,
                     cac_tbl, cam_tbl))

    # only the last pipe rank accumulated the loss (zeros elsewhere) —
    # the psum broadcasts it; replicated-param grads stay stage-partial
    # here (summed by the pipe-axis fused all-reduce in the tail) while
    # pipe-sharded grads reduce-scatter NOW — the scatter is their
    # cross-stage sum
    lvar_mean = jax.lax.psum(lvar_sum, axis) / M
    grads_out = {}
    for n in param_names:
        if n in sharded_params:
            grads_out[n] = jax.lax.psum_scatter(
                acc[n], axis, scatter_dimension=int(sharded_params[n]),
                tiled=True)
        else:
            grads_out[n] = acc[n]
    ctx.key = jax.random.split(base_key, 1)[0]
    env2 = dict(base_env)
    env2.update(feeds)
    env2.update(pvals)
    env2[loss_name] = lvar_mean
    for n in param_names:
        env2[grad_var_name(n)] = grads_out[n]
    env2[grad_var_name(loss_name)] = jnp.ones_like(lvar_mean)

    # the lowering census: tick tables the scan ACTUALLY consumed, the
    # no-op branch's primitive inventory (must be pure data movement),
    # and the weight-sharding summary — tests/test_pipeline.py asserts
    # census idle ticks == simulator bubble ticks and idle compute == 0
    census_idle = int(sum(1 for t in range(T) for r in range(S)
                          if code_rows[t][r] == 0))
    noop = make_noop()
    noop_prims = _jaxpr_prims(
        lambda mb: noop(init[0], init[1], init[4], init[5], mb),
        jnp.asarray(0, jnp.int32))
    idle_flop_prims = [p for p in (noop_prims or ())
                      if p not in _ZERO_FLOP_PRIMS]
    global _LAST_PIPE_REPORT
    _LAST_PIPE_REPORT = {
        "family": family, "num_ranks": S, "chunks": chunks,
        "num_virtual_stages": V, "num_microbatches": M,
        "ticks": T, "census_idle_slots": census_idle,
        "sim_idle_slots": int(sch["idle_slots"]),
        "bubble_ticks": float(sch["bubble_ticks"]),
        "bubble_frac": float(sch["bubble_frac"]),
        "ring_slots": [W_f, W_c],
        "idle_branch_prims": list(noop_prims or ()),
        "idle_branch_flop_prims": list(idle_flop_prims),
        "sharded_params": {n: int(d) for n, d in sharded_params.items()},
    }

    # stage-partial grads: a NaN on any pp rank poisons the probe on
    # every rank through the guard's all-axis psum
    _guardrails.stash_probe(env2, loss_name,
                            [grad_var_name(n) for n in param_names], ctx)
    env2 = run_ops(tail_ops, env2, ctx)
    _check_pipe_fetches(env2, fetch_names, "scheduled pipeline lowering")
    return env2


# PR 13 name kept for external callers; the 1F1B path is now one row of
# the schedule family
_lower_pipelined_1f1b = _lower_pipelined_schedule


def lower_block_with_backward(ops, env, ctx, bw_idx, fetch_names,
                              state_out_names):
    """Lower [forward ops][backward meta-op][update ops] with value_and_grad."""
    bw_op = ops[bw_idx]
    pipe_S = int(bw_op.attrs.get("pipe_stages") or 1)
    pipe_M = int(bw_op.attrs.get("pipe_microbatches") or 1)
    pipe_axis = bw_op.attrs.get("pipe_axis") or "pp"
    if pipe_S > 1 and ctx.axis_names and pipe_axis in ctx.axis_names:
        return _lower_pipelined_1f1b(ops, env, ctx, bw_idx, fetch_names,
                                     state_out_names)
    if pipe_M > 1:
        # pipelined program on a mesh WITHOUT the pipe axis (pipe = 1
        # degenerate), or the bare microbatch-accumulation substrate
        return _lower_microbatched(ops, env, ctx, bw_idx, fetch_names,
                                   state_out_names)
    fwd_ops = ops[:bw_idx]
    tail_ops = ops[bw_idx + 1:]
    param_names = list(bw_op.attrs["param_names"])
    loss_name = bw_op.attrs["loss_name"]
    checkpoints = bw_op.attrs.get("checkpoints") or []
    loss_scale = bw_op.attrs.get("loss_scale", 1.0)
    # dynamic loss scaling (AMP fp16 mode): scale lives in a persistable var
    loss_scale_var = bw_op.attrs.get("loss_scale_var")

    pvals = {n: env[n] for n in param_names}
    base_env = {k: v for k, v in env.items() if k not in pvals}
    always_live = set(fetch_names) | set(state_out_names) | {loss_name}

    segments = _segment_at_checkpoints(fwd_ops, checkpoints)

    # overlap-aware grad sync (compiler.insert_grad_sync ready-order
    # buckets): hooked collectives fire INSIDE the backward sweep; the
    # tail op is then skipped (its outputs already hold the reduced
    # grads).  Recompute-checkpointed programs keep tail placement (the
    # hook positions don't survive segment re-execution).
    from ..flags import flag
    hooks = []
    if len(segments) == 1 and tail_ops and flag("overlap_lowering"):
        hooks = _overlap_schedule(fwd_ops, tail_ops, param_names)
    hooked_ids = {id(op) for _, _, op in hooks}

    def fwd(p, key):
        e = dict(base_env)
        e.update(p)
        sub = LoweringContext(key, ctx.mesh, ctx.axis_names, ctx.is_test)
        if len(segments) == 1:
            if hooks:
                seg, cur = segments[0], 0
                for pos, pnames, op in hooks:
                    pos = min(max(pos, cur), len(seg))
                    e = run_ops(seg[cur:pos], e, sub)
                    seed = int(op.attrs.get("_bucket_index", 0)) + 0x0eaf
                    vals = _make_overlap_hook(op, ctx, seed)(
                        *[e[pn] for pn in pnames])
                    for pn, v in zip(pnames, vals):
                        e[pn] = v
                    cur = pos
                e = run_ops(seg[cur:], e, sub)
            else:
                e = run_ops(segments[0], e, sub)
        else:
            for i, seg in enumerate(segments):
                live = _live_names_after(segments, i, always_live)
                if i < len(segments) - 1:
                    def seg_fn(e_in, k_in, _seg=seg, _live=live):
                        c = LoweringContext(k_in, ctx.mesh, ctx.axis_names,
                                            ctx.is_test)
                        e_out = run_ops(_seg, dict(e_in), c)
                        return ({k: v for k, v in e_out.items()
                                 if k in _live or k in e_in}, c.key)
                    e, new_key = jax.checkpoint(seg_fn)(e, sub.key)
                    sub.key = new_key
                else:
                    e = run_ops(seg, e, sub)
        loss = e[loss_name]
        total = jnp.sum(loss) * loss_scale
        if loss_scale_var is not None:
            total = total * jax.lax.stop_gradient(
                e[loss_scale_var].reshape(()).astype(total.dtype))
        guard = getattr(ctx, "guard", None)
        if guard is not None and guard.use_scale:
            # guardrail dynamic loss scaling for non-AMP runs: same
            # scale-into-backward shape as the AMP path above; the
            # grads are unscaled (and the scale state updated through
            # the shared policy) after value_and_grad returns
            total = total * jax.lax.stop_gradient(
                jnp.asarray(e[_guardrails.GUARD_SCALE]).reshape(())
                .astype(total.dtype))
        return total, (e, sub.key)

    (loss_val, (env2, new_key)), grads = jax.value_and_grad(
        fwd, has_aux=True)(pvals, ctx.key)
    ctx.key = new_key
    env2.update(pvals)          # params themselves still visible downstream
    for n in param_names:
        env2[grad_var_name(n)] = grads[n]
    env2[grad_var_name(loss_name)] = jnp.ones_like(env2[loss_name])
    gnames = [grad_var_name(n) for n in param_names]
    # non-finite defense: fault injection + fused finite probe over the
    # RAW (possibly scaled) grads, before the tail's collectives /
    # check_finite can rewrite them (framework/guardrails.py)
    _guardrails.stash_probe(env2, loss_name, gnames, ctx)
    guard = getattr(ctx, "guard", None)
    if guard is not None and guard.use_scale:
        s = jnp.asarray(env2[_guardrails.GUARD_SCALE]).reshape(())
        for gn in gnames:
            g = env2[gn]
            env2[gn] = g / s.astype(g.dtype)
    if hooked_ids:
        # hooked buckets already reduced inside the backward sweep —
        # their grads arrived through value_and_grad; the tail op is
        # skipped.  (A quantized bucket's QScale var stays unset: it is
        # declared for the static byte-accounting layer only and has no
        # runtime reader.)
        tail_ops = [op for op in tail_ops if id(op) not in hooked_ids]
    return run_ops(tail_ops, env2, ctx)


def _merge_fetch(v, name, block, ctx, batch_axis, replicated_names,
                 seq_axis=None):
    """Cross-device fetch semantics under data parallelism — the analog of
    the reference's FetchOpHandle merging per-device results
    (ref: framework/details/fetch_op_handle.cc): batch-sharded tensors are
    all-gathered back to the global batch; scalar float metrics (mean loss,
    accuracy) are averaged; scalar int counters (Correct/Total) are summed;
    replicated values (persistables, allreduced grads, optimizer-zone
    temporaries) pass through untouched.  Scalars also reduce over the
    sequence-parallel axis (per-token losses are sharded over sp too)."""
    if not ctx.axis_names or batch_axis is None:
        return v
    if name in replicated_names:
        return v
    var = block._find_var_recursive(name)
    if var is not None and var.persistable:
        return v
    # batch_axis may be a TUPLE of axes (the planner's dp×fsdp layout
    # shards the batch over both) — flatten before membership checks
    from .mesh_layout import _flat_axes
    batch_axes = tuple(a for a in _flat_axes(batch_axis)
                       if a in ctx.axis_names)
    reduce_axes = batch_axes + tuple(
        a for a in (seq_axis,) if a and a in ctx.axis_names)
    if not reduce_axes:
        return v
    if getattr(v, "ndim", 0) == 0:
        if jnp.issubdtype(v.dtype, jnp.integer):
            return jax.lax.psum(v, reduce_axes)
        return jax.lax.pmean(v, reduce_axes)
    if not batch_axes:
        return v
    return jax.lax.all_gather(v, batch_axes, axis=0, tiled=True)


def _replicated_var_names(ops, bw_idx):
    """Vars that are replicated (not batch-sharded) under dp: param grads
    after the inserted c_allreduce_sum, plus everything first written by
    ops after the backward op (LR/optimizer zone)."""
    if bw_idx is None:
        return set()
    out = set()
    for op in ops[bw_idx:]:
        out |= set(op.output_names())
    return out


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


class _CompiledStep:
    def __init__(self, fn, state_in_names, state_out_names, feed_names,
                 fetch_names, raw_fn=None, mesh=None, feed_spec_fn=None,
                 state_in_specs=None, jit_fn=None, guard=None):
        # guardrail policy this step compiled with (None = unguarded);
        # a guarded step's fetches carry the guard scalar tail
        self.guard = guard
        self.fn = fn                 # jitted, donating state buffers
        self.raw_fn = raw_fn or fn   # unjitted pure step (for export)
        # the re-lowerable jax.jit wrapper when fn is a deserialized
        # jax.stages.Compiled from the AOT cache (introspection — e.g.
        # PreparedStep.donation() — needs .lower(), which Compiled lacks)
        self.jit_fn = jit_fn if jit_fn is not None else fn
        self.state_in_names = state_in_names
        self.state_out_names = state_out_names
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        # multi-process metadata: sharding specs for lifting process-local
        # feeds/state to global jax.Arrays when the mesh spans hosts
        self.mesh = mesh
        self.feed_spec_fn = feed_spec_fn
        self.state_in_specs = state_in_specs or {}
        # fixed per compiled step — don't walk mesh.devices every run()
        self.spans_processes = _mesh_spans_processes(mesh)


def _mesh_spans_processes(mesh):
    """True when the mesh contains devices owned by other processes — the
    multi-host (DCN) regime where inputs must be global jax.Arrays (the
    analog of the reference's num_trainers>1 NCCL comm spanning processes,
    ref: parallel_executor.cc:536)."""
    if mesh is None:
        return False
    pi = jax.process_index()
    return any(d.process_index != pi for d in mesh.devices.flat)


def _to_global(mesh, spec, value, local_shard=False):
    """Lift a value to a global array on a multi-process mesh.

    ``local_shard=True`` (feeds): each process passes only ITS slice of
    any sharded dim — the multi-host data-parallel input contract.
    ``local_shard=False`` (state/rng): every process holds the FULL value
    (the startup program runs replicated on each host), so the value is
    placed with global semantics — XLA keeps only this host's shards.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    if spec is None:
        spec = P()
    if isinstance(value, jax.Array) and \
            isinstance(value.sharding, NamedSharding) and \
            value.sharding.mesh == mesh:
        return value
    sh = NamedSharding(mesh, spec)
    if local_shard:
        return jax.make_array_from_process_local_data(sh, np.asarray(value))
    return jax.device_put(np.asarray(value), sh)


def _fetch_numpy(x):
    """np.asarray for fetches that works on multi-process (not fully
    addressable) arrays — fetches are replicated, so any local shard is
    the full value."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        return np.asarray(x.addressable_data(0))
    return np.asarray(x)


def _fetch_names(fetch_list):
    return [f.name if isinstance(f, Variable) else str(f)
            for f in fetch_list]


class _FeedDeviceCache:
    """Host→device feed cache keyed by buffer identity.

    Repeatedly feeding the same host array (fixed eval batches, constant
    tables, a benchmark loop) re-transfers it every ``run()`` — over a
    remote-chip link that is a full round trip per step.  The reference
    avoids this with staged double-buffer slots that keep the device copy
    alive across reads (ref: operators/reader/buffered_reader.cc:92);
    here the staged copy is cached under the host buffer's identity.

    Only arrays the caller has FROZEN (``arr.flags.writeable == False``)
    are cached: freezing is the caller's promise the buffer will not be
    mutated in place, which makes identity (object id + data pointer +
    shape + dtype) a sound key.  Entries hold a weakref to the source so
    a GC'd array (whose data pointer may be reused) drops its entry.

    Capacity comes from ``flag("feed_cache_size")`` (read live, so a
    serving process can widen it at runtime for a stream of distinct
    request tensors that would thrash the old hardcoded 64); hit/miss
    counters are published through the monitor registry and surfaced by
    ``profiler.step_breakdown()``.
    """

    def __init__(self, device, maxsize=None):
        self._device = device
        self._maxsize = maxsize      # explicit override (tests); else flag
        self._entries: Dict[Any, Any] = {}   # key -> (weakref, device_array)

    def capacity(self) -> int:
        if self._maxsize is not None:
            return self._maxsize
        from ..flags import flag
        return int(flag("feed_cache_size"))

    def lookup(self, arr):
        """Return a device-resident copy of ``arr``, or None if uncacheable."""
        if not isinstance(arr, np.ndarray) or arr.flags.writeable or \
                not arr.flags.owndata:
            # owndata guards against INCIDENTALLY read-only arrays
            # (np.broadcast_to views, dlpack wrappers, memmaps) whose
            # backing buffer can still change under the same pointer —
            # only an owning array somebody froze is a deliberate promise
            return None
        from ..monitor import stat
        key = (id(arr), arr.__array_interface__["data"][0], arr.shape,
               str(arr.dtype))
        hit = self._entries.get(key)
        if hit is not None:
            ref, buf = hit
            if ref() is arr:
                stat("feed_cache_hit").add()
                return buf
            del self._entries[key]
        stat("feed_cache_miss").add()
        cap = self.capacity()
        if cap <= 0:
            return None
        buf = jax.device_put(arr, self._device)
        while len(self._entries) >= cap:
            self._entries.pop(next(iter(self._entries)))
        self._entries[key] = (weakref.ref(arr), buf)
        return buf


def _mesh_identity(mesh):
    """Content-based mesh cache key — id(mesh) can be reused after GC."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), mesh.devices.shape,
            tuple(d.id for d in mesh.devices.flat))


class _FieldDumper:
    """Per-worker training observability (ref: trainer_desc.proto:12-15
    dump_fields/dump_fields_path/dump_param + device_worker.cc DumpField/
    DumpParam): configured through ``program._fleet_opt`` exactly like the
    reference's trainer factory (trainer_factory.py:65), writing one text
    file per worker under dump_fields_path.

    Formats mirror the reference: dump_fields emits one line per batch
    instance ``lineid \\t name:len:v0:v1...`` (2-D [batch, D] vars only,
    device_worker.cc CheckValidOutput); dump_param emits
    ``(batch,name):v0:v1...`` after the step's update."""

    def __init__(self, program, scope):
        opt_info = getattr(program, "_fleet_opt", None) or {}
        self.field_names = list(opt_info.get("dump_fields") or [])
        self.param_names = list(opt_info.get("dump_param") or [])
        self.path = opt_info.get("dump_fields_path")
        self.scope = scope
        self._f = None
        self._lineid = 0
        if (self.field_names or self.param_names) and not self.path:
            raise ValueError(
                "dump_fields/dump_param need dump_fields_path in "
                "_fleet_opt (ref: trainer_desc.proto:12)")
        if self.path and (self.field_names or self.param_names):
            import os
            os.makedirs(self.path, exist_ok=True)
            rank = jax.process_index()
            self._f = open(os.path.join(self.path, f"worker-{rank}"), "a")
        # unknown fields fail loudly at the first fetch, like a bad
        # fetch_list would

    @staticmethod
    def _fmt(vals):
        return ":".join(f"{v:.9g}" if isinstance(v, float) else str(v)
                        for v in vals)

    def after_step(self, step, field_vals):
        if self._f is None:
            return
        arrays = [np.asarray(_fetch_numpy(v)) for v in field_vals]
        if arrays:
            # derive the batch from the first field that PASSES the 2-D
            # check (a scalar loss listed first must not set batch=1 and
            # silently skip every valid field — advisor r4; the
            # reference's CheckValidOutput enforces instead of dropping)
            batch = next((a.shape[0] for a in arrays if a.ndim == 2), None)
            if batch is None:
                import warnings
                warnings.warn(
                    f"dump_fields {self.field_names}: no 2-D [batch, D] "
                    f"field (shapes "
                    f"{[tuple(a.shape) for a in arrays]}); nothing dumped "
                    f"(ref device_worker.cc CheckValidOutput)",
                    stacklevel=2)
            else:
                skipped = [n for n, a in zip(self.field_names, arrays)
                           if a.ndim != 2 or a.shape[0] != batch]
                if skipped:
                    import warnings
                    warnings.warn(
                        f"dump_fields: skipping non-[batch, D] fields "
                        f"{skipped} (ref CheckValidOutput)", stacklevel=2)
                for i in range(batch):
                    parts = [str(self._lineid)]
                    for name, a in zip(self.field_names, arrays):
                        if a.ndim != 2 or a.shape[0] != batch:
                            continue  # CheckValidOutput: 2-D batch vars
                        row = a[i].ravel().tolist()
                        parts.append(f"{name}:{len(row)}:{self._fmt(row)}")
                    self._f.write("\t".join(parts) + "\n")
                    self._lineid += 1
        for name in self.param_names:
            v = self.scope.find_var(name)
            if v is None:
                continue
            vals = np.asarray(_fetch_numpy(v)).ravel().tolist()
            self._f.write(f"({step},{name}):{self._fmt(vals)}\n")
        self._f.flush()

    def close(self):
        if self._f is not None:
            self._f.close()
            self._f = None


class FetchHandle:
    """Lazy fetch result: holds the device array a prepared step produced
    and blocks only on the first host read (``numpy()``/``__array__``) —
    the opposite of ``Executor.run``'s ``return_numpy=True``, which forces
    a device sync per fetch per step.  The host value is cached, so
    repeated reads sync once."""

    __slots__ = ("name", "_value", "_host", "_stats")

    def __init__(self, value, name=None, stats=None):
        self.name = name
        self._value = value
        self._host = None
        self._stats = stats

    @property
    def value(self):
        """The device array — no sync."""
        return self._value

    def is_ready(self):
        """True when the producing step has completed on device."""
        ready = getattr(self._value, "is_ready", None)
        return bool(ready()) if ready is not None else True

    def block_until_ready(self):
        jax.block_until_ready(self._value)
        return self

    def numpy(self):
        if self._host is None:
            from ..profiler import RecordEvent
            t0 = time.perf_counter_ns()
            with RecordEvent("prepared::fetch_sync"):
                self._host = _fetch_numpy(self._value)
            if self._stats is not None:
                self._stats["fetch_wait_ns"] += time.perf_counter_ns() - t0
        return self._host

    def __array__(self, dtype=None, copy=None):
        a = self.numpy()
        if dtype is not None:
            return a.astype(dtype)
        return np.array(a) if copy else a

    def __float__(self):
        return float(self.numpy().reshape(()))

    def __repr__(self):
        state = "host" if self._host is not None else (
            "ready" if self.is_ready() else "in-flight")
        return f"FetchHandle({self.name!r}, {state})"


class PreparedStep:
    """Steady-state executor fast path — the analog of the reference's
    ``Executor.prepare``/``RunPreparedContext`` pair (ref: executor.py:1084
    per-program ctx cache; executor.cc:368 Executor::Prepare) and of
    ``ParallelExecutor``'s reusable execution graph built once and re-run
    per step (ref: parallel_executor.cc:536).

    ``Executor.run`` pays per step for answers that never change: fetch
    name translation, pass-variant resolution, the compile-cache key, a
    Scope round trip for every persistable (``find_var`` per state var in,
    ``set_var`` per state var out), and — under ``return_numpy=True`` — a
    device sync per fetch.  ``prepare()`` resolves all of it once;
    ``run(feed)`` is the minimal hot loop:

      * state stays DEVICE-RESIDENT between steps and its buffers are
        donated to the compiled step (``donate_argnums`` over state_in —
        the ``tf.aliasing_output`` annotations the multichip census
        artifact counts), with NO Scope write-back until ``sync_scope()``;
        ``Executor.run``, io.save_*, and the param-swap optimizers flush
        implicitly through ``sync_prepared_state``, so checkpoints are
        never stale;
      * fetches return as lazy ``FetchHandle``s — the host blocks only on
        the first ``.numpy()`` read;
      * dispatch runs ahead of the device up to
        ``flag("max_inflight_steps")`` steps; when the window is full the
        host blocks once on the oldest in-flight step (state chains step
        to step, so one token bounds the whole queue) — backpressure
        instead of lockstep.

    External scope writes (load_persistables, a plain ``Executor.run``,
    user ``set_var``) bump the scope's version counter and make the next
    ``run`` re-pull state.  Two PreparedSteps updating the same state on
    one scope must interleave through ``sync_scope()`` — donation consumes
    the other's buffers otherwise.

    ``donate_state=False`` selects the READ-ONLY-STATE mode built for
    serving (AnalysisPredictor / ServingEngine): state buffers are passed
    to the compiled step WITHOUT donation and pass-through state is
    dropped from the step outputs entirely, so inference weights stay
    device-resident across requests, are never consumed, and never
    round-trip through a device copy per request.  The scope stays the
    owner of the buffers, so plain ``Executor.run`` / ``io.save_*``
    interleavings need no staleness flush, and many PreparedSteps (one
    per shape bucket) can share one scope safely.  Only persistables the
    program genuinely WRITES (none, in a well-formed served program —
    the inference verifier rejects them) still flow out and mark the
    step dirty."""

    def __init__(self, executor, program, feed_names, fetch_list, scope,
                 feed=None, donate_state=True):
        from .compiler import CompiledProgram
        self._exe = executor
        self._scope = scope
        self._donate_state = donate_state
        self._mesh = None
        self._axis_names = ()
        self._batch_axis = None
        self._seq_axis = None
        self._feed_specs = {}
        if isinstance(program, CompiledProgram):
            self._mesh = program._mesh
            self._axis_names = program._axis_names
            self._batch_axis = program._batch_axis
            self._seq_axis = program._seq_axis
            self._feed_specs = program._feed_specs
            # pass variants pinned ONCE — the hot loop never re-resolves
            prog, evicted = program._variant_for(_fetch_names(fetch_list))
            if evicted is not None:
                executor._evict_program(evicted)
            program = prog
        self._program = program
        self._fetch_names = _fetch_names(fetch_list)
        self._declared_feed_names = list(feed_names or [])
        from ..flags import flag
        if flag("verify_programs"):
            # static verification (framework/analysis.py): once per
            # program (_uid, _version) — the InferShape/PADDLE_ENFORCE
            # safety net, run before any trace/compile cost.  Errors are
            # InvalidArgumentError diagnostics anchored at the op's
            # creation site.  The prepared path also enforces the
            # donation soundness rules (donated-var-fetched), which are
            # real aliasing hazards under the device-resident fast path.
            from .analysis import verify_cached
            verify_cached(self._program,
                          feed_names=self._declared_feed_names,
                          fetch_names=self._fetch_names,
                          scope_names=scope.var_names(),
                          raise_on_error=True)
        if flag("hbm_budget_gb"):
            # budget gate at prepare time, before any compile is even
            # scheduled: exact when an example feed is given, a declared-
            # shape lower bound otherwise (the first run's _bind re-gates
            # with exact shapes through Executor._compile)
            from .memory_analysis import check_hbm_budget, mesh_axes_of
            check_hbm_budget(self._program, feed_shapes=feed,
                             fetch_names=self._fetch_names,
                             mesh_axes=mesh_axes_of(self._mesh),
                             batch_axis=self._batch_axis,
                             seq_axis=self._seq_axis,
                             feed_specs=self._feed_specs,
                             donate_state=donate_state)
        self._readers = tuple(getattr(program, "_py_readers", ()))
        # one _CompiledStep per feed signature (bucketed data keeps several
        # live); state is shared across them — same program, same vars
        self._steps: Dict[Any, _CompiledStep] = {}
        self._cur: Optional[_CompiledStep] = None
        self._cur_sig: Any = None
        self._cur_exact = False
        self._state: Optional[Dict[str, Any]] = None
        self._key = None
        self._dirty = False
        self._scope_version = None           # forces state pull on first run
        self._inflight: collections.deque = collections.deque()
        self._feed_struct: Dict[str, Any] = {}
        self._cur_check: list = []
        self.stats = {"steps": 0, "blocking_syncs": 0, "max_inflight": 0,
                      "dispatch_ns": 0, "feed_wait_ns": 0,
                      "fetch_wait_ns": 0}
        # device counters the program declares (``program._device_counters``:
        # persistable name -> ((stat key, reduce), ...)), folded at wait()
        self._device_counters = dict(
            getattr(program, "_device_counters", {}))
        self._counters_seen: Dict[str, Any] = {}
        for pairs in self._device_counters.values():
            self.stats.update({key: 0 for key, _ in pairs})
        # guardrail bookkeeping (framework/guardrails.py): per-dispatch
        # guard fetch handles pending a non-blocking host poll, and the
        # latest resolved skip/scale facts for telemetry
        self._guard_pending: collections.deque = collections.deque()
        self._guard_tick = 0
        self._guard_f32 = None
        self._fl_epoch = _FL_EPOCH[0]
        self.guard_stats: Dict[str, Any] = {
            "steps": 0, "skipped_total": 0, "consecutive": 0,
            "last_skipped": False, "loss_scale": None, "step": None}
        _wd_ensure()        # hang watchdog, when step_deadline_s is set
        scope._prepared.add(self)
        if feed is not None:
            feed = dict(feed)
            self._bind(feed, self._signature(feed))

    # -- resolution (cold path) ------------------------------------------
    @staticmethod
    def _signature(feed):
        """Shape/dtype signature; normalizes non-array values in place."""
        items = []
        for k, v in feed.items():
            if not hasattr(v, "dtype"):
                v = np.asarray(v)
                feed[k] = v
            items.append((k, tuple(v.shape), str(v.dtype)))
        items.sort()
        return tuple(items)

    def _bind(self, feed, sig):
        step = self._steps.get(sig)
        if step is None:
            from ..profiler import RecordEvent
            with RecordEvent("executor::compile",
                             program=self._program._uid,
                             version=self._program._version):
                step = self._exe._compile(
                    self._program, feed, self._fetch_names, self._scope,
                    self._mesh, self._axis_names, self._batch_axis,
                    self._seq_axis, self._feed_specs,
                    donate_state=self._donate_state)
            self._steps[sig] = step
        self._cur, self._cur_sig = step, sig
        self._cur_exact = set(step.state_in_names) == \
            set(step.state_out_names)
        self._feed_struct = {
            k: jax.ShapeDtypeStruct(tuple(feed[k].shape), feed[k].dtype)
            for k in step.feed_names}
        # steady-state check list: (name, shape, dtype) over the WHOLE
        # bound feed (extras included — an extra key must force the slow
        # path, not silently alias another signature)
        self._cur_check = [(k, tuple(v.shape), v.dtype)
                           for k, v in feed.items()]
        if self._state is not None:
            # a later signature must not lose state the earlier steps
            # already advanced — only fill names this one newly reads
            for n in step.state_in_names:
                if n not in self._state:
                    v = self._scope.find_var(n)
                    if v is None:
                        if _guardrails.is_guard_var(n):
                            v = _guardrails.init_value(n, step.guard)
                        else:
                            raise RuntimeError(
                                f"persistable var {n!r} not initialised "
                                f"in scope — run the startup program "
                                f"first")
                    self._state[n] = v
        return step

    def _refresh_state(self, step):
        """(Re-)pull state from the scope: first run, or an external write
        (load_persistables / Executor.run / user set_var) bumped the scope
        version while this step held device-resident state."""
        scope = self._scope
        state = {}
        for n in step.state_in_names:
            v = scope.find_var(n)
            if v is None:
                if _guardrails.is_guard_var(n):
                    v = _guardrails.init_value(n, step.guard)
                else:
                    raise RuntimeError(
                        f"persistable var {n!r} not initialised in scope "
                        f"— run the startup program first (ref semantics: "
                        f"executor.cc scope vars)")
            state[n] = v
        self._state = state
        rng = scope.find_var(_RNG_VAR)
        self._key = rng if rng is not None else \
            jax.random.PRNGKey(self._program.random_seed)
        self._scope_version = scope._version
        self._dirty = False
        self._inflight.clear()

    def _feed_matches(self, feed):
        """Steady-state check: does ``feed`` match the bound signature?
        Cheap identity-of-shape/dtype compare — no string building."""
        chk = self._cur_check
        if len(feed) != len(chk):
            return False
        try:
            for k, shp, dt in chk:
                v = feed[k]
                if v.shape != shp or v.dtype != dt:
                    return False
        except (KeyError, AttributeError):
            return False
        return True

    # -- hot loop ---------------------------------------------------------
    def run(self, feed=None, return_numpy=False):
        """One training step.  Returns ``FetchHandle``s (device-resident;
        block on first read) unless ``return_numpy=True``."""
        # watchdog beacon brackets the whole step so a stalled dispatch
        # or window sync is detectable; the stall seam is the drill's
        # way to induce exactly that hang
        _wd_begin("prepared")
        try:
            if _FL_ARMED:
                _faultline.crossing("step_stall")
            return self._run_inner(feed, return_numpy)
        finally:
            _wd_end("prepared")

    def _run_inner(self, feed, return_numpy):
        from ..flags import flag
        from ..profiler import RecordEvent
        # run-level step axis: one id per training step, shared with the
        # compile/serving/checkpoint spans (observability/tracing.py) and
        # the flight recorder's breadcrumb ring
        sid = _step_breadcrumb("prepared", self._program._uid)
        feed = dict(feed) if feed else {}
        if self._readers:
            t0 = time.perf_counter_ns()
            with RecordEvent("prepared::feed_wait"):
                for reader in self._readers:
                    if reader._started:
                        for k, v in reader._next_feed().items():
                            feed.setdefault(k, v)
            self.stats["feed_wait_ns"] += time.perf_counter_ns() - t0
        if self._fl_epoch != _FL_EPOCH[0]:
            # faultline arm/disarm invalidates compiled steps: trace-time
            # injections must never be masked by (or leak out of) a
            # cached executable.  One list-index compare on the hot path.
            self._fl_epoch = _FL_EPOCH[0]
            self._steps.clear()
            self._cur = None
            self._cur_sig = None
            self._cur_check = []
        if self._cur is not None and self._feed_matches(feed):
            step = self._cur
        else:
            sig = self._signature(feed)
            step = self._cur if sig == self._cur_sig else \
                self._bind(feed, sig)
        if self._scope._version != self._scope_version:
            self._refresh_state(step)
        state = self._state
        state_in = state if self._cur_exact else \
            {n: state[n] for n in step.state_in_names}
        feed_vals = {k: feed[k] for k in step.feed_names}
        rng_key = self._key
        if step.spans_processes:
            from jax.sharding import PartitionSpec as P
            mesh = self._mesh
            feed_vals = {k: _to_global(mesh, step.feed_spec_fn(k), v,
                                       local_shard=True)
                         for k, v in feed_vals.items()}
            state_in = {n: _to_global(mesh,
                                      step.state_in_specs.get(n, P()), v)
                        for n, v in state_in.items()}
            rng_key = _to_global(mesh, P(), rng_key)

        window = flag("max_inflight_steps")
        if window and window > 0:
            inflight = self._inflight
            while len(inflight) >= window:
                tok = inflight.popleft()
                ready = getattr(tok, "is_ready", None)
                if ready is None or not ready():
                    self.stats["blocking_syncs"] += 1
                    t0 = time.perf_counter_ns()
                    with RecordEvent("prepared::fetch_sync"):
                        jax.block_until_ready(tok)
                    self.stats["fetch_wait_ns"] += \
                        time.perf_counter_ns() - t0

        t0 = time.perf_counter_ns()
        try:
            with RecordEvent("prepared::dispatch"):
                fetches, state_out, new_key = step.fn(feed_vals, state_in,
                                                      rng_key)
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:
            # black box before the stack unwinds: which step died, on
            # which program, with what caches/flags live
            _flight.dump("prepared_step_exception", exc=e,
                         program=self._program,
                         extra={"step": sid,
                                "fetches": list(self._fetch_names)})
            raise
        self.stats["dispatch_ns"] += time.perf_counter_ns() - t0
        self.stats["steps"] += 1
        if self._donate_state:
            self._state = state_out
            self._dirty = True
        elif state_out:
            # read-only-state mode only round-trips persistables the
            # program actually writes; pass-through weights stay put
            self._state.update(state_out)
            self._dirty = True
        self._key = new_key
        if window and window > 0:
            self._inflight.append(new_key)
            if len(self._inflight) > self.stats["max_inflight"]:
                self.stats["max_inflight"] = len(self._inflight)

        if step.guard is not None:
            # split the non-donated guard scalar tail off the fetches
            # and queue it for a NON-blocking host poll.  The decode
            # (a device scalar read) runs every _GUARD_DECODE_EVERY
            # steps — skip counters are CUMULATIVE, so sampling the
            # newest completed step loses nothing — keeping the
            # per-step cost to a deque append + counter check (the
            # ≤5% stub-loop budget).  Blocking sync points (wait,
            # guard_info(sync=True)) always decode, so the budget abort
            # lags a burst by at most decode-period + window steps.
            gvals = fetches[len(self._fetch_names):]
            fetches = fetches[:len(self._fetch_names)]
            pend = self._guard_pending
            pend.append((sid, gvals, feed_vals, rng_key))
            self._guard_tick += 1
            if self._guard_tick >= _GUARD_DECODE_EVERY or \
                    len(pend) > _GUARD_PENDING_CAP:
                self._guard_tick = 0
                self._guard_poll(block=False)

        if flag("benchmark"):
            # per-step wall-clock mode: barrier covers fetches AND the
            # carried state + RNG key, like Executor.run's
            jax.block_until_ready((fetches, state_out, new_key))
        if flag("check_nan_inf"):
            self._exe._check_nan_inf(self._fetch_names, fetches, state_out)
        handles = [FetchHandle(v, n, self.stats)
                   for n, v in zip(self._fetch_names, fetches)]
        if return_numpy:
            return [h.numpy() for h in handles]
        return handles

    # -- guardrails -------------------------------------------------------
    def _guard_poll(self, block=False):
        """Decode the NEWEST completed guard tail into ``guard_stats``
        and enforce the consecutive-skip budget.  Older completed
        entries are discarded undecoded — every guard counter is
        cumulative, so the newest verdict subsumes them; this is what
        keeps the hot-loop cost amortized to a fraction of a device
        scalar read.  ``block=True`` (wait / guard_info(sync=True))
        drains everything dispatched.  Raises
        :class:`GuardrailViolation` (after dumping a flight bundle with
        replayable sidecars) when the budget is exhausted."""
        pend = self._guard_pending
        if not pend:
            return
        newest = None
        if block:
            newest = pend[-1]
            pend.clear()
        else:
            while pend:
                e = pend[0]
                ready = getattr(e[1][0], "is_ready", None)
                if ready is not None and not ready():
                    break
                newest = pend.popleft()
            if newest is None:
                return
        sid, gvals, feed_vals, rng_key = newest
        i = np.asarray(_fetch_numpy(gvals[0])).reshape(4)
        gs = self.guard_stats
        gs["steps"] = int(i[3])
        gs["last_skipped"] = bool(int(i[0]))
        gs["consecutive"] = int(i[1])
        gs["skipped_total"] = int(i[2])
        gs["step"] = sid
        try:
            # guardrail state on the scrape surface: operators watch the
            # skip counter without attaching a recorder (ROADMAP PR 14
            # follow-up; loss_scale lands in guard_info, its decoder)
            from ..observability import metrics as _obs_metrics
            _obs_metrics.gauge("guardrail::skipped_total").set(int(i[2]))
            _obs_metrics.gauge(
                "guardrail::consecutive_skipped").set(int(i[1]))
        except Exception:        # metrics must never break the hot loop
            pass
        # loss scale / probe decode deferred to guard_info (the f32 read
        # is only paid by consumers that want it)
        self._guard_f32 = gvals[1]
        policy = self._cur.guard if self._cur is not None else None
        budget = policy.max_skipped if policy is not None else 0
        if budget and int(i[1]) > budget:
            f = np.asarray(_fetch_numpy(gvals[1])).reshape(2)
            _guardrails.dump_abort_bundle(
                "guardrail_skip_budget_exhausted",
                program=self._program, step_id=sid,
                consecutive=int(i[1]), total=int(i[2]),
                probe=np.float32(f[0]), scale=float(f[1]),
                rng_key=rng_key, feed=feed_vals,
                step_counter=int(i[3]) - 1)
            from .errors import GuardrailViolation
            raise GuardrailViolation(
                f"non-finite step defense: {int(i[1])} consecutive "
                f"skipped steps exceed flag('max_skipped_steps')="
                f"{budget} at step {sid} — flight bundle dumped "
                f"(framework/guardrails.py)")

    def guard_info(self, sync=False) -> Dict[str, Any]:
        """Latest resolved guardrail facts (skipped/consecutive/loss
        scale) — the telemetry recorder's per-step source.  ``sync=True``
        blocks until every dispatched step's verdict is in."""
        self._guard_poll(block=sync)
        f32 = getattr(self, "_guard_f32", None)
        if f32 is not None:
            f = np.asarray(_fetch_numpy(f32)).reshape(2)
            self.guard_stats["loss_scale"] = float(f[1])
            self._guard_f32 = None
            try:
                from ..observability import metrics as _obs_metrics
                _obs_metrics.gauge("guardrail::loss_scale").set(
                    float(f[1]))
            except Exception:    # metrics must never break the hot loop
                pass
        return dict(self.guard_stats)

    # -- sync points ------------------------------------------------------
    def sync_scope(self):
        """Write the device-resident state (and RNG key) back into the
        Scope.  Cheap — dict writes of device arrays, no host transfer or
        device sync.  Called implicitly by Executor.run / io.save_* via
        ``sync_prepared_state``; call it yourself before reading state
        through the scope directly."""
        if not self._dirty:
            return
        from ..profiler import RecordEvent
        scope = self._scope
        with RecordEvent("prepared::scope_sync"):
            for n, v in self._state.items():
                scope.set_var(n, v)
            if self._key is not None:
                scope.set_var(_RNG_VAR, self._key)
        self._dirty = False
        self._scope_version = scope._version

    def wait(self):
        """Block until every dispatched step completed on device (state
        chains step-to-step, so the newest key is a full barrier)."""
        if self._key is not None:
            jax.block_until_ready(self._key)
        self._inflight.clear()
        self._guard_poll(block=True)
        if self._device_counters:
            self._fold_device_counters()
        return self

    def _fold_device_counters(self):
        """Add to ``stats`` what the program's device counters (int32
        persistables the step carries and adds to on the device, no fetch)
        gained since the last blocking point: ``stats[key] +=
        reduce(gain)`` for every declared pair.  The counters may wrap
        between two blocking points, their gains may not (2**31)."""
        state = self._state or {}
        for name, pairs in self._device_counters.items():
            if name not in state:
                continue
            now = np.asarray(state[name]).astype(np.uint32)
            gain = (now - self._counters_seen.get(
                name, np.zeros_like(now))).astype(np.int64)
            self._counters_seen[name] = now
            for key, reduce in pairs:
                self.stats[key] += reduce(gain)

    def close(self):
        self.sync_scope()
        self._scope._prepared.discard(self)
        self._steps.clear()
        self._cur = None
        self._cur_sig = None

    def drop_step(self, sig) -> bool:
        """Evict ONE compiled feed-signature variant (its executable and
        the executor's matching cache entry) while the rest stay hot —
        the per-bucket eviction lever ServingFleet's HBM admission uses.
        ``sig`` is a :meth:`_signature` tuple.  Returns False when no
        such variant is compiled."""
        step = self._steps.pop(sig, None)
        if step is None:
            return False
        if self._cur_sig == sig:
            self._cur = None
            self._cur_sig = None
            self._cur_check = []
        self._exe._evict_signature(self._program._uid, sig)
        return True

    # -- introspection ----------------------------------------------------
    def donation(self):
        """(donated_args, total_args) of the current step's lowered
        ``@main`` — the same ``tf.aliasing_output`` census
        tools/verify_multichip_lowering.donation_ratio reports for the
        multichip artifact, so prepared-step aliasing can be verified
        against it."""
        import re
        step = self._cur
        if step is None:
            raise RuntimeError("no step bound yet — run at least one step "
                               "(or prepare with an example feed)")
        state_src = self._state or {}
        abss = {}
        for n in step.state_in_names:
            v = state_src.get(n)
            if v is None:
                v = self._scope.find_var(n)
            if v is None and _guardrails.is_guard_var(n):
                v = _guardrails.init_value(n, step.guard)
            if not hasattr(v, "dtype"):
                v = np.asarray(v)
            abss[n] = jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
        key = self._key if self._key is not None else jax.random.PRNGKey(0)
        key_struct = jax.ShapeDtypeStruct(tuple(key.shape), key.dtype)
        txt = step.jit_fn.lower(self._feed_struct, abss,
                                key_struct).as_text()
        sig = re.search(r"func\.func public @main\((.*?)\)\s*->", txt,
                        re.DOTALL).group(1)
        return sig.count("tf.aliasing_output"), sig.count("tensor<")


class Executor:
    """User-facing executor (ref: python executor.py:896 Executor.run)."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place if place is not None else TPUPlace(0)
        self._device = _jax_device_for(self.place)
        self._cache: Dict[Any, _CompiledStep] = {}
        self._feed_cache = _FeedDeviceCache(self._device)

    # -- public API ------------------------------------------------------
    def run(self, program: Optional[Program] = None, feed=None,
            fetch_list=None, scope: Optional[Scope] = None,
            return_numpy: bool = True, use_prune: bool = False):
        program = program or default_main_program()
        scope = scope or global_scope()
        feed = feed or {}
        fetch_list = fetch_list or []

        if getattr(scope, "_prepared", None):
            # staleness guard: flush prepared fast-path state into the
            # scope before this run reads (and donates) it
            sync_prepared_state(scope)

        # CompiledProgram wrapper (data parallel etc.)
        from .compiler import CompiledProgram
        mesh = None
        axis_names = ()
        batch_axis = None
        seq_axis = None
        feed_specs = {}
        compiled_wrapper = None
        if isinstance(program, CompiledProgram):
            compiled_wrapper = program
            mesh = program._mesh
            axis_names = program._axis_names
            batch_axis = program._batch_axis
            seq_axis = program._seq_axis
            feed_specs = program._feed_specs
            program = program._program

        fetch_names = _fetch_names(fetch_list)

        # py_reader-backed programs: drain one batch per run into the
        # reader's data vars (the executor-side image of the reference's
        # in-graph `read` op popping the LoDTensorBlockingQueue,
        # ref: operators/reader/read_op.cc).  Default semantics match the
        # reference's Executor.run(use_prune=False): EVERY run executes
        # the whole program and consumes a batch.  ``use_prune=True``
        # (the reference's opt-in, executor.py use_prune) prunes to the
        # fetch targets, so an auxiliary fetch that doesn't depend on the
        # reader slots consumes nothing.
        readers = getattr(program, "_py_readers", ())
        if readers:
            slot_names = {v.name for r in readers for v in r.data_vars}
            if use_prune and fetch_names and not self._fetches_depend_on(
                    program, fetch_names, slot_names):
                program = self._pruned_for(program, fetch_list,
                                           fetch_names)
            else:
                for reader in readers:
                    if reader._started:
                        feed = dict(feed)   # don't mutate caller's dict
                        for k, v in reader._next_feed().items():
                            feed.setdefault(k, v)
                    else:
                        missing = [v.name for v in reader.data_vars
                                   if v.name not in feed]
                        if missing:
                            raise RuntimeError(
                                f"program reads py_reader "
                                f"{reader.name!r} slots {missing} but "
                                f"the reader is not started — call "
                                f"reader.start() (or feed the slots; "
                                f"ref: reader.py PyReader.start)")
        if compiled_wrapper is not None and compiled_wrapper._pending_passes:
            # strategy passes run against a clone per fetch list: fetched
            # intermediates are protected, and a later run with different
            # fetches sees the untouched original (no run-order dependence)
            program, evicted_uid = compiled_wrapper._variant_for(fetch_names)
            if evicted_uid is not None:
                self._evict_program(evicted_uid)
        feed = {k: np.asarray(v) if not hasattr(v, "dtype") else v
                for k, v in feed.items()}

        from ..profiler import RecordEvent
        from ..monitor import stat
        sid = _next_step_id()
        _flight.note_step(sid, "run", program._uid)
        with RecordEvent("executor::compile", program=program._uid,
                         version=program._version):
            step = self._compile(program, feed, fetch_names, scope, mesh,
                                 axis_names, batch_axis, seq_axis,
                                 feed_specs)

        state_in = {}
        for n in step.state_in_names:
            v = scope.find_var(n)
            if v is None:
                if _guardrails.is_guard_var(n):
                    v = _guardrails.init_value(n, step.guard)
                else:
                    raise RuntimeError(
                        f"persistable var {n!r} not initialised in scope — "
                        f"run the startup program first (ref semantics: "
                        f"executor.cc scope vars)")
            state_in[n] = v
        key = scope.find_var(_RNG_VAR)
        if key is None:
            key = jax.random.PRNGKey(program.random_seed)

        from ..flags import flag
        feed_vals = {k: feed[k] for k in step.feed_names}
        if mesh is None and flag("cache_feed_arrays"):
            for k, v in feed_vals.items():
                buf = self._feed_cache.lookup(v)
                if buf is not None:
                    feed_vals[k] = buf
        if step.spans_processes:
            # multi-host regime (ref: num_trainers>1): each process feeds
            # its LOCAL batch shard; lift everything to global jax.Arrays
            from jax.sharding import PartitionSpec as P
            feed_vals = {k: _to_global(mesh, step.feed_spec_fn(k), v,
                                       local_shard=True)
                         for k, v in feed_vals.items()}
            state_in = {n: _to_global(mesh, step.state_in_specs.get(n, P()),
                                      v)
                        for n, v in state_in.items()}
            key = _to_global(mesh, P(), key)
        used_fast_path = True
        with RecordEvent("executor::run"):
            try:
                if flag("check_nan_inf") and flag("check_nan_inf_per_op") \
                        and mesh is None:
                    used_fast_path = False
                    fetches, state_out, new_key = self._run_per_op_debug(
                        program, step, feed_vals, state_in, key,
                        fetch_names)
                else:
                    fetches, state_out, new_key = step.fn(feed_vals,
                                                          state_in, key)
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:
                _flight.dump("executor_run_exception", exc=e,
                             program=program,
                             extra={"step": sid,
                                    "fetches": list(fetch_names)})
                raise
            if flag("benchmark"):
                # ref: FLAGS_benchmark forces a device sync per run so
                # wall-clock timing is accurate; the barrier covers the
                # fetches AND the carried state + RNG key — a fetch-only
                # sync let state lag, and bench tools compensated by
                # blocking on the whole scope
                jax.block_until_ready((fetches, state_out, new_key))
        stat("executor_run_count").add()
        scope.set_var(_RNG_VAR, new_key)
        for n, v in state_out.items():
            scope.set_var(n, v)

        if step.guard is not None and used_fast_path:
            # split the non-donated guard scalar tail off the fetches
            # and enforce the consecutive-skip budget (slow path: a
            # scalar host read per run is fine here)
            gvals = fetches[len(fetch_names):]
            fetches = fetches[:len(fetch_names)]
            gd = _guardrails.decode_tail(_fetch_numpy(gvals[0]),
                                         _fetch_numpy(gvals[1]))
            cons = gd["consecutive"]
            budget = step.guard.max_skipped
            if budget and cons > budget:
                _guardrails.dump_abort_bundle(
                    "guardrail_skip_budget_exhausted", program=program,
                    step_id=sid, consecutive=cons,
                    total=gd["skipped_total"], probe=gd["probe"],
                    scale=gd["loss_scale"], rng_key=key,
                    feed={k: feed[k] for k in step.feed_names},
                    step_counter=gd["step_counter"] - 1)
                from .errors import GuardrailViolation
                raise GuardrailViolation(
                    f"non-finite step defense: {cons} consecutive "
                    f"skipped steps exceed flag('max_skipped_steps')="
                    f"{budget} — flight bundle dumped "
                    f"(framework/guardrails.py)")

        if flag("check_nan_inf"):
            # ref: FLAGS_check_nan_inf scans every op output
            # (framework/details/nan_inf_utils.h); here the whole block is
            # one XLA program, so the scan covers its observable outputs —
            # fetches and every persistable/state var — after each step
            self._check_nan_inf(fetch_names, fetches, state_out)

        if return_numpy:
            return [_fetch_numpy(f) for f in fetches]
        return list(fetches)

    def prepare(self, program: Optional[Program] = None, feed_names=None,
                fetch_list=None, scope: Optional[Scope] = None, feed=None,
                donate_state: bool = True):
        """Resolve ``program`` + ``fetch_list`` into a :class:`PreparedStep`
        whose ``run(feed)`` is the steady-state fast path (ref:
        Executor._prepare/ExecutorPrepareContext, executor.py:551, and the
        ParallelExecutor build-once/run-many contract).  Pass an example
        ``feed`` (shapes matter, values don't) to compile eagerly;
        otherwise compilation happens on the first ``run``.

        ``donate_state=False`` is the inference/serving mode: state is
        read-only for the compiled step (no buffer donation, no per-step
        state round-trip), so weights stay device-resident across
        requests and the scope remains the buffer owner."""
        program = program or default_main_program()
        scope = scope or global_scope()
        return PreparedStep(self, program, feed_names, fetch_list or [],
                            scope, feed=feed, donate_state=donate_state)

    def _evict_program(self, uid):
        """Drop compiled steps belonging to an evicted pass-variant clone."""
        self._cache = {k: v for k, v in self._cache.items() if k[0] != uid}

    def _evict_signature(self, uid, feed_sig):
        """Drop the compiled step(s) for ONE feed signature of a program
        (PreparedStep.drop_step's executor-cache half)."""
        self._cache = {k: v for k, v in self._cache.items()
                       if not (k[0] == uid and k[2] == feed_sig)}

    def _run_per_op_debug(self, program, step, feed_vals, state_in, key,
                          fetch_names):
        """Eager op-by-op execution that names the op producing the first
        NaN/Inf (FLAGS_check_nan_inf_per_op) — the analog of the
        reference's per-op scan (ref: framework/details/nan_inf_utils.h);
        here the production step is one fused XLA program, so localization
        runs the ops un-jitted instead.  Backward is one meta-op, so a
        NaN born inside autodiff is attributed at backward granularity."""
        block = program.global_block()
        ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
        bw_idx = next((i for i, op in enumerate(ops)
                       if op.type == "backward"), None)
        ctx = LoweringContext(key, None, (), program._is_test)
        env = dict(state_in)
        env.update(feed_vals)

        def check(op, names_vals):
            for n, v in names_vals:
                a = np.asarray(v)
                if np.issubdtype(a.dtype, np.floating) and \
                        not np.isfinite(a).all():
                    raise RuntimeError(
                        f"Operator {op.type!r} output {n!r} contains "
                        f"NaN/Inf (FLAGS_check_nan_inf per-op mode; ref: "
                        f"nan_inf_utils_detail PrintNanInf)")

        def run_one(op):
            impl = get_op(op.type)
            outs = impl(ctx, _gather_inputs(op, env), op.attrs)
            _scatter_outputs(op, outs, env)
            check(op, [(n, env[n]) for n in op.output_names()
                       if n in env])

        fwd_end = bw_idx if bw_idx is not None else len(ops)
        for op in ops[:fwd_end]:
            run_one(op)
        if bw_idx is not None:
            bw_op = ops[bw_idx]
            env2 = lower_block_with_backward(
                ops[:bw_idx + 1], dict(env), ctx, bw_idx, fetch_names,
                step.state_out_names)
            grad_checks = [(grad_var_name(n), env2[grad_var_name(n)])
                           for n in bw_op.attrs["param_names"]
                           if grad_var_name(n) in env2]
            check(bw_op, grad_checks)
            env = env2
            for op in ops[bw_idx + 1:]:
                run_one(op)
        fetches = [np.asarray(env[n]) for n in fetch_names]
        state_out = {n: env[n] for n in step.state_out_names if n in env}
        return fetches, state_out, ctx.key

    @staticmethod
    def _check_nan_inf(fetch_names, fetches, state_out):
        bad = []
        multihost = False
        for n, v in list(zip(fetch_names, fetches)) + list(state_out.items()):
            if _guardrails.is_guard_var(n):
                # the guard's own probe is DESIGNED to carry the NaN;
                # the skip machinery already handled the step
                continue
            if isinstance(v, jax.Array) and not v.is_fully_addressable:
                # multi-host array: scan the shards this process owns
                multihost = True
                arrs = [np.asarray(s.data) for s in v.addressable_shards]
            else:
                arrs = [np.asarray(v)]
            for a in arrs:
                if np.issubdtype(a.dtype, np.floating) and \
                        not np.isfinite(a).all():
                    bad.append(n)
                    break
        if multihost:
            # agree across processes so ALL ranks raise together — a
            # one-sided raise would leave the healthy ranks blocked in the
            # next step's collective
            from jax.experimental import multihost_utils
            all_bad = multihost_utils.process_allgather(
                np.asarray(len(bad), np.int32))
            if int(np.sum(all_bad)) and not bad:
                bad = ["<on another host>"]
        if bad:
            _flight.dump("non_finite_output",
                         extra={"bad_vars": list(bad)})
            raise RuntimeError(
                f"Operator output contains NaN/Inf (FLAGS_check_nan_inf): "
                f"{bad} (ref: nan_inf_utils_detail PrintNanInf)")

    # -- dataset training (ref: executor.py:1479 train_from_dataset →
    # TrainerDesc/DeviceWorker C++ threads; here the native datafeed
    # assembles batches behind a channel and ONE compiled XLA step
    # consumes them — thread-per-core hogwild doesn't map to a TPU, the
    # parallelism lives inside the compiled step) ------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           drop_last=True):
        return self._run_from_dataset(program, dataset, scope, fetch_list,
                                      fetch_info, print_period, debug,
                                      drop_last)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           drop_last=False):
        return self._run_from_dataset(program, dataset, scope, fetch_list,
                                      fetch_info, print_period, debug,
                                      drop_last)

    def _run_from_dataset(self, program, dataset, scope, fetch_list,
                          fetch_info, print_period, debug, drop_last):
        if dataset is None:
            raise ValueError("dataset must be provided")
        fetch_list = fetch_list or []
        fetch_info = fetch_info or _fetch_names(fetch_list)
        step = 0
        last = None
        # feed dicts may include '<slot>.lens' vars the program doesn't
        # declare — drop those (programs opt in by declaring them)
        prog = program or default_main_program()
        from .compiler import CompiledProgram
        raw_prog = (prog._program if isinstance(prog, CompiledProgram)
                    else prog)
        block = raw_prog.global_block()
        dumper = _FieldDumper(raw_prog, scope or global_scope())
        # dump fields are fetched in full, AFTER the user's fetch_list —
        # a name in both is fetched twice (same traced value, no extra
        # compute) so after_step's zip stays aligned with field_names
        run_fetches = list(fetch_list) + dumper.field_names
        for feed in dataset._iter_feed_dicts(drop_last=drop_last):
            feed = {k: v for k, v in feed.items() if block.has_var(k)}
            # fetches stay device-resident between print points so the
            # loop pipelines (dispatch step N+1 while N computes) instead
            # of forcing a device→host sync every step — the DeviceWorker
            # only materialises fetch_vars at print_period too
            # (ref: device_worker.cc PrintFetchVars cadence)
            last = self.run(prog, feed=feed, fetch_list=run_fetches,
                            scope=scope, return_numpy=False)
            dumper.after_step(step, last[len(fetch_list):])
            last = last[:len(fetch_list)]
            step += 1
            if fetch_list and (debug or step % print_period == 0):
                vals = ", ".join(f"{n}={_fetch_numpy(v).ravel()[:4]}"
                                 for n, v in zip(fetch_info, last))
                print(f"[train_from_dataset] step {step}: {vals}")
        dumper.close()
        if last is not None:
            last = [_fetch_numpy(v) for v in last]
        return last

    # -- py_reader support ----------------------------------------------
    def _fetches_depend_on(self, program, fetch_names, slot_names):
        """Do the fetch targets transitively read any reader slot?
        Cached per (program uid, version, fetches)."""
        key = (program._uid, program._version, tuple(fetch_names))
        cache = self.__dict__.setdefault("_dep_cache", {})
        if key not in cache:
            needed = set(fetch_names)
            for op in reversed(program.global_block().ops):
                if set(op.output_names()) & needed:
                    needed |= set(op.input_names())
            cache[key] = bool(needed & slot_names)
        return cache[key]

    def _pruned_for(self, program, fetch_list, fetch_names):
        """Program pruned to the fetch targets (reader-free auxiliary
        runs), cached per (uid, version, fetches)."""
        key = (program._uid, program._version, tuple(fetch_names))
        cache = self.__dict__.setdefault("_prune_cache", {})
        if key not in cache:
            cache[key] = program._prune(list(fetch_list))
        return cache[key]

    # -- compilation -----------------------------------------------------
    def _feed_signature(self, feed):
        return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in feed.items()))

    def _compile(self, program, feed, fetch_names, scope, mesh, axis_names,
                 batch_axis, seq_axis=None, feed_specs=None,
                 donate_state=True):
        from ..flags import flag
        # flags consulted at trace time are part of the executable identity
        key = (program._uid, program._version, self._feed_signature(feed),
               tuple(fetch_names), _mesh_identity(mesh),
               flag("use_flash_attention"), flag("use_pallas_fused"),
               flag("overlap_lowering"),
               flag("guard_nonfinite"), flag("guard_loss_scale"),
               _faultline.epoch(),
               donate_state, str(flag("aot_cache_dir") or ""))
        if key in self._cache:
            if flag("print_executor_cache_hits"):
                print(f"executor cache hit: program v{program._version}")
            return self._cache[key]
        _compile_t0 = time.perf_counter_ns()
        if flag("hbm_budget_gb"):
            # static pre-compile budget gate (memory_analysis.py): an
            # over-budget program is rejected HERE, with the top live
            # tensors and their creation sites, before any trace/compile
            # cost — feed shapes are exact at this point
            from .memory_analysis import check_hbm_budget, mesh_axes_of
            check_hbm_budget(program, feed_shapes=feed,
                             fetch_names=fetch_names,
                             mesh_axes=mesh_axes_of(mesh),
                             batch_axis=batch_axis, seq_axis=seq_axis,
                             feed_specs=feed_specs,
                             donate_state=donate_state)
        from ..monitor import stat

        block = program.global_block()
        ops = [op for op in block.ops if op.type not in ("feed", "fetch")]

        feed_names = sorted(feed)
        written: set = set()
        state_in_names: List[str] = []
        for op in ops:
            for n in op.input_names():
                if n in written or n in feed_names or n in state_in_names:
                    continue
                var = block._find_var_recursive(n)
                # vars declared only in sub-blocks (e.g. params created inside
                # a StaticRNN/while step block) aren't visible from the global
                # block, but live in the scope after the startup program ran
                if (var is not None and var.persistable) or \
                        scope.find_var(n) is not None:
                    state_in_names.append(n)
            written |= set(op.output_names())
        # fetch of a persistable that no op writes (e.g. fetch a param)
        for n in fetch_names:
            if n not in written and n not in feed_names and \
                    n not in state_in_names:
                state_in_names.append(n)

        written_state: List[str] = []
        for op in ops:
            for n in op.output_names():
                var = block._find_var_recursive(n)
                if var is not None and var.persistable and \
                        n not in written_state:
                    written_state.append(n)
        if donate_state:
            # every state input must come back out (read-only vars pass
            # through unchanged) — their buffers are donated, so the scope
            # must be handed fresh (aliased) arrays or it would retain
            # deleted buffers
            state_out_names = list(state_in_names)
            state_out_names += [n for n in written_state
                                if n not in state_out_names]
        else:
            # read-only-state mode: pass-through state is dropped from the
            # outputs entirely — no donation means returning it would force
            # a full device copy of the weights per request
            state_out_names = written_state

        bw_idx = next((i for i, op in enumerate(ops)
                       if op.type == "backward"), None)
        # device-chained decode (serving/decode.py): the marker op turns
        # the whole step into a chain_length-long lax.scan of the body
        chain_idx = next((i for i, op in enumerate(ops)
                          if op.type == "decode_chain"), None)
        chain_pools = frozenset(written_state) if chain_idx is not None \
            else frozenset()
        is_test = program._is_test
        replicated_names = _replicated_var_names(ops, bw_idx)

        # self-healing step runtime (framework/guardrails.py): resolve
        # the guard policy for this compile; active, it threads extra
        # reserved state (step/skip/scale counters) through the step and
        # appends a non-donated guard fetch tail the host polls
        guard = None
        no_gate: List[str] = []
        if bw_idx is not None and donate_state:
            bw_attrs = ops[bw_idx].attrs
            pipelined = int(bw_attrs.get("pipe_microbatches") or 1) > 1 \
                or int(bw_attrs.get("pipe_stages") or 1) > 1
            guard = _guardrails.active_policy(
                True, amp_scale_var=bw_attrs.get("loss_scale_var"),
                pipelined=pipelined)
        if guard is not None:
            for n in _guardrails.STATE_VARS:
                if n not in state_in_names:
                    state_in_names.append(n)
                if n not in state_out_names:
                    state_out_names.append(n)
            # the AMP scale-policy state must ADVANCE on a bad step —
            # backoff is the response, not a casualty of the gate
            no_gate = [n for op in ops if op.type == "update_loss_scaling"
                       for n in op.output_names()]

        def step(feed_vals, state_vals, rng_key):
            # distinct randomness per data/sequence shard (dropout masks must
            # differ across devices, as each device has a different NCCL-rank
            # curand seed in the reference) — but NOT across tp/pp, where
            # activations are replicated and masks must agree; the carried
            # key advances from the replicated base so state stays replicated
            from .mesh_layout import _flat_axes
            fold_axes = [a for a in _flat_axes(batch_axis) + (seq_axis,)
                         if a and a in axis_names]
            if mesh is not None and fold_axes:
                shard_key = rng_key
                for a in fold_axes:
                    shard_key = jax.random.fold_in(
                        shard_key, jax.lax.axis_index(a))
                next_base = jax.random.split(rng_key, 1)[0]
            else:
                shard_key, next_base = rng_key, None
            ctx = LoweringContext(shard_key, mesh, axis_names, is_test)
            ctx.guard = guard
            env = {}
            env.update(state_vals)
            env.update(feed_vals)
            if chain_idx is not None:
                env = lower_decode_chain(ops, chain_idx, env, ctx,
                                         chain_pools)
            elif bw_idx is None:
                env = run_ops(ops, env, ctx)
            else:
                env = lower_block_with_backward(
                    ops, env, ctx, bw_idx, fetch_names, state_out_names)
            fetches = [_merge_fetch(env[n], n, block, ctx, batch_axis,
                                    replicated_names, seq_axis)
                       for n in fetch_names]
            if guard is not None:
                # gate every written persistable on the fused finite
                # verdict (bitwise no-op step on NaN/Inf) and append the
                # guard scalars as NON-donated fetch outputs so the host
                # can poll skip state without touching the state chain
                state_out, guard_tail = _guardrails.guarded_state_out(
                    env, state_vals, state_out_names,
                    axis_names if mesh is not None else (), guard,
                    no_gate)
                fetches = list(fetches) + guard_tail
            else:
                state_out = {n: env[n] for n in state_out_names}
            return fetches, state_out, \
                (next_base if next_base is not None else ctx.key)

        from ..ops.registry import HOST_OPS
        host_idxs = [i for i, op in enumerate(ops) if op.type in HOST_OPS]
        if host_idxs:
            # PS-tier programs: host RPC ops (ps_send/ps_recv/
            # listen_and_serv/...) cannot live inside jit.  They sit before
            # the forward or after the backward by construction
            # (transpiler), so the step runs unjitted: jax ops execute
            # eagerly, host ops do RPC — the reference's op-loop semantics
            # (executor.cc:465 interleaves compute and RPC ops the same way)
            if bw_idx is not None and any(i < bw_idx for i in host_idxs):
                raise NotImplementedError(
                    "host ops inside the differentiated forward section "
                    "are not supported — pull host data before the step "
                    "(FleetWrapper pattern, ref: downpour_worker.cc:726)")
            if mesh is not None:
                raise NotImplementedError(
                    "PS host ops with a device mesh in one program are "
                    "unsupported; PS data-parallelism is multi-process")
            fn = step
        feed_spec_fn = None
        state_in_specs = None
        jit_fn = None
        fresh_trace = True          # False only on an AOT-cache disk hit
        if not host_idxs:
            if mesh is not None:
                fn, feed_spec_fn, state_in_specs = self._wrap_sharded(
                    step, mesh, axis_names, batch_axis, program, feed_names,
                    state_in_names, state_out_names, feed_specs or {},
                    donate_state=donate_state)
            else:
                jit_fn = jax.jit(step, donate_argnums=(1,)) if donate_state \
                    else jax.jit(step)
                fn = jit_fn
                aot_dir = str(flag("aot_cache_dir") or "")
                if aot_dir:
                    # persistent AOT executable cache: a restarted process
                    # deserializes the executable (~ms) instead of paying
                    # the trace+compile — the serving warm-restart path
                    loaded, fresh_trace = self._aot_resolve(
                        aot_dir, jit_fn, program, feed, feed_names,
                        fetch_names, scope, state_in_names, donate_state)
                    if loaded is not None:
                        fn = loaded
        if fresh_trace:
            stat("executor_compile_count").add()
        # wall time of the cold resolution path (trace/compile/AOT load)
        # — the telemetry recorder diffs this into per-step compile-stall
        # attribution (goodput accounting)
        stat("executor_compile_ns").add(time.perf_counter_ns() - _compile_t0)
        _flight.note_event("compile", program=program._uid,
                           fresh=fresh_trace)

        compiled = _CompiledStep(fn, state_in_names, state_out_names,
                                 feed_names, fetch_names, raw_fn=step,
                                 mesh=mesh, feed_spec_fn=feed_spec_fn,
                                 state_in_specs=state_in_specs,
                                 jit_fn=jit_fn, guard=guard)
        self._cache[key] = compiled
        return compiled

    def lower_for_audit(self, program, feed, fetch_names, scope,
                        mesh=None, axis_names=(), batch_axis=None,
                        seq_axis=None, feed_specs=None,
                        donate_state=True):
        """Lower the step ONCE for the differential spec auditor
        (framework/spec_audit.py): the exact executable path
        ``_compile`` builds (sharded wrap, guardrails, donation), traced
        but NOT executed.  Returns ``(step, lowered)`` —
        ``lowered.as_text()`` is the pre-compile StableHLO the wire
        census parses; whether to pay ``lowered.compile()`` (the
        cost/memory-analysis tiers) is the caller's choice.  Reuses the
        executor's compile cache, so auditing a program the executor
        already ran costs only the ``.lower`` trace."""
        step = self._compile(program, feed, fetch_names, scope, mesh,
                             tuple(axis_names), batch_axis,
                             seq_axis=seq_axis, feed_specs=feed_specs,
                             donate_state=donate_state)
        state = {n: np.asarray(scope.find_var(n))
                 for n in step.state_in_names}
        lowered = step.fn.lower({k: feed[k] for k in step.feed_names},
                                state, jax.random.PRNGKey(0))
        return step, lowered

    def _aot_resolve(self, cache_dir, jit_fn, program, feed, feed_names,
                     fetch_names, scope, state_in_names, donate_state):
        """Disk-backed executable resolution for single-device compiles
        (``flag("aot_cache_dir")``).  Returns ``(callable_or_None,
        fresh_trace)``: a cache hit deserializes the stored executable
        (no trace, no compile — ``fresh_trace=False``); a miss lowers and
        compiles eagerly at this exact feed/state signature, persists the
        result atomically, and returns the live ``jax.stages.Compiled``.
        Any serialization gap (backend without PJRT executable
        serialization, uninitialised state vars) degrades to the plain
        jitted path — the cache can never cost correctness."""
        from . import aot_cache
        from ..flags import flag

        feed_sig = self._feed_signature(feed)
        trace_flags = (flag("use_flash_attention"),
                       flag("use_pallas_fused"),
                       flag("overlap_lowering"),
                       flag("guard_nonfinite"), flag("guard_loss_scale"),
                       _faultline.epoch())
        devices = (self._device,)
        key = aot_cache.entry_key(program, feed_sig, fetch_names,
                                  donate_state, trace_flags, devices)
        cached = aot_cache.load(cache_dir, key, devices)
        if cached is not None:
            return cached, False

        on_device = jax.sharding.SingleDeviceSharding(self._device)

        def _struct(v):
            if not hasattr(v, "shape") or not hasattr(v, "dtype"):
                v = np.asarray(v)
            return jax.ShapeDtypeStruct(
                tuple(v.shape), jax.dtypes.canonicalize_dtype(v.dtype),
                sharding=on_device)

        state_structs = {}
        for n in state_in_names:
            v = scope.find_var(n)
            if v is None:
                # shapes unknown until the startup program runs — skip
                # the cache for this compile rather than guess
                return None, True
            state_structs[n] = _struct(v)
        rng = scope.find_var(_RNG_VAR)
        if rng is None:
            rng = jax.random.PRNGKey(program.random_seed)
        try:
            compiled = jit_fn.lower(
                {k: _struct(feed[k]) for k in feed_names},
                state_structs, _struct(rng)).compile()
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException:
            # counted, not silent: the lazy jit path recompiles and
            # surfaces a real error; restart legs assert the counter
            # stayed 0
            from ..monitor import stat
            stat("aot_cache_error").add()
            return None, True
        aot_cache.store(cache_dir, key, compiled,
                        meta={"fetches": list(fetch_names),
                              "feed_sig": [list(map(str, i))
                                           for i in feed_sig],
                              "donate_state": bool(donate_state)})
        return compiled, True

    def _wrap_sharded(self, step, mesh, axis_names, batch_axis, program,
                      feed_names, state_in_names, state_out_names,
                      feed_specs, donate_state=True):
        """Run the step under shard_map over the FULL named mesh: feeds
        sharded on their batch (dp) / sequence (sp) dims, params per their
        ``dist_attr`` PartitionSpec (tensor-parallel shards), everything
        else replicated.  Collective ops inside (c_allreduce_sum inserted by
        the collective transpiler, ref: transpiler/collective.py:209; the
        Megatron f/g pair from parallel/tp_layers.py) become XLA collectives
        over the corresponding ICI axes."""
        from jax.sharding import PartitionSpec as P

        def var_spec(name):
            from .mesh_layout import ShardSpec
            for b in program.blocks:
                v = b.vars.get(name)
                if v is not None:
                    da = ShardSpec.coerce(getattr(v, "dist_attr", None))
                    if da:
                        # axes absent from THIS mesh replicate: a program
                        # annotated for tp may run on an sp/dp-only mesh
                        # (the collectives degrade to identity the same
                        # way), so dangling axis names must not leak into
                        # shard_map specs.  Entries may be axis TUPLES
                        # (one dim over fsdp×tp) — filtered member-wise.
                        return P(*da.mesh_entries(axis_names))
                    return P()
            return P()

        def feed_spec(name):
            if name in feed_specs:
                s = feed_specs[name]
                return s if isinstance(s, P) else P(*s)
            # default: batch dim sharded over dp (feeds replicated when the
            # mesh has no data-parallel axis, e.g. pure tp/pp programs)
            return P(batch_axis) if batch_axis else P()

        state_in_specs = {n: var_spec(n) for n in state_in_names}
        state_out_specs = {n: var_spec(n) for n in state_out_names}

        def sharded(feed_vals, state_vals, rng_key):
            in_specs = ({k: feed_spec(k) for k in feed_vals},
                        {k: state_in_specs[k] for k in state_vals}, P())
            # fetches are merged to replicated inside the step; state keeps
            # its (possibly tp-sharded) layout
            fn = jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                               out_specs=(P(), state_out_specs, P()),
                               check_vma=False)
            return fn(feed_vals, state_vals, rng_key)

        # explicit GSPMD shardings on the jit boundary: without them XLA
        # cannot prove the donated state buffers alias their outputs and
        # silently DROPS the aliasing under shard_map — the multichip
        # census artifact showed arg donation 0/N until r07.  With
        # in+out shardings pinned to the shard_map specs, state donation
        # is live on the mesh path too (tf.aliasing_output per state arg)
        from jax.sharding import NamedSharding

        def ns(spec):
            return NamedSharding(mesh, spec)

        in_sh = ({k: ns(feed_spec(k)) for k in feed_names},
                 {n: ns(state_in_specs[n]) for n in state_in_names},
                 ns(P()))
        out_sh = (ns(P()),
                  {n: ns(state_out_specs[n]) for n in state_out_names},
                  ns(P()))
        fn = jax.jit(sharded,
                     donate_argnums=(1,) if donate_state else (),
                     in_shardings=in_sh, out_shardings=out_sh)
        return fn, feed_spec, state_in_specs

    def close(self):
        self._cache.clear()


__all__ = ["Executor", "Scope", "global_scope", "scope_guard",
           "PreparedStep", "FetchHandle", "sync_prepared_state"]
