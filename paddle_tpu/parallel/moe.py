"""Mixture-of-Experts layer API — expert parallelism over a mesh axis.

The reference framework has no MoE (SURVEY §2.3 lists expert parallelism
as the one strategy it lacks); this is a new TPU-native capability built
on the GShard layout: experts are sharded over the same mesh axis that
shards the batch (every device contributes tokens AND owns E/ep experts),
token exchange is one ``c_expert_alltoall`` each way riding ICI, and all
routing math is dense einsums on the MXU (ops/moe_ops.py).

The layer emits the DECOMPOSED pipeline

    moe_dispatch → [c_expert_alltoall] → moe_expert_ffn
                 → [c_expert_alltoall] → moe_combine

so the expert exchange is a registry-visible collective: the wire model
prices it per-config, spec_audit reconciles it against the StableHLO
census, and a ``quant_spec`` (CompressionSpec tier) compresses it on the
wire.  The exchange ops exist only when ``ep > 1`` — a dense build stays
collective-free (verify_inference contract) and can be retrofitted for
any expert degree by :func:`apply_expert_sharding` (the planner path).

Usage::

    out, aux = parallel.moe_ffn(x, num_experts=8, ffn_hidden=256,
                                ep_degree=4, axis_name="ep")
    loss = task_loss + 0.01 * aux
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from ..framework.layer_helper import LayerHelper
from ..framework.core import Block, Variable, grad_var_name
from ..framework.mesh_layout import MeshLayout, ShardSpec

EXCHANGE_SUFFIX = "@ep_exch"


def _quant_attr(quant_spec):
    """Normalize a CompressionSpec | dict | dtype-str to the plain-dict
    attr form collective ops carry (None passes through)."""
    if quant_spec is None:
        return None
    from ..ops.quantize_wire import CompressionSpec
    return CompressionSpec.from_attr(quant_spec).to_attr()


def _suffixed(attr, suffix):
    """One shared param_attr names several params — suffix each."""
    from ..framework.layer_helper import ParamAttr
    a = ParamAttr._to_attr(attr)
    if a and getattr(a, "name", None):
        import copy
        a = copy.copy(a)
        a.name = f"{a.name}_{suffix}"
    return a


def moe_ffn(x: Variable, num_experts: int, ffn_hidden: int,
            top_k: int = 2, capacity_factor: float = 1.25,
            ep_degree: Optional[int] = None, axis_name: str = "ep",
            act: str = "gelu", group_size: int = 0, param_attr=None,
            bias_attr=None, quant_spec=None,
            name: Optional[str] = None) -> Tuple[Variable, Variable]:
    """MoE feed-forward block: route each token to its top-k of
    ``num_experts`` expert FFNs (M → ffn_hidden → M).

    With ``ep_degree`` > 1 the expert dim of both weights is sharded over
    ``axis_name`` (dist_attr consumed by the executor's shard_map) and a
    ``c_expert_alltoall`` pair moves token blocks to their owners —
    optionally wire-compressed by ``quant_spec`` (bf16/int8/int4
    CompressionSpec tier).  Returns ``(out, aux_loss)`` — add
    ``aux_weight * aux_loss`` to the training loss (Switch-Transformer
    load-balance term)."""
    ep = int(ep_degree or 1)
    if num_experts % ep:
        raise ValueError(
            f"num_experts {num_experts} not divisible by ep degree {ep}")
    helper = LayerHelper(name or "moe_ffn", name=name)
    m = int(x.shape[-1])

    gate_w = helper.create_parameter(_suffixed(param_attr, "gate"),
                                     [m, num_experts], x.dtype)
    w1 = helper.create_parameter(_suffixed(param_attr, "w1"),
                                 [num_experts, m, ffn_hidden], x.dtype)
    w2 = helper.create_parameter(_suffixed(param_attr, "w2"),
                                 [num_experts, ffn_hidden, m], x.dtype)
    if ep > 1:
        # expert dim sharded; grads arrive pre-summed through the
        # transposed all_to_all (compiler skips the allreduce over this
        # axis but keeps the 1/n mean-loss scale)
        w1.dist_attr = ShardSpec((axis_name, None, None))
        w2.dist_attr = ShardSpec((axis_name, None, None))
    ffn_inputs: Dict[str, list] = {"W1": [w1], "W2": [w2]}
    if bias_attr is not False:
        b1 = helper.create_parameter(_suffixed(bias_attr, "b1"),
                                     [num_experts, ffn_hidden], x.dtype,
                                     is_bias=True)
        b2 = helper.create_parameter(_suffixed(bias_attr, "b2"),
                                     [num_experts, m], x.dtype, is_bias=True)
        if ep > 1:
            b1.dist_attr = ShardSpec((axis_name, None))
            b2.dist_attr = ShardSpec((axis_name, None))
        ffn_inputs["B1"], ffn_inputs["B2"] = [b1], [b2]

    from ..ops.moe_ops import _moe_static_dims
    _, g, sg, cap = _moe_static_dims(x.shape, num_experts, top_k,
                                     capacity_factor, group_size)
    gc = g * cap if (g > 0 and cap > 0) else -1

    xe = helper.create_variable_for_type_inference(
        x.dtype, [num_experts, gc, m])
    comb = helper.create_variable_for_type_inference(
        "float32", [g, sg, num_experts, cap])
    aux = helper.create_variable_for_type_inference("float32", ())
    helper.append_op(
        type="moe_dispatch", inputs={"X": [x], "GateW": [gate_w]},
        outputs={"Xe": [xe], "Combine": [comb], "AuxLoss": [aux]},
        attrs={"num_experts": num_experts, "top_k": top_k,
               "capacity_factor": capacity_factor,
               "group_size": group_size})

    qattr = _quant_attr(quant_spec)
    cur = xe
    if ep > 1:
        ex = helper.create_variable_for_type_inference(
            x.dtype, [num_experts, gc, m])
        helper.append_op(
            type="c_expert_alltoall", inputs={"X": [cur]},
            outputs={"Out": [ex]},
            attrs={"ring_id": 0, "_axis_name": axis_name,
                   "direction": "dispatch", "quant_spec": qattr})
        cur = ex

    ye = helper.create_variable_for_type_inference(
        x.dtype, [num_experts, gc, m])
    helper.append_op(
        type="moe_expert_ffn", inputs=dict(ffn_inputs, Xe=[cur]),
        outputs={"Out": [ye]}, attrs={"act": act})

    cur = ye
    if ep > 1:
        ex = helper.create_variable_for_type_inference(
            x.dtype, [num_experts, gc, m])
        helper.append_op(
            type="c_expert_alltoall", inputs={"X": [cur]},
            outputs={"Out": [ex]},
            attrs={"ring_id": 0, "_axis_name": axis_name,
                   "direction": "combine", "quant_spec": qattr})
        cur = ex

    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(
        type="moe_combine",
        inputs={"Ye": [cur], "Combine": [comb], "X": [x]},
        outputs={"Out": [out]}, attrs={})
    # record on the program being built (same lifetime as the graph) so
    # model builders can fold every routed block's balance term into the
    # loss without threading lists through their call stacks
    collect_aux_losses(helper.main_program, peek=True).append(aux)
    return out, aux


def moe_dropless_ffn(x: Variable, num_experts: int, ffn_hidden: int,
                     top_k: int, held_experts: Optional[Tuple[int, int]]
                     = None, norm_topk_prob: bool = True, param_attr=None,
                     name: Optional[str] = None, scoring: str = "softmax",
                     n_group: int = 1, topk_group: int = 1,
                     routed_scale: float = 1.0, shared_hidden: int = 0,
                     bias_attr=None, counter_tag=None) -> Variable:
    """Dropless SiLU-gated expert block: ``softmax(x W_r)`` over ALL
    ``num_experts`` without a capacity, the ``top_k`` largest
    (renormalised under ``norm_topk_prob``), and
    ``sum_k w_k (silu(x W_g) * x W_u) W_d`` over the experts HELD HERE,
    ``held_experts = (lo, hi)`` — what every chip of an expert-parallel
    layer computes before the exchange adds the parts; ``None`` holds
    them all.  Assignments to experts held elsewhere are skipped; none to
    a held expert is ever dropped.  Per-expert counts accumulate on the
    device in a persistable counter that ``PreparedStep.stats`` reads
    (``moe_assignments_local``, ``moe_expert_load_max|mean``).

    ``scoring="sigmoid"`` routes as the DeepSeek-V3 family does: sigmoid
    scores, a selection-only bias ``b`` (``bias_attr``; it chooses, it
    never weighs), ``n_group`` groups of which the ``topk_group`` best
    stay (ops/decoder_lm_ops.py).  Under either scoring the weights are
    times ``routed_scale`` (a softmax router at 1.0 carries no such
    attr, so its program is the one it was before the scale existed).
    ``shared_hidden`` adds a SHARED expert of that width which every
    token visits: computed once on the whole input, not through the
    sort, and held by every chip alike (so over the shares of an
    expert-parallel layer it counts once).  ``counter_tag`` names the
    load counter of THIS program (programs that share a scope — a decode
    engine's prefill, chunk and chain — count apart), which then also
    counts the held experts with at least one assignment a step;
    ``False`` builds no counter (a served decoder's score program runs
    in the reference scope, which holds weights and no engine state)."""
    from ..framework.initializer import ConstantInitializer
    from ..ops.decoder_lm_ops import LOAD_STATS_EXTRA
    lo, hi = held_experts if held_experts is not None else (0, num_experts)
    if not 0 <= lo < hi <= num_experts:
        raise ValueError(f"held_experts {held_experts} outside "
                         f"0..{num_experts}")
    helper = LayerHelper(name or "moe_dropless_ffn", name=name)
    d, e_local = int(x.shape[-1]), hi - lo

    def param(suffix, shape):
        return helper.create_parameter(_suffixed(param_attr, suffix), shape,
                                       x.dtype)

    router_w = param("router_w", [d, num_experts])
    wg = param("expert_gate_w", [e_local, d, ffn_hidden])
    wu = param("expert_up_w", [e_local, d, ffn_hidden])
    wd = param("expert_down_w", [e_local, ffn_hidden, d])
    weight = helper.create_variable_for_type_inference("float32",
                                                       (-1, top_k))
    index = helper.create_variable_for_type_inference(
        "int32", (-1, top_k), stop_gradient=True)
    router_in = {"X": [x], "W": [router_w]}
    router_attrs = {"top_k": top_k, "norm_topk_prob": norm_topk_prob}
    if scoring == "softmax" and routed_scale != 1.0:
        router_attrs["routed_scale"] = float(routed_scale)
    if scoring != "softmax":
        router_in["Bias"] = [helper.create_parameter(
            _suffixed(bias_attr if bias_attr is not None else param_attr,
                      "router_bias"), [num_experts], "float32",
            default_initializer=ConstantInitializer(0.0))]
        router_attrs.update(scoring=scoring, n_group=n_group,
                            topk_group=topk_group,
                            routed_scale=float(routed_scale))
    helper.append_op(
        type="moe_topk_router", inputs=router_in,
        outputs={"TopkWeight": [weight], "TopkIndex": [index]},
        attrs=router_attrs)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    count = helper.create_variable_for_type_inference(
        "int32", (e_local,), stop_gradient=True)
    helper.append_op(
        type="moe_grouped_ffn",
        inputs={"X": [x], "TopkWeight": [weight], "TopkIndex": [index],
                "WGate": [wg], "WUp": [wu], "WDown": [wd]},
        outputs={"Out": [out], "ExpertCount": [count]},
        attrs={"expert_offset": lo, "num_experts": num_experts})
    if shared_hidden:
        from .. import layers

        def shared(suffix, size, inp):
            return layers.fc(inp, size, num_flatten_dims=len(x.shape) - 1,
                             param_attr=_suffixed(param_attr, suffix),
                             bias_attr=False)

        hid = layers.elementwise_mul(
            layers.swish(shared("shared_gate_w", shared_hidden, x)),
            shared("shared_up_w", shared_hidden, x))
        out = layers.elementwise_add(
            out, shared("shared_down_w", d, hid))
    if counter_tag is False:
        return out
    # the load counter: a persistable the step carries on the device
    acc_name = f"{helper.name}.load_stats" \
        + (f".{counter_tag}" if counter_tag else "")
    shape = [e_local + LOAD_STATS_EXTRA + (1 if counter_tag else 0)]
    acc = helper.main_program.global_block().create_var(
        name=acc_name, shape=shape, dtype="int32", persistable=True,
        stop_gradient=True)
    sb = helper.startup_program.global_block()
    ConstantInitializer(0)(sb.create_var(
        name=acc_name, shape=shape, dtype="int32", persistable=True), sb)
    helper.append_op(type="moe_load_stats",
                     inputs={"Count": [count], "Acc": [acc]},
                     outputs={"AccOut": [acc]},
                     attrs={"count_hit": True} if counter_tag else {})
    # what PreparedStep.wait() adds to its stats from the counter's gain
    pairs = (
        ("moe_assignments_local", lambda g: int(g[:e_local].sum())),
        ("moe_expert_load_max", lambda g: int(g[e_local])),
        ("moe_expert_load_mean", lambda g: float(g[:e_local].sum())
         / e_local))
    if counter_tag:
        pairs += (("moe_experts_hit", lambda g: int(g[e_local + 2])),)
    helper.main_program.__dict__.setdefault("_device_counters", {})[
        acc_name] = pairs
    return out


def collect_aux_losses(program, peek: bool = False):
    """All MoE aux-loss Variables recorded while building ``program``.

    By default DRAINS the list (a loss builder consumes the terms once);
    ``peek=True`` returns the live list without clearing."""
    lst = program.__dict__.setdefault("_moe_aux_losses", [])
    if peek:
        return lst
    out = list(lst)
    lst.clear()
    return out


def _expert_spec(axis: str, rank: int) -> ShardSpec:
    """Dim-0 (expert dim) shard spec at the given tensor rank."""
    return ShardSpec((axis,) + (None,) * (rank - 1) if rank else (axis,))


def apply_expert_sharding(program, layout: MeshLayout,
                          quant_spec=None) -> Dict[str, Any]:
    """Rewrite a DENSE-built MoE ``program`` in place for expert
    parallelism over ``layout``'s expert axis: insert the
    ``c_expert_alltoall`` pair around every ``moe_expert_ffn`` and stamp
    the expert-dim params (+ grads + coupled optimizer accumulators)
    with the expert-axis ShardSpec.  The planner's expert rows price and
    stamp through this pass — same contract as
    :func:`apply_fsdp_sharding` (idempotent; call BEFORE fsdp sharding
    so the expert weights' dist_attr makes ZeRO-3 skip them, and BEFORE
    grad-sync insertion so ``insert_grad_sync`` skips the expert axis).

    Returns the rewrite report: per-block exchange insertion, stamped
    params, and the skip census."""
    ep = layout.expert
    axis = layout.expert_axis
    report: Dict[str, Any] = {"expert_axis": axis, "expert_degree": ep,
                              "rewritten": [], "stamped": [],
                              "skipped": []}
    if ep <= 1:
        return report
    block = program.global_block()
    if any(op.type == "c_expert_alltoall" for op in block.ops):
        report["skipped"].append(("<program>", "already-expert-sharded"))
        return report
    qattr = _quant_attr(quant_spec)
    bw_idx = next((i for i, op in enumerate(block.ops)
                   if op.type == "backward"), None)

    ffn_sites = [i for i, op in enumerate(block.ops)
                 if op.type == "moe_expert_ffn"]
    if not ffn_sites:
        report["skipped"].append(("<program>", "no-moe-ops"))
        return report

    from ..framework.fsdp import _rename_inputs

    # descending order: each insertion leaves earlier indices valid
    for i in reversed(ffn_sites):
        op = block.ops[i]
        xe_name = op.inputs["Xe"][0]
        ye_name = op.outputs["Out"][0]
        w1_name = op.inputs["W1"][0]
        w1 = block.vars[w1_name]
        e = int(w1.shape[0])
        if e % ep:
            raise ValueError(
                f"apply_expert_sharding: num_experts {e} of {w1_name} "
                f"not divisible by expert degree {ep}")
        xe_var = block.vars[xe_name]
        ye_var = block.vars[ye_name]
        disp = block.create_var(name=xe_name + EXCHANGE_SUFFIX,
                                shape=tuple(xe_var.shape),
                                dtype=xe_var.dtype)
        comb = block.create_var(name=ye_name + EXCHANGE_SUFFIX,
                                shape=tuple(ye_var.shape),
                                dtype=ye_var.dtype)
        # combine-side exchange first (index i+1 before the dispatch
        # insertion shifts it); every downstream reader of the expert
        # output switches to the exchanged (global-expert-order) tensor
        for later in block.ops[i + 1:]:
            _rename_inputs(later, ye_name, comb.name)
        block._insert_op(
            i + 1, type="c_expert_alltoall",
            inputs={"X": [ye_name]}, outputs={"Out": [comb.name]},
            attrs={"ring_id": 0, "_axis_name": axis,
                   "direction": "combine", "quant_spec": qattr})
        block._insert_op(
            i, type="c_expert_alltoall",
            inputs={"X": [xe_name]}, outputs={"Out": [disp.name]},
            attrs={"ring_id": 0, "_axis_name": axis,
                   "direction": "dispatch", "quant_spec": qattr})
        _rename_inputs(block.ops[i + 1], xe_name, disp.name)
        report["rewritten"].append(
            {"ffn": ye_name, "num_experts": e, "dispatch": disp.name,
             "combine": comb.name})

        # stamp the expert-dim weights (+ grad + coupled accumulators):
        # grads arrive pre-summed through the transposed a2a, so
        # insert_grad_sync must skip this axis via the dist_attr
        for slot in ("W1", "W2", "B1", "B2"):
            names = op.inputs.get(slot) or []
            if not names:
                continue
            p = block.vars.get(names[0])
            if p is None:
                continue
            if getattr(p, "dist_attr", None):
                report["skipped"].append((p.name, "already-sharded"))
                continue
            spec = _expert_spec(axis, len(p.shape))
            p.dist_attr = spec
            g = block.vars.get(grad_var_name(p.name))
            if g is not None:
                g.dist_attr = spec
            if bw_idx is not None:
                coupled = {p.name, grad_var_name(p.name)}
                for uop in block.ops[bw_idx:]:
                    names2 = set(uop.input_names()) | \
                        set(uop.output_names())
                    if not (names2 & coupled):
                        continue
                    for n in names2:
                        v = block._find_var_recursive(n)
                        if v is None or not v.persistable or \
                                n == p.name:
                            continue
                        if tuple(v.shape) == tuple(p.shape) and \
                                not getattr(v, "dist_attr", None):
                            v.dist_attr = spec
            report["stamped"].append(p.name)
    program._bump_version()
    return report
