"""Mixture-of-Experts tests: dense routing semantics on one device,
expert-parallel (ep over the batch axis, all_to_all exchange) parity with
the single-device run over the 8-device virtual CPU mesh, and the
planner-axis ladder:

* tight ≤1e-6 ep4 parity with the routing group size pinned (aligned
  per-group routing across shard counts);
* ep4 × fsdp2 composition — expert weights stay on the ep axis, ZeRO-3
  skips them and shards the rest;
* int8-quantized expert exchange trains within quantization tolerance;
* capacity-overflow drops are deterministic (bit-equal reruns);
* an ep4 checkpoint restores onto ep2 exactly (reshard.py plans the
  expert-axis flip, Adam state included);
* ``plan_sharding(max_expert=...)`` selects an expert row on a budget
  where every dense row rejects, with 0 compiles (monkeypatch-asserted);
* ``plan_stage_cuts`` never splits a dispatch→combine span;
* verify_moe's moe-axis diagnostics anchor to the offending op;
* auto_shard × a manual ep_degree build is a pick-one error.

The reference has no MoE — SURVEY §2.3 lists expert parallelism as the one
strategy it lacks; semantics follow the GShard/Switch formulation."""

import os
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import io, parallel
from paddle_tpu.framework import analysis
from paddle_tpu.framework.compiler import BuildStrategy, CompiledProgram
from paddle_tpu.framework.core import (Program, program_guard,
                                       reset_default_programs)
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.framework.fsdp import apply_fsdp_sharding
from paddle_tpu.framework.mesh_layout import MeshLayout
from paddle_tpu.parallel import apply_expert_sharding, build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

M, FFN, E = 8, 16, 8


def _attr(seed):
    return fluid.ParamAttr(
        initializer=fluid.initializer.UniformInitializer(-0.5, 0.5,
                                                         seed=seed))


def _build(top_k=2, cf=8.0, ep=None, aux_weight=0.0, group_size=0,
           quant_spec=None):
    x = fluid.layers.data("x", shape=[4, M])
    out, aux = parallel.moe_ffn(
        x, num_experts=E, ffn_hidden=FFN, top_k=top_k, capacity_factor=cf,
        ep_degree=ep, axis_name="dp", group_size=group_size,
        quant_spec=quant_spec, param_attr=_attr(7))
    loss = fluid.layers.mean(fluid.layers.square(out))
    if aux_weight:
        loss = fluid.layers.elementwise_add(
            loss, fluid.layers.scale(aux, scale=aux_weight))
    return loss, aux


def _run(steps, ep=None, mesh=None, top_k=2, cf=8.0, batch=8, seed=0,
         group_size=0):
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss, aux = _build(top_k=top_k, cf=cf, ep=ep,
                           group_size=group_size)
        fluid.optimizer.SGD(0.2).minimize(loss)
    prog = main
    if mesh is not None:
        prog = fluid.CompiledProgram(main).with_mesh(
            mesh, loss_name=loss.name, batch_axis="dp")
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(seed)
    feeds = [rng.uniform(-1, 1, (batch, 4, M)).astype(np.float32)
             for _ in range(steps)]
    losses, auxes = [], []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for f in feeds:
            l, a = exe.run(prog, feed={"x": f}, fetch_list=[loss, aux])
            losses.append(float(np.asarray(l).reshape(())))
            auxes.append(float(np.asarray(a).reshape(())))
    return losses, auxes


def test_moe_dense_trains():
    losses, auxes = _run(steps=4)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    # aux loss is ≥ 1 by Cauchy-Schwarz at balance, finite always
    assert all(a >= 0.99 for a in auxes)


def test_moe_top1_trains():
    losses, _ = _run(steps=4, top_k=1)
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_moe_aux_balanced_at_uniform_gates():
    """Zero gate weight → uniform softmax → aux loss exactly E·(1/E·1)=1
    (all top-1 traffic ties to expert 0, me uniform)."""
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4, M])
        out, aux = parallel.moe_ffn(
            x, num_experts=E, ffn_hidden=FFN, top_k=1, capacity_factor=50.0,
            param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.Constant(0.0)))
    exe = fluid.Executor(fluid.CPUPlace())
    xb = np.random.RandomState(0).rand(8, 4, M).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        a, = exe.run(main, feed={"x": xb}, fetch_list=[aux])
    assert abs(float(np.asarray(a).reshape(())) - 1.0) < 1e-5


def test_moe_capacity_drops_tokens():
    """Tiny capacity → overflowing tokens get zero output (pass-through by
    the surrounding residual, Switch semantics)."""
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4, M])
        out, aux = parallel.moe_ffn(
            x, num_experts=2, ffn_hidden=FFN, top_k=1,
            capacity_factor=0.125, param_attr=_attr(3))
    exe = fluid.Executor(fluid.CPUPlace())
    xb = np.random.RandomState(1).uniform(-1, 1, (8, 4, M)).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        o, = exe.run(main, feed={"x": xb}, fetch_list=[out])
    rows = np.asarray(o).reshape(-1, M)
    zero = np.all(rows == 0.0, axis=-1)
    assert zero.any(), "expected capacity-dropped tokens"
    assert (~zero).any(), "expected some tokens routed"


def test_moe_transformer_trains():
    """moe_experts on TransformerConfig swaps every FFN for a routed MoE
    block and folds the load-balance aux terms into the loss."""
    from paddle_tpu.models import transformer as T
    reset_default_programs()
    cfg = T.TransformerConfig(src_vocab_size=50, trg_vocab_size=50,
                              max_length=8, d_model=16, d_inner=32,
                              n_head=2, n_layer=1, dropout=0.0,
                              moe_experts=4, moe_top_k=2)
    main, startup = Program(), Program()
    with program_guard(main, startup):
        feeds, loss, logits = T.build_train_network(cfg)
        fluid.optimizer.Adam(1e-2).minimize(loss)
    rng = np.random.RandomState(0)
    src = [[3, 4, 5]] * 4
    trg = [[6, 7]] * 4
    batch = T.make_batch(src, trg, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(4):
            l, = exe.run(main, feed=batch, fetch_list=[loss])
            losses.append(float(np.asarray(l).reshape(())))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_expert_parallel_matches_single_device(top_k):
    """ep=4 over the dp axis (GShard layout: batch AND experts sharded over
    the same axis, all_to_all exchange) reproduces the single-device loss
    trajectory exactly when capacity is generous — validating dispatch,
    the transposed-all_to_all expert gradients, and the compiler's
    scale-without-allreduce handling of expert-sharded params."""
    ref, _ = _run(steps=3, top_k=top_k)
    mesh = build_mesh({"dp": 4})
    par, _ = _run(steps=3, top_k=top_k, ep=4, mesh=mesh)
    np.testing.assert_allclose(ref, par, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the planner-axis ladder: apply_expert_sharding on a DENSE build
# ---------------------------------------------------------------------------

GROUP = 4     # pinned routing group: per-group routing aligns across ep
STEPS = 3


def _build_dense(group_size=GROUP, quant_spec=None, opt="adam"):
    """Dense MoE build (the planner's input) + optimizer.  The aux term
    stays OUT of the parity loss: load-balance statistics (me, ce) are
    computed over the device-local token set (GShard semantics — the
    grad sync averages the per-device aux gradients), so the fetched aux
    VALUE legitimately differs across ep degrees while the routed output
    stays bit-exact."""
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss, aux = _build(group_size=group_size, quant_spec=quant_spec)
        if opt == "adam":
            fluid.optimizer.Adam(5e-3).minimize(loss)
        else:
            fluid.optimizer.SGD(0.2).minimize(loss)
    return main, startup, loss, aux


def _stamp(main, loss, layout, quant_spec=None, min_numel=16):
    """The planner's stamping order: expert axis FIRST (its dist_attr
    makes ZeRO-3 and grad-sync skip the expert weights), fsdp second."""
    rep = apply_expert_sharding(main, layout, quant_spec=quant_spec)
    fsdp_rep = None
    if layout.fsdp > 1:
        fsdp_rep = apply_fsdp_sharding(main, layout,
                                       min_shard_numel=min_numel)
    main._mesh_layout = layout
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    prog = CompiledProgram(main).with_mesh(
        layout.build_mesh(), loss_name=loss.name,
        batch_axis=layout.batch_axes, build_strategy=bs)
    return prog, rep, fsdp_rep


def _feeds(steps=STEPS, batch=8, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.uniform(-1, 1, (batch, 4, M)).astype(np.float32)
            for _ in range(steps)]


def _train(prog, startup, loss, feeds):
    exe = fluid.Executor(fluid.CPUPlace())
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for f in feeds:
            l, = exe.run(prog, feed={"x": f}, fetch_list=[loss])
            losses.append(float(np.asarray(l).reshape(())))
    return losses


def test_moe_ep4_parity_tight():
    """Planner-path ep4 (apply_expert_sharding retrofits the exchange
    onto the dense build) matches the dense loss trajectory to ≤1e-6
    when the routing group size is pinned — same groups, same routing,
    only placement differs."""
    main, startup, loss, _ = _build_dense()
    ref = _train(main, startup, loss, _feeds())

    main2, startup2, loss2, _ = _build_dense()
    layout = MeshLayout(data=2, expert=4)
    prog, rep, _ = _stamp(main2, loss2, layout)
    assert rep["rewritten"], "no exchange inserted"
    assert rep["stamped"], "no expert weight stamped"
    par = _train(prog, startup2, loss2, _feeds())
    np.testing.assert_allclose(ref, par, rtol=1e-6, atol=1e-7)


def test_moe_ep4_fsdp2_composition():
    """ep4 × fsdp2 on 8 devices: the expert weights keep their ep spec
    (ZeRO-3 must skip them — their grads arrive pre-summed through the
    transposed a2a), the dense remainder shards over fsdp, and the
    composed run still matches dense ≤1e-6."""
    main, startup, loss, _ = _build_dense()
    ref = _train(main, startup, loss, _feeds())

    main2, startup2, loss2, _ = _build_dense()
    layout = MeshLayout(data=1, fsdp=2, expert=4)
    prog, rep, fsdp_rep = _stamp(main2, loss2, layout)
    stamped = set(rep["stamped"])
    assert stamped, "no expert weight on the ep axis"
    fsdp_sharded = {s["param"] for s in fsdp_rep["sharded"]}
    assert not (stamped & fsdp_sharded), \
        f"ZeRO-3 re-sharded expert weights: {stamped & fsdp_sharded}"
    assert {n for n, why in fsdp_rep["skipped"]
            if why == "already-sharded"} >= stamped
    par = _train(prog, startup2, loss2, _feeds())
    np.testing.assert_allclose(ref, par, rtol=1e-6, atol=1e-7)


def test_moe_int8_exchange_trains_close_to_dense():
    """The int8-quantized expert exchange (CompressionSpec tier on the
    a2a payload, dequant-accumulate on receive) trains within
    quantization tolerance of the dense run — loose bound, the payload
    is lossy by design."""
    main, startup, loss, _ = _build_dense()
    ref = _train(main, startup, loss, _feeds())

    main2, startup2, loss2, _ = _build_dense()
    prog, rep, _ = _stamp(main2, loss2, MeshLayout(data=2, expert=4),
                          quant_spec="int8")
    par = _train(prog, startup2, loss2, _feeds())
    assert all(np.isfinite(par))
    assert par[-1] < par[0] * 1.05, "int8 exchange run diverged"
    np.testing.assert_allclose(ref, par, rtol=0.05, atol=0.01)


def test_moe_ep4_capacity_drops_are_deterministic():
    """Overflow drops under the exchange are a pure function of the
    routing — two runs of the same overflowing batch produce bit-equal
    outputs (no nondeterministic scatter order)."""
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4, M])
        out, aux = parallel.moe_ffn(
            x, num_experts=E, ffn_hidden=FFN, top_k=1,
            capacity_factor=0.125, group_size=GROUP, param_attr=_attr(3))
    layout = MeshLayout(data=2, expert=4)
    apply_expert_sharding(main, layout)
    main._mesh_layout = layout
    prog = CompiledProgram(main).with_mesh(
        layout.build_mesh(), batch_axis=layout.batch_axes)
    exe = fluid.Executor(fluid.CPUPlace())
    xb = np.random.RandomState(1).uniform(
        -1, 1, (8, 4, M)).astype(np.float32)

    def once():
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            o, = exe.run(prog, feed={"x": xb}, fetch_list=[out])
        return np.asarray(o)

    a, b = once(), once()
    zero = np.all(a.reshape(-1, M) == 0.0, axis=-1)
    assert zero.any() and (~zero).any(), "want a mixed drop pattern"
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# elastic: an ep4 checkpoint restores onto ep2 exactly
# ---------------------------------------------------------------------------

STEPS_BEFORE, STEPS_AFTER = 3, 3


def _build_ep(layout):
    main, startup, loss, _ = _build_dense()
    prog, _, _ = _stamp(main, loss, layout)
    return main, startup, loss, prog


def _run_span(exe, prog, loss, scope, feeds, start, n):
    losses = []
    with fluid.scope_guard(scope):
        for f in feeds[start:start + n]:
            l, = exe.run(prog, feed={"x": f}, fetch_list=[loss])
            losses.append(float(np.asarray(l).reshape(())))
    return losses


def test_moe_ep4_checkpoint_restores_onto_ep2(tmp_path):
    """The checkpoint carries the expert-axis ShardSpec (Adam moments
    included), so reshard.py plans the ep4→ep2 flip and the restored
    run continues the uninterrupted ep4 trajectory at ≤1e-6."""
    feeds = _feeds(STEPS_BEFORE + STEPS_AFTER)
    exe = fluid.Executor(fluid.CPUPlace())

    # uninterrupted ep4 reference
    main, startup, loss, prog = _build_ep(MeshLayout(data=2, expert=4))
    ref_scope = fluid.Scope()
    with fluid.scope_guard(ref_scope):
        exe.run(startup)
    ref = _run_span(exe, prog, loss, ref_scope, feeds, 0,
                    STEPS_BEFORE + STEPS_AFTER)

    # ep4 run checkpointed mid-way
    main, startup, loss, prog = _build_ep(MeshLayout(data=2, expert=4))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    before = _run_span(exe, prog, loss, scope, feeds, 0, STEPS_BEFORE)
    np.testing.assert_allclose(before, ref[:STEPS_BEFORE], rtol=1e-6)
    with fluid.scope_guard(scope):
        io.save_checkpoint(exe, str(tmp_path), io.TrainStatus(
            STEPS_BEFORE - 1, STEPS_BEFORE - 1), main)
    man = io._read_manifest(os.path.join(
        str(tmp_path), f"checkpoint_{STEPS_BEFORE - 1}"))
    assert dict(man["mesh_layout"]["axes"]).get("ep") == 4
    assert any("ep" in str(s) for s in man["shard_specs"].values()), \
        "no persistable carries the expert-axis spec in the manifest"

    # relaunch at ep2 (the surviving half of the expert axis)
    main2, startup2, loss2, prog2 = _build_ep(
        MeshLayout(data=4, expert=2))
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup2)
        st = io.load_checkpoint(exe, str(tmp_path), main_program=main2,
                                scope=scope2)
    assert st.reshard is not None
    assert st.reshard["src_layout"]["ep"] == 4
    assert st.reshard["dst_layout"]["ep"] == 2
    assert st.reshard["compiles_attempted"] == 0
    after = _run_span(exe, prog2, loss2, scope2, feeds, STEPS_BEFORE,
                      STEPS_AFTER)
    np.testing.assert_allclose(after, ref[STEPS_BEFORE:], rtol=1e-6,
                               atol=1e-7)


# ---------------------------------------------------------------------------
# the planner axis: expert rows win a budget no dense row fits
# ---------------------------------------------------------------------------


def test_moe_planner_selects_expert_row_zero_compiles(monkeypatch):
    """plan_sharding(max_expert=4) on the expert-dominated MoE BERT-tiny:
    the budget placed between the expert family's peak and the dense
    family's peak rejects every dense row and selects an expert row —
    monkeypatch-asserted that NO compile is even attempted during the
    whole two-pass search (pricing is byte arithmetic)."""
    from paddle_tpu.framework.executor import Executor
    from tools import moe_probe

    def boom(self, *a, **kw):
        raise AssertionError("compile attempted during the plan search")

    monkeypatch.setattr(Executor, "_compile", boom)
    try:
        section = moe_probe.probe_planner()
    finally:
        monkeypatch.undo()
    assert section["winner"]["expert"] > 1
    assert section["winner"]["data"] > 1            # dp·ep hybrid
    assert section["dense_rows_rejected"] >= 1
    assert section["compile_count_delta"] == 0
    assert set(section["expert_degrees_priced"]) >= {1, 2, 4}


def test_expert_alltoall_wire_tiers_priced():
    """The ``c_expert_alltoall`` pair priced by the op_spec wire channel
    at dp2·ep4: int8 moves >= 3.5x fewer wire bytes than fp32, bf16
    >= 1.9x, and both exchanges of the routed block are counted."""
    from tools import moe_probe
    tiers = moe_probe.probe_wire_census()["tiers"]
    assert tiers["int8"]["compression_vs_fp32"] >= 3.5
    assert tiers["bf16"]["compression_vs_fp32"] >= 1.9
    assert tiers["fp32"]["count"] >= 2


def test_moe_decode_greedy_parity_and_warm_restart():
    """The MoE BertDecoder through the decode engine: token-for-token the
    greedy reference, and a second engine over the same AOT cache warms
    the whole grid with 0 fresh compiles and emits the same tokens."""
    from tools import moe_probe
    d = moe_probe.probe_decode()
    assert d["greedy_parity"] is True
    assert d["cold_fresh_compiles"] >= d["executable_grid"] > 0
    assert d["warm_fresh_compiles"] == 0


# ---------------------------------------------------------------------------
# pipeline: a dispatch→combine span never splits across stages
# ---------------------------------------------------------------------------


def test_plan_stage_cuts_respects_moe_span():
    """plan_stage_cuts on a two-block MoE stack: the gate's routing
    decision (moe_dispatch's Combine weights) and its moe_combine stay
    in one stage — no cut lands inside either dispatch→combine span."""
    from paddle_tpu.framework import pipe as P

    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4, M])
        h = fluid.layers.fc(x, M, act="relu", param_attr=_attr(11))
        h, a1 = parallel.moe_ffn(h, num_experts=4, ffn_hidden=FFN,
                                 top_k=2, capacity_factor=8.0,
                                 param_attr=_attr(12), name="moe_a")
        h = fluid.layers.fc(h, M, act="relu", param_attr=_attr(13))
        h, a2 = parallel.moe_ffn(h, num_experts=4, ffn_hidden=FFN,
                                 top_k=2, capacity_factor=8.0,
                                 param_attr=_attr(14), name="moe_b")
        loss = fluid.layers.mean(fluid.layers.square(h))
        loss = fluid.layers.elementwise_add(
            loss, fluid.layers.scale(
                fluid.layers.elementwise_add(a1, a2), scale=0.01))
        fluid.optimizer.Adam(5e-3).minimize(loss)

    plan = P.plan_stage_cuts(main, 2,
                             feed_shapes={"x": ((8, 4, M), "float32")})
    assert len(plan.cuts) == 1

    block, ops, bw_idx = P._fwd_region(main)
    fwd_ops = ops[:bw_idx]
    def_idx, _ = P._fwd_liveness(block, fwd_ops)
    spans = P._moe_forbidden(block, fwd_ops, def_idx)
    assert spans, "the MoE spans produced no forbidden cut positions"
    assert len([op for op in fwd_ops if op.type == "moe_combine"]) == 2
    assert not (set(plan.cuts) & spans), \
        f"cut {plan.cuts} lands inside a dispatch→combine span"


# ---------------------------------------------------------------------------
# verify_moe diagnostics
# ---------------------------------------------------------------------------


def test_verify_moe_flags_unknown_axis_and_capacity_mismatch():
    """An exchange over an axis the layout doesn't carry anchors as
    moe-axis-unknown; an expert degree that doesn't divide num_experts
    anchors as moe-axis-capacity-mismatch; the correct stamping is
    clean."""
    main, startup, loss, _ = _build_dense()
    apply_expert_sharding(main, MeshLayout(data=2, expert=4))

    main._mesh_layout = MeshLayout(data=2, expert=4)
    res = analysis.verify_program(main)
    assert not res.by_code(analysis.MOE_AXIS_UNKNOWN)
    assert not res.by_code(analysis.MOE_AXIS_CAPACITY_MISMATCH)

    main._mesh_layout = MeshLayout(data=8)        # no expert axis
    res = analysis.verify_program(main)
    unknown = res.by_code(analysis.MOE_AXIS_UNKNOWN)
    assert unknown and all("ep" in d.message for d in unknown)

    main._mesh_layout = MeshLayout(data=1, expert=16)   # 8 % 16 != 0
    res = analysis.verify_program(main)
    assert res.by_code(analysis.MOE_AXIS_CAPACITY_MISMATCH)


# ---------------------------------------------------------------------------
# strategy validation: auto_shard × manual ep is pick-one
# ---------------------------------------------------------------------------


def test_auto_shard_rejects_manual_ep_build():
    """A moe_ffn(ep_degree=...) build wires its own expert exchange;
    composing it with the planner's expert search is a pick-one error
    naming both spellings."""
    from paddle_tpu.distributed.fleet import (fleet, DistributedStrategy,
                                              distributed_optimizer,
                                              UserDefinedRoleMaker)
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss, _ = _build(ep=2)
        fleet.init(UserDefinedRoleMaker(0, 1))
        s = DistributedStrategy()
        s.auto_shard = True
        opt = distributed_optimizer(fluid.optimizer.Adam(1e-3), s)
        with pytest.raises(InvalidArgumentError) as ei:
            opt.minimize(loss)
    msg = str(ei.value)
    assert "auto_shard" in msg and "max_expert" in msg
    assert "c_expert_alltoall" in msg
