"""Auto-sharding planner + ZeRO-3 legs (the dp8 BERT-tiny/MLP parity
harness for the named-axis layout system):

* config enumeration over (data, fsdp, tp) factorizations with
  tp-legality from program annotations;
* ``strategy.auto_shard=True`` selects a config, compiles ONLY the
  winner, and BIT-matches the hand-flagged dp8 run;
* ZeRO-3 (fsdp) parameter sharding: loss parity ≤1e-6 vs unsharded,
  per-device resident parameter bytes ÷ fsdp (live sharded arrays),
  windowed gathers;
* a tight ``hbm_budget_gb`` flips the chosen plan toward fsdp with 0
  compiles attempted for rejected configs (monitor stat delta);
* MeshLayout + ShardSpec serialization round-trip (a program planned
  on 32 devices reloads with its layout intact);
* strategy validation: auto_shard × manual sharding knobs raise.
"""

import json

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu.framework.core import (Program, program_guard,
                                       reset_default_programs)
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.framework.mesh_layout import MeshLayout, ShardSpec
from paddle_tpu.framework.fsdp import apply_fsdp_sharding, GATHER_SUFFIX
from paddle_tpu.framework.shard_planner import (enumerate_layouts,
                                                legal_tp_degrees,
                                                plan_sharding)
from paddle_tpu.framework.compiler import BuildStrategy, CompiledProgram
from paddle_tpu.distributed.fleet import (fleet, DistributedStrategy,
                                          distributed_optimizer,
                                          UserDefinedRoleMaker)
from paddle_tpu.monitor import stat

STEPS = 4


def _model():
    x = fluid.layers.data("x", shape=[16])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, 32, act="relu",
                        param_attr=fluid.ParamAttr(
                            name="w1",
                            initializer=fluid.initializer.Constant(0.05)),
                        bias_attr=False)
    h = fluid.layers.fc(h, 32, act="relu",
                        param_attr=fluid.ParamAttr(
                            name="w2",
                            initializer=fluid.initializer.Constant(0.04)),
                        bias_attr=False)
    pred = fluid.layers.fc(h, 4, act="softmax",
                           param_attr=fluid.ParamAttr(
                               name="w3",
                               initializer=fluid.initializer.Constant(0.05)),
                           bias_attr=False)
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    return loss


def _batches(n=STEPS):
    rng = np.random.RandomState(0)
    out = []
    for _ in range(n):
        xs = rng.randn(64, 16).astype(np.float32)
        ys = (xs.sum(1) > 0).astype(np.int64).reshape(-1, 1) * 3
        out.append((xs, ys))
    return out


def _train(prog_resolver, startup, loss):
    """Run STEPS batches; returns (losses, w1 ndarray, scope)."""
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        prog = prog_resolver()
        for xs, ys in _batches():
            l, = exe.run(prog, feed={"x": xs, "label": ys},
                         fetch_list=[loss])
            losses.append(float(np.asarray(l).reshape(())))
        w1_arr = scope.find_var("w1")
        w1 = np.asarray(w1_arr)
    return losses, w1, w1_arr


def _run_fleet(mutate_strategy):
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fleet.init(UserDefinedRoleMaker(0, 1))
        strategy = DistributedStrategy()
        mutate_strategy(strategy)
        opt = distributed_optimizer(fluid.optimizer.Adam(5e-3), strategy)
        opt.minimize(loss)
    return _train(lambda: fleet.main_program, startup, loss), main


def _run_single():
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    return _train(lambda: main, startup, loss), main


def _run_manual_fsdp(layout, min_numel=64):
    """Hand-applied ZeRO-3 (no planner): rewrite + with_mesh."""
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    report = apply_fsdp_sharding(main, layout, min_shard_numel=min_numel)
    main._mesh_layout = layout
    mesh = layout.build_mesh()
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    prog = CompiledProgram(main).with_mesh(
        mesh, loss_name=loss.name, batch_axis=layout.batch_axes,
        build_strategy=bs)
    return _train(lambda: prog, startup, loss), main, report


# ---------------------------------------------------------------------------
# config enumeration
# ---------------------------------------------------------------------------


def test_enumerate_layouts_plain_program():
    """A program without tp annotations only searches tp=1, over every
    (data, fsdp) factorization."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        _model()
    assert legal_tp_degrees(main, 8) == [1]
    layouts = enumerate_layouts(main, 8)
    triples = {(l.data, l.fsdp, l.tp) for l in layouts}
    assert triples == {(8, 1, 1), (4, 2, 1), (2, 4, 1), (1, 8, 1)}


def test_enumerate_layouts_tp_annotated():
    """tp-annotated dims + attention head counts bound the tp search."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16])
        from paddle_tpu.parallel import column_parallel_fc
        column_parallel_fc(x, 32, tp_degree=2)
    degrees = legal_tp_degrees(main, 8)
    assert 1 in degrees and 2 in degrees
    layouts = enumerate_layouts(main, 8)
    assert any(l.tp == 2 for l in layouts)
    assert all(l.data * l.fsdp * l.tp == 8 for l in layouts)


# ---------------------------------------------------------------------------
# auto_shard parity vs the hand-flagged run
# ---------------------------------------------------------------------------


def test_auto_shard_dp8_bit_matches_hand_flagged():
    """With everything fitting, the planner picks pure data parallelism
    (min wire, tie → max data) and the run BIT-matches the hand-flagged
    dp8 mesh: same program rewrite, same collective schedule, same
    squeezed ("dp",) mesh."""
    from jax.sharding import Mesh

    def hand(s):
        s.mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))

    (hand_l, hand_w, _), _ = _run_fleet(hand)

    def auto(s):
        s.auto_shard = True
        s.auto_shard_configs["min_shard_numel"] = 64

    (auto_l, auto_w, _), main = _run_fleet(auto)
    assert fleet.plan is not None
    win = fleet.plan.winner.layout
    assert (win.data, win.fsdp, win.tp) == (8, 1, 1)
    assert main._mesh_layout == win
    assert hand_l == auto_l                      # bitwise
    np.testing.assert_array_equal(hand_w, auto_w)


def test_auto_shard_compiles_only_winner():
    """The whole search is static: the planner itself attempts 0
    executor compiles, and the subsequent training run compiles exactly
    as many steps as the hand-flagged path would (one per feed sig)."""
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    before = stat("executor_compile_count").get()
    plan = plan_sharding(main, 8, loss_name=loss.name,
                         fetch_names=[loss.name])
    assert stat("executor_compile_count").get() == before
    assert plan.as_dict()["compiles_attempted"] == 0
    assert len(plan.configs) == 4 and plan.winner is not None


# ---------------------------------------------------------------------------
# ZeRO-3
# ---------------------------------------------------------------------------


def test_zero3_fsdp8_loss_parity_and_resident_shards():
    """Full FSDP over fsdp=8: loss parity ≤1e-6 vs the unsharded
    single-device run, and every sharded parameter's LIVE per-device
    resident buffer is exactly its 1/8 shard (the larger-than-HBM
    capability, census-asserted on the real arrays)."""
    (base_l, base_w, _), _ = _run_single()
    layout = MeshLayout(data=1, fsdp=8)
    (fs_l, fs_w, w1_arr), main, report = _run_manual_fsdp(layout)

    np.testing.assert_allclose(base_l, fs_l, rtol=1e-6)
    np.testing.assert_allclose(base_w, fs_w, rtol=1e-5)

    sharded = {r["param"] for r in report["sharded"]}
    assert sharded == {"w1", "w2", "w3"}
    # w1 [16, 32] fsdp-sharded dim 0 → per-device resident [2, 32]
    assert w1_arr.addressable_shards[0].data.shape == (2, 32)
    assert w1_arr.addressable_shards[0].data.nbytes * 8 == \
        16 * 32 * 4

    # windowed gathers: one per sharded param, placed at first use
    block = main.global_block()
    gathers = [op for op in block.ops if op.type == "fsdp_all_gather"]
    assert {op.input_names()[0] for op in gathers} == sharded
    for op in gathers:
        first, last = op.attrs["_window"]
        assert first <= last
    # the stamped spec rides params AND their grads AND the Adam moments
    for pname in sharded:
        p = block.vars[pname]
        assert isinstance(p.dist_attr, ShardSpec)
        assert "fsdp" in p.dist_attr.axes
        g = block.vars[pname + "@GRAD"]
        assert g.dist_attr == p.dist_attr
    moments = [v for n, v in block.vars.items()
               if "moment" in n and getattr(v, "dist_attr", None)]
    assert moments, "Adam moments did not inherit the fsdp spec"

    # static soundness: the rewritten program verifies clean
    from paddle_tpu.framework.analysis import verify_program
    vr = verify_program(main, fetch_names=[])
    assert vr.ok, vr.report()


def test_zero3_module_gathers_in_windows_and_scatters_grads():
    """fsdp8 BERT-tiny: after a real step every sharded parameter's
    resident buffer is its 1/8 shard, each has one windowed gather, and
    the TPU-lowered module holds those all_gathers AND the
    reduce_scatters their transposes become."""
    from tools.verify_multichip_lowering import fsdp_zero3_section
    sec = fsdp_zero3_section()
    assert sec["sharded_params"] >= 10
    assert sec["resident_param_bytes_per_device"] * 8 == \
        sec["full_param_bytes"]
    assert len(sec["gather_windows"]) == sec["sharded_params"]
    assert all(w[0] <= w[1] for w in sec["gather_windows"].values())
    assert sec["module_all_gather_count"] >= sec["sharded_params"]
    assert sec["module_reduce_scatter_count"] >= 1


def test_zero3_hybrid_dp2_fsdp4_parity():
    """HSDP-style grid: batch over dp×fsdp (tuple batch axis), params
    over fsdp only — parity holds through the tuple-axis executor
    path."""
    (base_l, _, _), _ = _run_single()
    (hy_l, _, _), main, report = _run_manual_fsdp(MeshLayout(data=2,
                                                             fsdp=4))
    np.testing.assert_allclose(base_l, hy_l, rtol=1e-6)
    assert len(report["sharded"]) == 3
    # grads of fsdp params reduce over dp ONLY (dist_attr excludes the
    # fsdp axis from the inserted sync) — the schedule stays sound
    from paddle_tpu.framework.analysis import verify_program
    assert verify_program(main).ok


def test_zero3_memory_estimate_shards_state():
    """The static estimator prices the fsdp layout: params + opt state
    divide by the fsdp axis, so the planner can see the ZeRO-3 saving
    before any compile."""
    from paddle_tpu.framework.memory_analysis import analyze_memory
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    est_full = analyze_memory(main, fetch_names=[loss.name])
    layout = MeshLayout(data=1, fsdp=8)
    apply_fsdp_sharding(main, layout, min_shard_numel=64)
    est_fsdp = analyze_memory(main, fetch_names=[loss.name],
                              mesh_axes=layout.mesh_axes,
                              batch_axis=layout.batch_axes)
    assert est_fsdp.state_bytes * 7 < est_full.state_bytes, \
        (est_full.state_bytes, est_fsdp.state_bytes)


# ---------------------------------------------------------------------------
# the budget-forcing leg
# ---------------------------------------------------------------------------


def test_tight_budget_flips_plan_toward_fsdp():
    """A tight hbm_budget_gb excludes the replicated-param configs and
    flips the winner toward fsdp — with 0 compiles attempted for the
    rejected configs — and the flipped config trains at parity."""
    (base_l, _, _), _ = _run_single()

    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)

    free = plan_sharding(main, 8, loss_name=loss.name,
                         fetch_names=[loss.name], min_shard_numel=64)
    assert free.winner.layout.fsdp == 1      # everything fits → pure dp
    peaks = sorted(c.peak_bytes for c in free.configs)
    budget_gb = (peaks[0] + peaks[-1]) / 2 / float(1 << 30)

    before = stat("executor_compile_count").get()
    plan = plan_sharding(main, 8, loss_name=loss.name,
                         fetch_names=[loss.name], min_shard_numel=64,
                         hbm_budget_gb=budget_gb)
    assert stat("executor_compile_count").get() == before, \
        "plan search attempted compiles"
    assert plan.winner is not None
    assert plan.winner.layout.fsdp > 1, plan.report()
    assert any(not c.fits for c in plan.configs)
    # winner minimizes wire among fitting configs
    fitting = [c for c in plan.configs if c.fits]
    assert plan.winner.wire_bytes == min(c.wire_bytes for c in fitting)

    # the flipped config is not just priced — it trains at parity
    (fs_l, _, _), _, _ = _run_manual_fsdp(plan.winner.layout)
    np.testing.assert_allclose(base_l, fs_l, rtol=1e-6)


def test_auto_shard_over_budget_raises_with_ranking():
    """No config fits → InvalidArgumentError carrying the ranked plan
    (0 compiles attempted)."""
    def auto(s):
        s.auto_shard = True
        s.auto_shard_configs["min_shard_numel"] = 64
        s.auto_shard_configs["hbm_budget_gb"] = 1e-9

    with pytest.raises(InvalidArgumentError) as ei:
        _run_fleet(auto)
    assert "no sharding configuration fits" in str(ei.value)
    assert "fsdp" in str(ei.value)


# ---------------------------------------------------------------------------
# strategy validation (pick-one semantics)
# ---------------------------------------------------------------------------


def test_auto_shard_rejects_manual_sharded_update():
    s = DistributedStrategy()
    s.auto_shard = True
    s.sharded_update = True
    from paddle_tpu.distributed.fleet import CollectiveOptimizer
    with pytest.raises(InvalidArgumentError) as ei:
        CollectiveOptimizer._validate(s)
    msg = str(ei.value)
    assert "auto_shard" in msg and "sharded_update" in msg


def test_auto_shard_rejects_manual_mesh():
    from jax.sharding import Mesh
    s = DistributedStrategy()
    s.auto_shard = True
    s.mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
    from paddle_tpu.distributed.fleet import CollectiveOptimizer
    with pytest.raises(InvalidArgumentError) as ei:
        CollectiveOptimizer._validate(s)
    assert "auto_shard" in str(ei.value) and "mesh" in str(ei.value)


def test_auto_shard_rejects_manual_fsdp_dist_attr():
    """A hand-stamped fsdp dist_attr conflicts with the planner the
    same way manual strategy flags do — both are named."""
    def auto(s):
        s.auto_shard = True

    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        main.global_block().vars["w1"].dist_attr = ("fsdp", None)
        fleet.init(UserDefinedRoleMaker(0, 1))
        strategy = DistributedStrategy()
        strategy.auto_shard = True
        opt = distributed_optimizer(fluid.optimizer.Adam(5e-3), strategy)
        with pytest.raises(InvalidArgumentError) as ei:
            opt.minimize(loss)
    msg = str(ei.value)
    assert "auto_shard" in msg and "w1" in msg and "dist_attr" in msg


# ---------------------------------------------------------------------------
# layout serialization round-trip
# ---------------------------------------------------------------------------


def test_mesh_layout_serialization_roundtrip():
    """A program planned on 32 devices (dp4 × fsdp4 × tp2) reloads with
    its layout AND its per-var ShardSpecs intact — axis sizes included,
    nested (fsdp, tp) dim entries included."""
    from paddle_tpu.framework.serialization import (desc_to_program,
                                                    program_to_desc)
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    layout = MeshLayout(data=4, fsdp=4, tp=2)
    main._mesh_layout = layout
    block = main.global_block()
    block.vars["w1"].dist_attr = layout.spec("fsdp", None)
    block.vars["w2"].dist_attr = layout.spec(("fsdp", "tp"), None)

    desc = program_to_desc(main)
    desc = json.loads(json.dumps(desc))      # must be pure JSON
    loaded = desc_to_program(desc)

    assert loaded._mesh_layout == layout
    assert loaded._mesh_layout.sizes == {"dp": 4, "fsdp": 4, "tp": 2}
    w1 = loaded.global_block().vars["w1"]
    assert isinstance(w1.dist_attr, ShardSpec)
    assert tuple(w1.dist_attr) == ("fsdp", None)
    w2 = loaded.global_block().vars["w2"]
    assert tuple(w2.dist_attr) == (("fsdp", "tp"), None)
    assert w2.dist_attr.divisor(layout.sizes) == 8


def test_shard_spec_legacy_tuple_shim():
    """The old bare-tuple dist_attr spelling still round-trips through
    every consumer: the setter coerces, tuple() equality holds."""
    main = Program()
    v = main.global_block().create_var(name="p", shape=(8, 8),
                                       dtype="float32")
    v.dist_attr = (None, "tp")
    assert isinstance(v.dist_attr, ShardSpec)
    assert tuple(v.dist_attr) == (None, "tp")
    assert v.dist_attr == (None, "tp")       # tuple equality preserved
    v.dist_attr = None
    assert v.dist_attr is None
