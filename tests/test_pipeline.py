"""Pipeline parallelism + rematerialization as planner dimensions.

Covers the framework/pipe.py rewrites (liveness-driven stage cuts, the
schedule family simulator — 1F1B, interleaved-1F1B, zero-bubble B/W
split — remat planning, pipe-axis weight sharding), the executor's
microbatched/scheduled lowerings (gradient-merge bitwise composition,
pp-mesh parity, census idle == simulator bubble ticks), the extended
(data, fsdp, tp, pipe, remat) × schedule planner with its 0-compile and
budget-flip contracts, the new analysis diagnostics, and the telemetry
bubble fraction."""


import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import paddle_tpu.fluid as fluid
from paddle_tpu import layers
from paddle_tpu.framework.core import (Program, program_guard,
                                       reset_default_programs)
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.framework.compiler import BuildStrategy, CompiledProgram
from paddle_tpu.framework.mesh_layout import MeshLayout
from paddle_tpu.framework.pipe import (apply_pipeline, apply_remat,
                                       plan_remat, plan_stage_cuts,
                                       schedule_1f1b, set_microbatches)
from paddle_tpu.framework.shard_planner import (enumerate_layouts,
                                                plan_sharding)
from paddle_tpu.monitor import stat


STEPS = 5


def _model(width=32):
    x = layers.data("x", shape=[-1, 16], append_batch_size=False)
    y = layers.data("label", shape=[-1, 1], dtype="float32",
                    append_batch_size=False)
    h = layers.fc(x, width, act="relu",
                  param_attr=fluid.ParamAttr(name="w1"))
    h = layers.fc(h, width, act="relu",
                  param_attr=fluid.ParamAttr(name="w2"))
    p = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="w3"))
    return layers.mean(layers.square(p - y))


_RNG = np.random.RandomState(0)
_XS = _RNG.randn(STEPS, 8, 16).astype("float32")
_YS = _RNG.randn(STEPS, 8, 1).astype("float32")


def _train(mutate, mesh_axes=(), fuse=True):
    """Build + mutate + train the MLP; returns (losses, w1)."""
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    mutate(main)
    prog = main
    if mesh_axes:
        names = tuple(a for a, _ in mesh_axes)
        sizes = tuple(n for _, n in mesh_axes)
        n = int(np.prod(sizes))
        mesh = Mesh(np.array(jax.devices()[:n]).reshape(sizes), names)
        bs = BuildStrategy()
        bs.fuse_all_reduce_ops = fuse
        prog = CompiledProgram(main).with_mesh(
            mesh, loss_name=loss.name, batch_axis="dp",
            build_strategy=bs)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for i in range(STEPS):
            (l,) = exe.run(prog, feed={"x": _XS[i], "label": _YS[i]},
                           fetch_list=[loss])
            losses.append(np.asarray(l).ravel())
        w1 = np.asarray(scope.find_var("w1"))
    return losses, w1


# ---------------------------------------------------------------------------
# stage-cut planning + schedule
# ---------------------------------------------------------------------------


def test_plan_stage_cuts_minimizes_boundary_and_balances():
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    plan = plan_stage_cuts(main, 2,
                           feed_shapes={"x": ((8, 16), "float32"),
                                        "label": ((8, 1), "float32")})
    assert len(plan.cuts) == 1 and len(plan.boundaries) == 1
    assert plan.boundary_bytes[0] > 0
    assert all(n > 0 for n in plan.stage_ops)
    # both stages carry compute (the FLOPs-balance constraint held)
    assert all(f > 0 for f in plan.stage_flops)


def test_plan_stage_cuts_requires_backward():
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        _model()
    with pytest.raises(InvalidArgumentError, match="backward"):
        plan_stage_cuts(main, 2)


def test_schedule_1f1b_shape_and_alternation():
    for S, M in ((2, 4), (4, 4), (3, 6)):
        sch = schedule_1f1b(S, M)
        order = sch["order"]
        # every (stage, phase, microbatch) unit exactly once
        assert len(order) == 2 * S * M
        assert len({(s, ph, m) for _, s, ph, m in order}) == 2 * S * M
        # last stage alternates F,B strictly — the 1F1B contract
        last = [(ph, m) for _, s, ph, m in order if s == S - 1]
        assert last == [(ph, m) for m in range(M) for ph in ("F", "B")]
        # a backward never precedes its own forward; cotangents flow
        # stage s+1 → s one tick apart
        ftick = {(s, m): t for t, s, ph, m in order if ph == "F"}
        btick = {(s, m): t for t, s, ph, m in order if ph == "B"}
        for (s, m), t in btick.items():
            assert t > ftick[(s, m)]
            if s < S - 1:
                assert t == btick[(s + 1, m)] + 1
        assert 1 <= sch["slots"] <= S
        # exact per-tick accounting (replaces the analytic (S-1)/M):
        # 1F1B idles 2·S·(S-1) rank-ticks regardless of M
        assert sch["idle_slots"] == 2 * S * (S - 1)
        assert sch["bubble_ticks"] == sch["idle_slots"]
        assert sch["bubble_frac"] == sch["idle_slots"] / (
            sch["ticks"] * S)


def test_apply_pipeline_idempotent_and_stamps():
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    rep = apply_pipeline(main, 2, 2)
    assert rep["num_stages"] == 2 and rep["grad_sync_ops"] >= 1
    block = main.global_block()
    assert sum(1 for op in block.ops
               if op.type == "pipe_stage_boundary") == 1
    bw = next(op for op in block.ops if op.type == "backward")
    assert bw.attrs["pipe_stages"] == 2
    assert bw.attrs["pipe_microbatches"] == 2
    assert bw.attrs["pipe_boundaries"] == rep["boundaries"]
    # second application is a no-op
    rep2 = apply_pipeline(main, 4, 8)
    assert rep2.get("already_pipelined")
    assert sum(1 for op in block.ops
               if op.type == "pipe_stage_boundary") == 1


# ---------------------------------------------------------------------------
# gradient-merge × pipeline composition (the microbatch substrate)
# ---------------------------------------------------------------------------


def test_microbatch_accumulation_matches_gradient_merge_bitwise():
    """pipe = 1, M = 2: the in-step microbatch scan must equal
    GradientMergeOptimizer over the same microbatch stream BITWISE
    (two-term accumulation commutes exactly; the 1/2 mean is an exact
    scale)."""
    lm, wm = _train(lambda p: set_microbatches(p, 2))

    def gm():
        reset_default_programs()
        from paddle_tpu.optimizer import GradientMergeOptimizer
        main, startup = Program(), Program()
        with program_guard(main, startup):
            loss = _model()
            GradientMergeOptimizer(fluid.optimizer.Adam(5e-3), k_steps=2,
                                   avg=True).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for i in range(STEPS):
                sub = []
                for m in range(2):
                    (l,) = exe.run(
                        main,
                        feed={"x": _XS[i][m * 4:(m + 1) * 4],
                              "label": _YS[i][m * 4:(m + 1) * 4]},
                        fetch_list=[loss])
                    sub.append(np.asarray(l).reshape(()))
                losses.append((sub[0] + sub[1]) / np.float32(2))
            w1 = np.asarray(scope.find_var("w1"))
        return losses, w1

    lg, wg = gm()
    assert np.array_equal(np.asarray(lm).ravel(), np.asarray(lg).ravel())
    assert np.array_equal(wm, wg)


def test_pipe2_matches_gradient_merge_1e6():
    """pipe = 2 (1F1B over a pp2 mesh): same math as gradient merge up
    to the schedule's reassociation — ≤ 1e-6 over 5 steps."""
    lm, wm = _train(lambda p: set_microbatches(p, 2))
    lp, wp = _train(lambda p: apply_pipeline(p, 2, 2),
                    mesh_axes=(("pp", 2),))
    a = np.asarray(lm, dtype=np.float64).ravel()
    b = np.asarray(lp, dtype=np.float64).ravel()
    assert np.abs(a - b).max() <= 1e-6
    assert np.abs(wm - wp).max() <= 1e-6


# ---------------------------------------------------------------------------
# 1F1B mesh lowering parity
# ---------------------------------------------------------------------------


def test_dp2_pp2_parity_and_composition():
    lb, wb = _train(lambda p: set_microbatches(p, 4),
                    mesh_axes=(("dp", 2),))
    lp, wp = _train(lambda p: apply_pipeline(p, 2, 4),
                    mesh_axes=(("dp", 2), ("pp", 2)))
    a = np.asarray(lb, dtype=np.float64).ravel()
    b = np.asarray(lp, dtype=np.float64).ravel()
    assert np.abs(a - b).max() <= 1e-6
    assert np.abs(wb - wp).max() <= 1e-6


def test_pp4_parity():
    lb, wb = _train(lambda p: set_microbatches(p, 4))
    lp, wp = _train(lambda p: apply_pipeline(p, 4, 4),
                    mesh_axes=(("pp", 4),))
    a = np.asarray(lb, dtype=np.float64).ravel()
    b = np.asarray(lp, dtype=np.float64).ravel()
    assert np.abs(a - b).max() <= 1e-6
    assert np.abs(wb - wp).max() <= 1e-6


def test_pipe_zero1_composition():
    """1F1B × ZeRO-1: the pipe-axis grad sum feeds the dp-axis
    reduce-scatter untouched."""
    from paddle_tpu.optimizer import ShardedUpdateOptimizer

    def build(pipelined):
        reset_default_programs()
        main, startup = Program(), Program()
        with program_guard(main, startup):
            loss = _model()
            ShardedUpdateOptimizer(fluid.optimizer.Adam(5e-3), nranks=2,
                                   axis_name="dp").minimize(loss)
        if pipelined:
            apply_pipeline(main, 2, 2)
            axes, shape = ("dp", "pp"), (2, 2)
        else:
            set_microbatches(main, 2)
            axes, shape = ("dp",), (2,)
        n = int(np.prod(shape))
        mesh = Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)
        prog = CompiledProgram(main).with_mesh(
            mesh, loss_name=None, batch_axis="dp",
            build_strategy=BuildStrategy())
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for i in range(STEPS):
                (l,) = exe.run(prog, feed={"x": _XS[i], "label": _YS[i]},
                               fetch_list=[loss])
                losses.append(np.asarray(l).ravel())
            w1 = np.asarray(scope.find_var("w1"))
        return np.asarray(losses, dtype=np.float64), w1

    lb, wb = build(False)
    lp, wp = build(True)
    assert np.abs(lb - lp).max() <= 1e-6
    assert np.abs(wb - wp).max() <= 1e-6


def test_pipelined_fetch_of_intermediate_raises():
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = layers.data("x", shape=[-1, 16], append_batch_size=False)
        y = layers.data("label", shape=[-1, 1], dtype="float32",
                        append_batch_size=False)
        h = layers.fc(x, 32, act="relu",
                      param_attr=fluid.ParamAttr(name="w1"))
        p = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="w3"))
        loss = layers.mean(layers.square(p - y))
        fluid.optimizer.Adam(5e-3).minimize(loss)
    set_microbatches(main, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(InvalidArgumentError,
                           match="per-microbatch"):
            exe.run(main, feed={"x": _XS[0], "label": _YS[0]},
                    fetch_list=[h.name])


# ---------------------------------------------------------------------------
# rematerialization
# ---------------------------------------------------------------------------


def _bert_tiny_train():
    from paddle_tpu.models import bert
    cfg = bert.BertConfig.tiny()
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        feeds, loss = bert.build_pretrain_network_parallel(cfg)
        fluid.optimizer.Adam(1e-4).minimize(loss)
    batch = bert.make_fake_parallel_batch(np.random.RandomState(0), cfg,
                                          batch_size=8, seq_len=64)
    fs = {k: (tuple(v.shape), str(v.dtype)) for k, v in batch.items()}
    return main, startup, loss, fs, batch


def test_plan_remat_reduces_estimate_and_prices_flops():
    main, _, loss, fs, _ = _bert_tiny_train()
    plan = plan_remat(main, feed_shapes=fs, fetch_names=[loss.name])
    assert plan is not None
    assert plan.est_after.peak_bytes < plan.est_before.peak_bytes
    assert plan.flops_delta > 0
    assert plan.checkpoints and plan.num_segments >= 2


def test_remat_on_reject_flag_admits_over_budget_program():
    from paddle_tpu import flags
    from paddle_tpu.framework.memory_analysis import (analyze_memory,
                                                      check_hbm_budget)
    main, _, loss, fs, _ = _bert_tiny_train()
    est = analyze_memory(main, feed_shapes=fs, fetch_names=[loss.name])
    plan = plan_remat(main.clone(), feed_shapes=fs,
                      fetch_names=[loss.name])
    # a budget between the remat-ed and the base peak: base rejects,
    # remat fits
    budget = (plan.est_after.peak_bytes + est.peak_bytes) / 2 / (1 << 30)
    with pytest.raises(InvalidArgumentError, match="hbm_budget_gb"):
        check_hbm_budget(main.clone(), feed_shapes=fs,
                         fetch_names=[loss.name], budget_gb=budget)
    flags.set_flags({"remat_on_reject": True})
    try:
        est2 = check_hbm_budget(main, feed_shapes=fs,
                                fetch_names=[loss.name],
                                budget_gb=budget)
    finally:
        flags.set_flags({"remat_on_reject": False})
    assert est2 is not None and est2.peak_gb <= budget
    bw = next(op for op in main.global_block().ops
              if op.type == "backward")
    assert bw.attrs.get("checkpoints")


def test_remat_program_still_trains_to_parity():
    def remat(p):
        plan = plan_remat(p, feed_shapes={"x": ((8, 16), "float32"),
                                          "label": ((8, 1), "float32")})
        assert plan is not None
        apply_remat(p, plan)

    lb, wb = _train(lambda p: None)
    lr, wr = _train(remat)
    a = np.asarray(lb, dtype=np.float64).ravel()
    b = np.asarray(lr, dtype=np.float64).ravel()
    assert np.abs(a - b).max() <= 1e-6
    assert np.abs(wb - wr).max() <= 1e-6


# ---------------------------------------------------------------------------
# the extended planner
# ---------------------------------------------------------------------------


def test_enumerate_layouts_pipe_dimension():
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    # opt-in only: default stays (data, fsdp, tp)
    assert all(l.pipe == 1 for l in enumerate_layouts(main, 8))
    layouts = enumerate_layouts(main, 8, max_pipe=4)
    pipes = {l.pipe for l in layouts}
    assert pipes == {1, 2, 4}
    assert all(l.num_devices == 8 for l in layouts)
    # inference programs never enumerate pipe > 1
    reset_default_programs()
    infer, startup = Program(), Program()
    with program_guard(infer, startup):
        _model()
    assert all(l.pipe == 1
               for l in enumerate_layouts(infer, 8, max_pipe=4))


def test_planner_pipe_and_remat_rows_zero_compiles():
    main, _, loss, fs, _ = _bert_tiny_train()
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    before = int(stat("executor_compile_count").get())
    probe = plan_sharding(main, 4, loss_name=loss.name, feed_shapes=fs,
                          fetch_names=[loss.name], build_strategy=bs,
                          max_pipe=2, num_microbatches=4)
    peaks = [c.peak_bytes for c in probe.configs
             if c.peak_bytes is not None]
    budget = min(peaks) * 0.92 / (1 << 30)
    plan = plan_sharding(main, 4, loss_name=loss.name, feed_shapes=fs,
                         fetch_names=[loss.name], build_strategy=bs,
                         max_pipe=2, num_microbatches=4,
                         hbm_budget_gb=budget, remat=True)
    assert int(stat("executor_compile_count").get()) == before, \
        "the plan search attempted a compile"
    pipes = {c.layout.pipe for c in plan.configs}
    assert pipes == {1, 2}
    # pipe rows carry the bubble term: cost > exposed
    for c in plan.configs:
        if c.layout.pipe > 1 and c.exposed:
            assert c.exposed["pipe_bubble_s"] > 0
            assert c.cost_s > c.exposed_comm_s
    # every base row rejected; at least one remat sibling admitted with
    # a priced FLOPs delta — the budget flip
    assert all(not c.fits for c in plan.configs if not c.remat)
    flipped = [c for c in plan.configs if c.remat and c.fits]
    assert flipped and all(c.remat_plan.flops_delta > 0 for c in flipped)
    assert plan.winner is not None and plan.winner.remat


def test_auto_shard_pipe_winner_runs():
    """auto_shard with the pipe dimension forced to win (pipe-only
    device split) stamps, builds the pp mesh and trains."""
    from paddle_tpu.distributed.fleet import (DistributedStrategy,
                                              distributed_optimizer,
                                              fleet)
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        s = DistributedStrategy()
        s.auto_shard = True
        s.auto_shard_configs = dict(
            s.auto_shard_configs, num_devices=2, max_pipe=2,
            num_microbatches=2,
            feed_shapes={"x": ((8, 16), "float32"),
                         "label": ((8, 1), "float32")})
        opt = distributed_optimizer(fluid.optimizer.Adam(5e-3), s)
        opt.minimize(loss)
    assert fleet.plan is not None
    assert {c.layout.pipe for c in fleet.plan.configs} == {1, 2}
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        (l,) = exe.run(fleet.main_program,
                       feed={"x": _XS[0], "label": _YS[0]},
                       fetch_list=[loss])
        assert np.isfinite(np.asarray(l)).all()


# ---------------------------------------------------------------------------
# diagnostics + satellite knobs
# ---------------------------------------------------------------------------


def test_pipe_collective_crosses_stage_diagnostic():
    from paddle_tpu.framework.analysis import (
        PIPE_COLLECTIVE_CROSSES_STAGE, verify_program)
    prog = Program()
    b = prog.global_block()
    b.create_var(name="x", shape=(8, 4), dtype="float32", is_data=True)
    b.create_var(name="h", shape=(8, 4), dtype="float32")
    b.append_op(type="scale", inputs={"X": ["x"]}, outputs={"Out": ["h"]},
                attrs={"scale": 1.0, "_pipe_stage": 0})
    b.append_op(type="c_allreduce_sum", inputs={"X": ["h"]},
                outputs={"Out": ["h"]},
                attrs={"ring_id": 0, "_axis_name": "tp",
                       "_pipe_stage": 1})
    b.append_op(type="backward", inputs={}, outputs={},
                attrs={"loss_name": "h", "param_names": [],
                       "pipe_stages": 2, "pipe_microbatches": 2,
                       "pipe_axis": "pp", "pipe_boundaries": [["h"]]})
    res = verify_program(prog)
    hits = res.by_code(PIPE_COLLECTIVE_CROSSES_STAGE)
    assert len(hits) == 1 and "stage 0" in hits[0].message


def test_remat_recompute_side_effect_diagnostic():
    from paddle_tpu.framework.analysis import (
        REMAT_RECOMPUTE_SIDE_EFFECT, verify_program)
    prog = Program()
    b = prog.global_block()
    b.create_var(name="x", shape=(8, 4), dtype="float32", is_data=True)
    for n in ("d", "m", "ck"):
        b.create_var(name=n, shape=(8, 4), dtype="float32")
    b.append_op(type="dropout", inputs={"X": ["x"]},
                outputs={"Out": ["d"], "Mask": ["m"]},
                attrs={"dropout_prob": 0.5, "is_test": False})
    b.append_op(type="scale", inputs={"X": ["d"]},
                outputs={"Out": ["ck"]}, attrs={"scale": 1.0})
    b.append_op(type="backward", inputs={}, outputs={},
                attrs={"loss_name": "ck", "param_names": [],
                       "checkpoints": ["ck"]})
    res = verify_program(prog)
    assert len(res.by_code(REMAT_RECOMPUTE_SIDE_EFFECT)) == 1
    # the audited-key stamp (pipe.apply_remat's contract) silences it
    b.ops[0].attrs["_folded_key"] = True
    prog._bump_version()
    assert not verify_program(prog).by_code(REMAT_RECOMPUTE_SIDE_EFFECT)


def test_overlap_compute_frac_flag():
    """Satellite: the 2/3 overlap constant is a flag now — default
    bit-identical, tunable for measured-cost calibration."""
    from paddle_tpu import flags
    from paddle_tpu.framework.memory_analysis import exposed_comm_model
    wire = {"grad_sync_wire_bytes": 9 * 10 ** 9,
            "forward_wire_bytes": 10 ** 9}
    base = exposed_comm_model(wire, flops_total=3e12, num_devices=2,
                              overlap=True, ici_gbps=1.0,
                              peak_flops=1e12)
    # default = the historical hard-coded constant, bit-for-bit
    assert base["overlap_compute_frac"] == 2.0 / 3.0
    assert base["overlappable_compute_s"] == \
        pytest.approx(1.5 * (2.0 / 3.0))
    assert base["cost_s"] == base["exposed_comm_s"]
    flags.set_flags({"overlap_compute_frac": 0.5})
    try:
        half = exposed_comm_model(wire, flops_total=3e12, num_devices=2,
                                  overlap=True, ici_gbps=1.0,
                                  peak_flops=1e12)
    finally:
        flags.set_flags({"overlap_compute_frac": 2.0 / 3.0})
    assert half["overlappable_compute_s"] == pytest.approx(0.75)
    assert half["exposed_comm_s"] > base["exposed_comm_s"]


def test_mesh_layout_pipe_axis_roundtrip():
    lay = MeshLayout(data=2, fsdp=1, tp=1, pipe=4)
    assert lay.pipe == 4 and lay.num_devices == 8
    assert lay.sizes["pp"] == 4
    assert lay.batch_axes == "dp"        # pipe never shards the batch
    back = MeshLayout.from_desc(lay.to_desc())
    assert back == lay and back.pipe == 4
    # pipe-less layouts keep the exact historical sizes dict
    assert MeshLayout(data=8).sizes == {"dp": 8, "fsdp": 1, "tp": 1}


# ---------------------------------------------------------------------------
# Pipeline v2: the schedule family simulator
# ---------------------------------------------------------------------------


def test_schedule_simulator_grid_invariants():
    """Every (family, S, M, v) cell: unit completeness, one unit per
    (tick, rank) slot, dependency order straight off the order table,
    exact idle accounting, and bubble ticks non-increasing in M."""
    from paddle_tpu.framework.pipe import simulate_schedule

    for S in (2, 4, 8):
        for family, v in (("1f1b", 1), ("interleaved", 2),
                          ("zero_bubble", 1)):
            prev_bubble = None
            for M in (1, 2, 8, 16):
                sch = simulate_schedule(family, S, M, chunks=v)
                V = sch["num_stages"]
                assert V == S * v and sch["num_ranks"] == S
                order = sch["order"]
                # unit completeness: F/B (+W for zero-bubble; stage 0's
                # whole backward IS its W) each exactly once per
                # (virtual stage, microbatch)
                units = {(k, ph, m) for _, k, ph, m in order}
                if family == "zero_bubble":
                    expect = {(k, ph, m) for k in range(V)
                              for m in range(M)
                              for ph in (("F", "W") if k == 0
                                         else ("F", "B", "W"))}
                else:
                    expect = {(k, ph, m) for k in range(V)
                              for m in range(M) for ph in ("F", "B")}
                assert units == expect and len(order) == len(expect)
                # one unit per (tick, rank) slot
                slots = [(t, k % S) for t, k, ph, m in order]
                assert len(slots) == len(set(slots))
                # dependency order from the table itself
                tick = {(k, ph, m): t for t, k, ph, m in order}
                for (k, ph, m), t in tick.items():
                    if ph == "F" and k > 0:
                        assert t > tick[(k - 1, "F", m)]
                    if ph == "B":
                        assert t > tick[(k, "F", m)]
                        if (k + 1, "B", m) in tick:
                            assert t > tick[(k + 1, "B", m)]
                    if ph == "W":
                        dep = (k, "B", m) if k > 0 else (1, "B", m)
                        if dep in tick:
                            assert t >= tick[dep]
                # exact idle accounting — the census-equality quantity
                assert sch["idle_slots"] == sch["ticks"] * S - len(order)
                assert sch["bubble_frac"] <= 1.0
                if prev_bubble is not None:
                    assert sch["bubble_ticks"] <= prev_bubble + 1e-9, \
                        f"{family} S{S}: bubble grew with M"
                prev_bubble = sch["bubble_ticks"]


def test_schedule_family_ordering():
    """1F1B bubbles are constant in M (2·S·(S−1)); interleaved v=2
    strictly beats it from M ≥ 2 (ties at M = 1); zero-bubble beats
    interleaved everywhere on the grid."""
    from paddle_tpu.framework.pipe import simulate_schedule

    for S in (2, 4, 8):
        for M in (1, 2, 8, 16):
            f1 = simulate_schedule("1f1b", S, M)
            iv = simulate_schedule("interleaved", S, M, chunks=2)
            zb = simulate_schedule("zero_bubble", S, M)
            assert f1["bubble_ticks"] == 2 * S * (S - 1)
            if M == 1:
                assert iv["bubble_ticks"] == f1["bubble_ticks"]
            else:
                assert iv["bubble_ticks"] < f1["bubble_ticks"]
            assert zb["bubble_ticks"] < iv["bubble_ticks"]


def test_enumerate_schedules_ranked():
    from paddle_tpu.framework.pipe import enumerate_schedules

    cands = enumerate_schedules(4, 8)
    assert {c["family"] for c in cands} == {"1f1b", "interleaved",
                                            "zero_bubble"}
    ticks = [c["bubble_ticks"] for c in cands]
    assert ticks == sorted(ticks)
    assert cands[0]["family"] == "zero_bubble"


# ---------------------------------------------------------------------------
# Pipeline v2: scheduled lowering parity + the idle-tick census
# ---------------------------------------------------------------------------


def _pipe_report():
    from paddle_tpu.framework.executor import last_pipeline_report
    rep = last_pipeline_report()
    assert rep, "no pipelined run recorded a report"
    return rep


def test_interleaved_schedule_parity_and_census():
    lb, wb = _train(lambda p: set_microbatches(p, 4))
    lp, wp = _train(lambda p: apply_pipeline(p, 2, 4,
                                             schedule="interleaved",
                                             chunks=2),
                    mesh_axes=(("pp", 2),))
    a = np.asarray(lb, dtype=np.float64).ravel()
    b = np.asarray(lp, dtype=np.float64).ravel()
    assert np.abs(a - b).max() <= 1e-6
    assert np.abs(wb - wp).max() <= 1e-6
    rep = _pipe_report()
    assert rep["family"] == "interleaved" and rep["chunks"] == 2
    assert rep["num_virtual_stages"] == 4
    assert rep["census_idle_slots"] == rep["sim_idle_slots"]
    assert rep["idle_branch_flop_prims"] == []


def test_zero_bubble_schedule_parity_and_census():
    lb, wb = _train(lambda p: set_microbatches(p, 4))
    lp, wp = _train(lambda p: apply_pipeline(p, 4, 4,
                                             schedule="zero_bubble"),
                    mesh_axes=(("pp", 4),))
    a = np.asarray(lb, dtype=np.float64).ravel()
    b = np.asarray(lp, dtype=np.float64).ravel()
    assert np.abs(a - b).max() <= 1e-6
    assert np.abs(wb - wp).max() <= 1e-6
    rep = _pipe_report()
    assert rep["family"] == "zero_bubble"
    assert rep["census_idle_slots"] == rep["sim_idle_slots"]
    assert rep["idle_branch_flop_prims"] == []


def test_1f1b_census_idle_equals_simulator():
    """The masked idle half-tick is gone: the lowering's per-tick busy
    census equals the simulator's idle slots EXACTLY, and the idle
    branch jaxpr contains zero FLOP primitives."""
    _train(lambda p: apply_pipeline(p, 2, 4), mesh_axes=(("pp", 2),))
    rep = _pipe_report()
    assert rep["family"] == "1f1b"
    assert rep["census_idle_slots"] == rep["sim_idle_slots"] == 4
    assert rep["idle_branch_flop_prims"] == []
    assert rep["bubble_frac"] == 4 / (rep["ticks"] * 2)


def test_pipe_weight_sharding_parity_and_specs():
    """shard_weights=True: pipe-axis ShardSpecs on params + coupled
    optimizer state, same losses/weights ≤ 1e-6, and the lowering
    census reports the sharded set."""
    from paddle_tpu.framework.pipe import apply_pipe_weight_sharding

    lb, wb = _train(lambda p: apply_pipeline(p, 2, 4),
                    mesh_axes=(("pp", 2),))
    specs = {}

    def mutate(p):
        apply_pipeline(p, 2, 4, shard_weights=True, min_shard_numel=1)
        blk = p.global_block()
        for prm in p.all_parameters():
            if prm.dist_attr:
                specs[prm.name] = tuple(prm.dist_attr)
        # Adam moments coupled to a sharded param carry the same spec
        m = next((v for n, v in blk.vars.items()
                  if n.startswith("w1_moment1")), None)
        assert m is not None
        assert tuple(m.dist_attr or ()) == specs.get("w1")

    ls, ws = _train(mutate, mesh_axes=(("pp", 2),))
    assert specs and any("pp" in s for s in specs.values())
    a = np.asarray(lb, dtype=np.float64).ravel()
    b = np.asarray(ls, dtype=np.float64).ravel()
    assert np.abs(a - b).max() <= 1e-6
    assert np.abs(wb - ws).max() <= 1e-6
    rep = _pipe_report()
    assert rep["sharded_params"], "lowering saw no sharded params"


def test_pipe_weight_sharding_divides_state_census():
    """memory_analysis divides resident persistable bytes by the pipe
    axis for the sharded set."""
    from paddle_tpu.framework.memory_analysis import analyze_memory

    def build(shard):
        reset_default_programs()
        main, startup = Program(), Program()
        with program_guard(main, startup):
            loss = _model()
            fluid.optimizer.Adam(5e-3).minimize(loss)
        apply_pipeline(main, 2, 4, shard_weights=shard,
                       min_shard_numel=1)
        fs = {"x": ((8, 16), "float32"), "label": ((8, 1), "float32")}
        return analyze_memory(main, feed_shapes=fs,
                              fetch_names=[loss.name],
                              mesh_axes={"pp": 2})

    rep_bytes = build(False).state_bytes
    sh_bytes = build(True).state_bytes
    assert sh_bytes < rep_bytes
    # the MLP's matrices all split: close to ÷2
    assert sh_bytes <= rep_bytes * 0.6


def test_pipe_sharded_checkpoint_restores_onto_fewer_stages(tmp_path):
    """A pp4 weight-sharded checkpoint restores onto a pp2 weight-sharded
    build mid-run (a planned reshard, no compile during the restore) and
    the continuation tracks the uninterrupted pp4 run to 1e-6."""
    from paddle_tpu import io
    from paddle_tpu.monitor import stat
    cut = 2

    def run(pp, steps, save_at=None, load=False):
        reset_default_programs()
        main, startup = Program(), Program()
        with program_guard(main, startup):
            loss = _model()
            fluid.optimizer.Adam(5e-3).minimize(loss)
        apply_pipeline(main, pp, 4, shard_weights=True, min_shard_numel=1)
        main._mesh_layout = MeshLayout(data=1, pipe=pp)
        prog = CompiledProgram(main).with_mesh(
            Mesh(np.array(jax.devices()[:pp]), ("pp",)),
            loss_name=loss.name, batch_axis="dp")
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        losses, status, compiles = [], None, 0
        with fluid.scope_guard(scope):
            exe.run(startup)
            if load:
                before = stat("executor_compile_count").get()
                status = io.load_checkpoint(exe, str(tmp_path),
                                            main_program=main, scope=scope)
                compiles = stat("executor_compile_count").get() - before
            for i in steps:
                (l,) = exe.run(prog, feed={"x": _XS[i], "label": _YS[i]},
                               fetch_list=[loss])
                losses.append(float(np.asarray(l).ravel()[0]))
                if i == save_at:
                    io.save_checkpoint(exe, str(tmp_path),
                                       io.TrainStatus(i, i), main)
        return losses, status, compiles

    ref, _, _ = run(4, range(STEPS))
    run(4, range(cut), save_at=cut - 1)
    cont, status, compiles = run(2, range(cut, STEPS), load=True)
    assert status is not None and status.reshard is not None
    assert status.reshard["src_layout"]["pp"] == 4
    assert compiles == 0
    assert np.abs(np.asarray(ref[cut:]) - np.asarray(cont)).max() <= 1e-6


# ---------------------------------------------------------------------------
# Pipeline v2: schedule diagnostics
# ---------------------------------------------------------------------------


def _pipelined_program():
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    apply_pipeline(main, 2, 2)
    blk = main.global_block()
    (bw,) = [op for op in blk.ops if op.type == "backward"]
    return main, bw


def test_pipe_schedule_order_diagnostic():
    from paddle_tpu.framework.analysis import (PIPE_SCHEDULE_ORDER,
                                               verify_program)
    main, bw = _pipelined_program()
    assert not verify_program(main).by_code(PIPE_SCHEDULE_ORDER)
    order = [list(u) for u in bw.attrs["pipe_schedule_order"]]
    # yank the first backward unit to tick 0 — before its own forward
    for u in order:
        if u[2] == "B":
            u[0] = 0
            break
    bw.attrs["pipe_schedule_order"] = [tuple(u) for u in order]
    hits = verify_program(main).by_code(PIPE_SCHEDULE_ORDER)
    assert hits and all(h.severity == "error" for h in hits)


def test_pipe_ring_overflow_diagnostic():
    from paddle_tpu.framework.analysis import (PIPE_RING_OVERFLOW,
                                               verify_program)
    main, bw = _pipelined_program()
    assert not verify_program(main).by_code(PIPE_RING_OVERFLOW)
    bw.attrs["pipe_ring_slots"] = [0, 0]
    hits = verify_program(main).by_code(PIPE_RING_OVERFLOW)
    assert hits and all(h.severity == "error" for h in hits)


# ---------------------------------------------------------------------------
# Pipeline v2: the schedule-aware planner
# ---------------------------------------------------------------------------


def test_planner_schedule_auto_picks_best_without_compiling(monkeypatch):
    """pipe_schedule="auto": every pipe row is priced with its
    bubble-ranked best schedule family — and the whole search runs with
    Executor._compile monkeypatched to raise, proving the pricing never
    leaves the static path."""
    from paddle_tpu.framework import executor as executor_mod

    main, _, loss, fs, _ = _bert_tiny_train()
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True

    def boom(*a, **k):
        raise AssertionError("plan search attempted a compile")

    monkeypatch.setattr(executor_mod.Executor, "_compile", boom)
    plan = plan_sharding(main, 4, loss_name=loss.name, feed_shapes=fs,
                         fetch_names=[loss.name], build_strategy=bs,
                         max_pipe=2, num_microbatches=4,
                         pipe_schedule="auto")
    assert plan.pipe_schedule == "auto"
    rows = [c for c in plan.configs if c.layout.pipe > 1
            and not c.error]
    assert rows
    for c in rows:
        summary = c.pipe_report["schedule_summary"]
        cands = c.pipe_report["schedule_candidates"]
        assert len(cands) >= 3
        assert summary["bubble_ticks"] == \
            min(x["bubble_ticks"] for x in cands)
        # the priced bubble is the winner's EXACT per-tick fraction,
        # not the analytic (pipe-1)/M
        assert c.exposed["bubble_frac"] == \
            pytest.approx(summary["bubble_frac"])


def test_planner_pipe1_rows_schedule_invariant():
    """pipe = 1 pricing is bit-stable across schedule knobs: the
    schedule only exists on pipe > 1 rows."""
    main, _, loss, fs, _ = _bert_tiny_train()
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True

    def rows(schedule):
        plan = plan_sharding(main, 4, loss_name=loss.name,
                             feed_shapes=fs, fetch_names=[loss.name],
                             build_strategy=bs, max_pipe=2,
                             num_microbatches=4,
                             pipe_schedule=schedule)
        return {tuple(sorted(c.layout.sizes.items())): c.as_dict()
                for c in plan.configs if c.layout.pipe == 1}

    base, auto = rows("1f1b"), rows("auto")
    assert base.keys() == auto.keys()
    for k in base:
        assert base[k] == auto[k]


# ---------------------------------------------------------------------------
# Pipeline v2: telemetry
# ---------------------------------------------------------------------------


def test_telemetry_records_bubble_frac(tmp_path):
    """A pipelined step's telemetry record carries the schedule's
    measured bubble fraction; validate_jsonl accepts it."""
    from paddle_tpu.observability.recorder import (TelemetryRecorder,
                                                   validate_jsonl)

    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    apply_pipeline(main, 2, 4)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("pp",))
    prog = CompiledProgram(main).with_mesh(mesh, loss_name=loss.name,
                                           batch_axis="dp",
                                           build_strategy=BuildStrategy())
    exe = fluid.Executor(fluid.CPUPlace())
    path = str(tmp_path / "t.jsonl")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with TelemetryRecorder(path, program=main) as rec:
            (l,) = exe.run(prog, feed={"x": _XS[0], "label": _YS[0]},
                           fetch_list=[loss])
            r = rec.record_step(wall_ns=1e9, loss=float(np.mean(l)))
    from paddle_tpu.framework.pipe import simulate_schedule
    expect = simulate_schedule("1f1b", 2, 4)["bubble_frac"]
    assert r["pipe_schedule"] == "1f1b"
    assert r["bubble_frac"] == pytest.approx(expect, abs=1e-6)
    facts = validate_jsonl(path)
    assert facts["steps"] == 1
