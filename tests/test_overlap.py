"""Overlap-aware collective scheduling legs (the ready-order grad-sync
harness): under ``strategy.overlap_grad_sync`` the bucket pass splits
grad-sync buckets by gradient ready rank (reverse layer order) and the
executor fires each bucket's fused collective INSIDE the backward sweep
via a custom-vjp hook, so the collective precedes the remaining
backward compute in the lowered module instead of sinking to the tail.

Contracts proven here:

* loss/weight BIT-parity on dp8 — overlap moves the collectives, not
  the math — for plain fp32, bf16-compressed, int8-quantized, ZeRO-1
  and fsdp-hybrid composition legs, each against its tail placement
  (``flag("overlap_lowering") = False`` lowers the identical ready-
  order IR at the tail) and the classic tail-fused baseline;
* program-level ready-order census: ≥4 buckets, ready ranks in
  emission order, hook positions strictly descending (last layer's
  grads sync first);
* lowered-module ordering census (importing the census helpers from
  tools/verify_multichip_lowering): overlapped grad-sync all_reduces
  precede later backward GEMMs, the tail-fused baseline's precede none;
* ZeRO-3 gather prefetch (``prefetch_distance``): issue positions lead
  first-use positions, bit-parity vs distance 0;
* the planner's exposed-comm roofline: ranking distinguishes configs
  with equal wire bytes but different hideability, and a forced HBM
  budget flips the winner while the winner still minimizes exposed
  comm among fitting configs;
* telemetry: steps carry ``exposed_comm_frac`` ∈ [0, 1];
* misuse diagnostics (overlap-single-bucket / overlap-tail-sunk) and
  the overlap × localsgd strategy rejection.
"""

import json
import re

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu import flags
from paddle_tpu.framework.core import (Program, program_guard,
                                       reset_default_programs)
from paddle_tpu.framework.compiler import (BuildStrategy, CompiledProgram,
                                           insert_grad_sync, make_mesh)
from paddle_tpu.framework.fsdp import apply_fsdp_sharding
from paddle_tpu.framework.mesh_layout import MeshLayout
from paddle_tpu.distributed.fleet import (fleet, DistributedStrategy,
                                          distributed_optimizer,
                                          UserDefinedRoleMaker)

STEPS = 4
N_LAYERS = 6


@pytest.fixture(autouse=True)
def _overlap_lowering_on():
    """Every leg starts from the default lowering mode."""
    flags.set_flags({"overlap_lowering": True})
    yield
    flags.set_flags({"overlap_lowering": True})


def _model():
    """A deep-enough fc stack that ready-order bucketing has layers to
    rank (one param per layer, constant init for determinism)."""
    x = fluid.layers.data("x", shape=[16])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, 32, act="relu",
                        param_attr=fluid.ParamAttr(
                            name="w0",
                            initializer=fluid.initializer.Constant(0.05)),
                        bias_attr=False)
    for i in range(1, N_LAYERS):
        h = fluid.layers.fc(
            h, 32, act="relu",
            param_attr=fluid.ParamAttr(
                name=f"w{i}",
                initializer=fluid.initializer.Constant(0.03 + 0.003 * i)),
            bias_attr=False)
    pred = fluid.layers.fc(h, 4, act="softmax",
                           param_attr=fluid.ParamAttr(
                               name="wp",
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


def _run_leg(mutate_strategy=None, ndev=8, lowering=True):
    """Train via the fleet surface; returns (losses, w1, main program).
    Losses are raw ndarrays so comparisons can be BITWISE."""
    flags.set_flags({"overlap_lowering": lowering})
    reset_default_programs()
    main, startup = Program(), Program()
    from jax.sharding import Mesh
    with program_guard(main, startup):
        loss = _model()
        fleet.init(UserDefinedRoleMaker(0, 1))
        strategy = DistributedStrategy()
        if ndev > 1:
            strategy.mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))
        else:
            strategy.mesh = None
        if mutate_strategy:
            mutate_strategy(strategy)
        opt = distributed_optimizer(fluid.optimizer.Adam(5e-3), strategy)
        opt.minimize(loss)
    prog = fleet.main_program if ndev > 1 else main
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for xs, ys in _batches():
            l, = exe.run(prog, feed={"x": xs, "label": ys},
                         fetch_list=[loss])
            losses.append(np.asarray(l))
        w1 = np.asarray(scope.find_var("w1"))
    return losses, w1, main


def _overlap(s):
    s.overlap_grad_sync = True
    s.overlap_configs = {"bucket_mb": 4, "min_buckets": 4}


def _bitwise(a, b):
    assert len(a) == len(b)
    return all(np.array_equal(x, y) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# dp8 bit-parity legs
# ---------------------------------------------------------------------------


def test_dp8_overlap_bit_parity_and_ready_order():
    """Overlap restructures WHEN the collectives run, not what they
    compute: dp8 losses/weights match the classic tail-fused run
    BITWISE, and the ready-order census holds (≥4 buckets, ranks in
    emission order, hook positions strictly descending)."""
    tail_l, tail_w, _ = _run_leg()                     # classic tail-fused
    ov_l, ov_w, main = _run_leg(_overlap)

    assert _bitwise(tail_l, ov_l)
    np.testing.assert_array_equal(tail_w, ov_w)

    buckets = [op for op in main.global_block().ops
               if op.type == "c_fused_allreduce_sum"]
    assert len(buckets) >= 4
    assert all(op.attrs.get("_overlap") for op in buckets)
    ranks = [op.attrs["_ready_rank"] for op in buckets]
    assert ranks == sorted(ranks), "buckets not emitted in ready order"
    hooks = [op.attrs["_overlap_hook_pos"] for op in buckets]
    assert hooks == sorted(hooks, reverse=True) and \
        len(set(hooks)) == len(hooks), \
        "ready order is not reverse first-use order"
    # bucket_index attrs ride along for the tracing spans
    assert [op.attrs["_bucket_index"] for op in buckets] == ranks


def test_dp8_overlap_tail_sunk_control_bit_parity():
    """flag("overlap_lowering")=False lowers the IDENTICAL ready-order
    IR with every collective at the tail — the schedule-only control:
    bitwise equality proves the hooks change placement, not values."""
    on_l, on_w, _ = _run_leg(_overlap, lowering=True)
    off_l, off_w, _ = _run_leg(_overlap, lowering=False)
    assert _bitwise(on_l, off_l)
    np.testing.assert_array_equal(on_w, off_w)


def test_dp8_overlap_bf16_bit_parity():
    def mut(s):
        _overlap(s)
        s.bf16_allreduce = True
    on_l, on_w, main = _run_leg(mut, lowering=True)
    off_l, off_w, _ = _run_leg(mut, lowering=False)
    assert _bitwise(on_l, off_l)
    np.testing.assert_array_equal(on_w, off_w)
    # the compressed tier rode the ready-order buckets
    buckets = [op for op in main.global_block().ops
               if op.type == "c_fused_allreduce_sum"]
    assert len(buckets) >= 4
    assert all(op.attrs.get("compress_dtype") == "bfloat16"
               for op in buckets)
    # loose sanity vs the fp32 overlap run (bf16 wire noise only)
    fp_l, _, _ = _run_leg(_overlap)
    np.testing.assert_allclose(
        [float(np.asarray(l).reshape(())) for l in on_l],
        [float(np.asarray(l).reshape(())) for l in fp_l], rtol=5e-2)


def test_dp8_overlap_int8_quant_bit_parity():
    def mut(s):
        _overlap(s)
        s.quant_allreduce = True
        s.quant_configs = {"dtype": "int8", "block_size": 64}
    on_l, on_w, main = _run_leg(mut, lowering=True)
    off_l, off_w, _ = _run_leg(mut, lowering=False)
    assert _bitwise(on_l, off_l)
    np.testing.assert_array_equal(on_w, off_w)
    buckets = [op for op in main.global_block().ops
               if op.type == "c_fused_quant_allreduce_sum"]
    assert len(buckets) >= 4
    assert all(op.attrs.get("_overlap") for op in buckets)
    fp_l, _, _ = _run_leg(_overlap)
    np.testing.assert_allclose(
        [float(np.asarray(l).reshape(())) for l in on_l],
        [float(np.asarray(l).reshape(())) for l in fp_l], rtol=5e-2)


def test_overlap_composes_with_zero1():
    """ZeRO-1's grad sync is its own reduce_scatter (no ready-order
    buckets to hook yet) — overlap_grad_sync must compose inertly:
    identical training bitwise, and no overlap-annotated ops."""
    def zero1(s):
        s.sharded_update = True

    def zero1_overlap(s):
        s.sharded_update = True
        _overlap(s)

    base_l, base_w, _ = _run_leg(zero1)
    ov_l, ov_w, main = _run_leg(zero1_overlap)
    assert _bitwise(base_l, ov_l)
    np.testing.assert_array_equal(base_w, ov_w)
    assert not any(op.attrs.get("_overlap")
                   for op in main.global_block().ops)


def test_overlap_composes_with_fsdp_hybrid():
    """data2 × fsdp4 HSDP: the fsdp grad sync rides the gather
    transposes (already inside backward); the remaining data-axis
    reduction rides the ready-order buckets.  Overlap-on vs tail
    placement is bitwise; both match the unsharded baseline loosely."""
    def build():
        reset_default_programs()
        main, startup = Program(), Program()
        with program_guard(main, startup):
            loss = _model()
            fluid.optimizer.Adam(5e-3).minimize(loss)
        layout = MeshLayout(data=2, fsdp=4, tp=1)
        apply_fsdp_sharding(main, layout, min_shard_numel=64)
        main._mesh_layout = layout
        bs = BuildStrategy()
        bs.fuse_all_reduce_ops = True
        bs.overlap_grad_sync = True
        bs.overlap_min_buckets = 4
        prog = CompiledProgram(main).with_mesh(
            layout.build_mesh(), loss_name=loss.name,
            batch_axis=layout.batch_axes, build_strategy=bs)
        return main, startup, prog, loss

    def train(lowering):
        flags.set_flags({"overlap_lowering": lowering})
        main, startup, prog, loss = build()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for xs, ys in _batches():
                l, = exe.run(prog, feed={"x": xs, "label": ys},
                             fetch_list=[loss])
                losses.append(np.asarray(l))
        return losses, main

    on_l, main = train(True)
    off_l, _ = train(False)
    assert _bitwise(on_l, off_l)
    # data-axis buckets exist and are ready-ordered; fsdp params reduce
    # over the data axis only (fsdp rides the gather transpose)
    buckets = [op for op in main.global_block().ops
               if op.type == "c_fused_allreduce_sum"
               and op.attrs.get("_overlap")]
    assert buckets, "no ready-order buckets on the hybrid layout"
    assert all(op.attrs["_axis_name"] == "dp" for op in buckets)
    base_l, _, _ = _run_leg(mutate_strategy=None, ndev=1)
    np.testing.assert_allclose(
        [float(np.asarray(l).reshape(())) for l in on_l],
        [float(np.asarray(l).reshape(())) for l in base_l], rtol=2e-3)


def test_overlap_composes_with_amp_and_gradient_merge():
    def stack(s):
        _overlap(s)
        s.amp = True
        s.gradient_merge = True
        s.gradient_merge_configs = {"k_steps": 2, "avg": True}
    on_l, on_w, _ = _run_leg(stack, lowering=True)
    off_l, off_w, _ = _run_leg(stack, lowering=False)
    assert _bitwise(on_l, off_l)
    np.testing.assert_array_equal(on_w, off_w)


# ---------------------------------------------------------------------------
# ZeRO-3 gather prefetch
# ---------------------------------------------------------------------------


def test_fsdp_prefetch_distance_issues_early_bit_parity():
    """prefetch_distance=1 inserts layer k+1's gather at layer k's
    first-use position (issue < first use for every non-leading
    gather), changing placement only: training is bitwise identical."""
    def build(dist):
        reset_default_programs()
        main, startup = Program(), Program()
        with program_guard(main, startup):
            loss = _model()
            fluid.optimizer.Adam(5e-3).minimize(loss)
        layout = MeshLayout(data=1, fsdp=8, tp=1)
        report = apply_fsdp_sharding(main, layout, min_shard_numel=64,
                                     prefetch_distance=dist)
        main._mesh_layout = layout
        bs = BuildStrategy()
        bs.fuse_all_reduce_ops = True
        prog = CompiledProgram(main).with_mesh(
            layout.build_mesh(), loss_name=loss.name,
            batch_axis=layout.batch_axes, build_strategy=bs)
        return main, startup, prog, loss, report

    def train(dist):
        main, startup, prog, loss, report = build(dist)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for xs, ys in _batches():
                l, = exe.run(prog, feed={"x": xs, "label": ys},
                             fetch_list=[loss])
                losses.append(np.asarray(l))
        return losses, main, report

    l0, main0, rep0 = train(0)
    l1, main1, rep1 = train(1)
    assert _bitwise(l0, l1)
    assert rep1["prefetch_distance"] == 1

    recs = sorted(rep1["sharded"], key=lambda r: r["window"][0])
    assert len(recs) >= 3
    # the leading gather stays at its first use; every later gather is
    # issued at the PREVIOUS gather's first-use position
    assert recs[0]["issue"] == recs[0]["window"][0]
    for prev, rec in zip(recs, recs[1:]):
        assert rec["issue"] == prev["window"][0] < rec["window"][0]
    # distance 0 keeps gather-at-first-use
    assert all(r["issue"] == r["window"][0] for r in rep0["sharded"])
    # and in the rewritten block each gather op really precedes the
    # recorded consumers: its full-copy output is defined before use
    block = main1.global_block()
    for i, op in enumerate(block.ops):
        if op.type != "fsdp_all_gather":
            continue
        out = op.outputs["Out"][0]
        readers = [j for j, o in enumerate(block.ops)
                   if out in o.input_names()]
        assert readers and min(readers) > i


# ---------------------------------------------------------------------------
# lowered-module ordering census
# ---------------------------------------------------------------------------


def _export_dp8(main, startup, loss_name, mesh):
    from jax import export as jexp
    from paddle_tpu.ops.pallas import lowering_target
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xs, ys = _batches(1)[0]
        feed = {"x": xs, "label": ys}
        step = exe._compile(main, feed, [loss_name], scope, mesh,
                            ("dp",), "dp")
        state = {}
        for n in step.state_in_names:
            a = np.asarray(scope.find_var(n))
            if a.dtype == np.float64:      # x64 off: canonicalize
                a = a.astype(np.float32)
            state[n] = a
        with lowering_target("tpu"):
            exported = jexp.export(step.fn, platforms=("tpu",))(
                feed, state, jax.random.PRNGKey(0))
    return exported.mlir_module()


def test_module_ordering_census_interleaves_grad_sync():
    """The lowered dp8 module carries the ready-order buckets BETWEEN
    backward GEMMs (each bucket except the final ones precedes later
    dot_generals); the tail-fused baseline's grad sync precedes none."""
    from tools.verify_multichip_lowering import ordering_census

    def build(overlap):
        reset_default_programs()
        main, startup = Program(), Program()
        with program_guard(main, startup):
            loss = _model()
            fluid.optimizer.Adam(5e-3).minimize(loss)
        mesh = make_mesh(8, "dp")
        bs = BuildStrategy()
        bs.fuse_all_reduce_ops = True
        bs.overlap_grad_sync = overlap
        bs.overlap_min_buckets = 4
        CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, mesh=mesh, build_strategy=bs)
        return main, startup, loss, mesh

    main, startup, loss, mesh = build(True)
    rows = ordering_census(_export_dp8(main, startup, loss.name, mesh))
    ar = [r for r in rows if r["kind"] == "all_reduce"]
    interleaved = [r for r in ar if r["compute_after"] > 0]
    assert len(interleaved) >= 4, rows
    n_overlapped = len(ar)

    main, startup, loss, mesh = build(False)
    rows = ordering_census(_export_dp8(main, startup, loss.name, mesh))
    ar = [r for r in rows if r["kind"] == "all_reduce"]
    assert all(r["compute_after"] == 0 for r in ar), rows
    # the tail-fused sync is a couple of giant collectives
    assert len(ar) <= 2 < n_overlapped


# ---------------------------------------------------------------------------
# exposed-comm pricing + planner ranking
# ---------------------------------------------------------------------------


def test_exposed_comm_model_math():
    from paddle_tpu.framework.memory_analysis import exposed_comm_model
    wire = {"grad_sync_wire_bytes": 90e9, "forward_wire_bytes": 45e9}
    # 1 s grad wire + 0.5 s fwd wire at 90 GB/s; 3e12 FLOPs over 2
    # devices at 1e12 FLOP/s → 1.5 s compute, 1 s of it backward
    m = exposed_comm_model(wire, flops_total=3e12, num_devices=2,
                           overlap=True, ici_gbps=90.0, peak_flops=1e12)
    assert m["overlappable_compute_s"] == pytest.approx(1.0)
    assert m["hidden_s"] == pytest.approx(1.0)       # grad wire hidden
    assert m["exposed_comm_s"] == pytest.approx(0.5)  # fwd wire exposed
    off = exposed_comm_model(wire, flops_total=3e12, num_devices=2,
                             overlap=False, ici_gbps=90.0,
                             peak_flops=1e12)
    assert off["hidden_s"] == 0.0
    assert off["exposed_comm_s"] == pytest.approx(1.5)
    # hiding clamps at the available grad wire
    m2 = exposed_comm_model({"grad_sync_wire_bytes": 9e9,
                             "forward_wire_bytes": 0}, flops_total=3e12,
                            num_devices=2, overlap=True, ici_gbps=90.0,
                            peak_flops=1e12)
    assert m2["hidden_s"] == pytest.approx(0.1)
    assert m2["exposed_comm_s"] == pytest.approx(0.0)


def test_planner_exposed_ranking_and_budget_flip():
    """With overlap pricing on, a pure-dp config's grad sync hides under
    backward compute while an fsdp config's forward gathers stay
    exposed — so at EQUAL total wire bytes dp8 outranks fsdp8 (the
    wire-only ranking cannot tell them apart).  A forced HBM budget
    then excludes the replicated-param dp configs and the winner flips
    to an fsdp config that minimizes EXPOSED comm among fitting."""
    from paddle_tpu.framework.shard_planner import plan_sharding

    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    bs.overlap_grad_sync = True
    # slow "device" → plenty of backward compute to hide under
    flags.set_flags({"device_peak_flops": 1e9})
    try:
        free = plan_sharding(main, 8, loss_name=loss.name,
                             fetch_names=[loss.name], build_strategy=bs,
                             min_shard_numel=64)
        by_layout = {(c.layout.data, c.layout.fsdp): c
                     for c in free.configs}
        dp8, fsdp8 = by_layout[(8, 1)], by_layout[(1, 8)]
        assert dp8.wire_bytes == fsdp8.wire_bytes, \
            "legs no longer comparable at equal wire"
        assert dp8.exposed_comm_s < fsdp8.exposed_comm_s, \
            "fsdp forward gathers should be exposed, dp grad sync hidden"
        assert free.winner.layout.fsdp == 1

        peaks = sorted(c.peak_bytes for c in free.configs)
        budget_gb = (peaks[0] + peaks[-1]) / 2 / float(1 << 30)
        plan = plan_sharding(main, 8, loss_name=loss.name,
                             fetch_names=[loss.name], build_strategy=bs,
                             min_shard_numel=64, hbm_budget_gb=budget_gb)
        assert plan.winner.layout.fsdp > 1, plan.report()
        fitting = [c for c in plan.configs if c.fits]
        best = min(round(c.exposed_comm_s * 1e9) for c in fitting)
        assert round(plan.winner.exposed_comm_s * 1e9) == best
    finally:
        flags.set_flags({"device_peak_flops": 0.0})


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


def test_telemetry_steps_report_exposed_comm_fraction(tmp_path):
    from paddle_tpu.observability.recorder import (TelemetryRecorder,
                                                   validate_jsonl)

    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    mesh = make_mesh(8, "dp")
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    bs.overlap_grad_sync = True
    prog = CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, mesh=mesh, build_strategy=bs)

    path = str(tmp_path / "telemetry.jsonl")
    xs, ys = _batches(1)[0]
    rec = TelemetryRecorder(
        path, program=main,
        feed_shapes={"x": (tuple(xs.shape), "float32"),
                     "label": (tuple(ys.shape), "int64")},
        fetch_names=[loss.name], mesh_axes={"dp": 8})
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for xs, ys in _batches(2):
            with rec.step(examples=64) as st:
                l, = exe.run(prog, feed={"x": xs, "label": ys},
                             fetch_list=[loss])
                st.loss = l
    rec.close()

    facts = validate_jsonl(path)
    header = facts["header"]
    assert header["static"]["overlap_grad_sync"] is True
    assert header["static"]["exposed_comm_s_per_step"] is not None
    assert header["static"]["grad_sync_wire_bytes"] > 0
    with open(path) as f:
        steps = [json.loads(ln) for ln in f if ln.strip()]
    steps = [s for s in steps if s.get("record") == "step"]
    assert len(steps) == 2
    for s in steps:
        assert 0.0 <= s["exposed_comm_frac"] <= 1.0
        assert s["exposed_comm_ms"] >= 0.0


# ---------------------------------------------------------------------------
# diagnostics + strategy validation
# ---------------------------------------------------------------------------


def test_overlap_diagnostics_single_bucket_and_tail_sunk():
    from paddle_tpu.framework.analysis import (OVERLAP_SINGLE_BUCKET,
                                               OVERLAP_TAIL_SUNK,
                                               verify_program)

    # a giant cap + min_buckets=1 coalesces the whole dtype group into
    # one bucket — overlap requested, nothing can hide
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    bs.overlap_grad_sync = True
    bs.overlap_bucket_size_in_MB = 1024
    bs.overlap_min_buckets = 1
    insert_grad_sync(main, bs, 8, ("dp",), axis_sizes={"dp": 8})
    res = verify_program(main)
    single = res.by_code(OVERLAP_SINGLE_BUCKET)
    assert len(single) == 1
    assert single[0].severity == "warning"
    assert "nothing hides" in single[0].message or \
        "cannot interleave" in single[0].message

    # a ready-ordered collective whose bucket has no hook position
    # (param without a recorded forward read) warns tail-sunk
    prog = Program()
    block = prog.global_block()
    for n in ("ga", "gb"):
        block.create_var(name=n, shape=(1 << 16,), dtype="float32",
                         is_data=True)
    base = {"ring_id": 0, "_axis_name": "dp", "_overlap": True}
    block.append_op(type="c_fused_allreduce_sum", inputs={"X": ["ga"]},
                    outputs={"Out": ["ga"]},
                    attrs=dict(base, _ready_rank=0, _bucket_index=0,
                               _overlap_hook_pos=4))
    block.append_op(type="c_fused_allreduce_sum", inputs={"X": ["gb"]},
                    outputs={"Out": ["gb"]},
                    attrs=dict(base, _ready_rank=1, _bucket_index=1))
    res = verify_program(prog)
    sunk = res.by_code(OVERLAP_TAIL_SUNK)
    assert len(sunk) == 1 and "gb" in sunk[0].message
    assert not res.by_code(OVERLAP_SINGLE_BUCKET)


def test_overlap_rejects_localsgd():
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fleet.init(UserDefinedRoleMaker(0, 1))
        s = DistributedStrategy()
        s.overlap_grad_sync = True
        s.localsgd = True
        opt = distributed_optimizer(fluid.optimizer.Adam(5e-3), s)
        with pytest.raises(ValueError, match="overlap_grad_sync"):
            opt.minimize(loss)
