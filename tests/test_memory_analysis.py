"""Static liveness & peak-HBM analyzer tests
(framework/memory_analysis.py): liveness intervals across while/cond
sub-blocks, sharding- and donation-aware per-device byte accounting,
seeded defects for the three memory lint classes with callstack-anchored
diagnostics, the ``hbm_budget_gb`` pre-compile gate, and the
estimator-vs-XLA tolerance legs on CPU."""

import json
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import flags
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.framework.memory_analysis import (
    DONATION_GAP, FETCH_RETENTION, GRAD_ACCUM_DOUBLING, RESIDUAL_FACTOR,
    analyze_memory, block_liveness, check_hbm_budget, lint_memory,
    mesh_axes_of, program_liveness, sig_bytes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _one(result, code, severity="warning"):
    hits = result.by_code(code)
    assert hits, (f"no {code!r} diagnostic; got "
                  f"{[(d.code, d.message) for d in result.diagnostics]}")
    assert all(d.severity == severity for d in hits)
    return hits[0]


def _assert_anchored(diag):
    assert any("test_memory_analysis.py" in frame
               for frame in diag.callstack), \
        f"callstack not anchored to user site: {diag.callstack}"


# ---------------------------------------------------------------------------
# byte pricing
# ---------------------------------------------------------------------------


def test_sig_bytes_prices_canonical_dtypes():
    from paddle_tpu.ops.registry import VarSig, dtype_nbytes
    # int64 feeds canonicalise to int32 on device (x64 off) — 4 bytes
    assert dtype_nbytes("int64") == 4
    assert dtype_nbytes("float32") == 4
    assert dtype_nbytes("bfloat16") == 2          # amp width is real
    assert sig_bytes(VarSig((4, 8), "int64")) == 4 * 8 * 4
    assert sig_bytes(VarSig((4, 8), "bfloat16")) == 4 * 8 * 2
    # unknown dims price at the hint
    assert sig_bytes(VarSig((-1, 8), "float32"), unknown_dim=16) == \
        16 * 8 * 4
    assert sig_bytes(None) == 0


# ---------------------------------------------------------------------------
# liveness: def/last-use intervals, sub-block recursion, pinning
# ---------------------------------------------------------------------------


def test_block_liveness_intervals_and_pinning():
    p = Program()
    b = p.global_block()
    b.create_var(name="x", shape=(4,), is_data=True)
    b.create_var(name="t1", shape=(4,))
    b.create_var(name="t2", shape=(4,))
    b.create_var(name="out", shape=(4,))
    b.append_op(type="relu", inputs={"X": ["x"]}, outputs={"Out": ["t1"]})
    b.append_op(type="tanh", inputs={"X": ["t1"]}, outputs={"Out": ["t2"]})
    b.append_op(type="scale", inputs={"X": ["t2"]},
                outputs={"Out": ["out"]}, attrs={"scale": 2.0})
    live = block_liveness(b, feed_names=["x"], fetch_names=["out"])
    assert live["t1"].def_idx == 0 and live["t1"].last_use == 1
    assert live["t2"].def_idx == 1 and live["t2"].last_use == 2
    assert not live["t1"].pinned
    assert live["x"].pinned                      # data/feed root
    assert live["out"].pinned                    # fetch target
    # t1 is dead at op #2, t2 is live there
    assert not live["t1"].live_at(2, 2)
    assert live["t2"].live_at(2, 2)
    # creation-site anchor rides the interval
    assert live["t1"].def_op.type == "relu"


def test_liveness_extends_across_while_subblock():
    """A var whose ONLY consumer lives inside a while body must stay
    live through the while op (the closure contract _prune follows)."""
    p = Program()
    b = p.global_block()
    b.create_var(name="x", shape=(4,), is_data=True)
    b.create_var(name="h", shape=(4,))
    b.create_var(name="out", shape=(4,))
    b.append_op(type="relu", inputs={"X": ["x"]}, outputs={"Out": ["h"]})
    b.append_op(type="tanh", inputs={"X": ["x"]}, outputs={"Out": ["out"]})
    sub = p._create_block()
    sub.append_op(type="tanh", inputs={"X": ["h"]}, outputs={"Out": ["h"]})
    p._rollback()
    b.append_op(type="while_loop", inputs={"X": ["x"]},
                outputs={"Out": ["out"]},
                attrs={"body_block": sub, "x_names": ["x"],
                       "closure_names": ["h"]})
    live = block_liveness(b)
    # without sub-block recursion h's last use would be op #0 (its def);
    # the while op at index 2 reads it through the body block
    assert live["h"].last_use == 2
    assert live["h"].live_at(1, 2) and live["h"].live_at(2, 2)


def test_program_liveness_covers_cond_subblocks():
    p = Program()
    b = p.global_block()
    b.create_var(name="x", shape=(4,), is_data=True)
    b.create_var(name="cond", shape=(1,), dtype="bool", is_data=True)
    b.create_var(name="out", shape=(4,))
    sub = p._create_block()
    sub.append_op(type="relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]})
    sub.append_op(type="tanh", inputs={"X": ["y"]}, outputs={"Out": ["y"]})
    p._rollback()
    b.append_op(type="conditional_block",
                inputs={"Cond": ["cond"], "Closure": ["x"]},
                outputs={"Out": ["out"]},
                attrs={"true_block": sub, "closure_names": ["x"]})
    tables = program_liveness(p)
    # the sub-block has its OWN interval table: y defined and consumed
    # inside it
    assert tables[sub.idx]["y"].def_idx == 0
    assert tables[sub.idx]["y"].last_use == 1
    # and the parent op pins x as a use at its own index
    assert tables[0]["x"].last_use == 0


# ---------------------------------------------------------------------------
# estimate: sharding- and donation-aware per-device accounting
# ---------------------------------------------------------------------------


def _mlp(hidden=64, feat=32):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[feat])
        h = fluid.layers.fc(x, hidden, act="relu", bias_attr=False)
        loss = fluid.layers.mean(h)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    return main, startup, loss


def test_estimate_components_add_up_and_report():
    main, startup, loss = _mlp()
    feed = {"x": np.zeros((8, 32), np.float32)}
    est = analyze_memory(main, feed_shapes=feed, fetch_names=[loss.name])
    assert est.peak_bytes == est.args_bytes + est.transient_bytes
    # params: w [32,64] fp32; opt state: two Adam moments + LR/betas
    assert est.param_bytes == 32 * 64 * 4
    assert est.opt_state_bytes >= 2 * 32 * 64 * 4
    assert est.feed_bytes == 8 * 32 * 4
    assert est.top_live and est.top_live[0].nbytes >= est.top_live[-1].nbytes
    r = est.report()
    assert "peak HBM estimate" in r and "top live tensors" in r
    d = est.as_dict()
    assert d["peak_bytes"] == est.peak_bytes
    assert d["top_live"][0]["bytes"] == est.top_live[0].nbytes


def test_estimate_prices_feed_dims_not_declared_dims():
    main, startup, loss = _mlp()
    small = analyze_memory(main, feed_shapes={"x": np.zeros((2, 32),
                                                            np.float32)},
                           fetch_names=[loss.name])
    big = analyze_memory(main, feed_shapes={"x": np.zeros((64, 32),
                                                          np.float32)},
                         fetch_names=[loss.name])
    assert big.feed_bytes == 32 * small.feed_bytes
    assert big.peak_bytes > small.peak_bytes


def test_estimate_divides_by_mesh_sharding():
    """Per-device accounting: feeds divide by the batch axis, dist_attr
    persistables (tp shards / ZeRO-1 flat state shards) by their axes,
    replicated params count full."""
    main, startup, loss = _mlp(hidden=128)
    blk = main.global_block()
    # pretend the Adam moments were ZeRO-1 sharded over dp
    for name, v in blk.vars.items():
        if "moment" in name:
            v.dist_attr = ("dp",)
    feed = {"x": np.zeros((64, 32), np.float32)}
    solo = analyze_memory(main, feed_shapes=feed, fetch_names=[loss.name])
    dp8 = analyze_memory(main, feed_shapes=feed, fetch_names=[loss.name],
                         mesh_axes={"dp": 8}, batch_axis="dp")
    assert dp8.feed_bytes == solo.feed_bytes // 8
    assert dp8.param_bytes == solo.param_bytes          # replicated
    # moments shard 1/8; the small scalar state (LR, betas) stays full
    assert dp8.opt_state_bytes < solo.opt_state_bytes
    moments = 2 * 32 * 128 * 4
    assert solo.opt_state_bytes - dp8.opt_state_bytes == \
        moments - moments // 8


def test_donate_state_false_counts_written_state_twice():
    main, startup, loss = _mlp()
    feed = {"x": np.zeros((8, 32), np.float32)}
    donated = analyze_memory(main, feed_shapes=feed,
                             fetch_names=[loss.name], donate_state=True)
    served = analyze_memory(main, feed_shapes=feed,
                            fetch_names=[loss.name], donate_state=False)
    # every written persistable is a fresh (non-aliased) output buffer
    assert served.peak_bytes > donated.peak_bytes
    assert served.output_bytes > donated.output_bytes
    assert any("counted twice" in n for n in served.notes)


def test_bf16_params_price_at_two_bytes():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16], dtype="bfloat16")
        w = main.global_block().create_parameter(
            name="wbf16", shape=(16, 16), dtype="bfloat16")
        y = fluid.layers.matmul(x, w)
        loss = fluid.layers.mean(y)
    est = analyze_memory(main, feed_shapes={"x": ((4, 16), "bfloat16")},
                         fetch_names=[loss.name])
    assert est.param_bytes == 16 * 16 * 2
    assert est.feed_bytes == 4 * 16 * 2


# ---------------------------------------------------------------------------
# seeded defects: the three memory lint classes
# ---------------------------------------------------------------------------


def test_lint_donation_gap_on_detached_update():
    """The optimizer's update lands in a separate buffer: the param gets
    a gradient but is never written — the 2× live-set growth class."""
    main, startup, loss = _mlp()
    blk = main.global_block()
    for op in blk.ops:
        if op.type == "adam":
            pname = op.outputs["ParamOut"][0]
            stale = blk.create_var(name=pname + "_detached",
                                   shape=blk.var(pname).shape)
            op.outputs["ParamOut"] = [stale.name]
    r = lint_memory(main, fetch_names=[loss.name])
    d = _one(r, DONATION_GAP)
    _assert_anchored(d)
    assert "never updated in place" in d.message
    # the healthy program is clean
    main2, startup2, loss2 = _mlp()
    assert not lint_memory(main2,
                           fetch_names=[loss2.name]).by_code(DONATION_GAP)


def test_lint_fetch_retention_on_early_activation():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[32])
        h1 = fluid.layers.fc(x, 256, act="relu")     # early, fat
        h2 = fluid.layers.fc(h1, 4)
        loss = fluid.layers.mean(h2)
        fluid.optimizer.SGD(0.1).minimize(loss)
    r = lint_memory(main, fetch_names=[loss.name, h1.name])
    d = _one(r, FETCH_RETENTION)
    _assert_anchored(d)
    assert "pins it across the peak" in d.message
    # fetching only the loss is clean
    assert not lint_memory(main,
                           fetch_names=[loss.name]).by_code(FETCH_RETENTION)


def test_lint_grad_accum_doubling_on_gradient_merge():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16])
        h = fluid.layers.fc(x, 32)
        loss = fluid.layers.mean(h)
        opt = fluid.optimizer.GradientMergeOptimizer(
            fluid.optimizer.SGD(0.1), k_steps=4)
        opt.minimize(loss)
    r = lint_memory(main, fetch_names=[loss.name])
    d = _one(r, GRAD_ACCUM_DOUBLING)
    _assert_anchored(d)
    assert "doubles the per-device gradient live set" in d.message
    # plain SGD has no accumulators
    main2, startup2, loss2 = _mlp()
    assert not lint_memory(
        main2, fetch_names=[loss2.name]).by_code(GRAD_ACCUM_DOUBLING)


# ---------------------------------------------------------------------------
# hbm_budget_gb: the pre-compile gate
# ---------------------------------------------------------------------------


def test_budget_gate_rejects_before_any_compile():
    from paddle_tpu.monitor import stat
    main, startup, loss = _mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.ones((8, 32), np.float32)}
    before = stat("executor_compile_count").get()
    flags.set_flags({"hbm_budget_gb": 1e-7})
    try:
        with pytest.raises(InvalidArgumentError) as ei:
            exe.prepare(main, fetch_list=[loss], feed=feed)
        msg = str(ei.value)
        assert "hbm_budget_gb" in msg and "rejected before compile" in msg
        assert "top live tensors" in msg          # actionable failure
        # the failure happened BEFORE any XLA compile was attempted
        assert stat("executor_compile_count").get() == before
        # Executor.run is gated too
        with pytest.raises(InvalidArgumentError):
            exe.run(main, feed=feed, fetch_list=[loss])
        assert stat("executor_compile_count").get() == before
    finally:
        flags.set_flags({"hbm_budget_gb": 0.0})


def test_budget_gate_admits_under_budget_and_default_off():
    main, startup, loss = _mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.ones((8, 32), np.float32)}
    flags.set_flags({"hbm_budget_gb": 4.0})
    try:
        p = exe.prepare(main, fetch_list=[loss], feed=feed)
        out, = p.run(feed)
        assert np.isfinite(out.numpy()).all()
        p.close()
    finally:
        flags.set_flags({"hbm_budget_gb": 0.0})
    # default is off: no flag set, no gate
    assert flags.flag("hbm_budget_gb") == 0.0
    out, = exe.run(main, feed=feed, fetch_list=[loss])
    assert np.isfinite(out).all()


def test_budget_gate_on_compiled_program_variant():
    main, startup, loss = _mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    strategy = fluid.BuildStrategy()
    strategy.fuse_elewise_add_act_ops = True
    cp = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, build_strategy=strategy,
        places=[fluid.CPUPlace()])
    flags.set_flags({"hbm_budget_gb": 1e-7})
    try:
        with pytest.raises(InvalidArgumentError):
            cp._variant_for([loss.name])
    finally:
        flags.set_flags({"hbm_budget_gb": 0.0})


def test_wire_accounting_quant_vs_full_precision():
    """The op_spec ``wire`` channel: grad-sync collectives report true
    ICI bytes — equal to logical for fp32 buckets (ratio 1.0), ≥3.5×
    smaller for int8-quantized buckets — and the fields ride
    ``as_dict``/``report`` for proglint/CI consumption."""
    import jax
    from paddle_tpu.framework.compiler import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh conftest")

    def leg(quant):
        main, startup, loss = _mlp()
        bs = fluid.BuildStrategy()
        bs.fuse_all_reduce_ops = True
        if quant:
            bs.allreduce_quant_spec = {"dtype": "int8", "block_size": 256}
        fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, mesh=make_mesh(8, "dp"),
            build_strategy=bs)
        return analyze_memory(main, fetch_names=[loss.name],
                              mesh_axes={"dp": 8}, batch_axis="dp")

    full, quant = leg(False), leg(True)
    assert full.wire_bytes == full.wire_logical_bytes > 0
    assert quant.wire_logical_bytes == full.wire_logical_bytes
    assert full.wire_bytes / quant.wire_bytes >= 3.5
    d = quant.as_dict()
    assert d["wire_compression_ratio"] >= 3.5
    assert "compression" in quant.report()
    assert full.as_dict()["wire_compression_ratio"] == 1.0


def test_check_hbm_budget_api_direct():
    main, startup, loss = _mlp()
    est = analyze_memory(main, fetch_names=[loss.name])
    with pytest.raises(InvalidArgumentError):
        check_hbm_budget(main, fetch_names=[loss.name],
                         budget_gb=est.peak_gb / 2)
    ok = check_hbm_budget(main, fetch_names=[loss.name],
                          budget_gb=est.peak_gb * 2)
    assert ok is not None and ok.peak_bytes == est.peak_bytes
    # gate off → no work, returns None
    assert check_hbm_budget(main, fetch_names=[loss.name],
                            budget_gb=0.0) is None


# ---------------------------------------------------------------------------
# estimator vs XLA ground truth (live CPU legs)
# ---------------------------------------------------------------------------


def test_estimator_within_tolerance_live_cpu_leg():
    """The smallest transformer-bench rung, live: static estimate within
    ±15% of XLA memory_analysis argument+temp bytes."""
    import sys
    sys.path.insert(0, REPO)
    try:
        from tools.mem_probe import TOLERANCE, ladder_leg
    finally:
        sys.path.pop(0)
    leg = ladder_leg(8, 4)
    assert leg["within_tolerance"], leg
    assert abs(leg["rel_err"]) <= TOLERANCE
    # arguments must match exactly: the sharding/donation/dtype
    # accounting is byte-precise even where the transient is a model
    assert leg["estimate"]["args_bytes"] == \
        leg["xla"]["argument_bytes"]


@pytest.mark.skipif(
    __import__("jax").device_count() < 8,
    reason="needs the 8-device virtual CPU mesh")
@pytest.mark.parametrize("sharded", [False, True], ids=["dp8", "zero1"])
def test_estimator_within_tolerance_dp8_leg_live(sharded):
    import sys
    sys.path.insert(0, REPO)
    try:
        from tools.mem_probe import multichip_leg
    finally:
        sys.path.pop(0)
    leg = multichip_leg(sharded=sharded)
    assert leg["within_tolerance"], leg
    assert leg["estimate"]["args_bytes"] == leg["xla"]["argument_bytes"]
    if sharded:
        # ZeRO-1 shards the update state: its arguments sit well under
        # the replicated leg's
        dp8 = multichip_leg(sharded=False)
        assert leg["xla"]["argument_bytes"] < \
            0.6 * dp8["xla"]["argument_bytes"]


# ---------------------------------------------------------------------------
# proglint: --memory / --json / --strict census gate
# ---------------------------------------------------------------------------


def _proglint():
    import sys
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import proglint
        return proglint
    finally:
        sys.path.pop(0)


def test_proglint_memory_json_report(capsys):
    proglint = _proglint()
    main, startup, loss = _mlp()
    rc = proglint.lint(main, fetch_names=[loss.name], memory=True,
                       as_json=True)
    out = capsys.readouterr().out
    assert rc == 0
    payload = json.loads(out)
    assert payload["errors"] == 0
    assert "unspecced_ops" in payload
    assert payload["memory"]["peak_bytes"] > 0
    assert payload["memory"]["param_bytes"] == 32 * 64 * 4
    assert isinstance(payload["diagnostics"], list)


def test_proglint_strict_fails_on_unspecced_census(capsys):
    from paddle_tpu.ops.registry import OPS, register
    proglint = _proglint()
    if "memtest_unspecced_op" not in OPS:
        register("memtest_unspecced_op")(
            lambda ctx, ins, attrs: {"Out": ins["X"][0]})
    p = Program()
    b = p.global_block()
    b.create_var(name="x", shape=(4,), is_data=True)
    b.create_var(name="y", shape=(4,))
    b.append_op(type="memtest_unspecced_op", inputs={"X": ["x"]},
                outputs={"Out": ["y"]})
    # non-strict: census is informational
    assert proglint.lint(p) == 0
    # strict: a non-empty unspecced census fails the gate, so op_spec
    # coverage can never silently regress
    assert proglint.lint(p, strict=True) == 1
    capsys.readouterr()
    # and the census itself rides the JSON report
    proglint.lint(p, as_json=True)
    payload = json.loads(capsys.readouterr().out)
    assert payload["unspecced_ops"] == {"memtest_unspecced_op": 1}


def test_proglint_memory_lints_ride_the_report(capsys):
    proglint = _proglint()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16])
        h = fluid.layers.fc(x, 32)
        loss = fluid.layers.mean(h)
        opt = fluid.optimizer.GradientMergeOptimizer(
            fluid.optimizer.SGD(0.1), k_steps=2)
        opt.minimize(loss)
    rc = proglint.lint(main, fetch_names=[loss.name], memory=True,
                       as_json=True)
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0                                # warnings, not errors
    codes = {d["code"] for d in payload["diagnostics"]}
    assert GRAD_ACCUM_DOUBLING in codes
