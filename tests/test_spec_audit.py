"""Differential spec auditor tests (framework/spec_audit.py): the
jaxpr flop counter and StableHLO collective census units, seeded drift
in each of the four channels (corrupt ONE spec, the auditor must anchor
exactly that op under the right ``spec-drift-*`` code, with zero false
positives on the clean program), the trace-free ``audit_static`` tier
wired into proglint/plan_sharding, and the spec-coverage ratchet."""

import json
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.framework.core import (Program, program_guard,
                                       reset_default_programs)
from paddle_tpu.framework.spec_audit import (
    DEFAULT_TOLERANCES, SPEC_KIND_DECOMP, audit_static, audit_step,
    count_jaxpr_flops, hlo_collective_census)
from paddle_tpu.ops.registry import OP_SPECS, VarSig, spec_coverage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(vocab=32, width=256, hidden=512):
    x = fluid.layers.data("x", shape=[width])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, hidden, act="relu", bias_attr=False)
    h2 = fluid.layers.fc(h, hidden, act="relu", bias_attr=False)
    pred = fluid.layers.fc(h2, vocab, act="softmax", bias_attr=False)
    return fluid.layers.mean(fluid.layers.cross_entropy(pred, label))


def _mlp_feed(vocab=32, width=256, batch=256):
    rng = np.random.RandomState(0)
    return {"x": rng.randn(batch, width).astype(np.float32),
            "label": rng.randint(0, vocab, (batch, 1)).astype(np.int64)}


def _single_device_audit(channels):
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _mlp()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        return audit_step(exe, main, _mlp_feed(), [loss.name], scope,
                          channels=channels)


# ---------------------------------------------------------------------------
# units: the two ground-truth parsers
# ---------------------------------------------------------------------------


def test_count_jaxpr_flops_dot_general_exact():
    import jax
    import jax.numpy as jnp
    jx = jax.make_jaxpr(jnp.dot)(np.ones((4, 8), np.float32),
                                 np.ones((8, 16), np.float32))
    assert count_jaxpr_flops(jx) == 2 * 4 * 8 * 16


def test_count_jaxpr_flops_elementwise_and_reduce():
    import jax
    import jax.numpy as jnp
    jx = jax.make_jaxpr(lambda a: jnp.sum(jnp.tanh(a)))(
        np.ones((8, 8), np.float32))
    # tanh: 64 output elems; reduce_sum: 64 operand elems
    assert count_jaxpr_flops(jx) == 64 + 64


def test_hlo_collective_census_region_and_inline_ops():
    txt = """module {
  %1 = "stablehlo.all_reduce"(%0) <{replica_groups = dense<0> : tensor<1x8xi64>}> ({
  ^bb0(%a: tensor<f32>, %b: tensor<f32>):
    stablehlo.return %a : tensor<f32>
  }) : (tensor<1024xf32>) -> tensor<1024xf32>
  %2 = "stablehlo.all_gather"(%1) <{all_gather_dim = 0 : i64, replica_groups = dense<0> : tensor<2x4xi64>}> : (tensor<8x4xf32>) -> tensor<32x4xf32>
}"""
    census = hlo_collective_census(txt)
    ar = census["all_reduce"]
    assert ar["count"] == 1 and ar["bytes"] == 1024 * 4
    # ring all_reduce: 2 passes of (n-1)/n payload, n=8
    assert ar["wire_bytes"] == pytest.approx(2 * (7 / 8) * 4096)
    ag = census["all_gather"]
    assert ag["count"] == 1 and ag["bytes"] == 32 * 4 * 4
    assert ag["wire_bytes"] == pytest.approx((3 / 4) * 512)
    assert "reduce_scatter" not in census


def test_spec_kind_decomp_fractions_sum_to_one():
    for op_type, parts in SPEC_KIND_DECOMP.items():
        assert sum(frac for _, frac in parts) == pytest.approx(1.0), \
            op_type


# ---------------------------------------------------------------------------
# seeded drift: one corrupt spec per channel, exact-op anchoring
# ---------------------------------------------------------------------------


def test_seeded_shape_drift_anchors_exactly_that_op():
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _mlp()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    shapes = {"x": ((256, 256), "float32"), "label": ((256, 1), "int64")}
    clean = audit_static(main, feed_shapes=shapes,
                         fetch_names=[loss.name])
    assert clean.ok and not clean.drift(), \
        [(d.code, d.op_type) for d in clean.drift()]
    spec = OP_SPECS["relu"]
    orig = spec.infer

    def bad_infer(ins, attrs):
        out = orig(ins, attrs)
        return {k: [VarSig(v.shape, "float16") for v in vs]
                for k, vs in out.items()}

    spec.infer = bad_infer
    try:
        rep = audit_static(main, feed_shapes=shapes,
                           fetch_names=[loss.name])
    finally:
        spec.infer = orig
    drift = rep.drift()
    assert drift and not rep.ok
    assert {d.op_type for d in drift} == {"relu"}
    assert all(d.code == "spec-drift-shape" for d in drift)
    # anchored at the op's creation site — this file
    assert any("test_spec_audit.py" in frame
               for frame in drift[0].callstack), drift[0].callstack


def test_seeded_flops_drift_anchors_worst_gap_op():
    spec = OP_SPECS["mul"]
    orig = spec.flops
    spec.flops = lambda ins, outs, attrs: (orig(ins, outs, attrs) or 0) * 2
    try:
        rep = _single_device_audit(("flops",))
    finally:
        spec.flops = orig
    drift = rep.drift("spec-drift-flops")
    assert drift and not rep.ok
    assert drift[0].op_type == "mul"
    assert "mul" in drift[0].message
    row = rep.channels["flops"]
    assert abs(row["rel_err"]) > row["tolerance"]
    # clean re-run of the same program: zero false positives
    rep = _single_device_audit(("flops",))
    assert rep.ok and not rep.drift(), \
        [(d.code, d.op_type) for d in rep.drift()]


@pytest.mark.skipif(
    __import__("jax").device_count() < 8,
    reason="needs the 8-device virtual CPU mesh")
def test_seeded_wire_drift_anchors_collective():
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.distributed.fleet import (DistributedStrategy,
                                              UserDefinedRoleMaker,
                                              distributed_optimizer,
                                              fleet)

    def build():
        reset_default_programs()
        main, startup = Program(), Program()
        with program_guard(main, startup):
            loss = _mlp()
            fleet.init(UserDefinedRoleMaker(0, 1))
            strategy = DistributedStrategy()
            mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
            strategy.mesh = mesh
            opt = distributed_optimizer(fluid.optimizer.Adam(5e-3),
                                        strategy)
            opt.minimize(loss)
        return fleet.main_program, startup, loss, mesh

    def run(prog, startup, loss, mesh):
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            return audit_step(exe, prog, _mlp_feed(), [loss.name],
                              scope, mesh=mesh, axis_names=("dp",),
                              batch_axis="dp", channels=("wire",))

    prog, startup, loss, mesh = build()
    present = {op.type for op in prog.global_block().ops}
    ar_type = next(t for t in ("c_fused_allreduce_sum",
                               "c_allreduce_sum") if t in present)
    rep = run(prog, startup, loss, mesh)
    assert rep.ok and not rep.drift(), \
        [(d.code, d.op_type) for d in rep.drift()]
    spec = OP_SPECS[ar_type]
    orig = spec.wire

    def half_wire(ins, attrs, mesh_axes):
        r = orig(ins, attrs, mesh_axes)
        if r is None:
            return None
        kind, wire = r
        return kind, wire * 0.5

    spec.wire = half_wire
    try:
        prog, startup, loss, mesh = build()
        rep = run(prog, startup, loss, mesh)
    finally:
        spec.wire = orig
    drift = rep.drift("spec-drift-wire")
    assert drift and not rep.ok
    # anchored at the program's heaviest contributor to the drifted kind
    assert drift[0].op_type == ar_type
    assert "all_reduce" in drift[0].message
    row = rep.channels["wire"]["kinds"]["all_reduce"]
    assert row["rel_err"] == pytest.approx(-0.5, abs=0.02)


def test_seeded_mem_drift_anchors_internal_bytes_suspect():
    """Dropping fused_attention's ``mem_backward_extra`` (the attention
    probability residuals) pushes the 64x8 transformer rung out of the
    mem band; the auditor must anchor fused_attention — the suspect
    whose lowered impl materialises the most op-internal bytes — not
    merely the first mem-unspecced op in block order."""
    import sys
    sys.path.insert(0, REPO)
    try:
        from tools.spec_audit_probe import ladder_leg
    finally:
        sys.path.pop(0)
    spec = OP_SPECS["fused_attention"]
    orig = spec.mem_backward_extra
    spec.mem_backward_extra = None
    try:
        leg = ladder_leg(64, 8)
    finally:
        spec.mem_backward_extra = orig
    drift = [d for d in leg["drift"] if d["code"] == "spec-drift-mem"]
    assert drift, leg["drift"]
    assert drift[0]["op_type"] == "fused_attention"
    assert "worst suspect 'fused_attention'" in drift[0]["message"]
    assert not leg["channels"]["mem"]["within_tolerance"]
    # the only drift is the seeded one — shape/flops stayed clean
    assert {d["code"] for d in leg["drift"]} == {"spec-drift-mem"}


def test_clean_single_device_audit_all_channels():
    """Zero drift on the clean MLP across every compiled channel."""
    rep = _single_device_audit(("shape", "flops", "mem"))
    assert rep.ok and not rep.drift(), \
        [(d.code, d.op_type, d.message) for d in rep.drift()]
    assert rep.channels["shape"]["checked"] > 0
    assert rep.channels["shape"]["drifted_ops"] == []
    assert rep.channels["flops"]["within_tolerance"]
    assert rep.channels["mem"]["within_tolerance"]


# ---------------------------------------------------------------------------
# the trace-free tier: proglint --audit and plan_sharding(audit_winner)
# ---------------------------------------------------------------------------


@pytest.mark.skipif(
    __import__("jax").device_count() < 8,
    reason="needs the 8-device virtual CPU mesh")
@pytest.mark.parametrize("leg", ["dp8", "zero3", "tp2", "pp4"])
def test_clean_mesh_audit_reconciles_wire(leg):
    """BERT-tiny on each mesh family audits clean, and the collectives
    the static wire channel prices are the ones in the lowered module."""
    import sys
    sys.path.insert(0, REPO)
    try:
        from tools import spec_audit_probe
    finally:
        sys.path.pop(0)
    rep = getattr(spec_audit_probe, leg + "_leg")()
    assert rep["ok"] and rep["drift"] == [], rep["drift"]
    assert rep["channels"]["shape"]["checked"] > 0
    assert rep["channels"]["shape"]["drifted_ops"] == []
    kinds = rep["channels"]["wire"]["kinds"]
    assert all(row["within_tolerance"] for row in kinds.values()), kinds
    want = {"dp8": {"all_reduce"},
            # the fsdp gather and its reduce_scatter transpose
            "zero3": {"all_gather", "reduce_scatter"},
            "tp2": {"all_reduce"},
            "pp4": {"collective_permute"}}[leg]
    assert all(kinds[k]["hlo_count"] >= 1 for k in want), kinds
    if leg == "dp8":
        assert rep["channels"]["flops"]["shard_divisor"] == 8
    if leg == "pp4":        # boundary hops lower; bytes are schedule-bound
        assert kinds["collective_permute"]["structural_only"]


def test_proglint_audit_flag_reports_and_gates():
    import io
    import sys
    sys.path.insert(0, REPO)
    try:
        from tools.proglint import lint
    finally:
        sys.path.pop(0)
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _mlp()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    sink = io.StringIO()
    rc = lint(main, fetch_names=[loss.name], audit=True, as_json=True,
              out=sink)
    payload = json.loads(sink.getvalue())
    assert rc == 0
    audit = payload["spec_audit"]
    assert audit["ok"] is True and audit["drift"] == []
    assert audit["channels"]["wire"]["static_only"] is True
    # the census keys are emitted sorted (byte-stable CI output)
    keys = list(payload.get("unspecced_ops", {}))
    assert keys == sorted(keys)
    # a corrupted spec flips the exit code through the same entrypoint
    spec = OP_SPECS["relu"]
    orig = spec.infer
    spec.infer = lambda ins, attrs: {
        k: [VarSig(v.shape, "float16") for v in vs]
        for k, vs in orig(ins, attrs).items()}
    try:
        sink = io.StringIO()
        rc = lint(main, fetch_names=[loss.name], audit=True,
                  as_json=True, out=sink)
    finally:
        spec.infer = orig
    payload = json.loads(sink.getvalue())
    assert rc != 0
    assert payload["spec_audit"]["ok"] is False
    assert payload["spec_audit"]["drift"][0]["op_type"] == "relu"


def test_plan_sharding_audits_winner_clone():
    from paddle_tpu.framework.shard_planner import plan_sharding
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _mlp(width=16, hidden=32, vocab=4)
        fluid.optimizer.Adam(5e-3).minimize(loss)
    plan = plan_sharding(main, 8, loss_name=loss.name,
                         fetch_names=[loss.name], audit_winner=True)
    assert plan.winner is not None
    audit = plan.winner_audit
    assert audit is not None and audit.get("ok") is True, audit
    assert audit["drift"] == []
    assert audit["layout"]["sizes"] if "sizes" in audit["layout"] \
        else audit["layout"]
    assert plan.as_dict()["winner_audit"]["ok"] is True
    # without the flag the plan stays audit-free (no hidden cost)
    plan2 = plan_sharding(main, 8, loss_name=loss.name,
                          fetch_names=[loss.name])
    assert plan2.winner_audit is None


# ---------------------------------------------------------------------------
# coverage ratchet
# ---------------------------------------------------------------------------

#: ops with an opinion on each spec channel; raise a floor when a PR adds
#: specs, lower it only with the op it counted
COVERAGE_FLOOR = {"infer": 125, "flops": 20, "wire": 17, "mem": 53}


def test_spec_coverage_ratchet_never_regresses():
    """Removing a spec (or a channel opinion) fails tier-1."""
    live = {ch: len(ops) for ch, ops in spec_coverage().items()}
    assert set(live) == set(COVERAGE_FLOOR)
    for ch, floor in COVERAGE_FLOOR.items():
        assert live[ch] >= floor, (ch, live[ch], floor)


def test_mem_uncovered_suspects_census():
    from paddle_tpu.framework.memory_analysis import mem_uncovered_suspects
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _mlp()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    suspects = mem_uncovered_suspects(main)
    assert suspects == sorted(set(suspects))
    # every suspect really is an op of the program with no mem opinion
    present = {op.type for op in main.global_block().ops}
    assert set(suspects) <= present
    for t in suspects:
        spec = OP_SPECS.get(t)
        if spec is not None:
            assert spec.mem_transparent is None
            assert spec.mem_backward_extra is None
