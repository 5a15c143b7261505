#!/usr/bin/env python
"""spec_audit_probe — differential audit of every static spec channel.

For each leg, builds the training program and runs the spec auditor
(framework/spec_audit.py): the program is lowered ONCE through the
executor's own lowering path (no execution) and each static channel is
reconciled against its ground truth —

  shape  per-op ``infer`` claims vs ``jax.eval_shape`` over the
         registered impls (the avals the real trace produces);
  flops  ``estimate_step_flops`` totals vs XLA ``cost_analysis()``
         (per-device module, so the spec total divides by the device
         count under a mesh);
  wire   ``wire()`` ring-priced collective bytes vs the StableHLO
         collective census of the lowered module (same ring model,
         replica groups parsed from the text);
  mem    ``analyze_memory`` peak-HBM vs compiled ``memory_analysis()``
         argument+temp bytes.

Legs:
  * transformer ladder (16x4, 64x8) — shape+flops+mem at two
    activation scales, single device;
  * dp8       — MLP under a dp=8 mesh: all four channels, the
    all_reduce grad sync priced byte-for-byte;
  * zero3     — BERT-tiny under fsdp=8 ZeRO-3: shape+wire, the fsdp
    gather/scatter pair decomposed 0.5/0.5 across HLO kinds;
  * tp2       — BERT-tiny Megatron tp=2 over a dp4xtp2 mesh:
    shape+wire, mp collectives plus the logits-gather transpose;
  * pp4       — BERT-tiny under a 4-stage pipeline: shape+wire with
    the structural collective_permute check (boundary hops must
    lower), flops/mem skipped (unbalanced stages break the ideal
    SPMD divisor).

tests/test_spec_audit.py runs the legs live.

Usage:
  python tools/spec_audit_probe.py              # all legs
  python tools/spec_audit_probe.py --selftest   # fast subset + seeded drift
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"
                           ).strip()

LADDER = ((16, 4), (64, 8))


def _leg_result(name, rep):
    return {
        "leg": name,
        "channels": {k: dict(v) for k, v in rep.channels.items()},
        "drift": [{"code": d.code, "op_type": d.op_type,
                   "message": d.message} for d in rep.drift()],
        "ok": rep.ok,
    }


def ladder_leg(bucket, batch):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.core import reset_default_programs
    from paddle_tpu.framework.spec_audit import audit_step
    from paddle_tpu.models import transformer

    reset_default_programs()
    cfg = transformer.TransformerConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        feeds, loss, logits = transformer.build_train_network(cfg)
        fluid.optimizer.Adam(1e-4).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    src = [list(rng.randint(3, 100, min(bucket - 2, cfg.max_length - 2)))
           for _ in range(batch)]
    trg = [list(rng.randint(3, 100, min(bucket - 3, cfg.max_length - 3)))
           for _ in range(batch)]
    feed = {k: np.asarray(v) for k, v in transformer.make_batch(
        src, trg, cfg, bucket_ladder=(bucket,)).items()}
    with fluid.scope_guard(scope):
        exe.run(startup)
        rep = audit_step(exe, main, feed, [loss.name], scope,
                         channels=("shape", "flops", "mem"))
    return _leg_result(f"transformer_ladder_{bucket}x{batch}", rep)


def dp8_leg():
    import jax
    import paddle_tpu.fluid as fluid
    from jax.sharding import Mesh
    from paddle_tpu.distributed.fleet import (DistributedStrategy,
                                              UserDefinedRoleMaker,
                                              distributed_optimizer, fleet)
    from paddle_tpu.framework.core import (Program, program_guard,
                                           reset_default_programs)
    from paddle_tpu.framework.spec_audit import audit_step

    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[256])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 512, act="relu", bias_attr=False)
        h2 = fluid.layers.fc(h, 512, act="relu", bias_attr=False)
        pred = fluid.layers.fc(h2, 32, act="softmax", bias_attr=False)
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fleet.init(UserDefinedRoleMaker(0, 1))
        strategy = DistributedStrategy()
        mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
        strategy.mesh = mesh
        opt = distributed_optimizer(fluid.optimizer.Adam(5e-3), strategy)
        opt.minimize(loss)
    prog = fleet.main_program
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(256, 256).astype(np.float32),
            "label": rng.randint(0, 32, (256, 1)).astype(np.int64)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        rep = audit_step(exe, prog, feed, [loss.name], scope, mesh=mesh,
                         axis_names=("dp",), batch_axis="dp")
    return _leg_result("dp8", rep)


def zero3_leg():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.compiler import (BuildStrategy,
                                               CompiledProgram)
    from paddle_tpu.framework.core import reset_default_programs
    from paddle_tpu.framework.fsdp import apply_fsdp_sharding
    from paddle_tpu.framework.mesh_layout import MeshLayout
    from paddle_tpu.framework.spec_audit import audit_step
    from paddle_tpu.models import bert

    reset_default_programs()
    cfg = bert.BertConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(cfg)
        fluid.optimizer.Adam(1e-4).minimize(total)
    layout = MeshLayout(data=1, fsdp=8, tp=1)
    apply_fsdp_sharding(main, layout)
    main._mesh_layout = layout
    mesh = layout.build_mesh()
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    CompiledProgram(main).with_mesh(mesh, loss_name=total.name,
                                    batch_axis=layout.batch_axes,
                                    build_strategy=bs)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        data = bert.make_fake_batch(np.random.RandomState(0), cfg,
                                    batch_size=8, seq_len=64, num_masks=3)
        feed = {k: np.asarray(v) for k, v in data.items()}
        rep = audit_step(exe, main, feed, [total.name], scope, mesh=mesh,
                         axis_names=tuple(mesh.axis_names),
                         batch_axis=layout.batch_axes,
                         channels=("shape", "wire"))
    return _leg_result("zero3_fsdp8", rep)


def tp2_leg():
    import jax
    import paddle_tpu.fluid as fluid
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.framework.core import reset_default_programs
    from paddle_tpu.framework.spec_audit import audit_step
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import build_mesh

    reset_default_programs()
    mesh = build_mesh({"dp": 4, "tp": 2}, jax.devices()[:8])
    cfg = bert.BertConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        feeds, loss = bert.build_pretrain_network_parallel(cfg, tp_degree=2)
        fluid.optimizer.Adam(1e-4).minimize(loss)
    feed_specs = {f.name: P("dp") for f in feeds}
    fluid.CompiledProgram(main).with_mesh(
        mesh, loss_name=loss.name, batch_axis="dp", feed_specs=feed_specs)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        data = bert.make_fake_parallel_batch(np.random.RandomState(0), cfg,
                                             batch_size=4, seq_len=64)
        feed = {k: np.asarray(v) for k, v in data.items()}
        rep = audit_step(exe, main, feed, [loss.name], scope, mesh=mesh,
                         axis_names=tuple(mesh.axis_names),
                         batch_axis="dp", feed_specs=feed_specs,
                         channels=("shape", "wire"))
    return _leg_result("tp2_dp4", rep)


def pp4_leg():
    import jax
    import paddle_tpu.fluid as fluid
    from jax.sharding import Mesh
    from paddle_tpu.framework.compiler import (BuildStrategy,
                                               CompiledProgram)
    from paddle_tpu.framework.core import Program, reset_default_programs
    from paddle_tpu.framework.pipe import apply_pipeline
    from paddle_tpu.framework.spec_audit import audit_step
    from paddle_tpu.models import bert

    reset_default_programs()
    cfg = bert.BertConfig.tiny()
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_dropout_prob = 0.0
    main, startup = Program(), Program()
    with fluid.program_guard(main, startup):
        feeds, loss = bert.build_pretrain_network_parallel(cfg)
        fluid.optimizer.Adam(1e-4).minimize(loss)
    batch = bert.make_fake_parallel_batch(np.random.RandomState(0), cfg,
                                          batch_size=8, seq_len=64)
    feed_shapes = {k: (tuple(v.shape), str(v.dtype))
                   for k, v in batch.items()}
    apply_pipeline(main, 4, 4, feed_shapes=feed_shapes)
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    mesh = Mesh(np.array(jax.devices()[:4]), ("pp",))
    CompiledProgram(main).with_mesh(mesh, loss_name=loss.name,
                                    batch_axis="dp", build_strategy=bs)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {k: np.asarray(v) for k, v in batch.items()}
        # the mesh carries no dp axis: with_mesh filters batch_axis the
        # same way, so the audit lowering must see None too
        rep = audit_step(exe, main, feed, [loss.name], scope, mesh=mesh,
                         axis_names=("pp",), batch_axis=None,
                         channels=("shape", "wire"))
    return _leg_result("pp4", rep)


def run_probe():
    from paddle_tpu.framework.spec_audit import DEFAULT_TOLERANCES
    from paddle_tpu.ops.registry import spec_coverage

    legs = [ladder_leg(b, n) for b, n in LADDER]
    legs += [dp8_leg(), zero3_leg(), tp2_leg(), pp4_leg()]
    worst = {"flops": 0.0, "wire": 0.0, "mem": 0.0}
    shape_drift = 0
    for leg in legs:
        ch = leg["channels"]
        shape_drift += len(ch.get("shape", {}).get("drifted_ops", []))
        for name in ("flops", "mem"):
            rel = ch.get(name, {}).get("rel_err")
            if rel is not None:
                worst[name] = max(worst[name], abs(rel))
        if "wire" in ch:
            worst["wire"] = max(worst["wire"],
                                ch["wire"].get("worst_abs_rel_err", 0.0))
    return {
        "metric": "spec_audit_differential",
        "definition": "per-channel reconciliation of the static op_spec "
                      "claims (infer/flops/wire/mem) against the lowered "
                      "program: jax.eval_shape avals, XLA cost_analysis, "
                      "the StableHLO collective census under the ring "
                      "model, and compiled memory_analysis arg+temp "
                      "bytes (CPU backend ground truth)",
        "tolerances": dict(DEFAULT_TOLERANCES),
        "coverage": {ch: {"count": len(ops), "ops": list(ops)}
                     for ch, ops in spec_coverage().items()},
        "worst_abs_rel_err": {k: round(v, 4) for k, v in worst.items()},
        "shape_drift_total": shape_drift,
        "all_within_tolerance": all(leg["ok"] for leg in legs),
        "legs": legs,
    }


def selftest():
    """Fast tier: one single-device leg with all compiled
    channels, the dp8 wire leg, and a seeded-drift smoke proving the
    auditor actually fires (corrupt one infer spec, expect exactly that
    op anchored under spec-drift-shape)."""
    from paddle_tpu.framework.spec_audit import audit_static
    from paddle_tpu.ops.registry import OP_SPECS, VarSig

    rep = ladder_leg(8, 4)
    if not rep["ok"] or rep["drift"]:
        print("spec_audit_probe selftest: clean ladder leg drifted:")
        for d in rep["drift"]:
            print(" ", d["code"], d["op_type"])
        return 1
    print("selftest: ladder 8x4 clean (shape/flops/mem)")

    rep = dp8_leg()
    if not rep["ok"] or rep["drift"]:
        print("spec_audit_probe selftest: clean dp8 leg drifted:")
        for d in rep["drift"]:
            print(" ", d["code"], d["op_type"])
        return 1
    ar = rep["channels"]["wire"]["kinds"].get("all_reduce", {})
    if not ar.get("hlo_count"):
        print("spec_audit_probe selftest: dp8 lowered no all_reduce — "
              "the wire ground truth is gone")
        return 1
    print("selftest: dp8 clean (wire all_reduce reconciled)")

    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.core import (Program, program_guard,
                                           reset_default_programs)

    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[64])
        h = fluid.layers.fc(x, 64, act="relu", bias_attr=False)
        loss = fluid.layers.mean(h)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    spec = OP_SPECS["relu"]
    orig = spec.infer

    def bad_infer(ins, attrs):
        out = orig(ins, attrs)
        return {k: [VarSig(v.shape, "float16") for v in vs]
                for k, vs in out.items()}

    spec.infer = bad_infer
    try:
        rep = audit_static(main, feed_shapes={"x": ((32, 64), "float32")},
                           fetch_names=[loss.name])
    finally:
        spec.infer = orig
    drift = rep.drift()
    if not drift or any(d.op_type != "relu" or d.code != "spec-drift-shape"
                        for d in drift):
        print("spec_audit_probe selftest: seeded relu infer corruption "
              "was not anchored as spec-drift-shape on relu:",
              [(d.code, d.op_type) for d in drift])
        return 1
    print("selftest: seeded drift caught (spec-drift-shape @ relu)")
    print("spec_audit_probe selftest OK")
    return 0


def main():
    if "--selftest" in sys.argv[1:]:
        return selftest()
    art = run_probe()
    for leg in art["legs"]:
        mark = "OK " if leg["ok"] else "FAIL"
        rows = []
        for name, ch in sorted(leg["channels"].items()):
            if "rel_err" in ch and ch["rel_err"] is not None:
                rows.append(f'{name}={ch["rel_err"]:+.3f}')
            elif name == "wire" and "worst_abs_rel_err" in ch:
                rows.append(f'wire<={ch["worst_abs_rel_err"]:.3f}')
            elif name == "shape":
                rows.append(f'shape={ch["checked"]}ok')
        print(f'{mark} {leg["leg"]:28s} ' + " ".join(rows))
    print(f'worst |rel_err| = {art["worst_abs_rel_err"]} '
          f'(bands {art["tolerances"]})')
    return 0 if art["all_within_tolerance"] and not art["shape_drift_total"] \
        else 1


if __name__ == "__main__":
    sys.exit(main())
