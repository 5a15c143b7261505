#!/usr/bin/env python
"""proglint — lint a serialized Program from the CLI.

The static-verifier front end (framework/analysis.py +
framework/memory_analysis.py): structural verification, op_spec
shape/dtype inference, distributed soundness, the unspecced-op census,
the memory lint profile and the per-device peak-HBM estimate, over a
program loaded from disk — so a saved artifact can be checked without
tracing or compiling anything.

Usage:
    python tools/proglint.py PATH [options]
    python tools/proglint.py --selftest [--memory]

PATH is one of:
  * a JSON program desc (the versioned schema framework/serialization.py
    writes, or an io.save_inference_model payload with "program_desc");
  * a directory containing an ``__model__`` inference artifact;
  * a legacy pickle of a live Program.

Options:
  --fetch NAME       fetch target(s) — enables donation-soundness checks
  --feed NAME        feed name(s) seeded as defined
  --startup PATH     startup program to cross-check parameter agreement
  --inference        lint in the SERVING profile: additionally reject
                     collectives, backward/grad ops, persistable writes,
                     and donation annotations (a served program must be a
                     pure read-only function of its feeds)
  --memory           run the memory lint profile (donation-gap /
                     fetch-retention / grad-accum-doubling) and print the
                     static per-device peak-HBM estimate with the top
                     live tensors at the peak point
  --kernels          print the Pallas kernel-routing report (which ops
                     WILL lower to a custom kernel for TPU at the
                     program's static shapes, and why the rest fall
                     back) — analysis.kernel_routing_report, 0 compiles
  --launch           run the static SPMD launch audit
                     (framework/launch_audit.py audit_launch): extract
                     the per-rank collective timelines (pipelined
                     programs expand through the stamped schedule
                     table), prove pairwise schedule compatibility +
                     deadlock-freedom, and print the launch fingerprint
                     — 0 compiles, 0 live collectives; exits non-zero
                     on any launch-* error.  Implied by --strict.
  --audit            run the differential spec auditor's static tier
                     (framework/spec_audit.py audit_static): abstract-
                     evaluate every specced op impl and cross-check the
                     infer channel's shape/dtype claims, plus the
                     collective wire-pricing coverage census — 0
                     compiles; exits non-zero on any spec-drift-* error
  --json             machine-readable report on stdout (diagnostics,
                     unspecced-op census, memory estimate, kernel
                     routing) for CI
  --strict           exit non-zero on warnings too, AND whenever the
                     unspecced-op census is non-empty — op_spec coverage
                     can never silently regress under a --strict CI gate
  --selftest         build, serialize, reload and lint a model-zoo
                     program plus every PassBuilder.INFERENCE_PASSES
                     output under flag("verify_passes") — the preflight
                     CI gate; with --memory also exercises the memory
                     profile + budget gate on the same program
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def load_program(path: str):
    from paddle_tpu.framework.serialization import desc_to_program
    if os.path.isdir(path):
        path = os.path.join(path, "__model__")
    with open(path, "rb") as f:
        head = f.read(1)
    if head in (b"{", b"["):
        with open(path) as f:
            payload = json.load(f)
        desc = payload.get("program_desc", payload)
        return desc_to_program(desc)
    import pickle
    with open(path, "rb") as f:
        payload = pickle.load(f)
    if isinstance(payload, dict) and "program_desc" in payload:
        return desc_to_program(payload["program_desc"])
    if isinstance(payload, dict) and "program" in payload:
        return payload["program"]
    return payload


def lint(program, startup=None, feed_names=(), fetch_names=(),
         strict=False, inference=False, memory=False, kernels=False,
         audit=False, launch=False, as_json=False, out=None):
    out = out if out is not None else sys.stdout
    from paddle_tpu.framework.analysis import (verify_inference,
                                               verify_program)
    if inference:
        result = verify_inference(program, feed_names=feed_names,
                                  fetch_names=fetch_names)
        if startup is not None:
            from paddle_tpu.framework.analysis import \
                verify_startup_agreement
            verify_startup_agreement(program, startup, result)
    else:
        result = verify_program(program, startup=startup,
                                feed_names=feed_names,
                                fetch_names=fetch_names)
    estimate = None
    if memory:
        from paddle_tpu.framework.memory_analysis import (analyze_memory,
                                                          lint_memory)
        lint_memory(program, fetch_names=fetch_names, result=result)
        estimate = analyze_memory(program, fetch_names=fetch_names)
    routing = None
    if kernels:
        from paddle_tpu.framework.analysis import kernel_routing_report
        routing = kernel_routing_report(program, fetch_names=fetch_names)
    audit_report = None
    if audit:
        from paddle_tpu.framework.spec_audit import audit_static
        audit_report = audit_static(program, fetch_names=fetch_names)
    launch_report = None
    if launch or strict:
        from paddle_tpu.framework.launch_audit import audit_launch
        launch_report = audit_launch(program)
    if as_json:
        payload = {
            "errors": len(result.errors()),
            "warnings": len(result.warnings()),
            "diagnostics": [
                {"severity": d.severity, "code": d.code,
                 "message": d.message, "op_type": d.op_type,
                 "block": d.block_idx, "op_index": d.op_index,
                 "callstack": list(d.callstack)}
                for d in result.diagnostics],
            # sorted for byte-stable CI output: the census is a dict
            # keyed by discovery order, which varies with block layout
            "unspecced_ops": {k: result.unspecced_ops[k]
                              for k in sorted(result.unspecced_ops)},
        }
        if estimate is not None:
            payload["memory"] = estimate.as_dict()
        if routing is not None:
            payload["kernel_routing"] = routing
        if audit_report is not None:
            payload["spec_audit"] = audit_report.as_dict()
        if launch_report is not None:
            payload["launch_audit"] = launch_report.as_dict()
        print(json.dumps(payload, indent=1), file=out)
    else:
        print(result.report(), file=out)
        if estimate is not None:
            print(estimate.report(), file=out)
        if audit_report is not None:
            print(audit_report.report(), file=out)
        if launch_report is not None:
            print(launch_report.report(), file=out)
        if routing is not None:
            print(f"pallas kernel routing (backend={routing['backend']}, "
                  "0 compiles):", file=out)
            for kernel, s in sorted(routing["summary"].items()):
                print(f"  {kernel}: {s['pallas']} pallas / "
                      f"{s['fallback']} fallback", file=out)
            for r in routing["rows"]:
                if r["route"] == "fallback":
                    print(f"    op[{r['index']}] {r['op']} -> fallback "
                          f"({r['reason']})", file=out)
    if result.errors():
        return 1
    if audit_report is not None and not audit_report.ok:
        return 1
    if launch_report is not None and not launch_report.ok:
        return 1
    if strict and (result.warnings() or result.unspecced_ops):
        return 1
    return 0


def selftest(memory=False) -> int:
    """Zero-setup lint path for CI: serialize a model-zoo program through
    the versioned desc schema, reload it, lint it; then run every
    INFERENCE_PASSES pipeline under pass-invariant checking.  With
    ``memory``, additionally exercise the memory profile: the training
    program must produce a positive peak estimate whose components add
    up, the JSON report must carry it, and the ``hbm_budget_gb`` gate
    must reject the program against a sub-estimate budget BEFORE any
    compile."""
    import tempfile

    import paddle_tpu.fluid as fluid
    from paddle_tpu import flags
    from paddle_tpu.framework.core import Program, program_guard
    from paddle_tpu.framework.passes import PassBuilder
    from paddle_tpu.framework.serialization import program_to_desc
    from paddle_tpu.models import bert

    main, startup = Program(), Program()
    with program_guard(main, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(
            bert.BertConfig.tiny())
        fluid.optimizer.Adam(1e-3).minimize(total)

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "prog.json")
        with open(path, "w") as f:
            json.dump({"program_desc": program_to_desc(main)}, f)
        prog = load_program(path)
    rc = lint(prog, startup=startup, fetch_names=[total.name])
    if rc:
        print("proglint selftest: serialized program FAILED lint")
        return rc

    # inference pipeline under pass-invariant checking
    infer = main.clone(for_test=True)
    flags.set_flags({"verify_passes": True})
    try:
        PassBuilder().apply(infer, fetch_names=[mlm.name, nsp.name])
    finally:
        flags.set_flags({"verify_passes": False})
    rc = lint(infer, fetch_names=[mlm.name, nsp.name])
    if rc:
        print("proglint selftest: INFERENCE_PASSES output FAILED lint")
        return rc

    # the SERVING profile must accept the pruned inference program and
    # reject the training program (backward + optimizer state writes)
    served = main.clone(for_test=True)._prune([mlm, nsp])
    rc = lint(served, fetch_names=[mlm.name, nsp.name], inference=True)
    if rc:
        print("proglint selftest: inference profile FAILED on the "
              "pruned program")
        return rc
    import io as _io
    sink = _io.StringIO()
    if lint(prog, fetch_names=[total.name], inference=True,
            out=sink) == 0:
        print("proglint selftest: inference profile ACCEPTED a training "
              "program")
        return 1

    # wire-compression lints: a tiny quantized collective must raise the
    # quant-small-bucket warning (scale overhead > byte saving), an
    # adequately sized one must not, and an integer payload must be an
    # error (the quantized analog of the bf16-on-integer rejection)
    from paddle_tpu.framework.analysis import (QUANT_COLLECTIVE_INTEGER,
                                               QUANT_SMALL_BUCKET,
                                               verify_program)
    qp = Program()
    qb = qp.global_block()
    qb.create_var(name="g_small", shape=(64,), dtype="float32",
                  is_data=True)
    qb.create_var(name="g_big", shape=(1 << 20,), dtype="float32",
                  is_data=True)
    qb.create_var(name="g_int", shape=(1 << 20,), dtype="int32",
                  is_data=True)
    qattrs = {"ring_id": 0,
              "quant_spec": {"dtype": "int8", "block_size": 64}}
    for g in ("g_small", "g_big", "g_int"):
        qb.append_op(type="c_quant_allreduce_sum", inputs={"X": [g]},
                     outputs={"Out": [g]}, attrs=dict(qattrs))
    qres = verify_program(qp)
    small = qres.by_code(QUANT_SMALL_BUCKET)
    if len(small) != 1 or "g_small" not in small[0].message:
        print("proglint selftest: quant-small-bucket lint fired "
              f"{len(small)}x (expected once, on the 256-byte payload)")
        return 1
    if not qres.by_code(QUANT_COLLECTIVE_INTEGER):
        print("proglint selftest: integer payload on a quantized "
              "collective was not rejected")
        return 1

    # MoE expert-exchange lints (parallel/moe.py): an exchange naming a
    # mesh axis the stamped MeshLayout lacks must error (at run time it
    # silently degrades to the identity — remote experts never fire), an
    # expert count that does not divide the axis must error (ragged
    # expert slices), a QUANTIZED exchange must NOT fire
    # quant-collective-non-sum (an all_to_all is a permutation — every
    # receive slice dequantizes whole), and an integer payload on the
    # quantized exchange reuses quant-collective-integer
    from paddle_tpu.framework.analysis import (MOE_AXIS_CAPACITY_MISMATCH,
                                               MOE_AXIS_UNKNOWN,
                                               QUANT_NON_SUM)
    from paddle_tpu.framework.mesh_layout import MeshLayout
    mp = Program()
    mb = mp.global_block()
    mb.create_var(name="xe_bad", shape=(6, 8, 4), dtype="float32",
                  is_data=True)
    mb.create_var(name="xe_q", shape=(8, 8, 4), dtype="float32",
                  is_data=True)
    mb.create_var(name="xe_int", shape=(8, 8, 4), dtype="int32",
                  is_data=True)
    mattrs = {"ring_id": 0, "direction": "dispatch"}
    qspec = {"dtype": "int8", "block_size": 64}
    mb.append_op(type="c_expert_alltoall", inputs={"X": ["xe_bad"]},
                 outputs={"Out": ["xe_bad"]},
                 attrs=dict(mattrs, _axis_name="xx"))
    mb.append_op(type="c_expert_alltoall", inputs={"X": ["xe_bad"]},
                 outputs={"Out": ["xe_bad"]},
                 attrs=dict(mattrs, _axis_name="ep"))
    mb.append_op(type="c_expert_alltoall", inputs={"X": ["xe_q"]},
                 outputs={"Out": ["xe_q"]},
                 attrs=dict(mattrs, _axis_name="ep", quant_spec=qspec))
    mb.append_op(type="c_expert_alltoall", inputs={"X": ["xe_int"]},
                 outputs={"Out": ["xe_int"]},
                 attrs=dict(mattrs, _axis_name="ep", quant_spec=qspec))
    mp._mesh_layout = MeshLayout(data=2, expert=4)
    mres = verify_program(mp)
    unknown = mres.by_code(MOE_AXIS_UNKNOWN)
    if len(unknown) != 1 or "xx" not in unknown[0].message:
        print(f"proglint selftest: moe-axis-unknown fired "
              f"{len(unknown)}x (expected once, on the 'xx' exchange)")
        return 1
    capm = mres.by_code(MOE_AXIS_CAPACITY_MISMATCH)
    if len(capm) != 1 or "6" not in capm[0].message:
        print(f"proglint selftest: moe-axis-capacity-mismatch fired "
              f"{len(capm)}x (expected once, on 6 experts over ep=4)")
        return 1
    if mres.by_code(QUANT_NON_SUM):
        print("proglint selftest: quantized expert all_to_all flagged "
              "as a non-sum reduction (it is a sound permutation)")
        return 1
    if not any("xe_int" in d.message
               for d in mres.by_code(QUANT_COLLECTIVE_INTEGER)):
        print("proglint selftest: integer payload on the quantized "
              "expert all_to_all was not rejected")
        return 1

    # overlap-scheduling lints (the ready-order grad-sync pass): a
    # (dtype, axes) group that coalesced into ONE overlap bucket must
    # warn (a lone collective has nothing to interleave with), a
    # ready-ordered collective with no hook position must warn (it
    # sinks to the program tail), and a well-split group must be clean
    from paddle_tpu.framework.analysis import (OVERLAP_SINGLE_BUCKET,
                                               OVERLAP_TAIL_SUNK)
    ov = Program()
    ob = ov.global_block()
    for n in ("og0", "og1", "og2", "ot0"):
        ob.create_var(name=n, shape=(1 << 16,), dtype="float32",
                      is_data=True)
    oattrs = {"ring_id": 0, "_axis_name": "dp", "_overlap": True}
    # dp group: two hooked buckets + one hook-less straggler
    ob.append_op(type="c_fused_allreduce_sum", inputs={"X": ["og0"]},
                 outputs={"Out": ["og0"]},
                 attrs=dict(oattrs, _ready_rank=0, _bucket_index=0,
                            _overlap_hook_pos=7))
    ob.append_op(type="c_fused_allreduce_sum", inputs={"X": ["og1"]},
                 outputs={"Out": ["og1"]},
                 attrs=dict(oattrs, _ready_rank=1, _bucket_index=1,
                            _overlap_hook_pos=2))
    ob.append_op(type="c_fused_allreduce_sum", inputs={"X": ["og2"]},
                 outputs={"Out": ["og2"]},
                 attrs=dict(oattrs, _ready_rank=2, _bucket_index=2))
    # tp group: a single coalesced bucket — nothing can hide
    ob.append_op(type="c_fused_allreduce_sum", inputs={"X": ["ot0"]},
                 outputs={"Out": ["ot0"]},
                 attrs={"ring_id": 0, "_axis_name": "tp",
                        "_overlap": True, "_ready_rank": 3,
                        "_bucket_index": 3, "_overlap_hook_pos": 0})
    ores = verify_program(ov)
    single = ores.by_code(OVERLAP_SINGLE_BUCKET)
    sunk = ores.by_code(OVERLAP_TAIL_SUNK)
    if len(single) != 1 or "tp" not in single[0].message:
        print(f"proglint selftest: overlap-single-bucket fired "
              f"{len(single)}x (expected once, on the tp group)")
        return 1
    if len(sunk) != 1 or "og2" not in sunk[0].message:
        print(f"proglint selftest: overlap-tail-sunk fired {len(sunk)}x "
              f"(expected once, on the hook-less bucket)")
        return 1

    # pipeline/remat soundness (framework/pipe.py rewrites): a collective
    # stranded across a stage cut must error; an RNG op inside a
    # recompute segment must warn until its key is audited (_folded_key)
    from paddle_tpu.framework.analysis import (
        PIPE_COLLECTIVE_CROSSES_STAGE, REMAT_RECOMPUTE_SIDE_EFFECT)
    pp = Program()
    pb = pp.global_block()
    for n in ("px", "ph"):
        pb.create_var(name=n, shape=(8, 16), dtype="float32",
                      is_data=(n == "px"))
    pb.create_var(name="pd", shape=(8, 16), dtype="float32")
    pb.append_op(type="scale", inputs={"X": ["px"]},
                 outputs={"Out": ["ph"]},
                 attrs={"scale": 2.0, "_pipe_stage": 0})
    pb.append_op(type="dropout", inputs={"X": ["ph"]},
                 outputs={"Out": ["pd"], "Mask": ["pd_mask"]},
                 attrs={"dropout_prob": 0.5, "is_test": False,
                        "_pipe_stage": 0})
    pb.create_var(name="pd_mask", shape=(8, 16), dtype="float32")
    pb.append_op(type="pipe_stage_boundary", inputs={"X": ["pd"]},
                 outputs={"Out": ["pd"]},
                 attrs={"_axis_name": "pp", "_pipe_cut": 0,
                        "_pipe_stage": 0})
    # the stranded collective: stage 1, reading a stage-0 value
    pb.append_op(type="c_allreduce_sum", inputs={"X": ["ph"]},
                 outputs={"Out": ["ph"]},
                 attrs={"ring_id": 0, "_axis_name": "tp",
                        "_pipe_stage": 1})
    pb.append_op(type="backward", inputs={}, outputs={},
                 attrs={"loss_name": "pd", "param_names": [],
                        "pipe_stages": 2, "pipe_microbatches": 2,
                        "pipe_axis": "pp", "pipe_boundaries": [["pd"]],
                        "checkpoints": ["pd"]})
    pres = verify_program(pp)
    crossed = pres.by_code(PIPE_COLLECTIVE_CROSSES_STAGE)
    rng_warn = pres.by_code(REMAT_RECOMPUTE_SIDE_EFFECT)
    if len(crossed) != 1 or "c_allreduce_sum" not in crossed[0].message:
        print(f"proglint selftest: pipe-collective-crosses-stage fired "
              f"{len(crossed)}x (expected once, on the stranded "
              f"collective)")
        return 1
    if len(rng_warn) != 1 or "dropout" not in rng_warn[0].message:
        print(f"proglint selftest: remat-recompute-side-effect fired "
              f"{len(rng_warn)}x (expected once, on the recomputed "
              f"dropout)")
        return 1
    # stamping the audited key silences the warning (pipe.apply_remat's
    # contract)
    for op in pb.ops:
        if op.type == "dropout":
            op.attrs["_folded_key"] = True
    pp._bump_version()
    if verify_program(pp).by_code(REMAT_RECOMPUTE_SIDE_EFFECT):
        print("proglint selftest: remat-recompute-side-effect still "
              "fires after _folded_key")
        return 1

    # scheduled-scan table soundness (pipe.simulate_schedule stamps):
    # the genuine simulated tables must stay clean; a backward moved
    # before any forward must fire pipe-schedule-order; an undersized
    # saved-input ring must fire pipe-ring-overflow
    from paddle_tpu.framework.analysis import (PIPE_RING_OVERFLOW,
                                               PIPE_SCHEDULE_ORDER)
    from paddle_tpu.framework.pipe import simulate_schedule
    sch = simulate_schedule("1f1b", 2, 2)
    bw_pp = next(op for op in pb.ops if op.type == "backward")
    bw_pp.attrs["pipe_ring_slots"] = [int(sch["slots"]),
                                      int(sch["ct_slots"])]
    bw_pp.attrs["pipe_schedule_order"] = [list(u) for u in sch["order"]]
    pp._bump_version()
    sres = verify_program(pp)
    if sres.by_code(PIPE_SCHEDULE_ORDER) or \
            sres.by_code(PIPE_RING_OVERFLOW):
        print("proglint selftest: genuine simulated schedule tables "
              "were flagged")
        return 1
    bad_order = [list(u) for u in sch["order"]]
    for u in bad_order:
        if u[2] == "B":
            u[0] = 0       # a backward at tick 0, before any forward
            break
    bw_pp.attrs["pipe_schedule_order"] = bad_order
    pp._bump_version()
    if not verify_program(pp).by_code(PIPE_SCHEDULE_ORDER):
        print("proglint selftest: pipe-schedule-order did not fire on "
              "a backward scheduled before its forward")
        return 1
    bw_pp.attrs["pipe_schedule_order"] = [list(u) for u in sch["order"]]
    bw_pp.attrs["pipe_ring_slots"] = [0, 0]
    pp._bump_version()
    if not verify_program(pp).by_code(PIPE_RING_OVERFLOW):
        print("proglint selftest: pipe-ring-overflow did not fire on "
              "an undersized ring")
        return 1

    # kernel-routing report (the Pallas tier, statically): the training
    # program must yield a non-empty report whose fused-LayerNorm summary
    # has hits (BERT-tiny's 128-wide rows), every row carries a route +
    # reason, and the --kernels --json payload embeds it
    from paddle_tpu.framework.analysis import kernel_routing_report
    krep = kernel_routing_report(main, fetch_names=[total.name])
    if not krep["rows"] or "fused_layer_norm" not in krep["summary"] or \
            krep["summary"]["fused_layer_norm"]["pallas"] < 1:
        print("proglint selftest: kernel-routing report empty or missing "
              "fused_layer_norm hits: " + json.dumps(krep["summary"]))
        return 1
    if any(r["route"] not in ("pallas", "fallback") or not r["reason"]
           for r in krep["rows"]):
        print("proglint selftest: kernel-routing rows malformed")
        return 1
    sink = _io.StringIO()
    rc = lint(main, fetch_names=[total.name], kernels=True, as_json=True,
              out=sink)
    if rc or '"kernel_routing"' not in sink.getvalue():
        print("proglint selftest: --kernels --json report missing the "
              "routing section")
        return 1

    # --audit: the static spec-audit tier must pass the clean program
    # and embed its section in the JSON payload; a corrupted infer spec
    # must flip the exit code (the differential auditor's CLI face)
    from paddle_tpu.framework.spec_audit import SPEC_DRIFT_SHAPE  # noqa: F401
    from paddle_tpu.ops.registry import OP_SPECS, VarSig
    sink = _io.StringIO()
    rc = lint(main, fetch_names=[total.name], audit=True, as_json=True,
              out=sink)
    payload = json.loads(sink.getvalue())
    if rc or not payload.get("spec_audit", {}).get("ok"):
        print("proglint selftest: --audit failed on the clean training "
              "program")
        return 1
    if list(payload["unspecced_ops"]) != sorted(payload["unspecced_ops"]):
        print("proglint selftest: unspecced-op census is not sorted")
        return 1
    gelu_spec = OP_SPECS["gelu"]
    orig_infer = gelu_spec.infer
    gelu_spec.infer = lambda ins, attrs: {
        "Out": [VarSig(ins["X"][0].shape, "float16")]}
    try:
        sink = _io.StringIO()
        rc = lint(main, fetch_names=[total.name], audit=True,
                  as_json=True, out=sink)
    finally:
        gelu_spec.infer = orig_infer
    drift = [d for d in json.loads(sink.getvalue())
             .get("spec_audit", {}).get("drift", [])
             if d["code"] == "spec-drift-shape"]
    if rc == 0 or not drift or drift[0]["op_type"] != "gelu":
        print("proglint selftest: --audit did not catch the corrupted "
              "gelu infer spec")
        return 1

    # --launch: the static launch auditor must pass the clean training
    # program (embedding its section in the JSON payload) and catch a
    # seeded collective under divergent control flow with the anchored
    # launch-deadlock-cycle — all with 0 compiles
    from paddle_tpu.framework.analysis import LAUNCH_DEADLOCK_CYCLE
    sink = _io.StringIO()
    rc = lint(main, fetch_names=[total.name], launch=True,
              as_json=True, out=sink)
    payload = json.loads(sink.getvalue())
    if rc or not payload.get("launch_audit", {}).get("ok"):
        print("proglint selftest: --launch failed on the clean training "
              "program")
        return 1
    lp = Program()
    lb = lp.global_block()
    lb.create_var(name="lx", shape=(8,), is_data=True)
    lb.create_var(name="lcond", shape=(1,), dtype="bool", is_data=True)
    lb.create_var(name="lout", shape=(8,))
    lsub = lp._create_block()
    lsub.append_op(type="c_allreduce_sum", inputs={"X": ["lx"]},
                   outputs={"Out": ["lx"]}, attrs={"ring_id": 0})
    lp._rollback()
    lb.append_op(type="conditional_block",
                 inputs={"Cond": ["lcond"], "Closure": ["lx"]},
                 outputs={"Out": ["lout"]},
                 attrs={"true_block": lsub, "false_block": lsub,
                        "closure_names": ["lx"],
                        "true_out_names": ["lx"],
                        "false_out_names": ["lx"]})
    sink = _io.StringIO()
    rc = lint(lp, launch=True, as_json=True, out=sink)
    lcodes = {d["code"] for d in json.loads(sink.getvalue())
              .get("launch_audit", {}).get("diagnostics", [])}
    if rc == 0 or LAUNCH_DEADLOCK_CYCLE not in lcodes:
        print("proglint selftest: --launch did not prove the hang of a "
              "collective under divergent control flow")
        return 1

    if memory:
        from paddle_tpu.framework.errors import InvalidArgumentError
        from paddle_tpu.framework.memory_analysis import (analyze_memory,
                                                          check_hbm_budget)
        est = analyze_memory(main, fetch_names=[total.name])
        ok = (est.peak_bytes > 0 and est.param_bytes > 0
              and est.args_bytes + est.transient_bytes == est.peak_bytes)
        if not ok:
            print("proglint selftest: memory estimate inconsistent: "
                  + json.dumps(est.as_dict()))
            return 1
        sink = _io.StringIO()
        rc = lint(main, fetch_names=[total.name], memory=True,
                  as_json=True, out=sink)
        if rc or '"memory"' not in sink.getvalue():
            print("proglint selftest: --memory --json report missing the "
                  "estimate")
            return 1
        try:
            check_hbm_budget(main, fetch_names=[total.name],
                             budget_gb=est.peak_gb / 2)
            print("proglint selftest: hbm budget gate ACCEPTED an "
                  "over-budget program")
            return 1
        except InvalidArgumentError:
            pass
        check_hbm_budget(main, fetch_names=[total.name],
                         budget_gb=est.peak_gb * 2)
        print("proglint memory selftest OK "
              f"(peak {est.peak_bytes / (1 << 20):.2f} MiB)")

    print("proglint selftest OK")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="proglint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("path", nargs="?", help="serialized program to lint")
    ap.add_argument("--fetch", action="append", default=[])
    ap.add_argument("--feed", action="append", default=[])
    ap.add_argument("--startup")
    ap.add_argument("--inference", action="store_true")
    ap.add_argument("--memory", action="store_true")
    ap.add_argument("--kernels", action="store_true")
    ap.add_argument("--audit", action="store_true")
    ap.add_argument("--launch", action="store_true")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--strict", action="store_true")
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest(memory=args.memory)
    if not args.path:
        ap.error("PATH required (or --selftest)")
    program = load_program(args.path)
    startup = load_program(args.startup) if args.startup else None
    return lint(program, startup=startup, feed_names=args.feed,
                fetch_names=args.fetch, strict=args.strict,
                inference=args.inference, memory=args.memory,
                kernels=args.kernels, audit=args.audit,
                launch=args.launch, as_json=args.as_json)


if __name__ == "__main__":
    sys.exit(main())
