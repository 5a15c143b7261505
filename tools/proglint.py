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
    args = ap.parse_args(argv)

    if not args.path:
        ap.error("PATH required")
    program = load_program(args.path)
    startup = load_program(args.startup) if args.startup else None
    return lint(program, startup=startup, feed_names=args.feed,
                fetch_names=args.fetch, strict=args.strict,
                inference=args.inference, memory=args.memory,
                kernels=args.kernels, audit=args.audit,
                launch=args.launch, as_json=args.as_json)


if __name__ == "__main__":
    sys.exit(main())
