#!/usr/bin/env python
"""Deterministic step replay from a flight bundle + checkpoint.

A flight bundle that cannot be replayed is a screenshot of a crash; one
that can is a debugger.  The guardrail's skip-budget abort bundle
(framework/guardrails.py ``dump_abort_bundle``) records the offending
step's full identity — the serialized program, the feed + RNG key +
guard counters as an npz sidecar, the loss scale, and the f32 finite
probe's exact bit pattern — and this tool proves the claim: it rebuilds
the program, restores the latest checkpoint (whose params are BITWISE
the pre-step state, because every poisoned step was skipped), re-arms
any recorded faultline specs, re-executes the step, and checks that

* the recomputed finite probe matches the recorded bit pattern exactly,
* the same non-finite gradients reappear, and
* two independent replays produce byte-identical gradients
  (determinism: the bundle pins everything that matters).

Usage::

    python tools/replay_step.py <flight_bundle.json> --checkpoint <dir>

Exit code 0 iff the anomaly reproduced.  ``replay()`` is importable —
tools/chaos_probe.py runs it in-process for its replay drill.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
from typing import Any, Dict

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _load_bundle(path: str) -> Dict[str, Any]:
    with open(path) as f:
        bundle = json.load(f)
    guard = (bundle.get("extra") or {}).get("guard")
    if not guard:
        raise SystemExit(f"{path}: not a guardrail bundle (no extra.guard "
                         f"section) — only skip-budget/NaN bundles are "
                         f"replayable")
    for field in ("feed_file", "program_file", "probe_bits",
                  "step_counter"):
        if guard.get(field) in (None, ""):
            raise SystemExit(f"{path}: guard section missing {field!r}")
    return bundle


def _run_once(bundle: Dict[str, Any], checkpoint_dir: str):
    """One replay execution: returns (probe_bits, grads dict, loss)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import io
    from paddle_tpu.flags import set_flags
    from paddle_tpu.framework import guardrails
    from paddle_tpu.framework.core import grad_var_name
    from paddle_tpu.framework.serialization import desc_to_program
    from paddle_tpu.testing import faultline

    guard = bundle["extra"]["guard"]
    with open(guard["program_file"]) as f:
        program = desc_to_program(json.load(f))
    side = np.load(guard["feed_file"])
    feed = {n: side[n] for n in side.files if not n.startswith("__")}

    set_flags({"guard_nonfinite": True})
    faultline.disarm()
    for spec in bundle["extra"].get("faultline", ()):
        faultline.arm(spec["seam"], action=spec["action"],
                      at=spec.get("at", 0), times=spec.get("times"),
                      match=spec.get("match"), **(spec.get("params") or {}))

    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        st = io.load_checkpoint(exe, checkpoint_dir, main_program=program,
                                scope=scope)
        if st.epoch_no < 0:
            raise SystemExit(f"no valid checkpoint under "
                             f"{checkpoint_dir!r} to replay from")
        # the bundle pins the step's exact inputs: RNG key, device step
        # counter (the faultline 'poison step k' gate), loss scale
        scope.set_var("@RNG_STATE@", np.asarray(side["__rng_key__"]))
        scope.set_var(guardrails.GUARD_STEP,
                      np.asarray(int(side["__step_counter__"]), np.int32))
        scope.set_var(guardrails.GUARD_SCALE,
                      np.asarray(side["__loss_scale__"], np.float32))

        bw = next(op for op in program.global_block().ops
                  if op.type == "backward")
        params = list(bw.attrs["param_names"])
        loss_name = bw.attrs["loss_name"]
        gnames = [grad_var_name(n) for n in params]
        vals = exe.run(program, feed=feed,
                       fetch_list=[loss_name] + gnames)
        probe = np.asarray(scope.find_var(guardrails.GUARD_PROBE))
    faultline.disarm()
    grads = {n: np.asarray(v) for n, v in zip(gnames, vals[1:])}
    return guardrails.probe_bits(probe), grads, float(
        np.asarray(vals[0]).reshape(()).astype(np.float64))


def _grad_digest(grads: Dict[str, np.ndarray]) -> str:
    h = hashlib.sha256()
    for n in sorted(grads):
        h.update(n.encode())
        h.update(np.ascontiguousarray(grads[n]).tobytes())
    return h.hexdigest()


def replay(bundle_path: str, checkpoint_dir: str) -> Dict[str, Any]:
    """Replay the bundle's offending step twice; returns the report."""
    bundle = _load_bundle(bundle_path)
    guard = bundle["extra"]["guard"]
    bits1, grads1, loss1 = _run_once(bundle, checkpoint_dir)
    bits2, grads2, _ = _run_once(bundle, checkpoint_dir)
    nonfinite = sorted(n for n, g in grads1.items()
                       if not np.isfinite(g).all())
    report = {
        "bundle": os.path.abspath(bundle_path),
        "recorded_probe_bits": guard["probe_bits"],
        "replayed_probe_bits": bits1,
        "probe_match": bits1 == guard["probe_bits"],
        "nonfinite_grads": nonfinite,
        "loss": loss1,
        "grad_digest": _grad_digest(grads1),
        "bit_exact_across_replays": (
            bits1 == bits2
            and _grad_digest(grads1) == _grad_digest(grads2)),
    }
    report["reproduced"] = bool(report["probe_match"] and nonfinite
                                and report["bit_exact_across_replays"])
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("bundle", help="flight bundle JSON (guardrail abort)")
    ap.add_argument("--checkpoint", required=True,
                    help="checkpoint root dir (io.save_checkpoint layout)")
    ap.add_argument("--json", help="write the replay report here")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    report = replay(args.bundle, args.checkpoint)
    print(json.dumps(report, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
    if not report["reproduced"]:
        print("replay did NOT reproduce the recorded anomaly",
              file=sys.stderr)
        return 1
    print(f"anomaly reproduced bit-exactly: probe {report['replayed_probe_bits']}"
          f" == recorded, non-finite grads {report['nonfinite_grads']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
