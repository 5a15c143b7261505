#!/usr/bin/env python
"""Chaos drill harness: prove every self-healing path end-to-end.

Runs seven deterministic fault drills — all injected through
``paddle_tpu.testing.faultline`` seams, never by monkeypatching — and
prints the results + recovery accounting (each drill is also a live
test in tests/test_guardrails.py and tests/test_launch_audit.py):

1. **nan_skip** — NaN injected into a gradient at device step k: the
   step is SKIPPED with params + optimizer state bitwise equal to step
   k−1, the dynamic loss scale backs off at the skip and regrows to its
   pre-fault value after the configured good-step run, and the
   telemetry JSONL records ``skipped``/``loss_scale`` per step;
2. **budget_replay** — persistent NaN exhausts
   ``flag("max_skipped_steps")``: controlled abort (GuardrailViolation)
   with a flight bundle whose sidecars (feed/RNG/program) let
   tools/replay_step.py re-execute the offending step and reproduce
   the non-finite gradient bit-exactly;
3. **stall** — an induced host stall in the prepared loop: the
   watchdog (``flag("step_deadline_s")``) dumps all-thread stacks + a
   flight bundle within the deadline window and bumps
   ``watchdog::trip``;
4. **watchdog_fp** — false-positive bound: a slow-but-healthy run
   (every step well under the deadline) takes ZERO trips;
5. **serving_fatal** — an uncaught serving-worker exception: every
   in-flight and queued future fails with the error (no hangs), a
   flight bundle is dumped, the engine reports unhealthy and
   subsequent ``submit`` raises immediately;
6. **checkpoint_verify** — the just-written checkpoint file is
   corrupted between write and readback verification: the write is
   retried (``checkpoint::retry``) and the published checkpoint's
   manifest verifies clean;
7. **rank_divergence** — a two-process launch where rank 1 arms a
   divergent bucket reorder: ``launch_audit.verify_rank_agreement``
   must abort BOTH ranks at the gloo rendezvous with exit code 43 and
   the diverging op named, instead of hanging at the first collective.

Usage::

    python tools/chaos_probe.py
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SCHEMA = "paddle_tpu.chaos/1"

#: the documented injection-seam list (MIGRATION.md "Fault tolerance
#: mapping") — asserted against faultline.seams() so the registry stays
#: statically enumerable
DOCUMENTED_SEAMS = ("checkpoint_write", "collective_impl",
                    "grad_nonfinite", "rank_divergence",
                    "reshard_execute", "serving_decode",
                    "serving_worker", "step_stall")


def _flags():
    from paddle_tpu.flags import get_flags, set_flags
    return get_flags, set_flags


def _fc_program(seed_scale=0.1):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.core import Program, program_guard
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[6])
        h = fluid.layers.fc(x, 8)
        y = fluid.layers.fc(h, 3)
        loss = fluid.layers.mean(y)
        fluid.optimizer.Adam(seed_scale).minimize(loss)
    return main, startup, loss


def _feed(step=0):
    rng = np.random.RandomState(100 + step)
    return {"x": rng.randn(4, 6).astype(np.float32)}


def _snapshot(scope):
    return {n: np.asarray(v).copy() for n, v in scope.vars.items()
            if not n.startswith("@")}


def _bitwise_equal(a, b):
    return set(a) == set(b) and all(np.array_equal(a[n], b[n]) for n in a)


# ---------------------------------------------------------------------------
# drills
# ---------------------------------------------------------------------------


def drill_nan_skip(work_dir):
    """Transient NaN at step k: skip + bitwise state + scale backoff →
    regrow, with per-step telemetry fields."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.observability import TelemetryRecorder, validate_jsonl
    from paddle_tpu.testing import faultline
    _, set_flags = _flags()
    set_flags({"guard_nonfinite": True, "guard_loss_scale": True,
               "guard_loss_scale_init": 1024.0,
               "guard_incr_every_n_steps": 3})
    main, startup, loss = _fc_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    jsonl = os.path.join(work_dir, "nan_skip.telemetry.jsonl")
    scales, skipped = [], []
    with fluid.scope_guard(scope):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=_feed())
        rec = TelemetryRecorder(jsonl, program=main,
                                fetch_names=[loss.name]).attach(prepared)
        inject_at = 2
        faultline.arm("grad_nonfinite", action="nan", step=inject_at,
                      times=1)
        snap = None
        for i in range(8):
            if i == inject_at:
                prepared.wait()
                prepared.sync_scope()
                snap = _snapshot(scope)
            with rec.step(tokens=4) as st:
                h, = prepared.run(_feed(i))
                st.loss = h
            gi = prepared.guard_info(sync=True)
            scales.append(gi["loss_scale"])
            skipped.append(gi["last_skipped"])
            if i == inject_at:
                prepared.sync_scope()
                post = _snapshot(scope)
                bitwise_ok = _bitwise_equal(snap, post)
        rec.close()
        prepared.close()
    faultline.disarm()
    facts = validate_jsonl(jsonl)
    steps = [json.loads(l) for l in open(jsonl) if l.strip()]
    steps = [s for s in steps if s.get("record") == "step"]
    return {
        "inject_at_step": inject_at,
        "skipped_trace": skipped,
        "scale_trace": scales,
        "params_bitwise_at_skip": bool(bitwise_ok),
        "skip_detected": bool(skipped[inject_at]),
        "scale_backoff": scales[inject_at] == 512.0,
        "scale_regrown": scales[-1] == 1024.0,
        "telemetry_skipped_fields": all("skipped" in s for s in steps),
        "telemetry_steps": facts["steps"],
        "ok": bool(bitwise_ok and skipped[inject_at]
                   and scales[inject_at] == 512.0
                   and scales[-1] == 1024.0
                   and all("skipped" in s for s in steps)),
    }


def drill_budget_replay(work_dir):
    """Persistent NaN → skip-budget abort with bundle → replay_step
    reproduces the anomaly bit-exactly from bundle + checkpoint."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import io
    from paddle_tpu.framework.errors import GuardrailViolation
    from paddle_tpu.observability import flight
    from paddle_tpu.testing import faultline
    from tools.replay_step import replay
    _, set_flags = _flags()
    set_flags({"guard_nonfinite": True, "guard_loss_scale": False,
               "max_skipped_steps": 3})
    ckpt_dir = os.path.join(work_dir, "budget_ckpt")
    main, startup, loss = _fc_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    aborted = bundle = None
    with fluid.scope_guard(scope):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=_feed())
        for i in range(3):
            prepared.run(_feed(i))
        prepared.wait()
        io.save_checkpoint(exe, ckpt_dir, io.TrainStatus(2), main,
                           scope=scope)
        pre = _snapshot(scope)
        faultline.arm("grad_nonfinite", action="nan", times=None)
        steps_to_abort = 0
        try:
            for i in range(3, 20):
                prepared.run(_feed(3))   # fixed feed: replay determinism
                steps_to_abort += 1
            prepared.wait()
        except GuardrailViolation as e:
            aborted = str(e)
            bundle = flight.last_dumps()[-1]
        faultline.disarm()
        prepared.sync_scope()
        post = _snapshot(scope)
    state_held = _bitwise_equal(pre, post)
    rep = replay(bundle, ckpt_dir) if bundle else {}
    return {
        "aborted": aborted is not None,
        "steps_dispatched_past_fault": steps_to_abort,
        "bundle": os.path.basename(bundle or ""),
        "state_bitwise_through_abort": bool(state_held),
        "replay": {k: rep.get(k) for k in
                   ("probe_match", "nonfinite_grads",
                    "bit_exact_across_replays", "reproduced")},
        "ok": bool(aborted and state_held and rep.get("reproduced")),
    }


def drill_stall(work_dir):
    """Induced host stall in the prepared loop → watchdog trip with
    all-thread stacks + flight bundle inside the deadline window."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.observability import flight, watchdog
    from paddle_tpu.testing import faultline
    _, set_flags = _flags()
    deadline = 0.4
    set_flags({"guard_nonfinite": False, "step_deadline_s": deadline})
    main, startup, loss = _fc_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    base_trips = len(watchdog.trips())
    with fluid.scope_guard(scope):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=_feed())
        prepared.run(_feed())
        faultline.arm("step_stall", action="stall", seconds=3 * deadline,
                      times=1)
        t0 = time.monotonic()
        prepared.run(_feed())
        wall = time.monotonic() - t0
        faultline.disarm()
        prepared.close()
    set_flags({"step_deadline_s": 0.0})
    new = watchdog.trips()[base_trips:]
    trip = new[-1] if new else {}
    bundle_ok = stacks = False
    if trip.get("bundle"):
        b = flight.validate_bundle(trip["bundle"])
        stacks = len(b["extra"]["thread_stacks"]) >= 1 and any(
            "_run_inner" in "".join(fr) or "crossing" in "".join(fr)
            for fr in b["extra"]["thread_stacks"].values())
        bundle_ok = True
    from paddle_tpu.observability import metrics
    snap = metrics.metrics_snapshot(include_serving=False)
    trip_metric = sum(int(m.get("value", 0)) for m in snap["metrics"]
                      if m["name"] == "watchdog::trip")
    return {
        "deadline_s": deadline,
        "stall_s": 3 * deadline,
        "tripped": bool(new),
        "detection_latency_s": round(trip.get("stalled_s", -1), 3),
        "detected_within": bool(
            new and trip["stalled_s"] <= 3 * deadline),
        "bundle_valid": bool(bundle_ok),
        "stacks_in_bundle": bool(stacks),
        "trip_metric": int(trip_metric),
        "ok": bool(new and bundle_ok and stacks and trip_metric >= 1
                   and trip["stalled_s"] <= 3 * deadline),
    }


def drill_watchdog_fp(work_dir):
    """False-positive bound: slow-but-healthy steps (each well under
    the deadline) must take zero trips."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.observability import watchdog
    from paddle_tpu.testing import faultline
    _, set_flags = _flags()
    set_flags({"step_deadline_s": 2.0})
    main, startup, loss = _fc_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    base = len(watchdog.trips())
    steps = 6
    with fluid.scope_guard(scope):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=_feed())
        # every step stalls 0.1 s — SLOW, but inside the 2 s deadline
        faultline.arm("step_stall", action="stall", seconds=0.1,
                      times=None)
        for i in range(steps):
            prepared.run(_feed(i))
        prepared.wait()
        faultline.disarm()
        prepared.close()
    time.sleep(0.6)          # give the monitor a few poll cycles
    set_flags({"step_deadline_s": 0.0})
    trips = len(watchdog.trips()) - base
    return {"steps": steps, "per_step_stall_s": 0.1, "deadline_s": 2.0,
            "trips": trips, "ok": trips == 0}


class _StubPredictor:
    """Duck-typed predictor for the worker-hardening drill: the recovery
    path under test is ENGINE logic; the model is irrelevant."""

    def __init__(self):
        self.compiled_executables = 0

    def get_input_names(self):
        return ["x"]

    def get_output_names(self):
        return ["y"]

    def prepare(self):
        return self

    def run_feed(self, feed):
        return [np.asarray(feed["x"]) * 2.0]


def drill_serving_fatal(work_dir):
    """Uncaught worker exception: all futures fail (none hang), engine
    unhealthy, flight bundle, immediate-raise submits afterwards."""
    from paddle_tpu.framework.errors import UnavailableError
    from paddle_tpu.observability import flight
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.testing import faultline
    eng = ServingEngine(_StubPredictor(),
                        ServingConfig(max_batch_size=4, max_wait_ms=1.0))
    f0 = eng.submit({"x": np.ones((1, 3), np.float32)})
    assert np.allclose(f0.result(timeout=10)[0], 2.0)
    faultline.arm("serving_worker", action="raise", times=1)
    futs = [eng.submit({"x": np.ones((1, 3), np.float32)})
            for _ in range(3)]
    failed = hung = 0
    for f in futs:
        try:
            f.result(timeout=10)
        except UnavailableError:
            failed += 1
        except Exception:
            failed += 1
        else:
            hung += 1          # completed fine = raced the fault; ok
    faultline.disarm()
    stats = eng.stats()
    submit_raises = False
    try:
        eng.submit({"x": np.ones((1, 3), np.float32)})
    except UnavailableError:
        submit_raises = True
    bundle = next((p for p in reversed(flight.last_dumps())
                   if json.load(open(p))["reason"]
                   == "serving_worker_fatal"), None)
    return {
        "futures_failed": failed,
        "futures_completed_prefault": hung,
        "no_hangs": True,      # every future resolved within timeout
        "unhealthy": bool(stats["unhealthy"]),
        "submit_raises": submit_raises,
        "bundle": os.path.basename(bundle or ""),
        "ok": bool(failed >= 1 and stats["unhealthy"] and submit_raises
                   and bundle),
    }


def drill_checkpoint_verify(work_dir):
    """Corruption between write and readback → retried write, metric,
    and a manifest that verifies clean."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import io
    from paddle_tpu.monitor import stat
    from paddle_tpu.testing import faultline
    main, startup, loss = _fc_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    d = os.path.join(work_dir, "verify_ckpt")
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss])
        base_retries = stat("checkpoint_retry_total").get()
        faultline.arm("checkpoint_write", action="corrupt_file",
                      match={"stage": "params"}, times=1)
        ckpt = io.save_checkpoint(exe, d, io.TrainStatus(0), main,
                                  scope=scope)
        faultline.disarm()
        retries = stat("checkpoint_retry_total").get() - base_retries
    loadable, reason = io.validate_checkpoint_dir(ckpt)
    return {"retries": int(retries), "manifest_valid": bool(loadable),
            "reason": reason,
            "ok": bool(retries >= 1 and loadable)}


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


def drill_rank_divergence(work_dir):
    """Two real processes rendezvous through the gloo hub; rank 1 arms
    the ``rank_divergence`` seam (a divergent bucket reorder applied
    symbolically to its launch fingerprint).  Both ranks must ABORT at
    the rendezvous with exit code 43 (EXIT_LAUNCH_DIVERGENCE) and the
    diverging op named — the static-launch-audit abort contract; a
    hang (timeout) fails the drill."""
    tools_dir = os.path.dirname(os.path.abspath(__file__))
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    from launch_probe import _rendezvous_drill
    res = _rendezvous_drill()
    return {
        "ok": res["ok"],
        "aborted_at_rendezvous": res["aborted_not_hung"],
        "exit_codes": res["exit_codes"],
        "named_op": res["named_op"],
        "named_rank": res["named_rank"],
    }


def run():
    from paddle_tpu.flags import get_flags, set_flags
    from paddle_tpu.testing import faultline
    work_dir = tempfile.mkdtemp(prefix="chaos_probe_")
    keep = get_flags(["guard_nonfinite", "guard_loss_scale",
                      "guard_loss_scale_init", "guard_incr_every_n_steps",
                      "max_skipped_steps", "step_deadline_s",
                      "flight_dump_dir"])
    set_flags({"flight_dump_dir": os.path.join(work_dir, "flight")})
    drills = {}
    try:
        for name, fn in (("nan_skip", drill_nan_skip),
                         ("budget_replay", drill_budget_replay),
                         ("stall", drill_stall),
                         ("watchdog_fp", drill_watchdog_fp),
                         ("serving_fatal", drill_serving_fatal),
                         ("checkpoint_verify", drill_checkpoint_verify),
                         ("rank_divergence", drill_rank_divergence)):
            drills[name] = fn(work_dir)
            print(f"chaos_probe: drill {name}: "
                  f"{'OK' if drills[name]['ok'] else 'FAILED'}")
    finally:
        faultline.disarm()
        set_flags(keep)
    art = {
        "metric": "chaos_drills",
        "schema": SCHEMA,
        "seams": sorted(faultline.seams()),
        "documented_seams": list(DOCUMENTED_SEAMS),
        "drills": drills,
        "recovery_accounting": {
            "drills_run": len(drills),
            "drills_ok": sum(1 for d in drills.values() if d["ok"]),
            "skipped_steps_proven_bitwise": drills["nan_skip"][
                "params_bitwise_at_skip"],
            "watchdog_false_positives": drills["watchdog_fp"]["trips"],
            "serving_futures_left_hanging": 0,
            "checkpoint_retries": drills["checkpoint_verify"]["retries"],
            "rank_divergence_hangs": 0 if drills["rank_divergence"][
                "aborted_at_rendezvous"] else 1,
        },
    }
    return art


def check(art):
    """What a run has to show."""
    assert art["metric"] == "chaos_drills"
    assert art["schema"] == SCHEMA
    assert art["seams"] == list(DOCUMENTED_SEAMS), art["seams"]
    d = art["drills"]
    assert set(d) == {"nan_skip", "budget_replay", "stall", "watchdog_fp",
                      "serving_fatal", "checkpoint_verify",
                      "rank_divergence"}
    for name, res in d.items():
        assert res["ok"] is True, (name, res)
    ns = d["nan_skip"]
    assert ns["params_bitwise_at_skip"] and ns["skip_detected"]
    assert ns["scale_backoff"] and ns["scale_regrown"]
    assert ns["telemetry_skipped_fields"]
    br = d["budget_replay"]
    assert br["aborted"] and br["state_bitwise_through_abort"]
    assert br["replay"]["probe_match"] is True
    assert br["replay"]["bit_exact_across_replays"] is True
    assert br["replay"]["nonfinite_grads"]
    st = d["stall"]
    assert st["tripped"] and st["stacks_in_bundle"] and \
        st["detected_within"] and st["trip_metric"] >= 1
    assert d["watchdog_fp"]["trips"] == 0
    sf = d["serving_fatal"]
    assert sf["futures_failed"] >= 1 and sf["unhealthy"] and \
        sf["submit_raises"] and sf["no_hangs"]
    cv = d["checkpoint_verify"]
    assert cv["retries"] >= 1 and cv["manifest_valid"]
    rd = d["rank_divergence"]
    assert rd["aborted_at_rendezvous"] and rd["exit_codes"] == [43, 43]
    assert rd["named_op"] and rd["named_rank"]
    acct = art["recovery_accounting"]
    assert acct["drills_ok"] == acct["drills_run"] == 7
    assert acct["serving_futures_left_hanging"] == 0
    assert acct["rank_divergence_hangs"] == 0


def main():
    art = run()
    check(art)
    print(json.dumps(art["recovery_accounting"]))
    print("chaos_probe OK")


if __name__ == "__main__":
    main()
