"""Self-healing step runtime (ISSUE 14): non-finite step defense
(fused finite probe, jnp.where-gated updates, skip budget → controlled
abort with replayable bundle), the unified loss-scale policy, the hang
watchdog, the faultline injection registry, serving-worker fatal
hardening, PreemptionHandler restore atomicity, checkpoint readback
verification, the composition legs (gradient merge / ZeRO-1 / 1F1B),
and the guard overhead bound."""

import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.framework import guardrails
from paddle_tpu.framework.core import (Program, program_guard,
                                       grad_var_name,
                                       reset_default_programs)
from paddle_tpu.framework.errors import (GuardrailViolation,
                                         PreconditionNotMetError,
                                         UnavailableError)
from paddle_tpu.observability import flight, metrics, watchdog
from paddle_tpu.testing import faultline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_GUARD_FLAGS = ["guard_nonfinite", "guard_loss_scale",
                "guard_loss_scale_init", "guard_incr_every_n_steps",
                "guard_incr_ratio", "guard_decr_ratio",
                "guard_loss_scale_max", "max_skipped_steps",
                "step_deadline_s", "watchdog_abort", "flight_dump_dir",
                "checkpoint_retries"]


@pytest.fixture(autouse=True)
def guard_hygiene(tmp_path):
    """Flags restored, seams disarmed, flight bundles into tmp, watchdog
    counters isolated — per test."""
    keep = get_flags(_GUARD_FLAGS)
    set_flags({"flight_dump_dir": str(tmp_path / "flight")})
    faultline.disarm()
    metrics.reset_metrics()
    base_trips = len(watchdog.trips())
    yield
    faultline.disarm()
    set_flags(keep)
    del base_trips


def _fc_train(lr=0.1, opt=None):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[6])
        h = fluid.layers.fc(x, 8)
        y = fluid.layers.fc(h, 3)
        loss = fluid.layers.mean(y)
        (opt or fluid.optimizer.Adam(lr)).minimize(loss)
    return main, startup, loss


def _feed(i=0, rows=4):
    rng = np.random.RandomState(7 + i)
    return {"x": rng.randn(rows, 6).astype(np.float32)}


def _snap(scope):
    """Every non-reserved scope var, as host copies."""
    return {n: np.asarray(v).copy() for n, v in scope.vars.items()
            if not n.startswith("@")}


def _assert_bitwise(a, b):
    assert set(a) == set(b)
    for n in a:
        assert np.array_equal(a[n], b[n]), f"{n} changed"


# ---------------------------------------------------------------------------
# faultline registry
# ---------------------------------------------------------------------------


def test_faultline_registry_static_and_documented():
    """The seam set is statically enumerable and matches the documented
    list (MIGRATION.md / chaos artifact) — injection sites cannot
    silently drift."""
    from tools.chaos_probe import DOCUMENTED_SEAMS
    assert sorted(faultline.seams()) == list(DOCUMENTED_SEAMS)
    with pytest.raises(KeyError):
        faultline.arm("no_such_seam")
    # with ANY seam armed, a typo'd crossing fails loudly
    faultline.arm("step_stall", action="stall", seconds=0)
    with pytest.raises(KeyError):
        faultline.crossing("no_such_seam_either")
    faultline.disarm()
    # unarmed crossing: no-op returning None
    assert faultline.crossing("step_stall") is None
    e0 = faultline.epoch()
    faultline.arm("step_stall", action="stall", seconds=0)
    assert faultline.epoch() == e0 + 1
    faultline.disarm("step_stall")
    assert faultline.epoch() == e0 + 2


def test_faultline_at_times_and_match_windows():
    spec = faultline.arm("checkpoint_write", action="raise", at=1,
                         times=1, match={"stage": "params"})
    assert faultline.crossing("checkpoint_write", stage="rng") is None
    assert faultline.crossing("checkpoint_write", stage="params") is None
    with pytest.raises(faultline.FaultlineError):
        faultline.crossing("checkpoint_write", stage="params")
    # window exhausted
    assert faultline.crossing("checkpoint_write", stage="params") is None
    assert spec.hits == 3 and spec.fired == 1


# ---------------------------------------------------------------------------
# non-finite step defense (tentpole)
# ---------------------------------------------------------------------------


def test_skip_step_bitwise_params_and_optimizer_state():
    """A NaN gradient at device step k skips the step: params AND Adam
    moments come out bitwise equal to step k−1; recovery resumes."""
    set_flags({"guard_nonfinite": True})
    main, startup, loss = _fc_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=_feed())
        for i in range(3):
            prepared.run(_feed(i))
        prepared.wait()
        prepared.sync_scope()
        snap = _snap(scope)
        faultline.arm("grad_nonfinite", action="nan", step=3, times=1)
        h, = prepared.run(_feed(3))
        # the LOSS of the poisoned step is still finite (the fault was
        # in the gradient) — only the update was suppressed
        assert np.isfinite(h.numpy()).all()
        gi = prepared.guard_info(sync=True)
        assert gi["last_skipped"] and gi["skipped_total"] == 1 \
            and gi["consecutive"] == 1
        prepared.sync_scope()
        _assert_bitwise(snap, _snap(scope))
        faultline.disarm()
        prepared.run(_feed(4))
        gi = prepared.guard_info(sync=True)
        assert not gi["last_skipped"] and gi["consecutive"] == 0
        prepared.sync_scope()
        moved = _snap(scope)
        assert any(not np.array_equal(moved[n], snap[n]) for n in snap)
        prepared.close()


def test_skip_detects_inf_not_just_nan():
    set_flags({"guard_nonfinite": True})
    main, startup, loss = _fc_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        # an Inf-producing feed: huge activations overflow f32 in the
        # matmul chain
        bad = {"x": np.full((4, 6), 3e38, np.float32)}
        exe.run(main, feed=_feed(), fetch_list=[loss])
        snap = _snap(scope)
        exe.run(main, feed=bad, fetch_list=[loss])
        post = _snap(scope)
        _assert_bitwise(snap, post)
        assert int(np.asarray(
            scope.find_var(guardrails.GUARD_SKIP_TOTAL))) == 1


def test_skip_budget_controlled_abort_with_replayable_bundle(tmp_path):
    set_flags({"guard_nonfinite": True, "max_skipped_steps": 2})
    main, startup, loss = _fc_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=_feed())
        prepared.run(_feed())
        faultline.arm("grad_nonfinite", action="nan", times=None)
        with pytest.raises(GuardrailViolation):
            for i in range(40):
                prepared.run(_feed(1))
            prepared.wait()
    bundle_path = flight.last_dumps()[-1]
    b = flight.validate_bundle(bundle_path)
    assert b["reason"] == "guardrail_skip_budget_exhausted"
    g = b["extra"]["guard"]
    assert g["consecutive_skipped"] > 2
    assert g["probe_bits"] and g["loss_scale"] == 1.0
    side = np.load(g["feed_file"])
    assert set(side.files) >= {"x", "__rng_key__", "__step_counter__",
                               "__loss_scale__"}
    from paddle_tpu.framework.serialization import desc_to_program
    prog = desc_to_program(json.load(open(g["program_file"])))
    assert any(op.type == "backward"
               for op in prog.global_block().ops)
    assert b["extra"]["faultline"][0]["seam"] == "grad_nonfinite"


def test_guard_loss_scale_backoff_and_regrow():
    """Shared policy on a plain fp32 run: backoff ×decr at the skip,
    regrow ×incr after incr_every good steps, capped at max."""
    set_flags({"guard_nonfinite": True, "guard_loss_scale": True,
               "guard_loss_scale_init": 256.0,
               "guard_incr_every_n_steps": 2,
               "guard_loss_scale_max": 256.0})
    main, startup, loss = _fc_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    scales = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=_feed())
        faultline.arm("grad_nonfinite", action="nan", step=1, times=1)
        for i in range(6):
            prepared.run(_feed(i))
            scales.append(prepared.guard_info(sync=True)["loss_scale"])
        prepared.close()
    faultline.disarm()
    assert scales[0] == 256.0          # healthy
    assert scales[1] == 128.0          # backoff at the skip
    assert scales[3] == 256.0          # regrown after 2 good steps
    assert scales[-1] == 256.0         # capped at max


def test_scale_policy_shared_with_amp_op():
    """update_loss_scaling (the AMP op) and the guardrail call ONE
    policy function — assert the op's output equals a direct policy
    call, both branches."""
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import get_op
    impl = get_op("update_loss_scaling")
    for found in (True, False):
        ins = {"X": [jnp.ones((3,))],
               "FoundInfinite": [jnp.asarray(found)],
               "PrevLossScaling": [jnp.asarray([1024.0], jnp.float32)],
               "InGoodSteps": [jnp.asarray([1], jnp.int32)],
               "InBadSteps": [jnp.asarray([1], jnp.int32)]}
        attrs = {"incr_every_n_steps": 2, "decr_every_n_nan_or_inf": 2,
                 "incr_ratio": 2.0, "decr_ratio": 0.5}
        out = impl(None, ins, attrs)
        scale, good, bad = guardrails.scale_policy_update(
            jnp.asarray(found), jnp.asarray([1024.0], jnp.float32),
            jnp.asarray([1], jnp.int32), jnp.asarray([1], jnp.int32),
            incr_every_n_steps=2, decr_every_n_nan_or_inf=2,
            incr_ratio=2.0, decr_ratio=0.5)
        assert np.array_equal(np.asarray(out["LossScaling"]),
                              np.asarray(scale))
        assert np.array_equal(np.asarray(out["OutGoodSteps"]),
                              np.asarray(good))
        assert np.array_equal(np.asarray(out["OutBadSteps"]),
                              np.asarray(bad))


def test_guard_composes_with_amp_dynamic_scaling():
    """fp16 AMP + guard: the poisoned step leaves params bitwise intact
    while AMP's OWN scale state advances (backoff is the response, not
    a casualty of the gate)."""
    from paddle_tpu.contrib.mixed_precision import decorate
    set_flags({"guard_nonfinite": True})
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[6])
        h = fluid.layers.fc(x, 8)
        loss = fluid.layers.mean(fluid.layers.fc(h, 3))
        opt = decorate(fluid.optimizer.SGD(0.1), use_pure_bf16=False,
                       init_loss_scaling=64.0,
                       decr_every_n_nan_or_inf=1, decr_ratio=0.5)
        opt.minimize(loss)
    scale_var = opt._loss_scale_var.name
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss])
        snap = _snap(scope)
        faultline.arm("grad_nonfinite", action="nan", times=1)
        exe.run(main, feed=_feed(1), fetch_list=[loss])
        faultline.disarm()
        post = _snap(scope)
        # AMP's scale state advanced (backoff 64 -> 32)...
        assert float(np.asarray(post[scale_var]).reshape(())) == 32.0
        # ...while every OTHER persistable is bitwise unchanged
        for n in snap:
            if n in (scale_var,) or "good_steps" in n or "bad_steps" in n:
                continue
            assert np.array_equal(snap[n], post[n]), n
        # guard telemetry reports AMP's scale, not its parked own
        gf32 = np.asarray(scope.find_var(guardrails.GUARD_SKIP_TOTAL))
        assert int(gf32) == 1


# ---------------------------------------------------------------------------
# composition legs: gradient merge / ZeRO-1 / pipelined 1F1B
# ---------------------------------------------------------------------------


def test_skip_composes_with_gradient_merge_microbatching():
    from paddle_tpu.framework.pipe import set_microbatches
    set_flags({"guard_nonfinite": True})
    main, startup, loss = _fc_train()
    set_microbatches(main, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(0, rows=8), fetch_list=[loss])
        snap = _snap(scope)
        faultline.arm("grad_nonfinite", action="nan", times=1)
        exe.run(main, feed=_feed(1, rows=8), fetch_list=[loss])
        faultline.disarm()
        _assert_bitwise(snap, _snap(scope))
        assert int(np.asarray(
            scope.find_var(guardrails.GUARD_SKIP_TOTAL))) == 1
        # recovery: the next clean step moves params again
        exe.run(main, feed=_feed(2, rows=8), fetch_list=[loss])
        post = _snap(scope)
        assert any(not np.array_equal(post[n], snap[n]) for n in snap)


def _zero1_dp8():
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.distributed.fleet import (fleet, DistributedStrategy,
                                              UserDefinedRoleMaker,
                                              distributed_optimizer)
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu",
                            param_attr=fluid.ParamAttr(name="w1"),
                            bias_attr=False)
        pred = fluid.layers.fc(h, 4, act="softmax",
                               param_attr=fluid.ParamAttr(name="w2"),
                               bias_attr=False)
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(pred, label))
        fleet.init(UserDefinedRoleMaker(0, 1))
        s = DistributedStrategy()
        s.sharded_update = True
        s.mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
        opt = distributed_optimizer(fluid.optimizer.Adam(5e-3), s)
        opt.minimize(loss)
    return fleet.main_program, startup, loss


def _zero1_batch(i):
    rng = np.random.RandomState(50 + i)
    xs = rng.randn(64, 16).astype(np.float32)
    ys = (xs.sum(1) > 0).astype(np.int64).reshape(-1, 1) * 3
    return {"x": xs, "label": ys}


def test_skip_composes_with_zero1_sharded_update():
    """Guard × ZeRO-1: the gate selects on the LOCAL flat optimizer
    shards inside shard_map — a poisoned step leaves params and the
    sharded Adam state bitwise intact on every replica."""
    set_flags({"guard_nonfinite": True})
    prog, startup, loss = _zero1_dp8()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=_zero1_batch(0), fetch_list=[loss])
        snap = _snap(scope)
        faultline.arm("grad_nonfinite", action="nan", times=1)
        exe.run(prog, feed=_zero1_batch(1), fetch_list=[loss])
        faultline.disarm()
        _assert_bitwise(snap, _snap(scope))
        assert int(np.asarray(
            scope.find_var(guardrails.GUARD_SKIP_TOTAL))) == 1
        exe.run(prog, feed=_zero1_batch(2), fetch_list=[loss])
        post = _snap(scope)
        assert any(not np.array_equal(post[n], snap[n]) for n in snap)


def test_skip_composes_with_pipelined_1f1b():
    """Guard × 1F1B over pp2: the probe psums across the pipe axis, so
    a stage-partial NaN skips the step on EVERY pp rank — params bitwise
    intact everywhere."""
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.framework.compiler import (BuildStrategy,
                                               CompiledProgram)
    from paddle_tpu.framework.pipe import apply_pipeline
    set_flags({"guard_nonfinite": True})
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[-1, 16],
                              append_batch_size=False)
        y = fluid.layers.data("label", shape=[-1, 1], dtype="float32",
                              append_batch_size=False)
        h = fluid.layers.fc(x, 32, act="relu",
                            param_attr=fluid.ParamAttr(name="w1"))
        h = fluid.layers.fc(h, 32, act="relu",
                            param_attr=fluid.ParamAttr(name="w2"))
        p = fluid.layers.fc(h, 1, param_attr=fluid.ParamAttr(name="w3"))
        loss = fluid.layers.mean(fluid.layers.square(p - y))
        fluid.optimizer.Adam(5e-3).minimize(loss)
    apply_pipeline(main, 2, 2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("pp",))
    prog = CompiledProgram(main).with_mesh(
        mesh, loss_name=loss.name, batch_axis="dp",
        build_strategy=BuildStrategy())
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.randn(8, 16).astype("float32"),
              "label": rng.randn(8, 1).astype("float32")}
             for _ in range(3)]
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=feeds[0], fetch_list=[loss])
        snap = _snap(scope)
        faultline.arm("grad_nonfinite", action="nan", times=1)
        exe.run(prog, feed=feeds[1], fetch_list=[loss])
        faultline.disarm()
        _assert_bitwise(snap, _snap(scope))
        assert int(np.asarray(
            scope.find_var(guardrails.GUARD_SKIP_TOTAL))) == 1
        exe.run(prog, feed=feeds[2], fetch_list=[loss])
        post = _snap(scope)
        assert any(not np.array_equal(post[n], snap[n]) for n in snap)


def test_guard_loss_scale_rejected_on_pipelined_program():
    from paddle_tpu.framework.pipe import set_microbatches
    from paddle_tpu.framework.errors import InvalidArgumentError
    set_flags({"guard_nonfinite": True, "guard_loss_scale": True})
    main, startup, loss = _fc_train()
    set_microbatches(main, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(InvalidArgumentError, match="guard_loss_scale"):
            exe.run(main, feed=_feed(0, rows=8), fetch_list=[loss])


# ---------------------------------------------------------------------------
# telemetry fields
# ---------------------------------------------------------------------------


def test_telemetry_records_skipped_and_loss_scale(tmp_path):
    from paddle_tpu.observability import TelemetryRecorder, validate_jsonl
    set_flags({"guard_nonfinite": True})
    main, startup, loss = _fc_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    jsonl = str(tmp_path / "t.jsonl")
    with fluid.scope_guard(scope):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=_feed())
        rec = TelemetryRecorder(jsonl, program=main,
                                fetch_names=[loss.name]).attach(prepared)
        faultline.arm("grad_nonfinite", action="nan", step=1, times=1)
        for i in range(3):
            with rec.step(tokens=4) as st:
                h, = prepared.run(_feed(i))
                st.loss = h
            prepared.guard_info(sync=True)
        rec.close()
        prepared.close()
    faultline.disarm()
    validate_jsonl(jsonl)
    steps = [json.loads(l) for l in open(jsonl) if l.strip()]
    steps = [s for s in steps if s.get("record") == "step"]
    assert [s["skipped"] for s in steps] == [False, True, False]
    assert all(s["loss_scale"] == 1.0 for s in steps)
    # the skipped step's LOSS stays finite — the defense acted on the
    # gradient before the optimizer, not after the crash
    assert all(s["loss_finite"] for s in steps)


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


def test_watchdog_trips_on_stall_with_stacks_and_metric():
    deadline = 0.3
    set_flags({"step_deadline_s": deadline})
    main, startup, loss = _fc_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    base = len(watchdog.trips())
    with fluid.scope_guard(scope):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=_feed())
        prepared.run(_feed())
        faultline.arm("step_stall", action="stall",
                      seconds=3 * deadline, times=1)
        prepared.run(_feed())
        faultline.disarm()
        prepared.close()
    set_flags({"step_deadline_s": 0.0})
    new = watchdog.trips()[base:]
    assert new, "watchdog did not trip on a stalled step"
    trip = new[-1]
    assert trip["beacon"] == "prepared"
    assert trip["stalled_s"] <= 3 * deadline + 0.5
    b = flight.validate_bundle(trip["bundle"])
    stacks = b["extra"]["thread_stacks"]
    assert len(stacks) >= 1
    assert any("crossing" in "".join(fr) or "_run_inner" in "".join(fr)
               for fr in stacks.values())
    snap = metrics.metrics_snapshot(include_serving=False)
    assert sum(int(m.get("value", 0)) for m in snap["metrics"]
               if m["name"] == "watchdog::trip") >= 1


def test_watchdog_false_positive_bound_slow_but_healthy():
    set_flags({"step_deadline_s": 2.0})
    main, startup, loss = _fc_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    base = len(watchdog.trips())
    with fluid.scope_guard(scope):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=_feed())
        faultline.arm("step_stall", action="stall", seconds=0.08,
                      times=None)
        for i in range(5):
            prepared.run(_feed(i))
        prepared.wait()
        faultline.disarm()
        prepared.close()
    time.sleep(0.4)
    set_flags({"step_deadline_s": 0.0})
    assert len(watchdog.trips()) == base


# ---------------------------------------------------------------------------
# serving worker hardening
# ---------------------------------------------------------------------------


class _StubPredictor:
    compiled_executables = 0

    def get_input_names(self):
        return ["x"]

    def get_output_names(self):
        return ["y"]

    def prepare(self):
        return self

    def run_feed(self, feed):
        return [np.asarray(feed["x"]) * 2.0]


def test_serving_worker_fatal_fails_all_futures_and_marks_unhealthy():
    from paddle_tpu.serving import ServingConfig, ServingEngine
    eng = ServingEngine(_StubPredictor(),
                        ServingConfig(max_batch_size=4, max_wait_ms=1.0))
    ok = eng.submit({"x": np.ones((1, 3), np.float32)})
    assert np.allclose(ok.result(timeout=10)[0], 2.0)
    faultline.arm("serving_worker", action="raise", times=1)
    futs = [eng.submit({"x": np.ones((1, 3), np.float32)})
            for _ in range(3)]
    resolved = 0
    for f in futs:
        with pytest.raises(UnavailableError, match="worker died"):
            f.result(timeout=10)
        resolved += 1
    assert resolved == 3          # nothing hung
    faultline.disarm()
    assert eng.stats()["unhealthy"] is True
    with pytest.raises(UnavailableError, match="unhealthy"):
        eng.submit({"x": np.ones((1, 3), np.float32)})
    assert any(json.load(open(p))["reason"] == "serving_worker_fatal"
               for p in flight.last_dumps())
    # drain() must not hang on a dead engine either
    assert eng.drain(timeout=5)


# ---------------------------------------------------------------------------
# preemption × restore atomicity
# ---------------------------------------------------------------------------


def test_preemption_signal_mid_reshard_is_deferred(tmp_path):
    """A SIGTERM delivered from INSIDE execute_reshard (faultline seam)
    must not fire the handler mid-restore: the flag is set only after
    the scope holds fully-restored state, and save() during restore
    refuses."""
    import signal
    import jax
    from jax.sharding import Mesh
    from paddle_tpu import io
    from paddle_tpu.distributed.fleet import (fleet, DistributedStrategy,
                                              UserDefinedRoleMaker,
                                              distributed_optimizer)
    from paddle_tpu.distributed.preemption import PreemptionHandler

    def build(ndev):
        reset_default_programs()
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = fluid.layers.data("x", shape=[16])
            label = fluid.layers.data("label", shape=[1], dtype="int64")
            h = fluid.layers.fc(x, 16, act="relu",
                                param_attr=fluid.ParamAttr(name="w1"),
                                bias_attr=False)
            pred = fluid.layers.fc(h, 4, act="softmax",
                                   param_attr=fluid.ParamAttr(name="w2"),
                                   bias_attr=False)
            loss = fluid.layers.mean(
                fluid.layers.cross_entropy(pred, label))
            fleet.init(UserDefinedRoleMaker(0, 1))
            s = DistributedStrategy()
            s.sharded_update = True
            s.mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))
            opt = distributed_optimizer(fluid.optimizer.Adam(5e-3), s)
            opt.minimize(loss)
        return fleet.main_program, startup, loss, main

    old_term = signal.getsignal(signal.SIGTERM)
    ckpt = str(tmp_path / "ckpt")
    prog8, startup8, loss8, main8 = build(8)
    exe = fluid.Executor(fluid.CPUPlace())
    scope8 = fluid.Scope()
    with fluid.scope_guard(scope8):
        exe.run(startup8)
        exe.run(prog8, feed=_zero1_batch(0), fetch_list=[loss8])
        io.save_checkpoint(exe, ckpt, io.TrainStatus(0, 0), main8,
                           scope=scope8)

    # relaunch on 4 devices: restore reshards (flat repad) — the seam
    # delivers SIGTERM mid-execute
    prog4, startup4, loss4, main4 = build(4)
    scope4 = fluid.Scope()
    with fluid.scope_guard(scope4):
        exe.run(startup4)
        handler = PreemptionHandler(exe, ckpt, main4, scope=scope4,
                                    exit_on_preempt=False,
                                    signals=(signal.SIGTERM,))
        faultline.arm("reshard_execute", action="signal",
                      sig=signal.SIGTERM, times=1)
        st = handler.restore()
        faultline.disarm()
        assert st.step == 0 and st.reshard is not None
        # the deferred signal fired AFTER restore completed
        assert handler.preempted is True
        # a clean reference restore must match — nothing was torn
        ref_scope = fluid.Scope()
        with fluid.scope_guard(ref_scope):
            exe.run(startup4)
            io.load_checkpoint(exe, ckpt, main_program=main4,
                               scope=ref_scope)
        for n in ("w1", "w2"):
            assert np.array_equal(np.asarray(scope4.find_var(n)),
                                  np.asarray(ref_scope.find_var(n))), n
        # save() during restore refuses (atomicity contract)
        handler._restoring = True
        with pytest.raises(PreconditionNotMetError):
            handler.save(1)
        handler._restoring = False
    signal.signal(signal.SIGTERM, old_term)


# ---------------------------------------------------------------------------
# checkpoint readback verification
# ---------------------------------------------------------------------------


def test_checkpoint_corruption_between_write_and_verify_is_retried(
        tmp_path):
    from paddle_tpu import io
    from paddle_tpu.monitor import stat
    main, startup, loss = _fc_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss])
        base = stat("checkpoint_retry_total").get()
        faultline.arm("checkpoint_write", action="corrupt_file",
                      match={"stage": "params"}, times=1)
        d = io.save_checkpoint(exe, str(tmp_path / "c"),
                               io.TrainStatus(0), main, scope=scope)
        faultline.disarm()
        assert stat("checkpoint_retry_total").get() - base >= 1
    loadable, reason = io.validate_checkpoint_dir(d)
    assert loadable, reason
    snap = metrics.metrics_snapshot(include_serving=False)
    assert any(m["name"] == "checkpoint::retry"
               and m["labels"].get("stage") == "params"
               for m in snap["metrics"])


def test_checkpoint_verify_exhausted_retries_raise(tmp_path):
    from paddle_tpu import io
    set_flags({"checkpoint_retries": 1})
    main, startup, loss = _fc_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss])
        faultline.arm("checkpoint_write", action="corrupt_file",
                      match={"stage": "params"}, times=None)
        with pytest.raises(io.ChecksumMismatchError):
            io.save_checkpoint(exe, str(tmp_path / "c"),
                               io.TrainStatus(0), main, scope=scope)
        faultline.disarm()


# ---------------------------------------------------------------------------
# collective seam + replay + artifact + overhead
# ---------------------------------------------------------------------------


def test_collective_impl_seam_raises_as_enforce_not_met():
    from paddle_tpu.framework.errors import EnforceNotMet
    main, startup, loss = _fc_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        faultline.arm("collective_impl", action="raise",
                      match={"op": "mean"}, times=1)
        with pytest.raises(EnforceNotMet, match="mean"):
            exe.run(main, feed=_feed(), fetch_list=[loss])
        faultline.disarm()


def test_replay_step_reproduces_bundle_anomaly(tmp_path):
    """End-to-end replay: abort bundle + checkpoint → re-executed step
    reproduces the non-finite gradient bit-exactly."""
    from paddle_tpu import io
    from tools.replay_step import replay
    set_flags({"guard_nonfinite": True, "max_skipped_steps": 2})
    main, startup, loss = _fc_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ckpt = str(tmp_path / "ckpt")
    with fluid.scope_guard(scope):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=_feed())
        for i in range(2):
            prepared.run(_feed(i))
        prepared.wait()
        io.save_checkpoint(exe, ckpt, io.TrainStatus(1), main,
                           scope=scope)
        faultline.arm("grad_nonfinite", action="nan", times=None)
        with pytest.raises(GuardrailViolation):
            for i in range(40):
                prepared.run(_feed(2))
            prepared.wait()
        faultline.disarm()
    bundle = flight.last_dumps()[-1]
    rep = replay(bundle, ckpt)
    assert rep["probe_match"], rep
    assert rep["nonfinite_grads"], rep
    assert rep["bit_exact_across_replays"], rep
    assert rep["reproduced"]


def test_guard_host_overhead_bound():
    """The guard's per-step HOST cost on the prepared loop — deque
    append + decode-cadence check, with the device read amortized over
    _GUARD_DECODE_EVERY steps — must stay ≤5% of the stub-step loop
    time (the PR 2 baseline survives; same cost-of-part-vs-whole
    methodology as the telemetry overhead test)."""
    import timeit
    import jax
    from paddle_tpu.framework import executor as executor_mod
    from paddle_tpu.framework.executor import _RNG_VAR

    # -- the stub-step loop (guard OFF: the baseline being protected)
    main, startup, loss = _fc_train()
    feed = _feed(rows=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        step = exe._compile(main, feed, [loss.name], scope, None, (),
                            None)
        real_fn = step.fn
        state_in = {n: scope.find_var(n) for n in step.state_in_names}
        template = real_fn({k: feed[k] for k in step.feed_names},
                           state_in, scope.find_var(_RNG_VAR))
        jax.block_until_ready(template)
        step.fn = lambda f, s, k: template
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                               feed=feed)
        prepared.run(feed)
        steps, loop_ns = 300, float("inf")
        try:
            for _ in range(5):
                prepared.run(feed)
                t0 = time.perf_counter_ns()
                for _ in range(steps):
                    prepared.run(feed)
                loop_ns = min(loop_ns,
                              (time.perf_counter_ns() - t0) / steps)
        finally:
            step.fn = real_fn
            prepared.close()

    # -- the guard's per-step host cost, measured as cost-of-parts:
    # every step pays one deque append + one int compare; one step in
    # _GUARD_DECODE_EVERY pays the is_ready probe + the packed i32
    # decode (device scalar read)
    import collections
    import jax.numpy as jnp
    g_i32 = jax.device_put(np.array([0, 0, 0, 5], np.int32))
    g_f32 = jax.device_put(np.array([0.0, 1.0], np.float32))
    jax.block_until_ready((g_i32, g_f32))
    pend = collections.deque()
    entry = (1, [g_i32, g_f32], feed, None)

    def per_step():
        pend.append(entry)
        pend.popleft()

    append_ns = min(timeit.repeat(per_step, number=50_000,
                                  repeat=5)) / 50_000 * 1e9
    decode_ns = min(timeit.repeat(
        lambda: (g_i32.is_ready(),
                 np.asarray(g_i32).reshape(4)),
        number=5_000, repeat=5)) / 5_000 * 1e9
    guard_ns = append_ns + \
        decode_ns / executor_mod._GUARD_DECODE_EVERY
    assert guard_ns <= 0.05 * loop_ns, (guard_ns, loop_ns)
