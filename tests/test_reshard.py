"""Elastic training: layout-portable checkpoints + planned resharding
restore (framework/reshard.py, io.py checkpoint format v2).

* plan structure: dp8→dp4 coarsens with grouped all_gathers, dp8→dp16
  refines with 0-wire slices, tp2→tp1 gathers over tp, general
  re-splits go all_to_all at lcm granularity;
* candidate schedules are priced statically — the naive
  gather-then-slice candidate is REJECTED with 0 compiles attempted;
* executing a plan moves exactly the bytes the plan priced (strict
  accounting) and reproduces the source state bit-for-bit;
* ZeRO-1 (sharded_update) dp8 checkpoints restore onto dp4 — the flat
  optimizer shards REPAD (1024→512 element padding) instead of crashing
  on a shape mismatch — and the loss curve continues within 1e-6 of the
  uninterrupted dp8 run (bit-exact when the layout matches);
* ZeRO-3 (fsdp) checkpoints restore across fsdp degrees the same way;
* corrupt/partial checkpoints are skipped for the newest VALID one;
  retention pruning keeps the newest ``max_checkpoints``; cold-start
  restore on an empty dir is clean;
* a layout mismatch raises an anchored InvalidArgumentError naming
  BOTH layouts (never a shape error deep in the executor).
"""

import json
import os

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu import io
from paddle_tpu.framework.analysis import verify_reshard
from paddle_tpu.framework.core import (Program, program_guard,
                                       reset_default_programs)
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.framework.fsdp import apply_fsdp_sharding
from paddle_tpu.framework.mesh_layout import MeshLayout, ShardSpec
from paddle_tpu.framework.reshard import (execute_reshard, flat_shard_meta,
                                          plan_reshard, plan_var_transfer)
from paddle_tpu.framework.compiler import BuildStrategy, CompiledProgram
from paddle_tpu.distributed.fleet import (fleet, DistributedStrategy,
                                          distributed_optimizer,
                                          UserDefinedRoleMaker)
from paddle_tpu.monitor import stat



# ---------------------------------------------------------------------------
# plan structure + pricing
# ---------------------------------------------------------------------------


def test_plan_dp8_to_dp4_grouped_gather():
    plan = plan_reshard(
        MeshLayout(data=1, fsdp=8), MeshLayout(data=1, fsdp=4),
        var_sigs={"w": ((64, 32), "float32")},
        src_specs={"w": ShardSpec(("fsdp", None))})
    (t,) = plan.moving
    assert [s.kind for s in t.steps] == ["all_gather"]
    assert t.steps[0].detail["group"] == 2
    # ring gather over groups of 2: each rank receives its peer's shard
    assert t.wire_bytes == 64 * 32 * 4
    assert plan.compiles_attempted == 0


def test_plan_dp8_to_dp16_is_free_slice():
    plan = plan_reshard(
        MeshLayout(data=1, fsdp=8), MeshLayout(data=1, fsdp=16),
        var_sigs={"w": ((64, 32), "float32")},
        src_specs={"w": ShardSpec(("fsdp", None))})
    (t,) = plan.moving
    assert [s.kind for s in t.steps] == ["slice"]
    assert plan.wire_bytes == 0


def test_plan_tp_flip_gathers_over_tp():
    plan = plan_reshard(
        MeshLayout(data=4, tp=2), MeshLayout(data=8, tp=1),
        var_sigs={"wq": ((32, 64), "float32"),
                  "b": ((64,), "float32")},
        src_specs={"wq": ShardSpec((None, "tp"))})
    (t,) = plan.moving
    assert t.name == "wq"
    assert [s.kind for s in t.steps] == ["all_gather"]
    assert t.steps[0].dim == 1
    assert plan.transfers["b"].identity       # replicated: untouched


def test_plan_general_resplit_moves_only_nonoverlap():
    # 8 → 6 shards: lcm=24 micro-shards; linear-colocated overlap keeps
    # part of the payload local, only the rest rides the all_to_all
    plan = plan_reshard(
        MeshLayout(data=1, fsdp=8), MeshLayout(data=1, fsdp=6),
        var_sigs={"w": ((48, 4), "float32")},
        src_specs={"w": ShardSpec(("fsdp", None))})
    (t,) = plan.moving
    assert [s.kind for s in t.steps] == ["all_to_all"]
    nbytes = 48 * 4 * 4
    assert 0 < t.wire_bytes < nbytes
    # and the candidate ledger shows the naive plan was priced + rejected
    names = {c["name"]: c for c in t.candidates}
    assert names["gather-then-slice"]["wire_bytes"] == 7 * nbytes
    assert not names["gather-then-slice"]["chosen"]
    assert names["direct"]["chosen"]


def test_rejected_candidates_cost_zero_compiles(monkeypatch):
    calls = []
    real_jit = jax.jit
    monkeypatch.setattr(jax, "jit",
                        lambda *a, **k: calls.append(1) or real_jit(*a, **k))
    before = stat("executor_compile_count").get()
    plan = plan_reshard(
        MeshLayout(data=1, fsdp=8), MeshLayout(data=1, fsdp=4),
        var_sigs={"w": ((64, 32), "float32"),
                  "v": ((48, 4), "float32")},
        src_specs={"w": ShardSpec(("fsdp", None)),
                   "v": ShardSpec(("fsdp", None))})
    plan.price()
    assert plan.candidates_rejected() >= 1
    assert calls == []
    assert stat("executor_compile_count").get() == before
    assert plan.as_dict()["compiles_attempted"] == 0


def test_execute_matches_planned_accounting_bitwise():
    rng = np.random.RandomState(0)
    arrays = {"w": rng.randn(48, 32).astype(np.float32),
              "v": rng.randn(48, 4).astype(np.float32)}
    plan = plan_reshard(
        MeshLayout(data=1, fsdp=8), MeshLayout(data=1, fsdp=6),
        var_sigs={k: (v.shape, str(v.dtype)) for k, v in arrays.items()},
        src_specs={"w": ShardSpec(("fsdp", None)),
                   "v": ShardSpec(("fsdp", None))})
    out, stats = execute_reshard(plan, arrays)   # strict: raises on drift
    assert stats["wire_bytes"] == plan.wire_bytes
    for k in arrays:
        np.testing.assert_array_equal(out[k], arrays[k])


def test_flat_repad_realigns_zero1_shards():
    numel, align = 1300, 128
    pad8 = numel + (-numel % (8 * align))      # 2048
    pad4 = numel + (-numel % (4 * align))      # 1536
    assert (pad8, pad4) == (2048, 1536)
    tr = plan_var_transfer(
        "m0", (pad8,), "float32", ShardSpec(("dp",)), MeshLayout(data=8),
        ShardSpec(("dp",)), MeshLayout(data=4),
        flat={"numel": numel, "align": align, "axes": ["dp"]})
    assert tr.dst_shape == (pad4,)
    assert [s.kind for s in tr.steps] == ["repad"]
    plan = plan_reshard(MeshLayout(data=8), MeshLayout(data=4),
                        var_sigs={"m0": ((pad8,), "float32")},
                        flat_meta={"m0": {"numel": numel, "align": align,
                                          "axes": ["dp"]}})
    arr = np.zeros(pad8, np.float32)
    arr[:numel] = np.arange(numel, dtype=np.float32)
    out, stats = execute_reshard(plan, {"m0": arr})
    assert out["m0"].shape == (pad4,)
    np.testing.assert_array_equal(out["m0"][:numel], arr[:numel])
    assert not out["m0"][numel:].any()         # padding stays inert zero


# ---------------------------------------------------------------------------
# verify_reshard diagnostics
# ---------------------------------------------------------------------------


def test_verify_reshard_indivisible_is_anchored_error():
    with pytest.raises(InvalidArgumentError) as ei:
        plan_reshard(MeshLayout(data=1, fsdp=8), MeshLayout(data=1, fsdp=3),
                     var_sigs={"w": ((30, 4), "float32")},
                     src_specs={"w": ShardSpec(("fsdp", None))})
    msg = str(ei.value)
    assert "reshard-indivisible" in msg and "'w'" in msg


def test_verify_reshard_dangling_axis_warns_not_errors():
    plan = plan_reshard(
        MeshLayout(data=8), MeshLayout(data=4),
        var_sigs={"w": ((64, 4), "float32")},
        src_specs={"w": ShardSpec(("sp", None))},    # sp not in layouts
        validate=False)
    res = verify_reshard(plan)
    assert res.ok
    assert res.by_code("reshard-axis-dangling")


def test_verify_reshard_schedule_wellformedness():
    plan = plan_reshard(
        MeshLayout(data=1, fsdp=8), MeshLayout(data=1, fsdp=4),
        var_sigs={"w": ((64, 32), "float32")},
        src_specs={"w": ShardSpec(("fsdp", None))})
    res = verify_reshard(plan)
    assert res.ok
    # break the schedule: the verifier must see the chain mismatch
    plan.transfers["w"].steps[0].src_parts = 5
    res2 = verify_reshard(plan)
    assert res2.by_code("reshard-divs-unresolved")


# ---------------------------------------------------------------------------
# end-to-end: ZeRO-1 dp8 checkpoint restores onto dp4 (flat repad)
# ---------------------------------------------------------------------------

STEPS_BEFORE, STEPS_AFTER = 3, 3


def _model():
    x = fluid.layers.data("x", shape=[16])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, 32, act="relu",
                        param_attr=fluid.ParamAttr(
                            name="w1",
                            initializer=fluid.initializer.Constant(0.05)),
                        bias_attr=False)
    h = fluid.layers.fc(h, 32, act="relu",
                        param_attr=fluid.ParamAttr(
                            name="w2",
                            initializer=fluid.initializer.Constant(0.04)),
                        bias_attr=False)
    pred = fluid.layers.fc(h, 4, act="softmax",
                           param_attr=fluid.ParamAttr(
                               name="w3",
                               initializer=fluid.initializer.Constant(0.05)),
                           bias_attr=False)
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    return loss


def _batch(step):
    rng = np.random.RandomState(1000 + step)
    xs = rng.randn(64, 16).astype(np.float32)
    ys = (xs.sum(1) > 0).astype(np.int64).reshape(-1, 1) * 3
    return xs, ys


def _build_zero1(ndev):
    from jax.sharding import Mesh
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fleet.init(UserDefinedRoleMaker(0, 1))
        s = DistributedStrategy()
        s.sharded_update = True
        s.mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))
        opt = distributed_optimizer(fluid.optimizer.Adam(5e-3), s)
        opt.minimize(loss)
    return main, startup, loss, fleet.main_program


def _run_steps(exe, prog, loss, scope, start, n):
    losses = []
    with fluid.scope_guard(scope):
        for i in range(start, start + n):
            xs, ys = _batch(i)
            l, = exe.run(prog, feed={"x": xs, "label": ys},
                         fetch_list=[loss])
            losses.append(float(np.asarray(l).reshape(())))
    return losses


def _digest(scope, names=("w1", "w2", "w3")):
    import hashlib
    h = hashlib.sha256()
    with fluid.scope_guard(scope):
        for n in names:
            h.update(np.asarray(scope.find_var(n)).tobytes())
    return h.hexdigest()


def test_zero1_dp8_checkpoint_restores_onto_dp4(tmp_path):
    exe = fluid.Executor(fluid.CPUPlace())

    # uninterrupted dp8 reference
    main, startup, loss, prog = _build_zero1(8)
    ref_scope = fluid.Scope()
    with fluid.scope_guard(ref_scope):
        exe.run(startup)
    ref = _run_steps(exe, prog, loss, ref_scope, 0,
                     STEPS_BEFORE + STEPS_AFTER)

    # dp8 run checkpointed mid-way — the flat ZeRO-1 shards are padded
    # for 8 ranks here
    main, startup, loss, prog = _build_zero1(8)
    fm = flat_shard_meta(main)
    assert fm, "ZeRO-1 rewrite produced no flat shard metadata"
    pads8 = {n: main.global_block().vars[n].shape[0] for n in fm}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    before = _run_steps(exe, prog, loss, scope, 0, STEPS_BEFORE)
    np.testing.assert_allclose(before, ref[:STEPS_BEFORE], rtol=1e-6)
    with fluid.scope_guard(scope):
        io.save_checkpoint(exe, str(tmp_path), io.TrainStatus(
            STEPS_BEFORE - 1, STEPS_BEFORE - 1), main)
    man = io._read_manifest(os.path.join(
        str(tmp_path), f"checkpoint_{STEPS_BEFORE - 1}"))
    assert man is not None and man["format_version"] == 2
    assert set(fm) <= set(man["flat_meta"])

    # relaunch on the 4 surviving devices: the dp4 program pads the flat
    # shards differently — restore must REPAD, not crash
    main4, startup4, loss4, prog4 = _build_zero1(4)
    fm4 = flat_shard_meta(main4)
    pads4 = {n: main4.global_block().vars[n].shape[0] for n in fm4}
    assert any(pads4[n] != pads8[n] for n in pads4), \
        "test needs a model whose flat padding differs between dp8/dp4"
    scope4 = fluid.Scope()
    with fluid.scope_guard(scope4):
        exe.run(startup4)
        before_compiles = stat("executor_compile_count").get()
        st = io.load_checkpoint(exe, str(tmp_path), main_program=main4,
                                scope=scope4)
        assert stat("executor_compile_count").get() == before_compiles
    assert st.step == STEPS_BEFORE - 1
    assert st.reshard is not None
    assert st.reshard["steps_by_kind"].get("repad", 0) >= 1
    assert st.reshard["compiles_attempted"] == 0
    after = _run_steps(exe, prog4, loss4, scope4, STEPS_BEFORE,
                       STEPS_AFTER)
    np.testing.assert_allclose(after, ref[STEPS_BEFORE:], rtol=1e-6,
                               atol=1e-7)


def test_zero1_dp8_same_layout_restore_is_bitexact(tmp_path):
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup, loss, prog = _build_zero1(8)
    ref_scope = fluid.Scope()
    with fluid.scope_guard(ref_scope):
        exe.run(startup)
    ref = _run_steps(exe, prog, loss, ref_scope, 0,
                     STEPS_BEFORE + STEPS_AFTER)
    ref_digest = _digest(ref_scope)

    main, startup, loss, prog = _build_zero1(8)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    _run_steps(exe, prog, loss, scope, 0, STEPS_BEFORE)
    with fluid.scope_guard(scope):
        io.save_checkpoint(exe, str(tmp_path), io.TrainStatus(
            STEPS_BEFORE - 1, STEPS_BEFORE - 1), main)

    main2, startup2, loss2, prog2 = _build_zero1(8)
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup2)
        st = io.load_checkpoint(exe, str(tmp_path), main_program=main2,
                                scope=scope2)
    assert st.reshard is None                  # identical layout: no-op
    after = _run_steps(exe, prog2, loss2, scope2, STEPS_BEFORE,
                       STEPS_AFTER)
    assert after == ref[STEPS_BEFORE:]         # bit-exact resume
    assert _digest(scope2) == ref_digest


# ---------------------------------------------------------------------------
# end-to-end: ZeRO-3 fsdp8 checkpoint restores onto fsdp4
# ---------------------------------------------------------------------------


def _build_fsdp(fsdp_degree):
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    layout = MeshLayout(data=1, fsdp=fsdp_degree)
    apply_fsdp_sharding(main, layout, min_shard_numel=64)
    main._mesh_layout = layout
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    prog = CompiledProgram(main).with_mesh(
        layout.build_mesh(), loss_name=loss.name,
        batch_axis=layout.batch_axes, build_strategy=bs)
    return main, startup, loss, prog


def test_zero3_fsdp8_checkpoint_restores_onto_fsdp4(tmp_path):
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup, loss, prog = _build_fsdp(8)
    ref_scope = fluid.Scope()
    with fluid.scope_guard(ref_scope):
        exe.run(startup)
    ref = _run_steps(exe, prog, loss, ref_scope, 0,
                     STEPS_BEFORE + STEPS_AFTER)

    main, startup, loss, prog = _build_fsdp(8)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    _run_steps(exe, prog, loss, scope, 0, STEPS_BEFORE)
    with fluid.scope_guard(scope):
        io.save_checkpoint(exe, str(tmp_path), io.TrainStatus(
            STEPS_BEFORE - 1, STEPS_BEFORE - 1), main)
    man = io._read_manifest(os.path.join(
        str(tmp_path), f"checkpoint_{STEPS_BEFORE - 1}"))
    assert man["mesh_layout"] is not None
    assert any(s for s in man["shard_specs"].values())

    main4, startup4, loss4, prog4 = _build_fsdp(4)
    scope4 = fluid.Scope()
    with fluid.scope_guard(scope4):
        exe.run(startup4)
        st = io.load_checkpoint(exe, str(tmp_path), main_program=main4,
                                scope=scope4)
    assert st.reshard is not None
    assert st.reshard["src_layout"]["fsdp"] == 8
    assert st.reshard["dst_layout"]["fsdp"] == 4
    assert st.reshard["steps_by_kind"].get("all_gather", 0) >= 1
    assert st.reshard["wire_bytes"] > 0
    after = _run_steps(exe, prog4, loss4, scope4, STEPS_BEFORE,
                       STEPS_AFTER)
    np.testing.assert_allclose(after, ref[STEPS_BEFORE:], rtol=1e-6,
                               atol=1e-7)


# ---------------------------------------------------------------------------
# restore edges: corruption fallback, retention, cold start, mismatch
# ---------------------------------------------------------------------------


def _tiny_ckpt(exe, path, step, main):
    io.save_checkpoint(exe, path, io.TrainStatus(step, step), main,
                       max_checkpoints=3)


def _tiny_program():
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _model()
        fluid.optimizer.Adam(5e-3).minimize(loss)
    return main, startup, loss


def test_corrupt_checkpoint_falls_back_to_newest_valid(tmp_path):
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup, loss = _tiny_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        _tiny_ckpt(exe, str(tmp_path), 1, main)
        _tiny_ckpt(exe, str(tmp_path), 2, main)
    # corrupt the NEWEST checkpoint's params file
    newest = os.path.join(str(tmp_path), "checkpoint_2", "params.npz")
    with open(newest, "r+b") as f:
        f.seek(0)
        f.write(b"\x00" * 16)
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup)
        st = io.load_checkpoint(exe, str(tmp_path), main_program=main,
                                scope=scope2)
    assert st.step == 1                       # fell back, didn't crash
    assert st.skipped_checkpoints and \
        "hash-mismatch" in st.skipped_checkpoints[0]["reason"]
    assert st.restored_from.endswith("checkpoint_1")


def test_all_checkpoints_corrupt_raises_with_skip_report(tmp_path):
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup, loss = _tiny_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        _tiny_ckpt(exe, str(tmp_path), 1, main)
    with open(os.path.join(str(tmp_path), "checkpoint_1", "params.npz"),
              "r+b") as f:
        f.write(b"\x00" * 8)
    with pytest.raises(InvalidArgumentError) as ei:
        io.load_checkpoint(exe, str(tmp_path), main_program=main,
                           scope=fluid.Scope())
    assert "hash-mismatch" in str(ei.value)


def test_retention_prunes_oldest_first(tmp_path):
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup, loss = _tiny_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in range(5):
            _tiny_ckpt(exe, str(tmp_path), step, main)
    kept = sorted(n for n in os.listdir(str(tmp_path))
                  if n.startswith("checkpoint_"))
    assert kept == ["checkpoint_2", "checkpoint_3", "checkpoint_4"]


def test_cold_start_restore_on_empty_dir(tmp_path):
    from paddle_tpu.distributed.preemption import PreemptionHandler
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup, loss = _tiny_program()
    handler = PreemptionHandler(exe, str(tmp_path / "nothing_here"), main)
    st = handler.restore()
    assert st.epoch_no == -1 and st.step == -1
    assert st.skipped_checkpoints == []


def test_layout_mismatch_raises_anchored_error_naming_both(tmp_path):
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup, loss, prog = _build_fsdp(8)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        io.save_checkpoint(exe, str(tmp_path), io.TrainStatus(0, 0), main)
    main4, startup4, loss4, prog4 = _build_fsdp(4)
    scope4 = fluid.Scope()
    with fluid.scope_guard(scope4):
        exe.run(startup4)
        with pytest.raises(InvalidArgumentError) as ei:
            io.load_checkpoint(exe, str(tmp_path), main_program=main4,
                               scope=scope4, reshard=False)
    msg = str(ei.value)
    assert "'fsdp': 8" in msg and "'fsdp': 4" in msg   # BOTH layouts named
    assert "reshard" in msg


def test_v1_shape_mismatch_fails_at_load_not_in_executor(tmp_path):
    """A checkpoint without a manifest (v1) whose arrays don't fit the
    program must fail AT LOAD with layouts named, not as a shape error
    deep in the executor (verify_programs gate)."""
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup, loss, prog = _build_zero1(8)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        _run_steps(exe, prog, loss, scope, 0, 1)
        io.save_checkpoint(exe, str(tmp_path), io.TrainStatus(0, 0), main)
    d = os.path.join(str(tmp_path), "checkpoint_0")
    os.remove(os.path.join(d, io.MANIFEST_FILE))      # simulate v1

    main4, startup4, loss4, prog4 = _build_zero1(4)
    scope4 = fluid.Scope()
    with fluid.scope_guard(scope4):
        exe.run(startup4)
        with pytest.raises(InvalidArgumentError) as ei:
            io.load_checkpoint(exe, str(tmp_path), main_program=main4,
                               scope=scope4)
    msg = str(ei.value)
    assert "layout" in msg and "declares" in msg


def test_checkpoint_write_retries_on_transient_io_error(tmp_path,
                                                        monkeypatch):
    from paddle_tpu.observability import metrics as obs_metrics
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup, loss = _tiny_program()
    scope = fluid.Scope()
    fails = {"n": 2}
    real_savez = np.savez

    def flaky(*a, **k):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("transient blob-store hiccup")
        return real_savez(*a, **k)

    monkeypatch.setattr(np, "savez", flaky)
    monkeypatch.setattr("paddle_tpu.flags._REGISTRY",
                        dict(__import__("paddle_tpu.flags",
                                        fromlist=["_REGISTRY"])._REGISTRY,
                             checkpoint_retry_backoff_s=0.001),
                        raising=True)
    before = obs_metrics.counter("checkpoint::retry", stage="params").get()
    with fluid.scope_guard(scope):
        exe.run(startup)
        _tiny_ckpt(exe, str(tmp_path), 0, main)       # succeeds via retry
    assert fails["n"] == 0
    got = obs_metrics.counter("checkpoint::retry", stage="params").get()
    assert got == before + 2
    st = io.load_checkpoint(exe, str(tmp_path), main_program=main,
                            scope=fluid.Scope())
    assert st.step == 0


def test_retry_exhaustion_propagates(tmp_path, monkeypatch):
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup, loss = _tiny_program()
    monkeypatch.setattr(np, "savez",
                        lambda *a, **k: (_ for _ in ()).throw(
                            OSError("disk on fire")))
    monkeypatch.setattr("paddle_tpu.flags._REGISTRY",
                        dict(__import__("paddle_tpu.flags",
                                        fromlist=["_REGISTRY"])._REGISTRY,
                             checkpoint_retry_backoff_s=0.001),
                        raising=True)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(OSError):
            _tiny_ckpt(exe, str(tmp_path), 0, main)


# ---------------------------------------------------------------------------
# rank-local byte-range restore (the multi-host sharded read path)
# ---------------------------------------------------------------------------


def _fake_sharded_ckpt(d, w, b, n_shards=4):
    """A v2 sharded checkpoint dir: ``w`` written as dim-0 shards,
    ``b`` whole — the layout save_persistables_sharded produces."""
    arrays, manifest = {}, {}
    h = w.shape[0] // n_shards
    manifest["w"] = {"shape": list(w.shape), "dtype": str(w.dtype),
                     "shards": [{"key": f"w@{k}",
                                 "index": [[k * h, (k + 1) * h]] +
                                 [[0, s] for s in w.shape[1:]]}
                                for k in range(n_shards)]}
    for k in range(n_shards):
        arrays[f"w@{k}"] = w[k * h:(k + 1) * h]
    arrays["b@full"] = b
    manifest["b"] = {"shape": list(b.shape), "dtype": str(b.dtype),
                     "shards": [{"key": "b@full", "index": None}]}
    np.savez(os.path.join(d, "shard_data_0.npz"), **arrays)
    with open(os.path.join(d, "shard_manifest_0.json"), "w") as f:
        json.dump({"format_version": 2, "vars": manifest}, f)


def test_restore_reads_only_planned_slice_bytes(tmp_path):
    """Satellite contract: a resharding restore reads ONLY the byte
    ranges the reshard schedule assigns to this rank — bytes-read must
    equal the planned slice bytes exactly, skipped shards are never
    opened, and the content of the owned rows is bit-correct."""
    d = str(tmp_path)
    w = np.arange(256 * 8, dtype="float32").reshape(256, 8)
    b = np.arange(64, dtype="float32")
    _fake_sharded_ckpt(d, w, b, n_shards=4)

    src = MeshLayout(data=4)
    dst = MeshLayout(data=8)
    plan = plan_reshard(
        src, dst,
        var_sigs={"w": ((256, 8), "float32"), "b": ((64,), "float32")},
        src_specs={"w": ShardSpec(("dp", None))},
        dst_specs={"w": ShardSpec(("dp", None))})
    # simulate one host of several: it owns dst blocks 5 and 6 of 8
    ranges = plan.dst_read_ranges({"w": [5, 6]})
    assert ranges == {"w": [(160, 224)]}
    stats = {}
    out = io._read_sharded_arrays(d, row_ranges=ranges, read_stats=stats)
    planned = sum(hi - lo for lo, hi in ranges["w"]) * 8 * 4 + b.nbytes
    assert stats["bytes_read"] == planned, \
        f"read {stats['bytes_read']} B != planned {planned} B"
    assert stats["members_skipped"] == 2       # shards 0 and 3 untouched
    assert stats["members_partial"] == 2       # shards 1 and 2 row-sliced
    assert np.array_equal(out["w"][160:224], w[160:224])
    assert not out["w"][:160].any() and not out["w"][224:].any()
    assert np.array_equal(out["b"], b)         # unranged var read whole
    # the whole-read path accounts everything and stays bit-identical
    stats_full = {}
    full = io._read_sharded_arrays(d, read_stats=stats_full)
    assert np.array_equal(full["w"], w)
    assert stats_full["bytes_read"] == w.nbytes + b.nbytes


def test_dst_read_ranges_flat_shard_clamps_padding(tmp_path):
    """ZeRO-1 flat shards: dst blocks map to logical rows with the
    appended padding clamped out — the last rank never reads padding
    bytes that exist only logically."""
    numel, align = 1000, 128
    n_src, n_dst = 2, 4
    src_pad = numel + (-numel % (n_src * align))   # 1024
    dst_pad = numel + (-numel % (n_dst * align))   # 1024
    plan = plan_reshard(
        MeshLayout(data=n_src), MeshLayout(data=n_dst),
        var_sigs={"f": ((src_pad,), "float32")},
        flat_meta={"f": {"numel": numel, "align": align, "axes": ["dp"],
                         "src_pad": src_pad, "n_src": n_src,
                         "dst_pad": dst_pad, "n_dst": n_dst}})
    ranges = plan.dst_read_ranges({"f": [3]})
    # block 3 of 4: rows [768, 1024) clamped to the logical numel 1000
    assert ranges == {"f": [(768, 1000)]}
    assert plan.dst_read_ranges({"f": [0]}) == {"f": [(0, 256)]}
