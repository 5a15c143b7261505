"""Prepared-execution fast path (Executor.prepare → PreparedStep):
N-step bit-exactness vs Executor.run (plain, py_reader-fed, and
CompiledProgram dp8 paths incl. the ZeRO-1 sharded_update), FetchHandle
laziness (no device sync until first read), in-flight window
backpressure, scope staleness guards (checkpoint + Executor.run
interleaving), pass-variant LRU promotion, and the sync bound on the
transformer bench."""


import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
import paddle_tpu.framework.executor as executor_mod
from paddle_tpu.framework.core import Program, program_guard

STEPS = 4


def _build_model(with_dropout=True):
    """Small train step with params, Adam state, and (optionally) RNG use
    so key threading is part of the exactness contract."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8])
        h = fluid.layers.fc(x, 16, act="tanh",
                            param_attr=fluid.ParamAttr(
                                name="w1",
                                initializer=fluid.initializer.Constant(0.1)),
                            bias_attr=False)
        if with_dropout:
            h = fluid.layers.dropout(h, dropout_prob=0.2)
        h = fluid.layers.fc(h, 4,
                            param_attr=fluid.ParamAttr(
                                name="w2",
                                initializer=fluid.initializer.Constant(0.05)),
                            bias_attr=False)
        loss = fluid.layers.mean(h)
        fluid.optimizer.Adam(1e-2).minimize(loss)
    return main, startup, loss


def _feeds(n=STEPS, batch=8, dim=8):
    rng = np.random.RandomState(7)
    return [rng.randn(batch, dim).astype(np.float32) for _ in range(n)]


def _snapshot(scope):
    return {n: np.array(np.asarray(v)) for n, v in scope.vars.items()}


def _load(scope, snap):
    for n, v in snap.items():
        scope.set_var(n, np.array(v))


# ---------------------------------------------------------------------------
# bit-exactness: prepared.run ≡ Executor.run
# ---------------------------------------------------------------------------


def test_prepared_bitexact_plain():
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    feeds = _feeds()

    sA = fluid.Scope()
    with fluid.scope_guard(sA):
        exe.run(startup)
    init = _snapshot(sA)

    lossesA = []
    with fluid.scope_guard(sA):
        for f in feeds:
            l, = exe.run(main, feed={"x": f}, fetch_list=[loss])
            lossesA.append(np.asarray(l))
        wA = {n: np.asarray(sA.find_var(n)) for n in ("w1", "w2")}

    sB = fluid.Scope()
    _load(sB, init)
    prepared = exe.prepare(main, fetch_list=[loss], scope=sB)
    lossesB = [prepared.run({"x": f})[0].numpy() for f in feeds]
    prepared.sync_scope()
    wB = {n: np.asarray(sB.find_var(n)) for n in ("w1", "w2")}

    for a, b in zip(lossesA, lossesB):
        assert np.array_equal(a, b), (a, b)
    for n in wA:
        assert np.array_equal(wA[n], wB[n]), n
    assert prepared.stats["steps"] == STEPS


def test_prepared_bitexact_py_reader():
    rng = np.random.RandomState(3)
    batches = [(rng.rand(8, 6).astype(np.float32),) for _ in range(STEPS)]

    reader = fluid.layers.py_reader(capacity=4, shapes=[(-1, 6)],
                                    dtypes=["float32"])
    (xv,) = [fluid.layers.read_file(reader)]
    h = fluid.layers.fc(xv, 4, act="tanh",
                        param_attr=fluid.ParamAttr(
                            name="wr",
                            initializer=fluid.initializer.Constant(0.2)),
                        bias_attr=False)
    loss = fluid.layers.mean(h)
    fluid.optimizer.SGD(0.1).minimize(loss)
    reader.decorate_tensor_provider(lambda: iter(batches))

    main = fluid.default_main_program()
    startup = fluid.default_startup_program()
    exe = fluid.Executor(fluid.CPUPlace())

    sA = fluid.Scope()
    with fluid.scope_guard(sA):
        exe.run(startup)
    init = _snapshot(sA)

    lossesA = []
    with fluid.scope_guard(sA):
        reader.start()
        try:
            while True:
                l, = exe.run(main, fetch_list=[loss])
                lossesA.append(np.asarray(l))
        except fluid.core.EOFException:
            reader.reset()
    assert len(lossesA) == STEPS

    sB = fluid.Scope()
    _load(sB, init)
    prepared = exe.prepare(main, fetch_list=[loss], scope=sB)
    lossesB = []
    reader.start()
    try:
        while True:
            h, = prepared.run()
            lossesB.append(h.numpy())
    except fluid.core.EOFException:
        reader.reset()
    prepared.sync_scope()

    assert len(lossesB) == STEPS
    for a, b in zip(lossesA, lossesB):
        assert np.array_equal(a, b)
    assert np.array_equal(np.asarray(sA.find_var("wr")),
                          np.asarray(sB.find_var("wr")))


def _dp8_program(sharded=False):
    from paddle_tpu.framework.compiler import make_mesh
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu",
                            param_attr=fluid.ParamAttr(
                                name="w1",
                                initializer=fluid.initializer.Constant(0.05)),
                            bias_attr=False)
        pred = fluid.layers.fc(h, 4, act="softmax",
                               param_attr=fluid.ParamAttr(
                                   name="w2",
                                   initializer=fluid.initializer.Constant(
                                       0.04)),
                               bias_attr=False)
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        if sharded:
            from paddle_tpu.optimizer import ShardedUpdateOptimizer
            ShardedUpdateOptimizer(fluid.optimizer.Adam(5e-3),
                                   nranks=8).minimize(loss)
        else:
            fluid.optimizer.Adam(5e-3).minimize(loss)
    mesh = make_mesh(8, "dp")
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=None if sharded else loss.name, mesh=mesh)
    return compiled, startup, loss


def _dp8_batches(n=STEPS):
    rng = np.random.RandomState(0)
    out = []
    for _ in range(n):
        xs = rng.randn(64, 16).astype(np.float32)
        ys = (xs.sum(1) > 0).astype(np.int64).reshape(-1, 1) * 3
        out.append({"x": xs, "label": ys})
    return out


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["dp8", "dp8_sharded_update"])
def test_prepared_bitexact_dp8(sharded):
    """CompiledProgram data-parallel path (and PR 1's ZeRO-1
    sharded_update): prepared vs Executor.run bit-identical over N steps
    on the 8-device virtual mesh."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh conftest")
    compiled, startup, loss = _dp8_program(sharded)
    exe = fluid.Executor(fluid.CPUPlace())
    batches = _dp8_batches()

    sA = fluid.Scope()
    lossesA = []
    with fluid.scope_guard(sA):
        exe.run(startup)
        for b in batches:
            l, = exe.run(compiled, feed=b, fetch_list=[loss])
            lossesA.append(np.asarray(l))
        wA = np.asarray(sA.find_var("w1"))

    sB = fluid.Scope()
    with fluid.scope_guard(sB):
        exe.run(startup)
    prepared = exe.prepare(compiled, fetch_list=[loss], scope=sB)
    lossesB = [prepared.run(b)[0].numpy() for b in batches]
    prepared.sync_scope()
    wB = np.asarray(sB.find_var("w1"))

    for a, b in zip(lossesA, lossesB):
        assert np.array_equal(a, b), (a, b)
    assert np.array_equal(wA, wB)


def test_prepared_interleaves_with_executor_run():
    """Handoff in BOTH directions: run → prepared (scope-version refresh
    after the run path donated the prepared path's buffers) and
    prepared → run (sync_prepared_state staleness guard) reproduce the
    pure Executor.run trajectory bit-exactly."""
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    feeds = _feeds(4)

    sA = fluid.Scope()
    with fluid.scope_guard(sA):
        exe.run(startup)
    init = _snapshot(sA)
    ref = []
    with fluid.scope_guard(sA):
        for f in feeds:
            l, = exe.run(main, feed={"x": f}, fetch_list=[loss])
            ref.append(np.asarray(l))

    sB = fluid.Scope()
    _load(sB, init)
    prepared = exe.prepare(main, fetch_list=[loss], scope=sB)
    got = []
    with fluid.scope_guard(sB):
        l, = exe.run(main, feed={"x": feeds[0]}, fetch_list=[loss])
        got.append(np.asarray(l))                       # step 1: run
        got.append(prepared.run({"x": feeds[1]})[0].numpy())  # 2: prepared
        l, = exe.run(main, feed={"x": feeds[2]}, fetch_list=[loss])
        got.append(np.asarray(l))                       # step 3: run
        got.append(prepared.run({"x": feeds[3]})[0].numpy())  # 4: prepared
    for a, b in zip(ref, got):
        assert np.array_equal(a, b), (a, b)


# ---------------------------------------------------------------------------
# FetchHandle laziness + in-flight window
# ---------------------------------------------------------------------------


def test_fetch_handle_lazy(monkeypatch):
    main, startup, loss = _build_model(with_dropout=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    prepared = exe.prepare(main, fetch_list=[loss], scope=scope)

    calls = []
    orig = executor_mod._fetch_numpy
    monkeypatch.setattr(executor_mod, "_fetch_numpy",
                        lambda v: calls.append(1) or orig(v))
    h, = prepared.run({"x": _feeds(1)[0]})
    assert isinstance(h, fluid.FetchHandle)
    assert not calls, "run() must not materialise fetches"
    v1 = h.numpy()
    assert len(calls) == 1
    v2 = h.numpy()
    assert len(calls) == 1, "host value is cached — one sync total"
    assert np.array_equal(v1, v2)
    assert float(h) == float(v1.reshape(()))


def test_inflight_window_backpressure():
    main, startup, loss = _build_model(with_dropout=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    f = _feeds(1)[0]
    try:
        fluid.set_flags({"FLAGS_max_inflight_steps": 2})
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
        n = 6
        for _ in range(n):
            prepared.run({"x": f})
        assert len(prepared._inflight) <= 2
        assert prepared.stats["max_inflight"] <= 2
        # ≤1 blocking device sync per in-flight window slot: the first
        # `window` dispatches never block, later ones block at most once
        assert prepared.stats["blocking_syncs"] <= n - 2
        prepared.close()

        # window 0 disables the queue entirely (unbounded run-ahead)
        fluid.set_flags({"FLAGS_max_inflight_steps": 0})
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
        for _ in range(3):
            prepared.run({"x": f})
        assert len(prepared._inflight) == 0
        assert prepared.stats["blocking_syncs"] == 0
        prepared.close()
    finally:
        fluid.set_flags({"FLAGS_max_inflight_steps": 2})


def test_no_blocking_sync_inside_first_window():
    main, startup, loss = _build_model(with_dropout=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
    f = _feeds(1)[0]
    prepared.run({"x": f})
    prepared.run({"x": f})
    assert prepared.stats["blocking_syncs"] == 0
    prepared.close()


# ---------------------------------------------------------------------------
# staleness guards
# ---------------------------------------------------------------------------


def test_checkpoint_after_prepared_sees_current_weights(tmp_path):
    """save_persistables after prepared steps (NO manual sync) must write
    the advanced weights, and they must match the Executor.run
    trajectory."""
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    feeds = _feeds()

    sA = fluid.Scope()
    with fluid.scope_guard(sA):
        exe.run(startup)
    init = _snapshot(sA)
    with fluid.scope_guard(sA):
        for f in feeds:
            exe.run(main, feed={"x": f}, fetch_list=[loss])
        wA = np.asarray(sA.find_var("w1"))

    sB = fluid.Scope()
    _load(sB, init)
    prepared = exe.prepare(main, fetch_list=[loss], scope=sB)
    for f in feeds:
        prepared.run({"x": f})
    # no explicit sync_scope: the io path must flush via
    # sync_prepared_state itself
    fluid.io.save_persistables(exe, str(tmp_path), main, scope=sB)

    sC = fluid.Scope()
    fluid.io.load_persistables(exe, str(tmp_path), main, scope=sC)
    wC = np.asarray(sC.find_var("w1"))
    assert np.array_equal(wA, wC)
    assert not np.array_equal(np.asarray(init["w1"]), wC), \
        "checkpoint must hold TRAINED weights, not the startup values"


def test_async_checkpointer_syncs_prepared(tmp_path):
    from paddle_tpu.io import AsyncCheckpointer, TrainStatus, load_checkpoint
    main, startup, loss = _build_model(with_dropout=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
    for f in _feeds(3):
        prepared.run({"x": f})
    ck = AsyncCheckpointer()
    ck.save(exe, str(tmp_path), TrainStatus(0), main, scope=scope)
    ck.wait()
    prepared.sync_scope()
    w_now = np.asarray(scope.find_var("w1"))
    s2 = fluid.Scope()
    load_checkpoint(exe, str(tmp_path), main_program=main, scope=s2)
    assert np.array_equal(w_now, np.asarray(s2.find_var("w1")))


# ---------------------------------------------------------------------------
# pass-variant LRU (satellite: promote on hit)
# ---------------------------------------------------------------------------


def test_pass_variant_lru_promotes_on_hit():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        h = fluid.layers.fc(x, 8, act="relu", bias_attr=False)
        outs = [fluid.layers.scale(h, scale=float(i + 1)) for i in range(10)]
    from paddle_tpu.framework.compiler import make_mesh
    bs = fluid.BuildStrategy()
    bs.fuse_elewise_add_act_ops = True       # forces pass variants
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=None, build_strategy=bs, mesh=make_mesh(1))

    hot, _ = compiled._variant_for([outs[0].name])
    # fill the cache to capacity with 7 more variants
    for o in outs[1:8]:
        compiled._variant_for([o.name])
    assert len(compiled._pass_variants) == 8
    # HIT the hot list — true LRU must promote it
    again, evicted = compiled._variant_for([outs[0].name])
    assert again is hot and evicted is None
    # inserting a 9th evicts the insertion-oldest COLD variant
    # (outs[1]), never the just-promoted hot one
    _, evicted_uid = compiled._variant_for([outs[8].name])
    assert evicted_uid is not None
    keys = list(compiled._pass_variants)
    assert (outs[0].name,) in keys, "hot variant was evicted — no LRU"
    assert (outs[1].name,) not in keys
    # and the hot one still resolves without a rebuild
    again2, _ = compiled._variant_for([outs[0].name])
    assert again2 is hot


# ---------------------------------------------------------------------------
# benchmark-mode sync covers state + key (satellite)
# ---------------------------------------------------------------------------


def test_benchmark_sync_covers_state_and_key():
    main, startup, loss = _build_model(with_dropout=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    try:
        fluid.set_flags({"FLAGS_benchmark": True})
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed={"x": _feeds(1)[0]}, fetch_list=[loss])
        for n, v in scope.vars.items():
            ready = getattr(v, "is_ready", None)
            assert ready is None or ready(), \
                f"benchmark sync left {n!r} in flight"
    finally:
        fluid.set_flags({"FLAGS_benchmark": False})


# ---------------------------------------------------------------------------
# DataLoader / profiler integration
# ---------------------------------------------------------------------------


def test_dataloader_run_prepared():
    from paddle_tpu.dataloader import DataLoader
    main, startup, loss = _build_model(with_dropout=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    feeds = _feeds(3)
    with program_guard(main, startup):
        x_var = main.global_block().var("x")
    loader = DataLoader.from_generator(feed_list=[x_var], capacity=4)
    loader.set_batch_generator(lambda: iter([(f,) for f in feeds]))
    prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
    losses = [h[0].numpy() for h in loader.run_prepared(prepared)]
    assert len(losses) == 3
    assert all(np.isfinite(l).all() for l in losses)
    prepared.close()


def test_profiler_step_breakdown():
    from paddle_tpu import profiler
    main, startup, loss = _build_model(with_dropout=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
    f = _feeds(1)[0]
    prepared.run({"x": f})               # bind outside the profile
    profiler.reset_profiler()
    profiler.start_profiler("CPU")
    h = None
    for _ in range(3):
        h, = prepared.run({"x": f})
    h.numpy()
    prepared.sync_scope()
    events = profiler.stop_profiler()
    bd = profiler.step_breakdown(events)
    assert bd["prepared::dispatch"]["calls"] == 3
    assert bd["prepared::fetch_sync"]["calls"] >= 1
    assert bd["prepared::scope_sync"]["calls"] == 1
    for name, rec in bd.items():
        if name in ("feed_cache", "aot_cache"):  # counters, not phases
            assert rec["hits"] >= 0 and rec["misses"] >= 0
            continue
        assert rec["avg_us"] >= 0
    assert bd["feed_cache"]["capacity"] > 0
    assert "dir" in bd["aot_cache"]


# ---------------------------------------------------------------------------
# HOST_OVERHEAD artifact + sync bound on the CPU transformer bench
# ---------------------------------------------------------------------------


def test_prepared_sync_bound_on_transformer_bench():
    """Live leg of the artifact contract: on the CPU transformer bench
    config the prepared path issues at most one blocking device sync per
    in-flight window slot — never per fetch, never per state var."""
    from paddle_tpu.models import transformer
    cfg = transformer.TransformerConfig.tiny()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        feeds, loss, logits = transformer.build_train_network(cfg)
        fluid.optimizer.Adam(1e-4).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    rng = np.random.RandomState(0)
    src = [list(rng.randint(3, 100, 6)) for _ in range(2)]
    trg = [list(rng.randint(3, 100, 5)) for _ in range(2)]
    feed = {k: np.asarray(v) for k, v in
            transformer.make_batch(src, trg, cfg,
                                   bucket_ladder=(8,)).items()}
    prepared = exe.prepare(main, fetch_list=[loss], scope=scope, feed=feed)
    window = int(fluid.get_flags("max_inflight_steps")["max_inflight_steps"])
    n = 6
    for _ in range(n):
        prepared.run(feed)
    assert prepared.stats["blocking_syncs"] <= max(0, n - window), \
        prepared.stats
    assert prepared.stats["max_inflight"] <= window
    # state donation is live on this step (the census the artifact records)
    donated, total = prepared.donation()
    assert donated == len(prepared._cur.state_in_names)
    h, = prepared.run(feed)
    assert np.isfinite(h.numpy()).all()
    prepared.close()
