"""Observability tentpole tests (ISSUE 9): step-id correlation across
executor and serving spans, MFU math against hand-computed FLOPs, flight
recorder dumps on non-finite loss and a raising op, the labeled metrics
registry + Prometheus export, the monitor satellite fixes, the profiler
tracer_option fix, the timeline merge upgrade, and the
disabled-telemetry overhead bound on the prepared hot loop."""

import gzip
import json
import os
import re
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import monitor, profiler
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.observability import (TelemetryRecorder, flight, flops,
                                      metrics, tracing, validate_jsonl)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture(autouse=True)
def fresh_observability():
    """Tracing buffer, metrics registry and flight ring are process
    globals — isolate them per test."""
    tracing.disable()
    tracing.clear_events()
    metrics.reset_metrics()
    flight.reset()
    yield
    tracing.disable()
    tracing.clear_events()
    metrics.reset_metrics()
    flight.reset()


def _fc_train_program(width=6, hidden=8, classes=3):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[width])
        h = fluid.layers.fc(x, hidden)
        y = fluid.layers.fc(h, classes)
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _prepared(main, startup, loss, feed):
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return exe.prepare(main, fetch_list=[loss], scope=scope, feed=feed)


# ---------------------------------------------------------------------------
# step-id correlation
# ---------------------------------------------------------------------------


def test_step_ids_monotone_and_thread_pinned():
    assert tracing.next_step_id() < tracing.next_step_id()
    base = tracing.current_step_id()
    with tracing.step_scope(7):
        assert tracing.current_step_id() == 7
        seen = []

        def other():
            seen.append(tracing.current_step_id())

        t = threading.Thread(target=other)
        t.start()
        t.join()
        # the pin is per-thread: another thread still sees the counter
        assert seen == [base]
    assert tracing.current_step_id() == base


def test_executor_spans_correlate_on_step_axis():
    main, startup, loss = _fc_train_program()
    feed = {"x": np.ones((2, 6), np.float32)}
    prepared = _prepared(main, startup, loss, feed)
    prepared.run(feed)[0].numpy()            # compile outside the window
    tracing.enable()
    try:
        sids = []
        for _ in range(3):
            prepared.run(feed)[0].numpy()
            sids.append(tracing.current_step_id())
    finally:
        tracing.disable()
    events = tracing.get_events()
    dispatch_sids = [a["step_id"] for n, s, e, t, a in events
                     if n == "prepared::dispatch"]
    assert dispatch_sids == sids            # one span per step, its id
    assert sids == sorted(sids) and len(set(sids)) == 3
    # every span closed during the window carries a step id
    assert all("step_id" in a for *_x, a in events)


def test_compile_span_carries_program_identity():
    main, startup, loss = _fc_train_program()
    feed = {"x": np.ones((2, 6), np.float32)}
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    tracing.enable()
    try:
        prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
        prepared.run(feed)[0].numpy()
    finally:
        tracing.disable()
    compiles = [a for n, *_x, a in tracing.get_events()
                if n == "executor::compile"]
    assert compiles and compiles[0]["program"] == main._uid
    assert compiles[0]["version"] == main._version


def test_collective_spans_carry_bucket_index_and_ready_rank():
    """Overlap-scheduled grad-sync buckets stamp their ready order on
    the ``collective::*`` spans (bucket_index / ready_rank / overlap
    attrs land in the Chrome trace ``args``), so tools/timeline.py
    renders WHICH bucket fired where in the interleaving."""
    import jax
    from paddle_tpu.framework.compiler import (BuildStrategy,
                                               CompiledProgram, make_mesh)

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16])
        h = x
        for _ in range(5):
            h = fluid.layers.fc(h, 32, act="relu", bias_attr=False)
        loss = fluid.layers.mean(fluid.layers.fc(h, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    mesh = make_mesh(8, "dp")
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    bs.overlap_grad_sync = True
    prog = CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, mesh=mesh, build_strategy=bs)
    n_buckets = sum(1 for op in main.global_block().ops
                    if op.type == "c_fused_allreduce_sum")
    assert n_buckets >= 4

    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((16, 16), np.float32)}
    tracing.enable()
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(prog, feed=feed, fetch_list=[loss])
    finally:
        tracing.disable()
    spans = [a for n, *_x, a in tracing.get_events()
             if n == "collective::c_fused_allreduce_sum"]
    assert len(spans) == n_buckets
    assert all(a.get("overlap") is True for a in spans)
    ranks = sorted(a["ready_rank"] for a in spans)
    assert ranks == list(range(n_buckets))
    assert sorted(a["bucket_index"] for a in spans) == ranks
    # wire pricing still rides the span (the hook passes real payloads)
    assert all(a.get("wire_bytes", 0) > 0 for a in spans)


def test_serving_spans_share_the_batch_step_id(tmp_path):
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
    from paddle_tpu.serving import ServingConfig, ServingEngine

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[6])
        y = fluid.layers.fc(x, 3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    d = str(tmp_path / "m")
    fluid.io.save_inference_model(d, ["x"], [y], exe, main)
    config = AnalysisConfig(d)
    config.disable_gpu()
    engine = ServingEngine(create_paddle_predictor(config),
                           ServingConfig(max_batch_size=2, max_wait_ms=1.0))
    rng = np.random.RandomState(0)
    tracing.enable()
    try:
        for _ in range(2):                   # two separate micro-batches
            fut = engine.submit({"x": rng.randn(1, 6).astype(np.float32)})
            fut.result(timeout=60)
        engine.drain(timeout=60)
    finally:
        tracing.disable()
        engine.shutdown()
    by_sid = {}
    for n, *_x, a in tracing.get_events():
        if n.startswith("serving::"):
            by_sid.setdefault(a["step_id"], set()).add(n)
    # each batch's pad/run/split spans share that batch's id
    full = [sid for sid, names in by_sid.items()
            if {"serving::pad", "serving::run", "serving::split"} <= names]
    assert len(full) >= 2


def test_checkpoint_spans_pin_snapshot_step(tmp_path):
    from paddle_tpu.io import AsyncCheckpointer, TrainStatus

    main, startup, loss = _fc_train_program()
    feed = {"x": np.ones((2, 6), np.float32)}
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=feed, fetch_list=[loss])
    before = monitor.stat("checkpoint_saves").get()
    tracing.enable()
    try:
        sid = tracing.current_step_id()
        ck = AsyncCheckpointer()
        ck.save(exe, str(tmp_path / "ckpt"), TrainStatus(epoch_no=1))
        ck.wait()
    finally:
        tracing.disable()
    assert monitor.stat("checkpoint_saves").get() == before + 1
    assert monitor.stat("checkpoint_snapshot_ns").get() > 0
    spans = {n: a for n, *_x, a in tracing.get_events()
             if n.startswith("checkpoint::")}
    assert {"checkpoint::snapshot", "checkpoint::write"} <= set(spans)
    # the background write keeps the snapshotting step's id
    assert spans["checkpoint::write"]["step_id"] == sid


# ---------------------------------------------------------------------------
# MFU math
# ---------------------------------------------------------------------------


def test_estimate_step_flops_hand_computed_fc():
    """2 FLOPs/MAC on both fc GEMMs, 3x for fwd+bwd — exact."""
    b, w, h, c = 4, 6, 8, 3
    main, startup, loss = _fc_train_program(w, h, c)
    est = flops.estimate_step_flops(
        main, feed_shapes={"x": np.zeros((b, w), np.float32)},
        fetch_names=[loss.name])
    hand_fwd = 2 * b * w * h + 2 * b * h * c
    assert est["fwd_flops"] == hand_fwd
    assert est["has_backward"] is True
    assert est["total_flops"] == 3 * hand_fwd
    assert est["unpriced"] == []


def test_estimate_step_flops_transformer_matches_analytic():
    """Op-spec pricing of a BERT-tiny pretrain step lands within 10% of
    the benchmark's analytic count (``benchmark/flops.py``)."""
    from benchmark.flops import bert_flops_per_step
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    batch, seq, masks = 4, 16, 2
    main, startup = Program(), Program()
    with program_guard(main, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(cfg)
        fluid.optimizer.Adam(1e-4).minimize(total)
    rng = np.random.RandomState(0)
    data = bert.make_fake_batch(rng, cfg, batch_size=batch, seq_len=seq,
                                num_masks=masks)
    est = flops.estimate_step_flops(main, feed_shapes=data,
                                    fetch_names=[total.name])
    analytic = bert_flops_per_step(vars(cfg), batch, seq, masks)
    assert 0.9 <= est["total_flops"] / analytic <= 1.1


def test_recorder_mfu_exact_with_overrides(tmp_path):
    """mfu = flops / wall / peak, to the bit, with every input pinned."""
    path = str(tmp_path / "t.jsonl")
    with TelemetryRecorder(path, flops_per_step=3e11, peak_flops=1e12,
                           tokens_per_step=128) as rec:
        r1 = rec.record_step(wall_ns=1e9, loss=1.25)       # 1 s
        r2 = rec.record_step(wall_ns=5e8)                  # 0.5 s
    assert r1["mfu"] == pytest.approx(0.3)
    assert r2["mfu"] == pytest.approx(0.6)
    assert r1["loss"] == 1.25 and r1["loss_finite"] is True
    facts = validate_jsonl(path)
    assert facts["steps"] == 2
    assert facts["summary"]["mfu_mean"] == pytest.approx(0.45)


def test_device_peak_flops_table_and_flag():
    class _Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert flops.device_peak_flops(_Dev()) == 197e12

    class _Unknown:
        platform = "tpu"
        device_kind = "TPU v99"

    with pytest.raises(ValueError, match="TPU v99"):
        flops.device_peak_flops(_Unknown())
    old = get_flags(["device_peak_flops"])
    set_flags({"device_peak_flops": 123.0})
    try:
        assert flops.device_peak_flops(_Dev()) == 123.0
    finally:
        set_flags(old)
    import jax
    assert flops.device_peak_flops(jax.devices()[0]) == \
        flops.CPU_FALLBACK_FLOPS


def test_recorder_goodput_attributes_compile_stall(tmp_path):
    """A fresh compile inside the step window shows up as compile stall
    and pushes goodput below 1."""
    main, startup, loss = _fc_train_program()
    feed = {"x": np.ones((2, 6), np.float32)}
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    # no feed at prepare time: the FIRST recorded step pays the compile
    prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
    path = str(tmp_path / "t.jsonl")
    with TelemetryRecorder(path, program=main, feed_shapes=feed,
                           fetch_names=[loss.name]) as rec:
        rec.attach(prepared)
        with rec.step() as st:               # first run pays the compile
            st.loss = prepared.run(feed)[0].numpy()
        rec1 = st.record
        with rec.step() as st:
            st.loss = prepared.run(feed)[0].numpy()
        rec2 = st.record
    assert rec1["compiles"] == 1
    assert rec1["stalls_ms"]["compile"] > 0
    assert rec1["goodput"] < 1.0
    assert rec2["compiles"] == 0
    assert rec2["goodput"] > rec1["goodput"]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def _flight_flags(tmp_path):
    old = get_flags(["flight_dump_dir", "flight_recorder"])
    set_flags({"flight_dump_dir": str(tmp_path / "flight"),
               "flight_recorder": True})
    return old


def test_flight_dump_on_nonfinite_loss(tmp_path):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        loss = fluid.layers.mean(fluid.layers.log(x))
    feed_ok = {"x": np.ones((2, 4), np.float32)}
    feed_bad = {"x": -np.ones((2, 4), np.float32)}
    prepared = _prepared(main, startup, loss, feed_ok)
    old = _flight_flags(tmp_path)
    path = str(tmp_path / "t.jsonl")
    try:
        with TelemetryRecorder(path, program=main, feed_shapes=feed_ok,
                               fetch_names=[loss.name]) as rec:
            with rec.step() as st:
                st.loss = prepared.run(feed_ok)[0].numpy()
            with rec.step() as st:
                st.loss = prepared.run(feed_bad)[0].numpy()
            bad = st.record
    finally:
        set_flags(old)
    assert bad["loss_finite"] is False
    bundle_path = bad["flight_bundle"]
    assert bundle_path and os.path.exists(bundle_path)
    bundle = flight.validate_bundle(bundle_path)
    assert bundle["reason"] == "non_finite_loss"
    assert bundle["extra"]["step"] == bad["step"]
    # breadcrumbs cover the run's steps (always-on, no tracing needed)
    assert any(s[1] == "prepared" for s in bundle["steps"])
    # the JSONL tail cross-references the same bundle
    events = [r for r in map(json.loads, open(path))
              if r.get("record") == "event"]
    assert events and events[0]["kind"] == "non_finite_loss"
    assert events[0]["flight_bundle"] == bundle_path


def test_flight_dump_on_raising_op(tmp_path):
    main, startup, loss = _fc_train_program()
    feed = {"x": np.ones((2, 6), np.float32)}
    prepared = _prepared(main, startup, loss, feed)
    prepared.run(feed)[0].numpy()
    old = _flight_flags(tmp_path)

    def boom(*a, **k):
        raise ValueError("injected device failure")

    try:
        for step in prepared._steps.values():
            step.fn = boom
        with pytest.raises(ValueError, match="injected device failure"):
            prepared.run(feed)
    finally:
        set_flags(old)
    bundles = flight.last_dumps()
    assert bundles
    bundle = flight.validate_bundle(bundles[-1])
    assert bundle["reason"] == "prepared_step_exception"
    assert bundle["exception"]["type"] == "ValueError"
    assert "injected device failure" in bundle["exception"]["message"]
    assert bundle["program"]["uid"] == main._uid
    assert bundle["extra"]["fetches"] == [loss.name]
    assert "flight_recorder" in bundle["flags"]


def test_flight_disabled_is_silent(tmp_path):
    old = get_flags(["flight_recorder"])
    set_flags({"flight_recorder": False})
    try:
        flight.note_step(1, "prepared", None)
        assert flight.dump("test_reason") is None
        assert flight.steps_snapshot() == []
    finally:
        set_flags(old)


# ---------------------------------------------------------------------------
# monitor satellites
# ---------------------------------------------------------------------------


def test_monitor_snapshot_and_reset_all():
    monitor.stat("obs_test_a").add(3)
    monitor.stat("obs_test_b").add(7)
    snap = monitor.stats_snapshot()
    assert snap["obs_test_a"] == 3 and snap["obs_test_b"] == 7
    snap["obs_test_a"] = 999                 # a copy, not the registry
    assert monitor.stat("obs_test_a").get() == 3
    monitor.reset_all()
    assert monitor.stat("obs_test_a").get() == 0
    assert monitor.stat("obs_test_b").get() == 0


def test_monitor_concurrent_adds_consistent():
    s = monitor.stat("obs_test_threads")
    s.reset()

    def work():
        for _ in range(1000):
            s.add(1)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert s.get() == 4000


# ---------------------------------------------------------------------------
# metrics registry + export
# ---------------------------------------------------------------------------


def test_metrics_registry_kinds_and_labels():
    c = metrics.counter("obs_requests", kind="allreduce")
    c.add(2)
    assert metrics.counter("obs_requests", kind="allreduce") is c
    assert metrics.counter("obs_requests", kind="gather") is not c
    g = metrics.gauge("obs_inflight")
    g.set(5)
    g.add(-2)
    assert g.get() == 3
    h = metrics.histogram("obs_latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["sum"] == pytest.approx(5.55)
    assert snap["buckets"] == [[0.1, 1], [1.0, 2]]   # cumulative
    with pytest.raises(TypeError):
        metrics.gauge("obs_requests", kind="allreduce")


def test_metrics_snapshot_includes_monitor_counters():
    monitor.stat("obs_snap_counter").add(11)
    metrics.gauge("obs_snap_gauge", shard="0").set(2.5)
    snap = metrics.metrics_snapshot()
    assert snap["schema"] == "paddle_tpu.metrics/1"
    assert snap["counters"]["obs_snap_counter"] == 11
    entry, = [m for m in snap["metrics"]
              if m["name"] == "obs_snap_gauge"]
    assert entry["kind"] == "gauge" and entry["value"] == 2.5
    assert entry["labels"] == {"shard": "0"}
    json.dumps(snap)                          # JSON-able end to end


def test_prometheus_text_format():
    monitor.stat("obs_prom_counter").add(4)
    metrics.gauge("obs_prom_gauge", model="bert", bucket="8x32").set(1.5)
    h = metrics.histogram("obs_prom_hist", buckets=(0.5, 2.0))
    h.observe(0.3)
    h.observe(1.0)
    text = metrics.prometheus_text()
    assert "# TYPE paddle_tpu_obs_prom_counter counter" in text
    assert "paddle_tpu_obs_prom_counter 4" in text
    assert "# TYPE paddle_tpu_obs_prom_gauge gauge" in text
    assert ('paddle_tpu_obs_prom_gauge{bucket="8x32",model="bert"} 1.5'
            in text)
    assert "# TYPE paddle_tpu_obs_prom_hist histogram" in text
    assert 'paddle_tpu_obs_prom_hist_bucket{le="0.5"} 1' in text
    assert 'paddle_tpu_obs_prom_hist_bucket{le="2"} 2' in text
    assert 'paddle_tpu_obs_prom_hist_bucket{le="+Inf"} 2' in text
    assert "paddle_tpu_obs_prom_hist_sum 1.3" in text
    assert "paddle_tpu_obs_prom_hist_count 2" in text
    # each # TYPE line appears once even with several label sets
    assert text.count("# TYPE paddle_tpu_obs_prom_gauge ") == 1


def test_metrics_http_endpoint():
    metrics.counter("obs_http_hits").add(9)
    with metrics.serve_metrics(port=0) as srv:
        text = urllib.request.urlopen(srv.url).read().decode()
        assert "paddle_tpu_obs_http_hits 9" in text
        js = json.loads(urllib.request.urlopen(
            srv.url + ".json").read().decode())
        assert js["schema"] == "paddle_tpu.metrics/1"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://{srv.addr}:{srv.port}/nope")


def test_prometheus_guardrail_series():
    """The PR 14 guardrail state is on the scrape surface (ROADMAP
    follow-up): ``guardrail::skipped_total`` / ``guardrail::loss_scale``
    gauges and the ``watchdog::trip`` counter all appear in
    ``prometheus_text()`` and through the HTTP endpoint after a guarded
    run + an induced stall."""
    from paddle_tpu.testing import faultline
    keep = get_flags(["guard_nonfinite", "guard_loss_scale",
                      "step_deadline_s"])
    deadline = 0.3
    set_flags({"guard_nonfinite": True, "guard_loss_scale": True,
               "step_deadline_s": deadline})
    try:
        main, startup, loss = _fc_train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        rng = np.random.RandomState(3)
        feed = {"x": rng.randn(4, 6).astype(np.float32)}
        with fluid.scope_guard(scope):
            exe.run(startup)
            prepared = exe.prepare(main, fetch_list=[loss], scope=scope,
                                   feed=feed)
            prepared.run(feed)
            info = prepared.guard_info(sync=True)   # decodes both gauges
            assert info["loss_scale"] is not None
            # the first run above compiles, which can itself outlast the
            # deadline: count the trips the ARMED stall adds
            trips = metrics.counter("watchdog::trip", beacon="prepared")
            before = trips.get()
            faultline.arm("step_stall", action="stall",
                          seconds=3 * deadline, times=1)
            prepared.run(feed)                      # watchdog trips
            faultline.disarm()
            prepared.wait()
            prepared.close()
        text = metrics.prometheus_text()
        assert "# TYPE paddle_tpu_guardrail::skipped_total gauge" in text
        assert "paddle_tpu_guardrail::skipped_total 0" in text
        assert "# TYPE paddle_tpu_guardrail::loss_scale gauge" in text
        assert "paddle_tpu_guardrail::loss_scale " in text
        assert "# TYPE paddle_tpu_watchdog::trip counter" in text
        (count,) = re.findall(
            r'paddle_tpu_watchdog::trip\{beacon="prepared"\} (\d+)\n', text)
        assert int(count) >= before + 1
        with metrics.serve_metrics(port=0) as srv:
            scraped = urllib.request.urlopen(srv.url).read().decode()
        assert "paddle_tpu_guardrail::skipped_total" in scraped
        assert "paddle_tpu_guardrail::loss_scale" in scraped
        assert "paddle_tpu_watchdog::trip" in scraped
    finally:
        faultline.disarm()
        set_flags(keep)


# ---------------------------------------------------------------------------
# profiler satellites
# ---------------------------------------------------------------------------


def test_profiler_forwards_tracer_option():
    with profiler.profiler("CPU", tracer_option="OpDetail"):
        assert profiler.tracer_option() == "OpDetail"
        assert profiler.is_profiler_enabled()
    assert not profiler.is_profiler_enabled()
    with pytest.raises(ValueError, match="tracer_option"):
        profiler.start_profiler("CPU", tracer_option="Bogus")


def test_stop_profiler_restores_state_when_stop_trace_raises(
        tmp_path, monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))

    def raising_stop():
        calls.append(("stop",))
        raise RuntimeError("backend died mid-trace")

    monkeypatch.setattr(jax.profiler, "stop_trace", raising_stop)
    profiler.start_profiler("All", trace_dir=str(tmp_path))
    assert profiler._jax_trace_dir == str(tmp_path)
    profiler.stop_profiler()                  # must not raise
    assert ("stop",) in calls
    assert profiler._jax_trace_dir is None    # restored despite the raise
    assert not profiler.is_profiler_enabled()
    # a second stop must not double-stop the jax trace
    n_stops = calls.count(("stop",))
    profiler.stop_profiler()
    assert calls.count(("stop",)) == n_stops


def test_chrome_trace_carries_args_and_thread_names(tmp_path):
    tracing.enable()
    try:
        with tracing.Span("op::custom", cache="hit", step_id=41):
            pass
    finally:
        tracing.disable()
    path = str(tmp_path / "trace.json")
    profiler.save_chrome_trace(path)
    trace = json.load(open(path))
    ev, = [e for e in trace["traceEvents"] if e["name"] == "op::custom"]
    assert ev["args"]["cache"] == "hit" and ev["args"]["step_id"] == 41
    metas = [e for e in trace["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert any(m["tid"] == ev["tid"] for m in metas)


# ---------------------------------------------------------------------------
# timeline merge upgrade
# ---------------------------------------------------------------------------


def test_timeline_merge_preserves_metadata_and_order(tmp_path):
    from tools.timeline import merge
    trace = {"traceEvents": [
        {"name": "thread_name", "ph": "M", "pid": 0, "tid": 9,
         "args": {"name": "serving-worker"}},
        {"name": "step", "ph": "X", "ts": 0, "dur": 5, "pid": 0,
         "tid": 9, "args": {"step_id": 12}},
    ]}
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    p1.write_text(json.dumps(trace))
    p2.write_text(json.dumps(trace))
    out = str(tmp_path / "merged.json")
    n, out_path = merge([f"trainer0:{p1}", f"trainer1:{p2}"], out)
    assert out_path == out
    merged = json.load(open(out))
    assert n == len(merged["traceEvents"])
    sort_meta = {ev["pid"]: ev["args"]["sort_index"]
                 for ev in merged["traceEvents"]
                 if ev["name"] == "process_sort_index"}
    assert sort_meta == {0: 0, 1: 1}          # trainer order
    tnames = [ev for ev in merged["traceEvents"]
              if ev["name"] == "thread_name"]
    assert len(tnames) == 2                   # one per process, with tid
    assert {ev["tid"] for ev in tnames} == {9}
    spans = [ev for ev in merged["traceEvents"] if ev["name"] == "step"]
    assert {ev["pid"] for ev in spans} == {0, 1}
    assert all(ev["args"]["step_id"] == 12 for ev in spans)


def test_timeline_perfetto_writes_gzip(tmp_path):
    from tools.timeline import merge
    p = tmp_path / "a.json"
    p.write_text(json.dumps({"traceEvents": [
        {"name": "s", "ph": "X", "ts": 0, "dur": 1, "pid": 0, "tid": 1}]}))
    out = str(tmp_path / "merged.json")
    n, out_path = merge([str(p)], out, perfetto=True)
    assert out_path.endswith(".gz")
    with gzip.open(out_path, "rt") as f:
        merged = json.load(f)
    assert len(merged["traceEvents"]) == n


# ---------------------------------------------------------------------------
# disabled-telemetry overhead bound (the PR 2 hot-loop contract)
# ---------------------------------------------------------------------------


def test_disabled_telemetry_overhead_bound():
    """With tracing OFF, the per-step observability hook (the fused
    step-id bump + flight breadcrumb — the ONLY telemetry code on the
    prepared hot path) must cost ≤5% of the prepared loop: the PR 2
    10 μs/step baseline must survive telemetry being compiled in.

    The hook cost is microbenched directly (10⁵ calls per sample,
    min-of-repeats: stable to a few ns) against the stub-step loop time
    (a stubbed compiled step) — a subtraction of two full
    loop timings cannot resolve a ~0.2 μs delta on a shared CI host,
    but cost-of-part vs cost-of-whole can."""
    import timeit

    import jax
    from paddle_tpu.framework import executor as executor_mod
    from paddle_tpu.framework.executor import _RNG_VAR

    # -- the hook, exactly as the hot loop pays it (global lookup + call)
    hook_ns = min(timeit.repeat(
        "_h('prepared', _u)",
        globals={"_h": executor_mod._step_breadcrumb, "_u": "prog_uid"},
        number=100_000, repeat=7)) / 100_000 * 1e9

    # -- the loop (stubbed compiled step: host framework time only)
    main, startup, loss = _fc_train_program()
    feed = {"x": np.ones((2, 6), np.float32)}
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=feed, fetch_list=[loss])      # compile + warm
    scope = fluid.global_scope()
    step = exe._compile(main, feed, [loss.name], scope, None, (), None)
    real_fn = step.fn
    # template built from live scope state BEFORE any donation consumes it
    state_in = {n: scope.find_var(n) for n in step.state_in_names}
    template = real_fn({k: feed[k] for k in step.feed_names}, state_in,
                       scope.find_var(_RNG_VAR))
    jax.block_until_ready(template)
    step.fn = lambda feed_vals, state_vals, k: template
    prepared = exe.prepare(main, fetch_list=[loss], feed=feed)
    prepared.run(feed)                                # bind + state pull
    assert not tracing.is_enabled()
    steps, loop_ns = 400, float("inf")
    try:
        for _ in range(5):
            prepared.run(feed)               # settle the window
            t0 = time.perf_counter_ns()
            for _ in range(steps):
                prepared.run(feed)
            loop_ns = min(loop_ns,
                          (time.perf_counter_ns() - t0) / steps)
    finally:
        step.fn = real_fn
        prepared.close()
    # the loop here is an fc model (~6 μs class — SMALLER than PR 2's
    # 10 μs bench loop, so the ratio bound is tested conservatively)
    assert hook_ns <= 0.05 * loop_ns, (hook_ns, loop_ns)


def test_span_lands_in_a_bare_jax_profiler_session(tmp_path):
    """A ``Span`` opens a ``jax.profiler.TraceAnnotation`` whenever ANY
    profiler session runs — the program's own tracing stays off and
    records nothing — so the device trace's host plane carries the span
    under its own name with its attributes and ``step_id``; with no
    session it opens none."""
    import glob

    import jax
    assert not tracing.is_enabled()
    idle = tracing.Span("unit::no_session")
    with idle:
        assert idle._ann is None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracing.step_scope(4217), \
                tracing.Span("unit::outer", program="p7") as sp:
            sp.set(cache_hit=True)
            with tracing.Span("unit::inner"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert not [e for e in tracing.get_events()
                if e[0].startswith("unit::")]
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns,
                       {k: str(v) for k, v in ev.stats})
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("unit::")}
    assert set(found) == {"unit::outer", "unit::inner"}
    a, b, stats = found["unit::outer"]
    assert stats["program"] == "p7" and stats["step_id"] == "4217"
    assert stats["cache_hit"] in ("True", "1")
    assert a <= found["unit::inner"][0] <= found["unit::inner"][1] <= b
