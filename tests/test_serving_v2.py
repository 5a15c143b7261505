"""Serving v2 tests (ISSUE 7): ragged sequence packing (bit-parity vs
the lone packed run, float-noise vs raw), the persistent AOT executable
cache across a simulated process restart (fresh Executor, same cache
dir; corrupt-entry fallback), continuous-batching lifecycle races, the
queue-discipline fixes (head-of-line packing, whole-queue deadline
sweep, notify-driven idle wait), ServingFleet HBM admission with
eviction-under-budget."""

import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.framework.errors import (ExecutionTimeoutError,
                                         InvalidArgumentError,
                                         UnavailableError)
from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
from paddle_tpu.serving import (ServingConfig, ServingEngine, ServingFleet,
                                pack_requests)


SEQ_FEEDS = ("src_ids", "pos_ids", "sent_ids", "input_mask")


# ---------------------------------------------------------------------------
# model builders
# ---------------------------------------------------------------------------


def _save_fc_model(tmp_path):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[6])
        h = fluid.layers.fc(x, 8, act="relu")
        y = fluid.layers.fc(h, 3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    d = str(tmp_path / "fc_model")
    fluid.io.save_inference_model(d, ["x"], [y], exe, main)
    return d


def _bert1_cfg():
    from paddle_tpu.models import bert
    return bert.BertConfig(vocab_size=211, hidden_size=32,
                           num_hidden_layers=1, num_attention_heads=2,
                           intermediate_size=64,
                           max_position_embeddings=64, type_vocab_size=2)


def _save_bert_model(tmp_path, fetch="pooled", name="bert_model"):
    from paddle_tpu.models import bert
    cfg = _bert1_cfg()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        src = fluid.layers.data("src_ids", shape=[-1, -1], dtype="int64",
                                append_batch_size=False)
        pos = fluid.layers.data("pos_ids", shape=[-1, -1], dtype="int64",
                                append_batch_size=False)
        sent = fluid.layers.data("sent_ids", shape=[-1, -1], dtype="int64",
                                 append_batch_size=False)
        mask = fluid.layers.data("input_mask", shape=[-1, -1, 1],
                                 dtype="float32", append_batch_size=False)
        seq_out, pooled = bert.bert_encoder(src, pos, sent, mask, cfg,
                                            is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    targets = [seq_out] if fetch == "seq" else [pooled]
    d = str(tmp_path / name)
    fluid.io.save_inference_model(d, list(SEQ_FEEDS), targets, exe, main)
    return d, cfg


def _bert_req(rng, cfg, b, s):
    return {
        "src_ids": rng.randint(0, cfg.vocab_size, (b, s)).astype("int64"),
        "pos_ids": np.tile(np.arange(s, dtype="int64"), (b, 1)),
        "sent_ids": rng.randint(0, cfg.type_vocab_size,
                                (b, s)).astype("int64"),
        "input_mask": np.ones((b, s, 1), dtype="float32"),
    }


def _cpu_predictor(model_dir):
    config = AnalysisConfig(model_dir)
    config.disable_gpu()
    return create_paddle_predictor(config)


# ---------------------------------------------------------------------------
# ragged sequence packing
# ---------------------------------------------------------------------------


class TestRaggedPacking:
    def test_packed_batch_bit_parity_and_placements(self, tmp_path):
        """The packing contract: every per-request result is bit-identical
        to slicing a lone ``predictor.run`` of the ``pack_requests`` feed
        (same executable, same bits), and within float noise of the raw
        unpadded run (block-diagonal segment masking)."""
        d, cfg = _save_bert_model(tmp_path, fetch="seq")
        baseline = _cpu_predictor(d)
        seq_fetch = baseline.get_output_names()[0]
        scfg = ServingConfig(max_batch_size=4, max_wait_ms=5.0,
                             batch_buckets=(1, 2, 4), seq_buckets=(16, 32),
                             seq_feeds=SEQ_FEEDS, seq_fetches=(seq_fetch,),
                             packing=True, mask_feed="input_mask",
                             pack_max_segments=4)
        engine = ServingEngine(_cpu_predictor(d), scfg, auto_start=False)
        rng = np.random.RandomState(0)
        lengths = (9, 11, 16, 5, 7, 30)
        reqs = [_bert_req(rng, cfg, 1, s) for s in lengths]
        futs = [engine.submit(r) for r in reqs]       # all queue: one batch
        engine.start()
        assert engine.drain(timeout=300)

        packed, placements, bucket = pack_requests(reqs, scfg,
                                                   list(SEQ_FEEDS))
        # multiple segments really share rows (the packing actually packs)
        rows_used = {row for p in placements for row, _ in p}
        assert len(rows_used) < len(reqs)
        ref, = baseline.run([packed[n] for n in SEQ_FEEDS])
        for r, f, s, place in zip(reqs, futs, lengths, placements):
            out, = f.result(timeout=5)
            assert f.bucket == bucket
            assert f.placement == place
            assert out.shape[:2] == (1, s)
            for (row, off), orow in zip(place, out):
                np.testing.assert_array_equal(orow, ref[row, off:off + s])
            raw, = baseline.run([r[n] for n in SEQ_FEEDS])
            np.testing.assert_allclose(out, raw, rtol=2e-5, atol=2e-6)
        stats = engine.stats()
        assert stats["packing"] is True
        assert stats["batches"] == 1
        # packing occupancy beats one-row-per-request padding of the same
        # requests (each row at its seq bucket, rows at the batch bucket)
        one_row = 8 * 32        # 6 requests -> batch bucket 4 + 2 -> 8 rows
        assert stats["padding_waste"] < 1.0 - sum(lengths) / one_row
        assert stats["padding_waste"] < 0.5
        engine.shutdown()

    def test_multi_row_requests_pack_per_row(self, tmp_path):
        d, cfg = _save_bert_model(tmp_path, fetch="seq")
        baseline = _cpu_predictor(d)
        seq_fetch = baseline.get_output_names()[0]
        scfg = ServingConfig(max_batch_size=4, max_wait_ms=5.0,
                             batch_buckets=(1, 2, 4), seq_buckets=(16,),
                             seq_feeds=SEQ_FEEDS, seq_fetches=(seq_fetch,),
                             packing=True, mask_feed="input_mask",
                             pack_max_segments=2)
        engine = ServingEngine(_cpu_predictor(d), scfg, auto_start=False)
        rng = np.random.RandomState(1)
        reqs = [_bert_req(rng, cfg, 2, 7), _bert_req(rng, cfg, 1, 9),
                _bert_req(rng, cfg, 2, 8)]
        futs = [engine.submit(r) for r in reqs]
        engine.start()
        assert engine.drain(timeout=300)
        packed, placements, bucket = pack_requests(reqs, scfg,
                                                   list(SEQ_FEEDS))
        ref, = baseline.run([packed[n] for n in SEQ_FEEDS])
        for r, f, place in zip(reqs, futs, placements):
            out, = f.result(timeout=5)
            rows = r["src_ids"].shape[0]
            s = r["src_ids"].shape[1]
            assert out.shape[:2] == (rows, s)
            for (row, off), orow in zip(place, out):
                np.testing.assert_array_equal(orow, ref[row, off:off + s])
        engine.shutdown()

    def test_packing_config_validation(self, tmp_path):
        d, cfg = _save_bert_model(tmp_path, fetch="pooled")
        with pytest.raises(InvalidArgumentError):
            ServingConfig(packing=True, seq_buckets=(16,),
                          seq_feeds=SEQ_FEEDS)          # no mask_feed
        with pytest.raises(InvalidArgumentError):
            ServingConfig(packing=True, seq_feeds=SEQ_FEEDS,
                          mask_feed="input_mask")       # no seq_buckets
        # a pooled (non-seq) fetch cannot be split back per segment —
        # the engine refuses the configuration at init
        scfg = ServingConfig(max_batch_size=2, seq_buckets=(16,),
                             seq_feeds=SEQ_FEEDS, packing=True,
                             mask_feed="input_mask")
        with pytest.raises(InvalidArgumentError):
            ServingEngine(_cpu_predictor(d), scfg, auto_start=False)

    def test_packing_mask_shape_validated_at_submit(self, tmp_path):
        d, cfg = _save_bert_model(tmp_path, fetch="seq")
        pred = _cpu_predictor(d)
        seq_fetch = pred.get_output_names()[0]
        engine = ServingEngine(
            pred, ServingConfig(max_batch_size=2, seq_buckets=(16,),
                                seq_feeds=SEQ_FEEDS,
                                seq_fetches=(seq_fetch,), packing=True,
                                mask_feed="input_mask"),
            auto_start=False)
        r = _bert_req(np.random.RandomState(2), cfg, 1, 8)
        r["input_mask"] = np.ones((1, 8, 2), np.float32)  # engine owns K
        with pytest.raises(InvalidArgumentError):
            engine.submit(r)
        engine.shutdown(drain=False)


# ---------------------------------------------------------------------------
# queue discipline: head-of-line, deadline sweep, notify-driven idle
# ---------------------------------------------------------------------------


class TestQueueDiscipline:
    def test_head_of_line_overflow_keeps_scanning(self, tmp_path):
        """A request that would overflow max_batch_size no longer blocks
        later smaller requests from joining the batch."""
        d = _save_fc_model(tmp_path)
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=4,
                                             max_wait_ms=1.0),
                               auto_start=False)
        rng = np.random.RandomState(3)
        for rows in (3, 2, 1):
            engine.submit({"x": rng.randn(rows, 6).astype(np.float32)})
        batch = engine._next_batch(block=False)
        assert [r.rows for r in batch.picked] == [3, 1]   # 2 skipped, 1 in
        assert batch.rows_total == 4
        # the skipped request is still queued for the next batch
        assert engine.stats()["pending"] == 1
        engine.shutdown(drain=False)

    def test_deadline_sweep_covers_non_head_groups(self, tmp_path):
        """A queued request from another group times out on schedule even
        when the head group has live work (the old scan only expired the
        head group's requests)."""
        d = _save_fc_model(tmp_path)
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=4,
                                             max_wait_ms=1.0,
                                             timeout_ms=10000.0),
                               auto_start=False)
        rng = np.random.RandomState(4)
        fut_a = engine.submit({"x": rng.randn(1, 6).astype(np.float32)})
        fut_b = engine.submit({"x": rng.randn(1, 7).astype(np.float32)})
        # force B (non-head group) past its deadline; A stays live
        with engine._cond:
            engine._queue[1].deadline = time.monotonic() - 1.0
        batch = engine._next_batch(block=False)
        assert [r.future for r in batch.picked] == [fut_a]
        with pytest.raises(ExecutionTimeoutError):
            fut_b.result(timeout=1)
        assert engine.stats()["timed_out"] == 1
        engine.shutdown(drain=False)

    def test_idle_engine_takes_zero_wakeups(self, tmp_path):
        """The idle worker is notify-driven (no 20 Hz poll): an idle
        window takes ZERO spurious wakeups, and the engine still serves
        immediately afterwards."""
        d = _save_fc_model(tmp_path)
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=4,
                                             max_wait_ms=1.0))
        rng = np.random.RandomState(5)
        out, = engine.submit(
            {"x": rng.randn(1, 6).astype(np.float32)}).result(timeout=60)
        assert np.isfinite(out).all()
        base = engine.stats()["spurious_wakeups"]
        time.sleep(0.4)                 # ~8 wakeups under the old poll
        assert engine.stats()["spurious_wakeups"] == base
        out, = engine.submit(
            {"x": rng.randn(1, 6).astype(np.float32)}).result(timeout=60)
        assert np.isfinite(out).all()
        engine.shutdown()


# ---------------------------------------------------------------------------
# continuous batching lifecycle races
# ---------------------------------------------------------------------------


class TestContinuousLifecycle:
    def test_shutdown_drain_races_inflight_batches(self, tmp_path):
        """shutdown(drain=True) issued while batches are in flight on the
        pipelined worker resolves every future."""
        d = _save_fc_model(tmp_path)
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=2,
                                             max_wait_ms=0.5,
                                             max_inflight_batches=2))
        rng = np.random.RandomState(6)
        futs = [engine.submit({"x": rng.randn(1, 6).astype(np.float32)})
                for _ in range(16)]
        assert engine.shutdown(drain=True, timeout=120)
        for f in futs:
            out, = f.result(timeout=1)
            assert np.isfinite(out).all()
        stats = engine.stats()
        assert stats["completed"] == 16
        assert stats["batches"] >= 8      # max 2 rows per batch

    def test_shutdown_nodrain_fails_queued_but_inflight_completes(
            self, tmp_path):
        d = _save_fc_model(tmp_path)
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=2,
                                             max_wait_ms=0.5))
        rng = np.random.RandomState(7)
        futs = [engine.submit({"x": rng.randn(1, 6).astype(np.float32)})
                for _ in range(12)]
        engine.shutdown(drain=False, timeout=120)
        done, cancelled = 0, 0
        for f in futs:
            try:
                f.result(timeout=1)
                done += 1
            except UnavailableError:
                cancelled += 1
        assert done + cancelled == 12
        stats = engine.stats()
        assert stats["cancelled"] == cancelled
        assert stats["completed"] == done

    def test_concurrent_submit_during_drain(self, tmp_path):
        d = _save_fc_model(tmp_path)
        baseline = _cpu_predictor(d)
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=4,
                                             max_wait_ms=0.5))
        errors = []
        results = {}

        def client(tid):
            rng = np.random.RandomState(50 + tid)
            try:
                for i in range(5):
                    x = rng.randn(1, 6).astype(np.float32)
                    out, = engine.submit({"x": x}).result(timeout=60)
                    results[(tid, i)] = (x, out)
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(3)]
        for t in threads:
            t.start()
        for _ in range(5):
            engine.drain(timeout=60)
        for t in threads:
            t.join(120)
        assert not errors
        for (tid, i), (x, out) in results.items():
            # a batched row differs from a lone run by float noise; a
            # misrouted one by O(1)
            ref, = baseline.run([x])
            np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)
        engine.shutdown()


# ---------------------------------------------------------------------------
# persistent AOT executable cache
# ---------------------------------------------------------------------------


class TestAotCache:
    def _with_cache(self, tmp_path):
        cache = str(tmp_path / "aot_cache")
        old = fluid.get_flags("aot_cache_dir")["aot_cache_dir"]
        fluid.set_flags({"aot_cache_dir": cache})
        return cache, old

    def test_restart_round_trip_bit_parity(self, tmp_path):
        """A fresh Executor (the simulated restarted process) with a
        populated cache dir performs ZERO fresh compiles and reproduces
        the cold run's results bit-for-bit."""
        from paddle_tpu.monitor import stat
        d = _save_fc_model(tmp_path)
        cache, old = self._with_cache(tmp_path)
        try:
            x = np.random.RandomState(8).randn(2, 6).astype(np.float32)
            p1 = _cpu_predictor(d)
            p1.prepare()
            m0 = stat("aot_cache_miss").get()
            o1, = p1.run([x])
            assert stat("aot_cache_miss").get() == m0 + 1
            assert stat("aot_cache_store").get() >= 1
            assert os.listdir(cache)

            c0 = stat("executor_compile_count").get()
            h0 = stat("aot_cache_hit").get()
            p2 = _cpu_predictor(d)          # fresh Executor + scope
            p2.prepare()
            o2, = p2.run([x])
            assert stat("executor_compile_count").get() == c0
            assert stat("aot_cache_hit").get() == h0 + 1
            np.testing.assert_array_equal(o1, o2)
        finally:
            fluid.set_flags({"aot_cache_dir": old})

    def test_corrupt_entry_falls_back_to_recompile(self, tmp_path):
        from paddle_tpu.monitor import stat
        d = _save_fc_model(tmp_path)
        cache, old = self._with_cache(tmp_path)
        try:
            x = np.random.RandomState(9).randn(1, 6).astype(np.float32)
            p1 = _cpu_predictor(d)
            p1.prepare()
            o1, = p1.run([x])
            entries = [os.path.join(cache, n) for n in os.listdir(cache)]
            assert entries
            with open(entries[0], "wb") as f:
                f.write(b"not a pickled executable")
            e0 = stat("aot_cache_error").get()
            c0 = stat("executor_compile_count").get()
            p2 = _cpu_predictor(d)
            p2.prepare()
            o2, = p2.run([x])
            assert stat("aot_cache_error").get() == e0 + 1
            assert stat("executor_compile_count").get() == c0 + 1  # recompiled
            np.testing.assert_array_equal(o1, o2)
            # the bad entry was replaced by a good one: next restart hits
            h0 = stat("aot_cache_hit").get()
            p3 = _cpu_predictor(d)
            p3.prepare()
            o3, = p3.run([x])
            assert stat("aot_cache_hit").get() == h0 + 1
            np.testing.assert_array_equal(o1, o3)
        finally:
            fluid.set_flags({"aot_cache_dir": old})

    def test_engine_warm_restart_deserializes_grid(self, tmp_path):
        """ServingEngine.warmup on a 'restarted' predictor (fresh
        Executor, same cache dir) is pure deserialization: 0 fresh
        compiles, every combo a cache hit."""
        from paddle_tpu.monitor import stat
        d, cfg = _save_bert_model(tmp_path)
        cache, old = self._with_cache(tmp_path)
        try:
            scfg = ServingConfig(max_batch_size=2, max_wait_ms=1.0,
                                 batch_buckets=(1, 2), seq_buckets=(16,),
                                 seq_feeds=SEQ_FEEDS)
            rng = np.random.RandomState(10)
            ex = _bert_req(rng, cfg, 1, 12)
            e1 = ServingEngine(_cpu_predictor(d), scfg, auto_start=False)
            assert e1.warmup(ex) == 2
            e1.shutdown(drain=False)

            c0 = stat("executor_compile_count").get()
            h0 = stat("aot_cache_hit").get()
            e2 = ServingEngine(_cpu_predictor(d), scfg, auto_start=False)
            assert e2.warmup(ex) == 2
            assert stat("executor_compile_count").get() == c0
            assert stat("aot_cache_hit").get() == h0 + 2
            e2.shutdown(drain=False)
        finally:
            fluid.set_flags({"aot_cache_dir": old})


# ---------------------------------------------------------------------------
# ServingFleet: multi-tenant HBM admission
# ---------------------------------------------------------------------------


class TestServingFleet:
    def test_reject_precompile_then_evict_admits(self, tmp_path):
        from paddle_tpu.monitor import stat
        d1, cfg = _save_bert_model(tmp_path, name="model_a")
        d2, _ = _save_bert_model(tmp_path, name="model_b")
        scfg = dict(max_batch_size=2, max_wait_ms=1.0,
                    batch_buckets=(1, 2), seq_buckets=(16, 32),
                    seq_feeds=SEQ_FEEDS)
        ex = _bert_req(np.random.RandomState(11), cfg, 1, 16)

        probe = ServingFleet(hbm_budget_gb=0)     # admission off: sizing
        probe.add_model("probe", d1, ServingConfig(**scfg),
                        example_feed=ex, warmup=False)
        rep = probe.admission_report()["models"]["probe"]
        probe.shutdown(drain=False)
        dyn = sorted(rep["variants"].values())
        budget_gb = (2 * rep["cost_mb"] - (dyn[-1] - dyn[-2]) / 2) / 1024.0

        fleet = ServingFleet(hbm_budget_gb=budget_gb)
        fleet.add_model("model_a", d1, ServingConfig(**scfg),
                        example_feed=ex, warmup=False)
        c0 = stat("executor_compile_count").get()
        with pytest.raises(InvalidArgumentError) as ei:
            fleet.add_model("model_b", d2, ServingConfig(**scfg),
                            example_feed=ex, warmup=False)
        msg = str(ei.value)
        assert "model_b" in msg                  # offending model named
        assert "top live tensors" in msg         # ...with its live set
        assert stat("executor_compile_count").get() == c0   # pre-compile
        assert fleet.models() == ["model_a"]

        # evicting one bucket variant of the resident tenant admits it
        assert fleet.evict("model_a", (2, 32))
        fleet.add_model("model_b", d2, ServingConfig(**scfg),
                        example_feed=ex, warmup=False)
        assert fleet.models() == ["model_a", "model_b"]
        f1 = fleet.submit("model_a", _bert_req(
            np.random.RandomState(12), cfg, 1, 9))
        f2 = fleet.submit("model_b", _bert_req(
            np.random.RandomState(13), cfg, 1, 12))
        assert np.isfinite(f1.result(timeout=300)[0]).all()
        assert np.isfinite(f2.result(timeout=300)[0]).all()
        report = fleet.admission_report()
        assert report["total_mb"] <= budget_gb * 1024 + 1e-6
        fleet.shutdown()

    def test_evict_lru_makes_room_automatically(self, tmp_path):
        d1, cfg = _save_bert_model(tmp_path, name="model_a")
        d2, _ = _save_bert_model(tmp_path, name="model_b")
        scfg = dict(max_batch_size=2, max_wait_ms=1.0,
                    batch_buckets=(1, 2), seq_buckets=(16, 32),
                    seq_feeds=SEQ_FEEDS)
        ex = _bert_req(np.random.RandomState(14), cfg, 1, 16)
        probe = ServingFleet(hbm_budget_gb=0)
        probe.add_model("probe", d1, ServingConfig(**scfg),
                        example_feed=ex, warmup=False)
        rep = probe.admission_report()["models"]["probe"]
        probe.shutdown(drain=False)
        dyn = sorted(rep["variants"].values())
        budget_gb = (2 * rep["cost_mb"] - (dyn[-1] - dyn[-2]) / 2) / 1024.0

        fleet = ServingFleet(hbm_budget_gb=budget_gb)
        fleet.add_model("model_a", d1, ServingConfig(**scfg),
                        example_feed=ex, warmup=False)
        a_before = set(fleet._models["model_a"].admitted)
        fleet.add_model("model_b", d2, ServingConfig(**scfg),
                        example_feed=ex, warmup=False, evict_lru=True)
        assert fleet.models() == ["model_a", "model_b"]
        a_after = set(fleet._models["model_a"].admitted)
        assert len(a_after) < len(a_before)      # something was evicted
        fleet.shutdown(drain=False)

    def test_estimate_alias(self, tmp_path):
        from paddle_tpu.framework import memory_analysis
        d, cfg = _save_bert_model(tmp_path)
        pred = _cpu_predictor(d)
        ex = _bert_req(np.random.RandomState(15), cfg, 2, 16)
        est = memory_analysis.estimate(pred.program, feed_shapes=ex,
                                       fetch_names=pred.get_output_names(),
                                       donate_state=False)
        assert est.peak_bytes > est.state_bytes > 0
        assert est.as_dict()["peak_bytes"] == est.peak_bytes
