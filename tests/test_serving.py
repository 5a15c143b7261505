"""Serving engine tests (ISSUE 4): dynamic micro-batching bit-parity,
shape-bucketed compile bounds, concurrent submit routing, lifecycle
(drain/shutdown/timeout), the predictor arity fix, the feed-cache flag,
and the inference verification profile."""

import json
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
from paddle_tpu.serving import ServingConfig, ServingEngine, pad_request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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
        # train ops must be pruned away on save
        fluid.optimizer.SGD(0.1).minimize(fluid.layers.mean(y))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    d = str(tmp_path / "fc_model")
    fluid.io.save_inference_model(d, ["x"], [y], exe, main)
    return d


def _bert1_cfg():
    from paddle_tpu.models import bert
    # 1-layer narrow config: the serving semantics under test don't need
    # depth, and compile time dominates these tests
    return bert.BertConfig(vocab_size=211, hidden_size=32,
                           num_hidden_layers=1, num_attention_heads=2,
                           intermediate_size=64,
                           max_position_embeddings=64, type_vocab_size=2)


def _save_bert_model(tmp_path, fetch_seq=False):
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
    targets = [seq_out, pooled] if fetch_seq else [pooled]
    d = str(tmp_path / "bert_model")
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
# (a) bit-parity: batched+padded engine output vs per-request runs
# ---------------------------------------------------------------------------


class TestBatchedParity:
    """The serving bit-parity contract, in two shape-sound layers:

    1. a request whose (rows, seq) lands exactly on buckets and rides
       alone in its micro-batch runs at EXACTLY the raw per-request
       shape — same executable, bit-identical to ``predictor.run``;
    2. ANY request, however it was coalesced, is bit-identical to a lone
       ``predictor.run`` of ``pad_request(feed, *future.bucket)`` — the
       canonical shape the engine reports.  Mask-aware padding makes
       row/position computations independent, so co-batched requests
       cannot perturb each other's bits at a fixed executable shape.

    (Bitwise equality across DIFFERENT XLA executable shapes is not a
    defined property of the backend — the float-noise legs cover that.)
    """

    def test_fc_model_bit_parity(self, tmp_path):
        d = _save_fc_model(tmp_path)
        baseline = _cpu_predictor(d)
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=4,
                                             max_wait_ms=5.0))
        rng = np.random.RandomState(0)
        # layer 1: lone exact-bucket requests == raw run, bit for bit
        for b in (1, 2, 4):
            r = rng.randn(b, 6).astype(np.float32)
            fut = engine.submit({"x": r})
            assert engine.drain(timeout=60)
            assert fut.bucket == (b, None)
            out, = fut.result(timeout=1)
            ref, = baseline.run([r])
            np.testing.assert_array_equal(out, ref)
        # layer 2: coalesced, padded batches == lone canonical runs
        reqs = [rng.randn(b, 6).astype(np.float32)
                for b in (1, 2, 3, 1, 4, 2)]
        futs = [engine.submit({"x": r}) for r in reqs]
        for r, f in zip(reqs, futs):
            out, = f.result(timeout=60)
            bb, _ = f.bucket
            canon = pad_request({"x": r}, None, (), batch_bucket=bb)
            ref, = baseline.run([canon["x"]])
            np.testing.assert_array_equal(out, ref[:r.shape[0]])
        engine.shutdown()

    def test_bert_exact_bucket_bit_parity(self, tmp_path):
        """Lone requests landing exactly on (batch, seq) buckets run at
        the raw per-request shape — bit-identical to predictor.run."""
        d, cfg = _save_bert_model(tmp_path)
        baseline = _cpu_predictor(d)
        engine = ServingEngine(
            _cpu_predictor(d),
            ServingConfig(max_batch_size=4, max_wait_ms=5.0,
                          batch_buckets=(1, 2, 4),
                          seq_buckets=(16, 32), seq_feeds=SEQ_FEEDS))
        rng = np.random.RandomState(1)
        for b, s in ((1, 16), (2, 16), (1, 32), (4, 32), (2, 32)):
            r = _bert_req(rng, cfg, b, s)
            fut = engine.submit(r)
            assert engine.drain(timeout=180)
            assert fut.bucket == (b, s)      # no padding happened
            out, = fut.result(timeout=1)
            ref, = baseline.run([r[n] for n in SEQ_FEEDS])
            np.testing.assert_array_equal(out, ref)
        engine.shutdown()

    def test_bert_mixed_length_parity_mask_aware(self, tmp_path):
        """Mixed-length coalesced requests: bit-identical to the lone
        per-request run at the engine's reported canonical bucket shape,
        and equal within float noise to the raw unpadded run — the
        mask-aware padding contract."""
        d, cfg = _save_bert_model(tmp_path, fetch_seq=True)
        baseline = _cpu_predictor(d)
        seq_fetch = baseline.get_output_names()[0]
        engine = ServingEngine(
            _cpu_predictor(d),
            ServingConfig(max_batch_size=4, max_wait_ms=5.0,
                          seq_buckets=(16, 32), seq_feeds=SEQ_FEEDS,
                          seq_fetches=(seq_fetch,)))
        rng = np.random.RandomState(2)
        lengths = (9, 11, 16, 23, 29, 32)
        reqs = [_bert_req(rng, cfg, 1, s) for s in lengths]
        futs = [engine.submit(r) for r in reqs]
        for r, f, s in zip(reqs, futs, lengths):
            seq_piece, pooled = f.result(timeout=180)
            assert seq_piece.shape[1] == s
            bb, sb = f.bucket
            assert sb >= s
            # bit-identical to the lone run at the canonical bucket shape
            canon = pad_request(r, sb, SEQ_FEEDS, batch_bucket=bb)
            ref_seq, ref_pool = baseline.run([canon[n]
                                              for n in SEQ_FEEDS])
            np.testing.assert_array_equal(pooled, ref_pool[:1])
            np.testing.assert_array_equal(seq_piece, ref_seq[:1, :s])
            # within float noise of the raw unpadded request
            raw_seq, raw_pool = baseline.run([r[n] for n in SEQ_FEEDS])
            np.testing.assert_allclose(pooled, raw_pool, rtol=2e-5,
                                       atol=2e-6)
            np.testing.assert_allclose(seq_piece, raw_seq, rtol=2e-5,
                                       atol=2e-6)
        engine.shutdown()


# ---------------------------------------------------------------------------
# (b) compile count bounded by the bucket grid
# ---------------------------------------------------------------------------


class TestCompileBudget:
    def test_mixed_sweep_compiles_at_most_bucket_grid(self, tmp_path):
        """>= 12 distinct (batch, seq) request shapes compile at most
        len(batch_buckets) x len(seq_buckets) executables, with engine
        outputs bit-identical to unbatched per-request runs (raw shape
        for the exact-bucket shapes, canonical bucket shape for the
        rest)."""
        d, cfg = _save_bert_model(tmp_path)
        pred = _cpu_predictor(d)
        baseline = _cpu_predictor(d)
        scfg = ServingConfig(max_batch_size=4, max_wait_ms=1.0,
                             batch_buckets=(1, 2, 4),
                             seq_buckets=(8, 16, 24, 32),
                             seq_feeds=SEQ_FEEDS)
        engine = ServingEngine(pred, scfg)
        assert scfg.bucket_capacity == 12
        rng = np.random.RandomState(3)
        exact = [(b, s) for b in (1, 2, 4) for s in (8, 16, 24, 32)]
        off = [(1, 5), (2, 13), (3, 22), (1, 31), (3, 9), (2, 27)]
        assert len(exact) + len(off) >= 12 + 6       # 18 distinct shapes

        # exact-bucket shapes ride alone: raw-shape bit identity
        for b, s in exact:
            r = _bert_req(rng, cfg, b, s)
            fut = engine.submit(r)
            assert engine.drain(timeout=180)
            assert fut.bucket == (b, s)
            out, = fut.result(timeout=1)
            ref, = baseline.run([r[n] for n in SEQ_FEEDS])
            np.testing.assert_array_equal(out, ref)

        # off-bucket shapes coalesce freely: canonical-shape bit identity
        off_reqs = [_bert_req(rng, cfg, b, s) for b, s in off]
        futs = [engine.submit(r) for r in off_reqs]
        for r, f in zip(off_reqs, futs):
            out, = f.result(timeout=180)
            bb, sb = f.bucket
            canon = pad_request(r, sb, SEQ_FEEDS, batch_bucket=bb)
            ref, = baseline.run([canon[n] for n in SEQ_FEEDS])
            rows = r["src_ids"].shape[0]
            np.testing.assert_array_equal(out, ref[:rows])

        stats = engine.stats()
        assert pred.compiled_executables <= scfg.bucket_capacity, stats
        assert stats["compile_count"] == pred.compiled_executables
        assert stats["completed"] == len(exact) + len(off)
        assert 0.0 <= stats["padding_waste"] < 1.0
        assert stats["p50_ms"] <= stats["p99_ms"]
        assert stats["qps"] > 0
        engine.shutdown()

    def test_warmup_precompiles_every_bucket_combo(self, tmp_path):
        d, cfg = _save_bert_model(tmp_path)
        pred = _cpu_predictor(d)
        scfg = ServingConfig(max_batch_size=2, max_wait_ms=1.0,
                             batch_buckets=(1, 2), seq_buckets=(16, 32),
                             seq_feeds=SEQ_FEEDS)
        engine = ServingEngine(pred, scfg, auto_start=False)
        rng = np.random.RandomState(4)
        combos = engine.warmup(_bert_req(rng, cfg, 1, 20))
        assert combos == 4
        assert pred.compiled_executables == 4
        engine.start()
        # a mixed stream inside the warmed buckets compiles NOTHING new
        futs = [engine.submit(_bert_req(rng, cfg, b, s))
                for b, s in ((1, 7), (2, 19), (1, 32), (2, 16))]
        for f in futs:
            f.result(timeout=120)
        assert pred.compiled_executables == 4
        engine.shutdown()


# ---------------------------------------------------------------------------
# (c) concurrent submission with per-request result routing
# ---------------------------------------------------------------------------


class TestConcurrentSubmit:
    def test_threaded_submit_routes_results(self, tmp_path):
        d = _save_fc_model(tmp_path)
        baseline = _cpu_predictor(d)
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=8,
                                             max_wait_ms=1.0))
        n_threads, per_thread = 4, 6
        results = {}
        errors = []

        def client(tid):
            rng = np.random.RandomState(100 + tid)
            try:
                for i in range(per_thread):
                    x = rng.randn(1, 6).astype(np.float32)
                    out, = engine.submit({"x": x}).result(timeout=60)
                    results[(tid, i)] = (x, out)
            except Exception as e:          # surface in the main thread
                errors.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors
        assert len(results) == n_threads * per_thread
        for (tid, i), (x, out) in results.items():
            # a batched row differs from a lone run by float noise; a
            # misrouted one by O(1)
            ref, = baseline.run([x])
            np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)
        stats = engine.stats()
        assert stats["completed"] == n_threads * per_thread
        assert stats["batches"] <= stats["completed"]
        engine.shutdown()


# ---------------------------------------------------------------------------
# (d) lifecycle: drain, shutdown, timeout
# ---------------------------------------------------------------------------


class TestLifecycle:
    def test_drain_completes_everything(self, tmp_path):
        d = _save_fc_model(tmp_path)
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=4,
                                             max_wait_ms=1.0))
        rng = np.random.RandomState(5)
        futs = [engine.submit({"x": rng.randn(1, 6).astype(np.float32)})
                for _ in range(7)]
        assert engine.drain(timeout=60)
        assert all(f.done() for f in futs)
        # engine still accepts after a drain
        out, = engine.submit(
            {"x": rng.randn(1, 6).astype(np.float32)}).result(timeout=60)
        assert np.isfinite(out).all()
        engine.shutdown()

    def test_shutdown_drain_finishes_pending(self, tmp_path):
        d = _save_fc_model(tmp_path)
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=4,
                                             max_wait_ms=50.0))
        rng = np.random.RandomState(6)
        futs = [engine.submit({"x": rng.randn(1, 6).astype(np.float32)})
                for _ in range(3)]
        assert engine.shutdown(drain=True, timeout=120)
        for f in futs:
            out, = f.result(timeout=1)
            assert np.isfinite(out).all()
        with pytest.raises(UnavailableError):
            engine.submit({"x": rng.randn(1, 6).astype(np.float32)})

    def test_shutdown_cancel_fails_pending(self, tmp_path):
        d = _save_fc_model(tmp_path)
        # worker never started -> requests deterministically still queued
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=4),
                               auto_start=False)
        rng = np.random.RandomState(7)
        futs = [engine.submit({"x": rng.randn(1, 6).astype(np.float32)})
                for _ in range(2)]
        engine.shutdown(drain=False)
        for f in futs:
            with pytest.raises(UnavailableError):
                f.result(timeout=1)
        assert engine.stats()["cancelled"] == 2

    def test_request_timeout(self, tmp_path):
        d = _save_fc_model(tmp_path)
        # deadline (0.01 ms) expires long before the batch window
        # (80 ms) closes -> the worker must fail the request, not run it
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=8,
                                             max_wait_ms=80.0,
                                             timeout_ms=0.01))
        fut = engine.submit({"x": np.zeros((1, 6), np.float32)})
        with pytest.raises(ExecutionTimeoutError):
            fut.result(timeout=60)
        assert engine.stats()["timed_out"] == 1
        engine.shutdown()

    def test_submit_validation(self, tmp_path):
        d = _save_fc_model(tmp_path)
        engine = ServingEngine(_cpu_predictor(d),
                               ServingConfig(max_batch_size=2),
                               auto_start=False)
        with pytest.raises(InvalidArgumentError):
            engine.submit({})                                  # missing
        with pytest.raises(InvalidArgumentError):
            engine.submit({"x": np.zeros((1, 6), np.float32),
                           "bogus": np.zeros(1)})              # extra
        with pytest.raises(InvalidArgumentError):
            engine.submit({"x": np.zeros((3, 6), np.float32)})  # > max
        engine.shutdown(drain=False)


# ---------------------------------------------------------------------------
# satellite: AnalysisPredictor arity contract
# ---------------------------------------------------------------------------


class TestPredictorArity:
    def test_run_arity_mismatch_raises(self, tmp_path):
        d = _save_fc_model(tmp_path)
        pred = _cpu_predictor(d)
        x = np.zeros((2, 6), np.float32)
        with pytest.raises(InvalidArgumentError):
            pred.run([x, x])            # extra input was silently dropped
        with pytest.raises(InvalidArgumentError):
            pred.run([])                # missing input fed garbage
        with pytest.raises(InvalidArgumentError):
            pred.run_feed({"x": x, "y": x})
        with pytest.raises(InvalidArgumentError):
            pred.run_feed({})
        out, = pred.run([x])            # correct arity still works
        assert out.shape == (2, 3)


# ---------------------------------------------------------------------------
# satellite: feed-cache flag + counters
# ---------------------------------------------------------------------------


class TestFeedCacheFlag:
    def test_flag_controls_capacity_and_counters_surface(self):
        import jax
        from paddle_tpu import profiler
        from paddle_tpu.framework.executor import _FeedDeviceCache
        from paddle_tpu.monitor import stat
        old = fluid.get_flags("feed_cache_size")["feed_cache_size"]
        fluid.set_flags({"feed_cache_size": 2})
        try:
            cache = _FeedDeviceCache(jax.devices("cpu")[0])
            assert cache.capacity() == 2
            arrays = []
            for i in range(3):
                a = np.full((4,), i, np.float32)
                a.flags.writeable = False
                arrays.append(a)
                cache.lookup(a)
            assert len(cache._entries) <= 2      # flag-sized eviction
            h0 = stat("feed_cache_hit").get()
            cache.lookup(arrays[-1])             # still resident -> hit
            assert stat("feed_cache_hit").get() == h0 + 1
            bd = profiler.step_breakdown([])
            assert bd["feed_cache"]["capacity"] == 2
            assert bd["feed_cache"]["hits"] >= 1
            assert bd["feed_cache"]["misses"] >= 3
        finally:
            fluid.set_flags({"feed_cache_size": old})

    def test_zero_capacity_disables_caching(self):
        import jax
        from paddle_tpu.framework.executor import _FeedDeviceCache
        old = fluid.get_flags("feed_cache_size")["feed_cache_size"]
        fluid.set_flags({"feed_cache_size": 0})
        try:
            cache = _FeedDeviceCache(jax.devices("cpu")[0])
            a = np.ones((4,), np.float32)
            a.flags.writeable = False
            assert cache.lookup(a) is None
            assert not cache._entries
        finally:
            fluid.set_flags({"feed_cache_size": old})


# ---------------------------------------------------------------------------
# satellite: inference verification profile
# ---------------------------------------------------------------------------


class TestInferenceVerifier:
    def _train_program(self):
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4])
            y = fluid.layers.fc(x, 2)
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, loss

    def test_training_program_rejected(self):
        from paddle_tpu.framework import analysis
        main, loss = self._train_program()
        res = analysis.verify_inference(main, feed_names=["x"],
                                        fetch_names=[loss.name])
        codes = {d.code for d in res.errors()}
        assert analysis.INFERENCE_TRAINING_OP in codes    # backward op
        assert analysis.INFERENCE_STATE_WRITE in codes    # sgd param write
        with pytest.raises(InvalidArgumentError):
            res.raise_on_error()

    def test_collective_rejected(self):
        from paddle_tpu.framework import analysis
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4])
            y = fluid.layers.fc(x, 2)
        blk = main.global_block()
        blk.append_op(type="c_allreduce_sum", inputs={"X": [y.name]},
                      outputs={"Out": [y.name]}, attrs={"ring_id": 0})
        res = analysis.verify_inference(main, feed_names=["x"],
                                        fetch_names=[y.name])
        assert res.by_code(analysis.INFERENCE_COLLECTIVE)

    def test_pruned_program_accepted(self, tmp_path):
        from paddle_tpu.framework import analysis
        d = _save_fc_model(tmp_path)
        pred = _cpu_predictor(d)      # load itself verifies under the flag
        res = analysis.verify_inference(
            pred.program, feed_names=pred.get_input_names(),
            fetch_names=pred.get_output_names())
        assert res.ok, res.report()

    def test_predictor_load_rejects_state_writing_program(self, tmp_path):
        """An artifact whose program updates a persistable is not
        servable — the predictor must refuse it at load."""
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4])
            y = fluid.layers.fc(x, 2)
            ctr = fluid.layers.create_parameter([1], "float32",
                                                name="serve_ctr")
            ctr = fluid.layers.increment(ctr)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        d = str(tmp_path / "bad_model")
        fluid.io.save_inference_model(d, ["x"], [y, ctr], exe, main)
        with pytest.raises(InvalidArgumentError):
            _cpu_predictor(d)

    def test_proglint_inference_mode(self, tmp_path, capsys):
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import proglint
        finally:
            sys.path.pop(0)
        d = _save_fc_model(tmp_path)
        model = os.path.join(d, "__model__")
        assert proglint.main([model, "--inference"]) == 0
        # a collective-carrying program fails the inference profile
        from paddle_tpu.framework.serialization import program_to_desc
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4])
            y = fluid.layers.fc(x, 2)
        main.global_block().append_op(
            type="c_allreduce_sum", inputs={"X": [y.name]},
            outputs={"Out": [y.name]}, attrs={"ring_id": 0})
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            json.dump({"program_desc": program_to_desc(main)}, f)
        assert proglint.main([bad, "--inference"]) == 1
        capsys.readouterr()


# ---------------------------------------------------------------------------
# read-only-state prepared mode (the serving fast path substrate)
# ---------------------------------------------------------------------------


class TestReadOnlyPreparedMode:
    def test_no_donation_and_no_state_round_trip(self, tmp_path):
        d = _save_fc_model(tmp_path)
        pred = _cpu_predictor(d)
        x = np.random.RandomState(8).randn(2, 6).astype(np.float32)
        ref, = pred.run([x])            # slow path, before prepare
        prepared = pred.prepare()
        out, = pred.run([x])            # now the prepared fast path
        np.testing.assert_array_equal(out, ref)
        step = prepared._cur
        assert step.state_in_names                 # weights are read
        assert step.state_out_names == []          # ...but never returned
        donated, total = prepared.donation()
        assert donated == 0 and total > 0          # read-only: no donation
        # repeated runs keep the scope buffers intact (no consumption)
        for _ in range(3):
            out2, = pred.run([x])
            np.testing.assert_array_equal(out2, ref)
        # a plain Executor.run over the same scope needs no staleness
        # flush: the prepared step never dirtied it
        assert prepared._dirty is False

    def test_interleaves_with_plain_run_and_zero_copy(self, tmp_path):
        d = _save_fc_model(tmp_path)
        pred = _cpu_predictor(d)
        pred.prepare()
        rng = np.random.RandomState(9)
        x = rng.randn(3, 6).astype(np.float32)
        fast, = pred.run([x])
        t = pred.get_input_tensor("x")
        t.copy_from_cpu(x)
        pred.zero_copy_run()            # legacy scope-based path
        slow = pred.get_output_tensor(pred.get_output_names()[0])
        np.testing.assert_array_equal(fast, slow.copy_to_cpu())
        fast2, = pred.run([x])
        np.testing.assert_array_equal(fast, fast2)
