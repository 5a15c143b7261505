"""Executor fast-path tests: feed device cache, async fetch pipelining,
DataLoader device prefetch (the r4 perf work — VERDICT r3 #1).

These validate semantics on CPU; the throughput effect is the
benchmark's to measure on the chip (``feed_wait_ms.train``).
"""

import numpy as np
import jax
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.framework.executor import _FeedDeviceCache
from paddle_tpu.dataloader.reader import DataLoader, _DeviceFeedIterator


def _simple_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[3])
        w = fluid.layers.create_parameter([3, 2], "float32", name="w")
        y = fluid.layers.mul(x, w)
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


class TestFeedDeviceCache:
    def test_frozen_array_cached(self):
        cache = _FeedDeviceCache(jax.devices("cpu")[0])
        a = np.ones((4, 3), np.float32)
        a.flags.writeable = False
        b1 = cache.lookup(a)
        b2 = cache.lookup(a)
        assert b1 is not None and b1 is b2          # same device buffer

    def test_writable_array_not_cached(self):
        cache = _FeedDeviceCache(jax.devices("cpu")[0])
        a = np.ones((4, 3), np.float32)
        assert cache.lookup(a) is None

    def test_dead_weakref_entry_not_returned(self):
        # a stale entry whose source array died (data pointer may have been
        # reused by a NEW array with the same id/ptr/shape) must be treated
        # as a miss, not served
        cache = _FeedDeviceCache(jax.devices("cpu")[0])
        a = np.ones((2,), np.float32)
        a.flags.writeable = False
        cache.lookup(a)
        key = (id(a), a.__array_interface__["data"][0], a.shape,
               str(a.dtype))
        poison = jax.device_put(np.full((2,), 99.0, np.float32))
        cache._entries[key] = (lambda: None, poison)   # dead-ref entry
        fresh = cache.lookup(a)
        assert fresh is not poison
        np.testing.assert_array_equal(np.asarray(fresh), np.ones((2,)))

    def test_executor_run_hits_cache(self):
        main, startup, loss = _simple_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        x = np.random.RandomState(0).randn(4, 3).astype(np.float32)
        x.flags.writeable = False
        l1, = exe.run(main, feed={"x": x}, fetch_list=[loss])
        assert len(exe._feed_cache._entries) == 1
        l2, = exe.run(main, feed={"x": x}, fetch_list=[loss])
        # SGD stepped, so losses differ but both finite
        assert np.isfinite(l1).all() and np.isfinite(l2).all()

    def test_cached_and_uncached_feeds_agree(self):
        main, startup, loss = _simple_program()
        x = np.random.RandomState(1).randn(4, 3).astype(np.float32)
        frozen = x.copy()
        frozen.flags.writeable = False

        def run_once(feed_x):
            exe = fluid.Executor(fluid.CPUPlace())
            fluid.global_scope().drop_all()
            exe.run(startup)
            out, = exe.run(main, feed={"x": feed_x}, fetch_list=[loss])
            out2, = exe.run(main, feed={"x": feed_x}, fetch_list=[loss])
            return out, out2

        a1, a2 = run_once(x)
        b1, b2 = run_once(frozen)
        np.testing.assert_allclose(a1, b1, rtol=1e-6)
        np.testing.assert_allclose(a2, b2, rtol=1e-6)


class TestAsyncFetch:
    def test_return_numpy_false_returns_device_arrays(self):
        main, startup, loss = _simple_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        x = np.zeros((4, 3), np.float32)
        out, = exe.run(main, feed={"x": x}, fetch_list=[loss],
                       return_numpy=False)
        assert isinstance(out, jax.Array)
        assert np.isfinite(np.asarray(out)).all()


class TestDeviceFeedIterator:
    def test_dict_batches_become_device_arrays(self):
        batches = [{"x": np.full((2, 2), i, np.float32)} for i in range(3)]
        it = _DeviceFeedIterator(iter(batches))
        got = list(it)
        assert len(got) == 3
        for i, b in enumerate(got):
            assert isinstance(b["x"], jax.Array)
            np.testing.assert_array_equal(np.asarray(b["x"]),
                                          np.full((2, 2), i))

    def test_loader_double_buffer_end_to_end(self):
        main, startup, loss = _simple_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)

        def gen():
            for _ in range(4):
                yield (rng.randn(4, 3).astype(np.float32),)

        x_var = main.global_block().var("x")
        loader = DataLoader.from_generator(feed_list=[x_var], capacity=2,
                                           use_double_buffer=True)
        loader.set_batch_generator(gen)
        n = 0
        for feed in loader:
            assert isinstance(feed["x"], jax.Array)
            l, = exe.run(main, feed=feed, fetch_list=[loss])
            assert np.isfinite(l).all()
            n += 1
        assert n == 4

    def test_empty_iterator(self):
        it = _DeviceFeedIterator(iter([]))
        assert list(it) == []


class TestTrainFromDatasetAsync:
    def test_loop_still_prints_and_returns_numpy(self, capsys, tmp_path):
        # minimal in-memory dataset path exercising the async loop
        main, startup, loss = _simple_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)

        class FakeDataset:
            def _iter_feed_dicts(self, drop_last=True):
                rng = np.random.RandomState(0)
                for _ in range(3):
                    yield {"x": rng.randn(4, 3).astype(np.float32)}

        last = exe.train_from_dataset(program=main, dataset=FakeDataset(),
                                      fetch_list=[loss], print_period=2)
        assert isinstance(last[0], np.ndarray)
        out = capsys.readouterr().out
        assert "step 2" in out
