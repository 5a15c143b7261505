"""Multiprocess DataLoader workers with shared-memory batch transport.

The reference feeds training from worker PROCESSES through mmap shared
memory into its blocking queue (ref: python/paddle/fluid/reader.py:113
_reader_process_loop + paddle/fluid/memory/allocation/mmap_allocator.h);
the thread-prefetch loader alone is GIL-bound for Python-heavy sample
pipelines.

Design: worker ``w`` owns batch indices ``w, w+N, ...`` and its OWN
bounded result queue.  The parent always knows which worker produces the
next sequence number, so it pops exactly that worker's queue — global
order is preserved with no reorder buffer, and each queue's bound gives
true per-worker backpressure (a slow worker cannot let the others run
ahead unboundedly).  Batches travel as one ``multiprocessing.
shared_memory`` block each; the parent copies the arrays out ONCE and
unlinks immediately (handing out zero-copy views whose block is later
unlinked is a dangling-pointer footgun, and the memcpy is noise next to
the sample work being parallelized).

Generator datasets (``from_generator(use_multiprocess=True)``) run in
ONE worker: a generator cannot be split across processes without
re-executing it in each (wrong for nondeterministic streams), so the
win there is moving the producer off the training process, as the
reference's single _reader_process does.

Start method: ``fork`` by default (dataset/generator need no pickling —
the reference and torch do the same on Linux).  Workers only run
numpy, so on the CPU backend the forked-JAX hazards don't apply to the
child's work.  A process that holds the TPU runtime must not fork (a
chip belongs to one process; the child inherits the runtime's threads
and device handles), so on a TPU backend ``fork`` is refused — pass
``mp_start_method="spawn"`` with a picklable dataset, or use the
threaded loader (``use_multiprocess=False``), which is what the device
double buffer sits on.
"""

from __future__ import annotations

import multiprocessing as mp
import queue as queue_mod
from multiprocessing import shared_memory
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

_STOP = "__stop__"
_ERROR = "__error__"


def _pack_batch(arrays: Sequence[np.ndarray]) -> Tuple[shared_memory.SharedMemory, list]:
    """Copy arrays into one fresh shm block; returns (block, layout)."""
    arrays = [np.ascontiguousarray(a) for a in arrays]
    total = sum(a.nbytes for a in arrays) or 1
    shm = shared_memory.SharedMemory(create=True, size=total)
    layout = []
    off = 0
    for a in arrays:
        shm.buf[off:off + a.nbytes] = a.tobytes()
        layout.append((str(a.dtype), a.shape, off))
        off += a.nbytes
    return shm, layout


def _unpack_batch(shm: shared_memory.SharedMemory, layout) -> List[np.ndarray]:
    """Copy arrays out of the block (owned by the caller afterwards)."""
    out = []
    for dtype, shape, off in layout:
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        view = np.ndarray(shape, dtype=np.dtype(dtype),
                          buffer=shm.buf[off:off + n])
        out.append(view.copy())
    return out


def _normalize(batch):
    """batch (dict | tuple/list | array) → (arrays, is_dict, keys)."""
    if isinstance(batch, dict):
        keys = list(batch.keys())
        return [np.asarray(batch[k]) for k in keys], True, keys
    if isinstance(batch, (tuple, list)):
        return [np.asarray(a) for a in batch], False, None
    return [np.asarray(batch)], False, None


def _worker_loop(worker_id, num_workers, dataset, index_batches, collate_fn,
                 generator, result_q, quit_ev):
    """Produce this worker's share of batches into ITS queue."""
    try:
        if generator is not None:
            it = (b for b in generator())          # single worker owns all
        else:
            it = ([dataset[j] for j in index_batches[i]]
                  for i in range(worker_id, len(index_batches),
                                 num_workers))
        for raw in it:
            if quit_ev.is_set():
                return
            batch = raw if generator is not None else collate_fn(raw)
            arrays, is_dict, keys = _normalize(batch)
            shm, layout = _pack_batch(arrays)
            shm.close()   # parent unlinks; worker drops its handle
            while not quit_ev.is_set():
                try:
                    result_q.put((shm.name, layout, is_dict, keys),
                                 timeout=0.2)
                    break
                except queue_mod.Full:
                    continue
        result_q.put((_STOP, None, None, None))
    except BaseException as e:   # surface in the parent
        try:
            result_q.put((_ERROR, repr(e), None, None))
        except Exception:
            pass


class MultiprocessIterator:
    """Order-preserving iterator: next batch always comes from worker
    ``next_seq % num_workers`` — no reorder buffer needed."""

    def __init__(self, dataset=None, index_batches=None, collate_fn=None,
                 generator: Optional[Callable] = None, num_workers: int = 2,
                 capacity: int = 8, to_feed=None, mp_start_method="fork"):
        if generator is not None:
            num_workers = 1          # see module docstring
        if mp_start_method == "fork":
            import jax
            if jax.default_backend() == "tpu":
                raise RuntimeError(
                    "multiprocess DataLoader: refusing to fork a process "
                    "that holds the TPU runtime — pass "
                    "mp_start_method='spawn' (picklable dataset) or use "
                    "the threaded loader (use_multiprocess=False)")
        ctx = mp.get_context(mp_start_method)
        per_q = max(2, capacity // max(num_workers, 1))
        self._queues = [ctx.Queue(maxsize=per_q) for _ in range(num_workers)]
        self._quit = ctx.Event()
        self._procs = []
        self._done = [False] * num_workers
        self._next_seq = 0
        self._num_workers = num_workers
        self._to_feed = to_feed or (lambda b: b)
        self._closed = False
        index_batches = (list(index_batches)
                         if index_batches is not None else None)
        for w in range(num_workers):
            p = ctx.Process(
                target=_worker_loop,
                args=(w, num_workers, dataset, index_batches, collate_fn,
                      generator, self._queues[w], self._quit),
                daemon=True)
            p.start()
            self._procs.append(p)

    def __iter__(self):
        return self

    def __next__(self):
        while True:
            w = self._next_seq % self._num_workers
            if self._done[w]:
                # this worker exhausted its share ⇒ all earlier seqs done
                self.close()
                raise StopIteration
            try:
                name, layout, is_dict, keys = self._queues[w].get(
                    timeout=1.0)
            except queue_mod.Empty:
                if not self._procs[w].is_alive():
                    self.close()
                    raise RuntimeError(
                        f"DataLoader worker {w} died without reporting "
                        f"(killed? exitcode={self._procs[w].exitcode})")
                continue
            if name == _STOP:
                self._done[w] = True
                continue
            if name == _ERROR:
                self.close()
                raise RuntimeError(f"DataLoader worker failed: {layout}")
            self._next_seq += 1
            return self._materialize(name, layout, is_dict, keys)

    def _materialize(self, name, layout, is_dict, keys):
        shm = shared_memory.SharedMemory(name=name)
        try:
            arrays = _unpack_batch(shm, layout)
        finally:
            shm.close()
            try:
                shm.unlink()
            except FileNotFoundError:
                pass
        batch = dict(zip(keys, arrays)) if is_dict else tuple(arrays)
        return self._to_feed(batch)

    def close(self):
        if self._closed:
            return
        self._closed = True
        self._quit.set()
        # drain + unlink any blocks still queued
        for q in self._queues:
            while True:
                try:
                    name, *_ = q.get_nowait()
                except (queue_mod.Empty, OSError, ValueError):
                    break
                if name not in (_STOP, _ERROR):
                    try:
                        s = shared_memory.SharedMemory(name=name)
                        s.close()
                        s.unlink()
                    except FileNotFoundError:
                        pass
        for p in self._procs:
            p.join(timeout=2)
            if p.is_alive():
                p.terminate()

    def __del__(self):
        try:
            self.close()
        except BaseException:
            pass
