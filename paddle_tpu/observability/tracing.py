"""Structured run-level tracing: spans with attributes on a shared step
axis.

The round-2 profiler (``paddle_tpu/profiler.py``) records flat host
markers — a name and a wall interval.  That is enough for the per-phase
breakdown table but not for *correlation*: nothing ties the
``executor::compile`` that stalled step 4 217 to step 4 217, and a
serving worker's ``serving::run`` spans are indistinguishable from a
training thread's.  This module is the substrate the profiler now sits
on:

* **spans** — RAII markers like ``RecordEvent``, but carrying an
  attribute dict (program uid, cache hit/miss, bucket shape, collective
  kind/bytes) that lands in the Chrome trace's ``args`` column;
* **step ids** — one process-wide monotonically increasing counter,
  bumped once per training step (``PreparedStep.run`` / ``Executor.run``)
  and once per serving micro-batch.  Every span closed while a step is
  current records that ``step_id``, so one merged timeline shows host
  phases, compiles, AOT-cache hits, collective dispatches and
  checkpoint writes on a single correlated axis;
* **thread pinning** — ``step_scope(sid)`` pins the id for one thread:
  the serving worker tags a batch's assemble/dispatch/split spans with
  the *batch's* id even while the global counter advances, and the
  AsyncCheckpointer's writer thread keeps the id of the step that
  snapshotted;
* **flight ring** — independent of the enable flag consumers see, the
  last ``RING_SIZE`` closed spans are kept in a lock-free ring the
  crash flight recorder (``observability/flight.py``) snapshots into
  its diagnostic bundle.

* **the profiler's clock** — whenever a ``jax.profiler`` session is
  running (``profiler.start_profiler(trace_dir=...)`` or a bare
  ``jax.profiler.start_trace``), every span also opens a
  ``jax.profiler.TraceAnnotation`` under its own name, so it lands in
  the xplane's host plane on the device trace's timeline.  This does
  NOT depend on the enable flag below: a device trace taken by someone
  else still sees the program's phases.

Disabled-path cost is the contract the prepared hot loop depends on
(≤5 % of the 10 μs/step PR-2 baseline, asserted by
tests/test_observability.py): ``Span.__enter__``/``__exit__`` reduce to
one module-global bool test plus one ``TraceMe.is_enabled()`` call
(~75 ns; constructing an inactive ``TraceAnnotation`` would cost ~400),
and ``next_step_id`` to one list-slot increment.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _TraceAnnotation

#: True while any jax.profiler session is collecting host events
_session_active = _TraceAnnotation.is_enabled

# (name, start_ns, end_ns, tid, attrs-or-None) — the profiler's event
# buffer lives HERE now; profiler.py re-exports its legacy API over it
_events: List[tuple] = []
_lock = threading.Lock()
_enabled = False

#: last-N closed spans for the flight recorder (deque.append is
#: GIL-atomic — no lock on the hot path)
RING_SIZE = 512
_ring: collections.deque = collections.deque(maxlen=RING_SIZE)

#: tid → thread name, captured at span close so chrome traces can emit
#: thread_name metadata (tools/timeline.py preserves it across merges)
_thread_names: Dict[int, str] = {}

_STEP = [0]                    # process-wide monotonically increasing
_tls = threading.local()       # per-thread pinned step id


def is_enabled() -> bool:
    return _enabled


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def next_step_id() -> int:
    """Advance the run-level step counter (one bump per training step /
    serving micro-batch).  Plain list-slot increment: the id must be
    monotone and cheap, not a synchronization primitive."""
    _STEP[0] += 1
    return _STEP[0]


def current_step_id() -> int:
    sid = getattr(_tls, "step_id", None)
    return _STEP[0] if sid is None else sid


def set_step_id(value: int):
    """Re-seed the counter (resume from a checkpointed step so trace step
    ids line up with the training schedule's)."""
    _STEP[0] = int(value)


@contextlib.contextmanager
def step_scope(step_id: int):
    """Pin ``step_id`` for spans closed on THIS thread — the serving
    worker wraps each micro-batch, the checkpoint writer thread wraps its
    write, so their spans correlate to the step that owns them."""
    old = getattr(_tls, "step_id", None)
    _tls.step_id = step_id
    try:
        yield
    finally:
        _tls.step_id = old


class Span:
    """RAII span.  ``attrs`` (or keyword attributes) land in the trace's
    ``args``; ``step_id`` is attached automatically at close.  Cheap
    no-op while tracing is disabled and no profiler session runs — one
    bool test and one ``is_enabled()`` per enter, two tests per exit."""

    __slots__ = ("name", "attrs", "_start", "_ann")

    def __init__(self, name: str, attrs: Optional[Dict[str, Any]] = None,
                 **kw):
        self.name = name
        if kw:
            attrs = dict(attrs) if attrs else {}
            attrs.update(kw)
        self.attrs = attrs
        self._start = None
        self._ann = None

    @property
    def recording(self) -> bool:
        """Whether anything keeps this (entered) span — lets a caller
        skip building an expensive attribute nobody will read."""
        return self._start is not None or self._ann is not None

    def set(self, **kw):
        """Attach attributes discovered mid-span (e.g. cache hit/miss)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(kw)
        if self._ann is not None:
            self._ann.set_metadata(**kw)
        return self

    def __enter__(self):
        if _enabled:
            self._start = time.perf_counter_ns()
        if _session_active():
            self._ann = _TraceAnnotation(
                self.name, step_id=current_step_id(), **(self.attrs or {}))
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        if self._start is not None:
            end = time.perf_counter_ns()
            tid = threading.get_ident()
            attrs = dict(self.attrs) if self.attrs else {}
            attrs.setdefault("step_id", current_step_id())
            rec = (self.name, self._start, end, tid, attrs)
            if tid not in _thread_names:
                _thread_names[tid] = threading.current_thread().name
            with _lock:
                _events.append(rec)
            _ring.append(rec)
        return False


def span(name: str, **attrs) -> Span:
    return Span(name, attrs or None)


@contextlib.contextmanager
def traced(name: str, **attrs):
    with Span(name, attrs or None):
        yield


def get_events() -> List[tuple]:
    with _lock:
        return list(_events)


def clear_events():
    with _lock:
        _events.clear()


def ring_snapshot() -> List[tuple]:
    """Copy of the last-N span ring (newest last) — the flight
    recorder's span section."""
    return list(_ring)


def thread_names() -> Dict[int, str]:
    return dict(_thread_names)


__all__ = ["Span", "span", "traced", "is_enabled", "enable", "disable",
           "next_step_id", "current_step_id", "set_step_id", "step_scope",
           "get_events", "clear_events", "ring_snapshot", "thread_names",
           "RING_SIZE"]
