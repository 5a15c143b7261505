"""Profiler (ref: platform/profiler.h:201-211 RecordEvent/Enable/Disable,
python/paddle/fluid/profiler.py context managers, tools/timeline.py chrome
trace output).

Host side: ``RecordEvent`` RAII markers collected into an in-process event
buffer; ``stop_profiler`` prints the reference-style aggregated table
(calls/total/min/max/avg per event name) and can dump a Chrome trace JSON
readable at chrome://tracing — the reference needs tools/timeline.py to
convert its proto, here the trace is written directly.

Since PR 9 the buffer and the enable flag live in
``paddle_tpu.observability.tracing``: every marker is a structured SPAN
carrying an attribute dict and the run-level ``step_id``, so the Chrome
trace correlates host phases, compiles, cache hits, collective dispatches
and checkpoint writes on one step axis (``args.step_id`` per event).
This module keeps the reference-shaped API on top.

Device side: the reference uses a CUPTI DeviceTracer; the TPU analog is
jax.profiler (XPlane/TensorBoard).  ``start_profiler`` forwards to
``jax.profiler.start_trace`` when a trace dir is given."""

from __future__ import annotations

import contextlib
import json
from typing import List, Optional

from .observability import tracing
from .observability.tracing import Span as RecordEvent   # noqa: F401 — API

_jax_trace_dir: Optional[str] = None
_tracer_option: str = "Default"

#: reference tracer options (fluid/profiler.py): Default = framework
#: markers only; OpDetail/AllOpDetail additionally keep per-op spans the
#: collective/compile layers emit at trace time
TRACER_OPTIONS = ("Default", "OpDetail", "AllOpDetail")


def is_profiler_enabled() -> bool:
    return tracing.is_enabled()


def tracer_option() -> str:
    return _tracer_option


@contextlib.contextmanager
def record_event(name: str, **attrs):
    with RecordEvent(name, attrs or None):
        yield


def reset_profiler():
    """ref: fluid/profiler.py reset_profiler."""
    tracing.clear_events()


def start_profiler(state: str = "All", tracer_option: str = "Default",
                   trace_dir: Optional[str] = None):
    """ref: fluid/profiler.py start_profiler.  ``state`` in
    {CPU, GPU, All} — device states additionally start a jax.profiler trace
    when ``trace_dir`` is given (TensorBoard XPlane, the CUPTI analog)."""
    global _jax_trace_dir, _tracer_option
    if state not in ("CPU", "GPU", "All"):
        raise ValueError("state must be 'CPU', 'GPU' or 'All'")
    if tracer_option not in TRACER_OPTIONS:
        raise ValueError(f"tracer_option must be one of {TRACER_OPTIONS}, "
                         f"got {tracer_option!r}")
    _tracer_option = tracer_option
    tracing.enable()
    if trace_dir and state in ("GPU", "All"):
        import jax
        try:
            jax.profiler.start_trace(trace_dir)
            _jax_trace_dir = trace_dir
        except Exception:
            _jax_trace_dir = None   # tracing unsupported on this backend


def stop_profiler(sorted_key: str = "total",
                  profile_path: Optional[str] = None):
    """ref: fluid/profiler.py stop_profiler — prints the aggregated event
    table; writes a Chrome trace JSON to ``profile_path`` if given.

    State restoration is exception-safe: a raising
    ``jax.profiler.stop_trace`` (backend died mid-trace) still clears
    ``_jax_trace_dir`` and the enabled flag, so the next
    ``start_profiler`` starts clean instead of double-stopping."""
    global _jax_trace_dir
    tracing.disable()
    if _jax_trace_dir is not None:
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        finally:
            _jax_trace_dir = None
    events = tracing.get_events()
    if profile_path:
        save_chrome_trace(profile_path, events)
    _print_summary(events, sorted_key)
    return events


def save_chrome_trace(path: str, events=None):
    """Chrome trace (tools/timeline.py input format): one ``X`` event per
    span with its attributes (incl. ``step_id``) under ``args``, plus
    ``thread_name`` metadata per tid so merged multi-process traces keep
    readable lanes."""
    if events is None:
        events = tracing.get_events()
    trace_events = [
        {"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
         "args": {"name": tname}}
        for tid, tname in sorted(tracing.thread_names().items())]
    for ev in events:
        name, start, end, tid = ev[0], ev[1], ev[2], ev[3]
        attrs = ev[4] if len(ev) > 4 else None
        rec = {"name": name, "cat": "host", "ph": "X",
               "ts": start / 1e3,                 # chrome wants microseconds
               "dur": (end - start) / 1e3,
               "pid": 0, "tid": tid}
        if attrs:
            rec["args"] = attrs
        trace_events.append(rec)
    with open(path, "w") as f:
        json.dump({"traceEvents": trace_events}, f, default=str)


def _print_summary(events, sorted_key):
    agg = {}
    for ev in events:
        name, start, end = ev[0], ev[1], ev[2]
        ms = (end - start) / 1e6
        c = agg.setdefault(name, [0, 0.0, float("inf"), 0.0])
        c[0] += 1
        c[1] += ms
        c[2] = min(c[2], ms)
        c[3] = max(c[3], ms)
    keyfn = {"total": lambda kv: -kv[1][1], "calls": lambda kv: -kv[1][0],
             "max": lambda kv: -kv[1][3], "min": lambda kv: kv[1][2],
             "ave": lambda kv: -(kv[1][1] / kv[1][0])}.get(
                 sorted_key, lambda kv: -kv[1][1])
    rows = sorted(agg.items(), key=keyfn)
    if not rows:
        return
    print(f"{'Event':<40}{'Calls':>8}{'Total(ms)':>12}{'Min(ms)':>10}"
          f"{'Max(ms)':>10}{'Ave(ms)':>10}")
    for name, (calls, total, mn, mx) in rows:
        print(f"{name:<40}{calls:>8}{total:>12.3f}{mn:>10.3f}"
              f"{mx:>10.3f}{total / calls:>10.3f}")


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str = "total",
             profile_path: Optional[str] = None,
             trace_dir: Optional[str] = None,
             tracer_option: str = "Default"):
    """ref: fluid/profiler.py profiler context manager.  ``tracer_option``
    is forwarded to :func:`start_profiler` (it used to be silently
    dropped)."""
    start_profiler(state, tracer_option=tracer_option,
                   trace_dir=trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


def get_events():
    return tracing.get_events()


# ---------------------------------------------------------------------------
# prepared-executor per-step breakdown
# ---------------------------------------------------------------------------

# the four host-side phases of one prepared train step (PreparedStep.run
# emits these markers): waiting on the input pipeline, python+jit dispatch,
# blocking on device results (backpressure + FetchHandle reads), and the
# explicit scope write-back
PREPARED_PHASES = ("prepared::feed_wait", "prepared::dispatch",
                   "prepared::fetch_sync", "prepared::scope_sync")

# the host-side phases of one serving micro-batch (ServingEngine's worker
# emits these): waiting for the batch window to close, padding/assembly
# into the bucket shape (``serving::pack`` is the ragged token-packing
# assembly of the packing mode), the predictor dispatch, and splitting
# fetches back per request
SERVING_PHASES = ("serving::wait", "serving::pad", "serving::pack",
                  "serving::run", "serving::split")

# the persistent AOT executable cache's host phases (framework/
# aot_cache.py): deserializing a stored executable vs serializing a
# fresh compile to disk
AOT_CACHE_PHASES = ("aot_cache::load", "aot_cache::save")

# the async checkpointer's phases (io.py): the synchronous device→host
# snapshot (a training-thread stall the telemetry recorder attributes)
# and the background write
CHECKPOINT_PHASES = ("checkpoint::snapshot", "checkpoint::write")


def step_breakdown(events=None):
    """Aggregate the prepared fast path's and the serving engine's
    per-step markers into ``{phase: {"calls", "total_ms", "avg_us"}}`` —
    the host-side story of a training step / serving micro-batch (where
    did the host time go: feed-wait / dispatch / fetch-sync / scope-sync,
    batch-wait / pad / run / split), complementing the event table with a
    per-phase view the reference exposes through its DeviceTracer
    sections.  The extra ``"feed_cache"`` entry carries the
    _FeedDeviceCache hit/miss counters and its live
    ``flag("feed_cache_size")`` capacity."""
    if events is None:
        events = tracing.get_events()
    phases = PREPARED_PHASES + SERVING_PHASES + AOT_CACHE_PHASES + \
        CHECKPOINT_PHASES
    out = {}
    for ev in events:
        name, start, end = ev[0], ev[1], ev[2]
        if name in phases:
            rec = out.setdefault(name, {"calls": 0, "total_ms": 0.0})
            rec["calls"] += 1
            rec["total_ms"] += (end - start) / 1e6
    for rec in out.values():
        rec["avg_us"] = rec["total_ms"] * 1e3 / rec["calls"]
    from .monitor import stat
    from .flags import flag
    out["feed_cache"] = {"hits": stat("feed_cache_hit").get(),
                         "misses": stat("feed_cache_miss").get(),
                         "capacity": int(flag("feed_cache_size"))}
    # persistent AOT executable cache counters (framework/aot_cache.py):
    # a warm serving restart shows hits == its bucket grid and ZERO
    # fresh executor compiles
    from .framework.aot_cache import cache_stats
    out["aot_cache"] = dict(cache_stats())
    out["aot_cache"]["dir"] = str(flag("aot_cache_dir") or "")
    return out


# ---------------------------------------------------------------------------
# serving-engine stats (ServingEngine registers itself here)
# ---------------------------------------------------------------------------

import weakref as _weakref

_serving_engines: List = []   # weakrefs to live ServingEngines


def register_serving_engine(engine):
    """Expose a serving engine's ``stats()`` (``ServingEngine``,
    ``DecodeEngine``) through :func:`serving_stats`, which ``/metrics``
    and ``metrics_snapshot()`` pull at scrape time — called by the
    engine constructor."""
    _serving_engines.append(_weakref.ref(engine))


def serving_stats():
    """Snapshot of every live serving engine's counters (QPS, p50/p99
    latency, padding-waste ratio, compile count, batch-size histogram) —
    the profiler-side view of the serving tier."""
    out = []
    dead = []
    for ref in _serving_engines:
        engine = ref()
        if engine is None:
            dead.append(ref)
            continue
        out.append(engine.stats())
    for ref in dead:
        _serving_engines.remove(ref)
    return out
