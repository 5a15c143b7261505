"""Run-level telemetry recorder: an append-only JSONL stream per run.

Every perf PR so far proved its win with a bespoke one-shot artifact;
this is the continuous version — cheap enough to leave on, structured
enough to query.  One :class:`TelemetryRecorder` owns one output file
and writes three record kinds (``"record"`` field):

* ``header`` (first line) — schema version, run id, device identity
  and peak FLOPs, the program's STATIC context priced once: GEMM FLOPs
  per step (op-spec ``flops`` channel, ``observability/flops.py``),
  per-device peak-HBM estimate (framework/memory_analysis.py),
  per-step collective wire/logical bytes (``collective_wire_summary``);
* ``step`` (one line per training step) — wall time, tokens/examples,
  **measured MFU** (static FLOPs ÷ wall ÷ device peak), **goodput**
  (1 − attributable stall fraction: feed-wait + compile + checkpoint
  snapshot time inside the step interval), loss value + finiteness,
  grad norm, per-step collective wire bytes, live HBM headroom vs the
  static estimate (when the backend exposes ``memory_stats``), and the
  step's compile/AOT-cache counter deltas;
* ``summary`` (last line, on ``close()``) — step count, wall/MFU/
  goodput aggregates.

A non-finite loss triggers the crash flight recorder
(``observability/flight.py``) at the offending step, so the JSONL tail
and the diagnostic bundle cross-reference the same ``step_id``.

Schema is versioned (``SCHEMA``); :func:`validate_jsonl` is the
contract checker tier-1 asserts.
"""

from __future__ import annotations

import json
import math
import os
import time
import uuid
from typing import Any, Dict, Iterable, Optional

import numpy as np

from . import flight, flops, tracing

SCHEMA = "paddle_tpu.telemetry/1"

#: monitor counters diffed per step (ns counters are bumped by the
#: executor / AsyncCheckpointer instrumentation)
_STALL_COUNTERS = ("executor_compile_ns", "checkpoint_snapshot_ns")
_DELTA_COUNTERS = ("executor_compile_count", "aot_cache_hit",
                   "aot_cache_miss")


def _fnum(v):
    if v is None:
        return None
    try:
        f = float(np.asarray(v).reshape(()))
    except Exception:
        return None
    return f


class TelemetryRecorder:
    """Append-only per-run JSONL telemetry stream (see module docstring).

    ``program``/``feed_shapes``/``fetch_names`` price the static context
    (FLOPs, peak HBM, wire bytes); pass ``flops_per_step`` /
    ``peak_flops`` to override.  ``tokens_per_step`` /
    ``examples_per_step`` are defaults for steps that don't pass their
    own.  ``attach(prepared)`` lets the recorder diff the prepared
    step's feed-wait/fetch-wait stats into the goodput accounting."""

    def __init__(self, path: str, program=None, feed_shapes=None,
                 fetch_names: Iterable[str] = (),
                 run_id: Optional[str] = None,
                 tokens_per_step: Optional[float] = None,
                 examples_per_step: Optional[float] = None,
                 mesh_axes: Optional[Dict[str, int]] = None,
                 flops_per_step: Optional[float] = None,
                 peak_flops: Optional[float] = None):
        self.path = str(path)
        self.run_id = run_id or uuid.uuid4().hex[:12]
        self._tokens_default = tokens_per_step
        self._examples_default = examples_per_step
        self._prepared = None
        self._prev_prepared: Dict[str, int] = {}
        self._prev_counters: Dict[str, int] = {}
        self._steps = 0
        self._wall_ns_total = 0
        self._mfu_sum = 0.0
        self._goodput_sum = 0.0
        self._nonfinite_steps = 0
        self._closed = False

        dev = flops.device_info()
        self.peak_flops = float(peak_flops or dev["peak_flops"])
        static: Dict[str, Any] = {}
        if flops_per_step is not None:
            static["flops_per_step"] = float(flops_per_step)
            static["flops_source"] = "caller"
        elif program is not None:
            try:
                est = flops.estimate_step_flops(
                    program, feed_shapes=feed_shapes,
                    fetch_names=list(fetch_names))
                static["flops_per_step"] = est["total_flops"]
                static["flops_fwd"] = est["fwd_flops"]
                static["flops_source"] = "op_spec"
                static["flops_unpriced_ops"] = est["unpriced"]
            except Exception as e:   # pricing gap ≠ telemetry outage
                static["flops_per_step"] = None
                static["flops_error"] = str(e)
        else:
            static["flops_per_step"] = None
        if program is not None:
            from ..framework.memory_analysis import (analyze_memory,
                                                     collective_wire_summary)
            try:
                mem = analyze_memory(program, feed_shapes=feed_shapes,
                                     fetch_names=list(fetch_names),
                                     mesh_axes=mesh_axes)
                static["peak_hbm_bytes"] = int(mem.peak_bytes)
                static["state_bytes"] = int(mem.state_bytes)
            except Exception as e:
                static["peak_hbm_bytes"] = None
                static["mem_error"] = str(e)
            try:
                wire = collective_wire_summary(
                    program, feed_shapes=feed_shapes,
                    fetch_names=list(fetch_names), mesh_axes=mesh_axes)
                static["wire_bytes_per_step"] = int(wire["wire_bytes"])
                static["logical_bytes_per_step"] = \
                    int(wire["logical_bytes"])
                static["grad_sync_wire_bytes"] = int(
                    wire.get("grad_sync_wire_bytes", 0))
                static["forward_wire_bytes"] = int(
                    wire.get("forward_wire_bytes", 0))
                # static exposed-comm roofline (the overlap scheduler's
                # cost model): collective wire time not coverable by
                # compute — each step reports the fraction of its
                # measured wall this exposure accounts for, so overlap
                # wins show up in MFU/goodput, not just in the census
                from ..framework.memory_analysis import exposed_comm_model
                blk = program.global_block()
                overlap = any(op.attrs.get("_overlap") for op in blk.ops)
                has_bw = any(op.type == "backward" for op in blk.ops)
                ndev = 1
                for sz in (mesh_axes or {}).values():
                    ndev *= max(int(sz), 1)
                model = exposed_comm_model(
                    wire, static.get("flops_per_step") or 0.0,
                    num_devices=ndev, overlap=overlap,
                    has_backward=has_bw, peak_flops=self.peak_flops)
                static["overlap_grad_sync"] = bool(overlap)
                static["exposed_comm_s_per_step"] = \
                    model["exposed_comm_s"]
                static["exposed_comm_model"] = {
                    k: model[k] for k in
                    ("wire_time_s", "overlappable_compute_s",
                     "hidden_s", "ici_gbps")}
            except Exception as e:
                static["wire_bytes_per_step"] = None
                static["wire_error"] = str(e)
        self.static = static
        self.flops_per_step = static.get("flops_per_step")
        self._program = program
        self._pipelined = bool(program is not None and any(
            op.type == "backward" and int(op.attrs.get("pipe_stages")
                                          or 1) > 1
            for op in program.global_block().ops))

        header = {
            "record": "header", "schema": SCHEMA, "run_id": self.run_id,
            "time": time.time(), "device": dev,
            "peak_flops": self.peak_flops, "static": static,
        }
        if program is not None:
            header["program"] = {"uid": getattr(program, "_uid", None),
                                 "version": getattr(program, "_version",
                                                    None)}
        if tokens_per_step is not None:
            header["tokens_per_step"] = tokens_per_step
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(self.path, "a")
        self._write(header)
        self._snap_counters()

    # -- wiring -----------------------------------------------------------
    def attach(self, prepared):
        """Diff ``prepared.stats`` (feed-wait / fetch-wait / blocking
        syncs) into each step record's stall accounting."""
        self._prepared = prepared
        self._prev_prepared = dict(prepared.stats)
        return self

    def _write(self, rec: Dict[str, Any]):
        self._f.write(json.dumps(rec, default=str) + "\n")
        self._f.flush()

    def _snap_counters(self):
        from ..monitor import stat
        self._prev_counters = {
            n: stat(n).get() for n in _STALL_COUNTERS + _DELTA_COUNTERS}

    # -- per-step ---------------------------------------------------------
    def step(self, tokens=None, examples=None):
        """Context manager timing one training step::

            with rec.step(tokens=batch*seq) as st:
                handles = prepared.run(feed)
                st.loss = handles[0]       # optional: recorded + checked
        """
        return _StepTimer(self, tokens, examples)

    def record_step(self, wall_ns: float, step_id: Optional[int] = None,
                    tokens=None, examples=None, loss=None, grad_norm=None,
                    extra: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
        """Record one step observed to take ``wall_ns``.  Returns the
        record written (with derived MFU/goodput)."""
        from ..monitor import stat
        wall_ns = max(float(wall_ns), 1.0)
        sid = tracing.current_step_id() if step_id is None else step_id
        now_counters = {
            n: stat(n).get() for n in _STALL_COUNTERS + _DELTA_COUNTERS}
        deltas = {n: now_counters[n] - self._prev_counters.get(n, 0)
                  for n in now_counters}
        self._prev_counters = now_counters
        stalls_ns = {
            "compile": deltas["executor_compile_ns"],
            "checkpoint": deltas["checkpoint_snapshot_ns"],
            "feed_wait": 0,
        }
        if self._prepared is not None:
            cur = dict(self._prepared.stats)
            stalls_ns["feed_wait"] = cur.get("feed_wait_ns", 0) - \
                self._prev_prepared.get("feed_wait_ns", 0)
            stalls_ns["fetch_wait"] = cur.get("fetch_wait_ns", 0) - \
                self._prev_prepared.get("fetch_wait_ns", 0)
            self._prev_prepared = cur
        stall_total = sum(max(v, 0) for k, v in stalls_ns.items()
                          if k != "fetch_wait")
        goodput = max(0.0, min(1.0, 1.0 - stall_total / wall_ns))

        tokens = tokens if tokens is not None else self._tokens_default
        examples = examples if examples is not None \
            else self._examples_default
        loss_f = _fnum(loss)
        loss_finite = None if loss_f is None else bool(math.isfinite(loss_f))
        mfu = None
        if self.flops_per_step:
            mfu = self.flops_per_step / (wall_ns / 1e9) / self.peak_flops
        rec = {
            "record": "step", "step": sid,
            "wall_ms": round(wall_ns / 1e6, 4),
            "tokens": tokens, "examples": examples,
            "mfu": mfu, "goodput": round(goodput, 6),
            "stalls_ms": {k: round(v / 1e6, 4)
                          for k, v in stalls_ns.items()},
            "loss": loss_f, "loss_finite": loss_finite,
            "grad_norm": _fnum(grad_norm),
            "wire_bytes": self.static.get("wire_bytes_per_step"),
            "compiles": deltas["executor_compile_count"],
            "aot_cache": {"hits": deltas["aot_cache_hit"],
                          "misses": deltas["aot_cache_miss"]},
        }
        # guardrail facts (framework/guardrails.py): when the attached
        # prepared loop runs with guard_nonfinite, each step records
        # whether it was skipped and the live loss scale — the JSONL is
        # the run's recovery ledger, not just its perf ledger
        ginfo = getattr(self._prepared, "guard_info", None)
        if ginfo is not None:
            gs = ginfo(sync=False)
            if gs.get("step") is not None:
                rec["skipped"] = bool(gs["last_skipped"])
                rec["skipped_total"] = int(gs["skipped_total"])
                if gs.get("loss_scale") is not None:
                    rec["loss_scale"] = float(gs["loss_scale"])
        # pipeline-schedule facts (executor scheduled-scan census): the
        # per-step bubble fraction of the schedule the step ACTUALLY
        # ran — exact per-tick accounting from the lowering's consumed
        # tables, so a telemetry reader can line perf regressions up
        # against schedule choice without reopening the plan artifact
        if self._pipelined:
            try:
                from ..framework.executor import last_pipeline_report
                prep = last_pipeline_report()
            except Exception:
                prep = {}
            if prep.get("bubble_frac") is not None:
                rec["bubble_frac"] = round(float(prep["bubble_frac"]), 6)
                rec["pipe_schedule"] = prep.get("family")
        exposed_s = self.static.get("exposed_comm_s_per_step")
        if exposed_s is not None:
            # share of this step's measured wall the statically-priced
            # exposed collective time accounts for (0 = fully hidden)
            rec["exposed_comm_ms"] = round(exposed_s * 1e3, 4)
            rec["exposed_comm_frac"] = round(
                max(0.0, min(1.0, exposed_s * 1e9 / wall_ns)), 6)
        headroom = self._hbm_headroom()
        if headroom is not None:
            rec["hbm_headroom_bytes"] = headroom
        if extra:
            rec.update(extra)
        self._write(rec)
        self._steps += 1
        self._wall_ns_total += wall_ns
        if mfu is not None:
            self._mfu_sum += mfu
        self._goodput_sum += goodput
        from . import metrics
        metrics.histogram("telemetry_step_wall_seconds",
                          run=self.run_id).observe(wall_ns / 1e9)
        if mfu is not None:
            metrics.gauge("telemetry_mfu", run=self.run_id).set(mfu)
        metrics.gauge("telemetry_goodput", run=self.run_id).set(goodput)
        if loss_finite is False:
            self._nonfinite_steps += 1
            bundle = flight.dump(
                "non_finite_loss", program=self._program,
                extra={"loss": loss_f, "telemetry_path": self.path,
                       "step": sid})
            rec["flight_bundle"] = bundle
            self._write({"record": "event", "kind": "non_finite_loss",
                         "step": sid, "flight_bundle": bundle})
        return rec

    def _hbm_headroom(self) -> Optional[int]:
        """bytes_limit − static peak estimate, when the backend exposes
        live memory stats (TPU/GPU; CPU returns None)."""
        peak = self.static.get("peak_hbm_bytes")
        if not peak:
            return None
        try:
            import jax
            ms = jax.devices()[0].memory_stats()
        except Exception:
            return None
        if not ms or "bytes_limit" not in ms:
            return None
        return int(ms["bytes_limit"]) - int(peak)

    # -- lifecycle --------------------------------------------------------
    def close(self) -> Dict[str, Any]:
        if self._closed:
            return {}
        self._closed = True
        steps = self._steps
        summary = {
            "record": "summary", "steps": steps,
            "wall_ms_total": round(self._wall_ns_total / 1e6, 3),
            "wall_ms_mean": round(self._wall_ns_total / 1e6 / steps, 4)
            if steps else None,
            "mfu_mean": (self._mfu_sum / steps)
            if steps and self.flops_per_step else None,
            "goodput_mean": (self._goodput_sum / steps) if steps else None,
            "nonfinite_steps": self._nonfinite_steps,
        }
        self._write(summary)
        self._f.close()
        return summary

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class _StepTimer:
    __slots__ = ("_rec", "_tokens", "_examples", "_t0", "loss",
                 "grad_norm", "record")

    def __init__(self, rec, tokens, examples):
        self._rec = rec
        self._tokens = tokens
        self._examples = examples
        self.loss = None
        self.grad_norm = None
        self.record = None

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = time.perf_counter_ns() - self._t0
        if exc is None:
            self.record = self._rec.record_step(
                wall, tokens=self._tokens, examples=self._examples,
                loss=self.loss, grad_norm=self.grad_norm)
        return False


def validate_jsonl(path: str) -> Dict[str, Any]:
    """Schema-check one telemetry stream; raises ValueError on the first
    violation and returns aggregate facts otherwise (the contract
    tier-1 asserts)."""
    with open(path) as f:
        lines = [json.loads(l) for l in f if l.strip()]
    if not lines:
        raise ValueError("empty telemetry stream")
    header = lines[0]
    if header.get("record") != "header" or header.get("schema") != SCHEMA:
        raise ValueError(f"first record must be a {SCHEMA} header, got "
                         f"{header.get('record')!r}/"
                         f"{header.get('schema')!r}")
    if not isinstance(header.get("peak_flops"), (int, float)) or \
            header["peak_flops"] <= 0:
        raise ValueError("header.peak_flops must be > 0")
    steps = [l for l in lines if l.get("record") == "step"]
    mfus = []
    for s in steps:
        for field in ("step", "wall_ms", "goodput", "stalls_ms"):
            if field not in s:
                raise ValueError(f"step record missing {field!r}: {s}")
        if s["wall_ms"] <= 0:
            raise ValueError(f"non-positive wall_ms: {s}")
        if not (0.0 <= s["goodput"] <= 1.0):
            raise ValueError(f"goodput out of [0,1]: {s}")
        if s.get("mfu") is not None:
            if not (0.0 < s["mfu"] <= 1.0):
                raise ValueError(f"mfu out of (0,1]: {s}")
            mfus.append(s["mfu"])
        if s.get("exposed_comm_frac") is not None and \
                not (0.0 <= s["exposed_comm_frac"] <= 1.0):
            raise ValueError(f"exposed_comm_frac out of [0,1]: {s}")
        if s.get("bubble_frac") is not None and \
                not (0.0 <= s["bubble_frac"] <= 1.0):
            raise ValueError(f"bubble_frac out of [0,1]: {s}")
        if "skipped" in s and not isinstance(s["skipped"], bool):
            raise ValueError(f"skipped must be a bool: {s}")
        if s.get("loss_scale") is not None and \
                not (isinstance(s["loss_scale"], (int, float))
                     and s["loss_scale"] >= 1.0):
            raise ValueError(f"loss_scale must be >= 1.0: {s}")
    sids = [s["step"] for s in steps]
    if sids != sorted(sids):
        raise ValueError("step ids are not monotonically increasing")
    summaries = [l for l in lines if l.get("record") == "summary"]
    return {"header": header, "steps": len(steps),
            "mfu_mean": (sum(mfus) / len(mfus)) if mfus else None,
            "nonfinite_steps": sum(
                1 for s in steps if s.get("loss_finite") is False),
            "summary": summaries[-1] if summaries else None}


__all__ = ["TelemetryRecorder", "validate_jsonl", "SCHEMA"]
