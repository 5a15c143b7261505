#!/usr/bin/env python
"""The decode worker's ``decode::`` spans against the device's own record,
from one xplane — what ``benchmark/trace_reduce.py`` cannot say until it
keeps the program's span names (ROADMAP I2), and the check of the
in-flight ledger (``DecodeEngine.stats()["device_ns"|"starved_ns"]``)
that needs no ledger.

    python tools/ledger_probe.py <file.xplane.pb>
    python tools/ledger_probe.py --workload <cell> --seed N --seconds S --trace 1

The first form reads a saved trace (any ``jax.profiler.start_trace``
session over a running engine).  The second IS ``benchmark/run.py``'s
run with those arguments: the harness deletes its xplane once reduced,
so the file is read on its way into the reducer (the one seam:
``trace_reduce.load_xplane``) and the result goes out on an earlier line
(``ledger xplane: ...``); the result object is still the last line.

Printed: which plane and line hold the worker's spans; the device's
record of each executable (plane ``/device:TPU:0``, line ``XLA Modules``,
one event a launch) matched to the launch span that dispatched it, so
device seconds by launch kind WITHOUT the ledger; and the device's idle
between those events by the worker phase it fell in and by that phase's
``starved`` attribute.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _overlap(a0, a1, spans):
    """Seconds of [a0, a1] under each of ``spans`` [(key, b0, b1)]."""
    out = {}
    for key, b0, b1 in spans:
        lo, hi = max(a0, b0), min(a1, b1)
        if hi > lo:
            out[key] = out.get(key, 0.0) + (hi - lo) * 1e-9
    return out


def read_xplane(path: str) -> dict:
    """The worker's spans against the device's own record, from one
    xplane: see the module docstring."""
    from jax.profiler import ProfileData
    modules, spans, where = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name == "/device:TPU:0" and line.name == "XLA Modules":
                modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name) for ev in line.events)
            elif plane.name.startswith("/host:"):
                mine = [(ev.name[len("decode::"):], ev.start_ns,
                         ev.start_ns + ev.duration_ns,
                         {k: str(v) for k, v in ev.stats})
                        for ev in line.events
                        if ev.name.startswith("decode::")]
                if mine:
                    where[f"{plane.name} | {line.name}"] = len(mine)
                    spans.extend(mine)
    out = {"decode_spans_in": where, "modules": len(modules)}
    if modules and spans:
        out.update(device_against_spans(modules, spans))
    return out


def device_against_spans(modules, spans) -> dict:
    """``modules``: the device's executables [(start ns, end ns, name)]
    in order; ``spans``: the worker's [(name less ``decode::``, start ns,
    end ns, attributes)]."""
    from paddle_tpu.serving.decode import LAUNCH_KINDS
    out = {}
    launches = sorted((b0, b1, name) for name, b0, b1, _ in spans
                      if name in LAUNCH_KINDS)
    dispatches = sorted((b0, b1) for name, b0, b1, _ in spans
                        if name == "dispatch")
    # the device runs the launches in the order of their dispatches: a
    # module event takes the next dispatch not yet taken, if that opened
    # before the event started (an event dispatched before the trace
    # began takes none), and the dispatch's launch span names its kind
    by_kind, unmatched, nxt = {}, 0, 0
    for m0, m1, _ in modules:
        kind = None
        if nxt < len(dispatches) and dispatches[nxt][0] <= m0:
            d0 = dispatches[nxt][0]
            nxt += 1
            kind = next((name for l0, l1, name in launches
                         if l0 <= d0 <= l1), None)
        if kind is None:
            unmatched += 1
            continue
        n, sec = by_kind.get(kind, (0, 0.0))
        by_kind[kind] = (n + 1, sec + (m1 - m0) * 1e-9)
    out["device_s_by_kind"] = {k: {"launches": n, "seconds": sec}
                               for k, (n, sec) in by_kind.items()}
    by_name = {}
    for m0, m1, name in modules:    # the same seconds by executable
        n, sec = by_name.get(name, (0, 0.0))
        by_name[name] = (n + 1, sec + (m1 - m0) * 1e-9)
    out["device_s_by_module"] = {k: {"launches": n, "seconds": sec}
                                 for k, (n, sec) in by_name.items()}
    out["modules_unmatched"] = unmatched
    phases = [((name, st.get("starved")), b0, b1)
              for name, b0, b1, st in spans
              if name not in LAUNCH_KINDS]
    idle, gaps_s = {}, 0.0
    for (_, end, _), (start, _, _) in zip(modules, modules[1:]):
        if start <= end:
            continue
        gaps_s += (start - end) * 1e-9
        for (name, starved), sec in _overlap(end, start, phases).items():
            key = f"{name}.starved={starved}"
            idle[key] = idle.get(key, 0.0) + sec
    out["device_idle_between_modules_s"] = gaps_s
    out["device_window_s"] = (modules[-1][1] - modules[0][0]) * 1e-9
    out["device_idle_s_by_phase"] = idle
    return out


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if len(argv) == 1:
        print(json.dumps(read_xplane(argv[0])))
        return
    if "--rehearse" in argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark import harness, run as bench
    load_xplane = harness.trace_reduce.load_xplane

    def read_first(path):
        try:
            found = read_xplane(path)
        except Exception as e:      # noqa: BLE001 — the run goes on
            found = {"error": repr(e)}
        harness.say("ledger xplane: " + json.dumps(found))
        return load_xplane(path)

    harness.trace_reduce.load_xplane = read_first
    bench.main(argv)


if __name__ == "__main__":
    main()
