"""From a profiler trace to numbers: device busy and idle time, the
device operations that took most time, the longest idle gaps and what the
host was doing in them, and all-reduce time no other operation covers.

The reduction works on a plain structure, so the tests can feed it a
small recorded trace kept as JSON::

    {"devices": {"0": [[name, start_s, dur_s], ...], ...},   # op events
     "host": [[name, start_s, dur_s], ...]}                  # host spans

``load_xplane`` builds that structure from the ``.xplane.pb`` the JAX
profiler writes, with nothing but JAX.  Device events come from the
``XLA Ops`` line of each ``/device:TPU:n`` plane; host spans are the
benchmark's own ``jax.profiler.TraceAnnotation``s (names starting
``bench::``) — the program's spans do not reach the profiler yet, so a
gap inside the engine's worker thread stays "unattributed".
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Sequence  # [name, start_s, dur_s]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench::"
#: gaps shorter than this are launch latency between back-to-back ops,
#: summed under one name
SHORT_GAP_S = 50e-6
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"all-to-all|collective-permute)")


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            events = out["devices"].setdefault(m.group(1), [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    events.extend([ev.name, ev.start_ns * 1e-9,
                                   ev.duration_ns * 1e-9]
                                  for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9]
                    for ev in line.events
                    if ev.name.startswith(HOST_SPAN_PREFIX))
    for events in out["devices"].values():
        events.sort(key=lambda e: (e[1], -e[2]))
    out["host"].sort(key=lambda e: (e[1], -e[2]))
    return out


def describe_xplane(path: str, per_line: int = 3) -> List[str]:
    """Planes, lines and a few events of each, for reading a trace by
    hand (printed on earlier lines of a traced run)."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            names = [e.name[:60] for e in events[:per_line]]
            stats = [(k, str(v)[:40]) for k, v in events[0].stats][:8] \
                if events else []
            rows.append(f"{plane.name} | {line.name} | {len(events)} "
                        f"events | {names} | first event's stats {stats}")
    return rows


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------

def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(events: Sequence[Event], t0: float, t1: float
          ) -> List[Tuple[float, float]]:
    out = []
    for _, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((a, b))
    return out


def window_of(trace: dict) -> Tuple[float, float]:
    """The traced window as the device saw it: first op start to last op
    end over all devices."""
    starts = [e[1] for ev in trace["devices"].values() for e in ev]
    ends = [e[1] + e[2] for ev in trace["devices"].values() for e in ev]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_seconds(events: Sequence[Event], t0: float, t1: float) -> float:
    """Seconds of [t0, t1] in which at least one operation ran: the
    union of the events' intervals, so nested events count once."""
    return sum(b - a for a, b in _union(_clip(events, t0, t1)))


def idle_gaps(events: Sequence[Event], t0: float, t1: float
              ) -> List[Tuple[float, float]]:
    busy = _union(_clip(events, t0, t1))
    gaps, at = [], t0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = b
    if t1 > at:
        gaps.append((at, t1))
    return gaps


# ---------------------------------------------------------------------------
# operation classes and self time
# ---------------------------------------------------------------------------

def op_class(name: str) -> str:
    """The operation's kind without its instance number:
    ``%fusion.123`` -> ``fusion``, ``all-reduce-done.4`` ->
    ``all-reduce-done``; a Pallas kernel keeps its kernel name."""
    name = name.lstrip("%").split(" ")[0].split("=")[0]
    return re.sub(r"[.\d]+$", "", name) or name


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """(name, self seconds) per event: its duration minus what its
    children — events nested inside it on the same line, as the body of
    a ``while`` is — cover.  Needs events sorted by (start, -duration)."""
    out: List[List] = []
    stack: List[int] = []
    for name, start, dur in events:
        end = start + dur
        while stack and start >= out[stack[-1]][2] - 1e-12:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(dur, out[stack[-1]][2] - start)
        out.append([name, dur, end])
        stack.append(len(out) - 1)
    return [(n, max(t, 0.0)) for n, t, _ in out]


def top_ops(events: Sequence[Event], n: int = 10) -> List[List]:
    total: Dict[str, float] = {}
    for name, t in self_times(events):
        cls = op_class(name)
        total[cls] = total.get(cls, 0.0) + t
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]


def top_gaps(events: Sequence[Event], host: Sequence[Event], t0: float,
             t1: float, n: int = 10) -> List[List]:
    """Idle seconds by what the host was doing when each gap began: the
    innermost ``bench::`` span open at that instant, else
    "unattributed"."""
    total: Dict[str, float] = {}
    for a, b in idle_gaps(events, t0, t1):
        if b - a < SHORT_GAP_S:
            key = "short_gaps"
        else:
            key = "unattributed"
            best = None
            for name, start, dur in host:
                if start <= a < start + dur and \
                        (best is None or dur < best[1]):
                    best = (name, dur)
            if best is not None:
                key = best[0]
        total[key] = total.get(key, 0.0) + (b - a)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]


def exposed_collective_seconds(events: Sequence[Event], t0: float, t1: float,
                               pattern=COLLECTIVE) -> float:
    """Seconds of [t0, t1] in which a collective operation ran on this
    device and no other operation did: time the step spends waiting for
    the exchange, not hidden behind compute.  An enclosing ``while`` or
    ``call`` is not "another operation" — only self time counts."""
    coll = [e for e in events if pattern.match(op_class(e[0]))]
    if not coll:
        return 0.0
    # other work = self-time intervals are not available per event, so
    # take leaf events (no child) that are not collectives
    leaves = _leaves(events)
    other = _union(_clip([e for e in leaves
                          if not pattern.match(op_class(e[0]))], t0, t1))
    exposed = 0.0
    for a, b in _union(_clip(coll, t0, t1)):
        covered = sum(min(b, d) - max(a, c) for c, d in other
                      if min(b, d) > max(a, c))
        exposed += (b - a) - covered
    return exposed


def _leaves(events: Sequence[Event]) -> List[Event]:
    out = []
    ev = list(events)
    for i, (name, start, dur) in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt[1] < start + dur - 1e-12 \
                and nxt[1] + nxt[2] <= start + dur + 1e-9 and dur > 0:
            continue        # has a child
        out.append((name, start, dur))
    return out


# ---------------------------------------------------------------------------
# the summary a run reports
# ---------------------------------------------------------------------------

def summarize(trace: dict, chips: int) -> dict:
    """busy_s / window_s averaged over the chips used, device 0's top
    operations and idle gaps, and the exposed collective time."""
    t0, t1 = window_of(trace)
    ids = sorted(trace["devices"], key=int)[:chips]
    ids = [i for i in ids if trace["devices"][i]]
    if not ids:
        raise ValueError("no operation ran on any device in the trace")
    busy = [busy_seconds(trace["devices"][i], t0, t1) for i in ids]
    first = trace["devices"][ids[0]]
    return {
        "window_s": t1 - t0,
        "busy_s": sum(busy) / len(busy),
        "busy_s_by_device": dict(zip(ids, busy)),
        "device0_busy_s": busy[0],
        "device_ops": top_ops(first),
        "idle_gaps": top_gaps(first, trace["host"], t0, t1),
        "collective_exposed_s": exposed_collective_seconds(first, t0, t1),
        "op_events": len(first),
    }
