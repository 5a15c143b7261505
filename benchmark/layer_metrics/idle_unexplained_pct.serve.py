"""Executor host path, serving: how far the program's own split of the
device's idle time by phase is from the device's, in the traced tail —
the distance from 100 of 100 x the tail's ``sum(starved_ns) +
phase_ns["idle"]`` (``DecodeEngine.stats()`` after the tail less before
it) over the trace's ``window_s - device0_busy_s``.  0 means the
engine's starved phases ARE the device's idle.  Both ways of missing
count alike: the device idling inside intervals the ledger books to a
launch (between the device finishing and the worker's wake-up, inside
``sync``), and the device already running inside a dispatch the ledger
calls starved."""


def read(run):
    tr, tail = run.get("trace"), run.get("tail")
    if not tr or not tail:
        return None
    after, before = tail["stats1"], tail["stats0"]
    if "starved_ns" not in after or "starved_ns" not in before:
        return None
    idle_s = tr["window_s"] - tr["device0_busy_s"]
    if idle_s <= 0:
        return None
    explained = sum(after["starved_ns"].values()) \
        - sum(before["starved_ns"].values()) \
        + after["phase_ns"]["idle"] - before["phase_ns"]["idle"]
    return abs(100.0 - 100.0 * explained * 1e-9 / idle_s)
