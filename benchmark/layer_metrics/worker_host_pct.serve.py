"""Executor host path, serving: the share of the decode worker's wall
time over the window in which it was NOT waiting on the device —
``admit + feed + dispatch + emit + retire`` over the sum of all of
``DecodeEngine.stats()["phase_ns"]`` (``sync`` and ``idle`` are the
waits).  An upper bound on the device's idle share, and equal to it
while no un-synced chunk is in flight."""

HOST = ("admit", "feed", "dispatch", "emit", "retire")


def read(run):
    ph = (run.get("engine_stats") or {}).get("phase_ns")
    if not ph:
        return None
    total = sum(ph.values())
    if total <= 0:
        return None
    return 100.0 * sum(ph.get(k, 0) for k in HOST) / total
