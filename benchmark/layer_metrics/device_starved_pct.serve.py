"""Executor host path, serving: the share of the decode worker's wall
time over the window in which the device had nothing to run and waited
on the HOST — the ``admit``, ``feed``, ``dispatch``, ``emit`` and
``retire`` intervals that opened with no launch in flight
(``DecodeEngine.stats()["starved_ns"]``) over the sum of ``phase_ns``.
``worker_host_pct.serve`` counts those phases whole, so it is this
number plus the host work a chunk in flight covered."""


def read(run):
    st = run.get("engine_stats") or {}
    starved, ph = st.get("starved_ns"), st.get("phase_ns")
    if not starved or not ph:
        return None
    total = sum(ph.values())
    if total <= 0:
        return None
    return 100.0 * sum(starved.values()) / total
