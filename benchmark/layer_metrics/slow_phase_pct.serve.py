"""Executor host path, serving: the share of the decode worker's wall
time over the window spent in slow phases
(``DecodeEngine.stats()["slow_phase_ns"]``): non-idle phase intervals of
four times what the device usually takes over the launches the phase
waited on or the worker last dispatched (a healthy ``sync`` is about
once that, a host phase a small part of it; a dispatch that compiled is
not judged) — 0 in a sound window, whatever the model's size.  The rows of
``stats()["slow_phases"]`` on the run's earlier line name the phase and
what the worker was launching."""


def read(run):
    st = run.get("engine_stats") or {}
    slow, ph = st.get("slow_phase_ns"), st.get("phase_ns")
    if slow is None or not ph:
        return None
    total = sum(ph.values())
    if total <= 0:
        return None
    return 100.0 * sum(slow.values()) / total
