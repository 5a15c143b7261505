"""Compiled step, serving: one decode step at the cell's batch —
dispatch + sync time of the window's decode chains
(``stats()["launch_ns"]["chain"]``; the worker is synchronous, so that
is device time plus launch latency) over the decode steps they ran, ms.
Without the chain's quantum, prefill stalls or host gaps."""


def read(run):
    st = run.get("engine_stats") or {}
    launch = st.get("launch_ns")
    if not launch or "chain" not in launch or not st.get("decode_steps"):
        return None
    return launch["chain"] / st["decode_steps"] / 1e6
