"""Compiled step, serving: one decode step at the cell's batch, by the
DEVICE — the time the window's decode chains held the device
(``stats()["device_ns"]["chain"]``, the engine's in-flight ledger: each
launch from the later of the launch before it completing and its own
dispatch, to its own completion) over the decode steps they ran, ms.
``decode_step_ms.serve`` reads the launch clock instead, which charges
a non-final chunk's device time to the chain whose sync waits it out.
A completion is a stamp of the HOST, taken when the worker's blocking
call returns: the number is an UPPER BOUND on the device's own record
(the trace's ``XLA Modules`` events) and holds the worker's wake-up
after each chain — 0.7 % over in ``reason_closed``, 7 % over in
``chat_closed`` (chains of 41 ms, wake-ups of 0.5-1.9 ms; PERF.md
section 6, PR 38), so there it also moves with the host's load."""


def read(run):
    st = run.get("engine_stats") or {}
    device = st.get("device_ns")
    if not device or "chain" not in device or not st.get("decode_steps"):
        return None
    return device["chain"] / st["decode_steps"] / 1e6
