"""Decode scheduler: mean time a request admitted in the window spent
queued before admission (``admit - submit`` on the engine's clock), ms,
from ``stats()["queue_wait_ns"]`` over ``stats()["admitted"]`` — the
part of time to first token that is not prefill."""


def read(run):
    st = run.get("engine_stats") or {}
    if "queue_wait_ns" not in st or not st.get("admitted"):
        return None
    return st["queue_wait_ns"] / st["admitted"] / 1e6
