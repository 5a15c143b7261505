"""Decode scheduler: host time handing a token back — the worker's
``emit`` phase (token loops, ``on_token`` callbacks, prefix promotion)
over the window's output tokens, microseconds a token, from
``DecodeEngine.stats()``."""


def read(run):
    st = run.get("engine_stats") or {}
    ph = st.get("phase_ns")
    if not ph or "emit" not in ph or not st.get("tokens_out"):
        return None
    return ph["emit"] / st["tokens_out"] / 1e3
