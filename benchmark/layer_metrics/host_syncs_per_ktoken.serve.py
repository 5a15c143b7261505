"""Executor host path, serving: device-to-host syncs per thousand output
tokens, from ``DecodeEngine.stats()`` over the window."""


def read(run):
    st = run.get("engine_stats")
    if not st or not st.get("tokens_out"):
        return None
    return 1e3 * st["host_syncs"] / st["tokens_out"]
