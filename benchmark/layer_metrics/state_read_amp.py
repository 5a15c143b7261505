"""KV pool (the recurrent-state pool beside it): state rows the decode
launches of the window moved (bucket rows x decode steps: a pad row
moves the scratch slot) over state rows a live sequence owned, from the
engine's counters.  1.0 is a full bucket."""


def read(run):
    stats = run.get("engine_stats") or {}
    live = stats.get("state_rows_live")
    if run["kind"] != "serve" or not live:
        return None
    return stats["state_rows_launched"] / live
