"""KV pool: the K/V bytes the engine holds for each live context token,
over the window: ``stats()["kv_bytes_held"]`` (integrated once a
scheduling round: full-layer blocks in use and window-ring pages in use,
each times its bytes) over ``stats()["ctx_tokens_live"]`` (the live
context tokens of the admitted rows, the same rounds).  Every layer
keeping the whole context reads 2 x layers x K/V heads x head_dim x 2 B;
a window layer's ring holds at most its window and a chunk a row.  An
engine without the counters reports nothing."""


def read(run):
    stats = run.get("engine_stats") or {}
    live = stats.get("ctx_tokens_live")
    if run["kind"] != "serve" or not live:
        return None
    return stats["kv_bytes_held"] / live
