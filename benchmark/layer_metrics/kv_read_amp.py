"""KV pool: read amplification of the gather — ``max_seq_len`` (what
``gather_cache`` reads for every live row) over the mean live context of
the window's decode steps (what attention needs), from the load
generator's own bookkeeping."""

from benchmark.decode_book import decode_work, window_range


def read(run):
    if run["kind"] != "serve":
        return None
    work = decode_work(run["events"], *window_range(run["events"]))
    if not work["tokens"]:
        return None
    return run["max_seq_len"] / work["mean_context"]
