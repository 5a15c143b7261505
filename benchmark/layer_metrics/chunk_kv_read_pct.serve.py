"""Kernels: the share of the block tables the window's prefill chunks
read, from the engine's counters (``stats()["chunk_kv_pages_read"]`` over
``["chunk_kv_pages_spanned"]``: per query block of the paged chunk
kernel, the pages up to its causal frontier, of ``max_blocks_per_seq``).
100 is a read of the whole table, as the gather before the kernel made.
An engine without the counters reports nothing."""


def read(run):
    stats = run.get("engine_stats") or {}
    spanned = stats.get("chunk_kv_pages_spanned")
    if run["kind"] != "serve" or not spanned:
        return None
    return 100.0 * stats["chunk_kv_pages_read"] / spanned
