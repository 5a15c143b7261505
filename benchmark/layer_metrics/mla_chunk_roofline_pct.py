"""Kernels, serving: the share of the bf16 peak the prefill chunks' read
of the paged latent cache reaches while it runs.  The FLOPs the traced
tail's chunks NEEDED — the causal (query, key) pairs their real queries
saw and their contexts' positions, from the engine's counters
(``stats()["chunk_causal_pairs"]`` and ``["chunk_ctx_positions"]``, the
tail's after less before), priced by ``benchmark/flops_mla_chunk.py``:
81 920 FLOP a pair and 33 554 432 a position a layer at the published
widths, one up-projection a position — over the peak, over the device
seconds of the trace rows whose name starts ``mla_chunk_attn``.  A kernel
that redoes work reads lower, never higher.  A program without the
counters or the kernel reports nothing."""

import re

from benchmark.flops_mla_chunk import chunk_needed_flops

ROWS = re.compile(r"^mla_chunk_attn")


def read(run):
    tr, tail = run.get("trace"), run.get("tail")
    m = (run.get("config") or {}).get("model") or {}
    if run["kind"] != "serve" or not tr or not tail \
            or not run.get("peaks") or "kv_lora_rank" not in m:
        return None
    after, before = tail["stats1"], tail["stats0"]
    if "chunk_causal_pairs" not in after or "chunk_causal_pairs" not in before:
        return None
    seconds = sum(t for name, t in tr["device_ops"] if ROWS.match(name))
    pairs = after["chunk_causal_pairs"] - before["chunk_causal_pairs"]
    positions = after["chunk_ctx_positions"] - before["chunk_ctx_positions"]
    if not seconds or not pairs:
        return None
    return 100.0 * chunk_needed_flops(m, pairs, positions) \
        / run["peaks"]["bf16_flops"] / seconds
