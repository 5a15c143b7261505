"""Kernels, serving: the share of its roofline the decode steps' read of
the paged latent cache reaches while it runs.  The larger of (latent
bytes of the context positions the traced tail's decode tokens attended,
from the load generator's log, over the HBM peak) and (their score and
value FLOPs over the bf16 peak) — ``benchmark/flops_mla.py`` — over the
device seconds of the trace rows whose name starts ``mla_paged_decode``:
the same needed traffic whatever implements the read."""

import re

from benchmark.decode_book import decode_work
from benchmark.flops_mla import mla_decode_needed_seconds

ROWS = re.compile(r"^mla_paged_decode")


def read(run):
    tr, tail = run.get("trace"), run.get("tail")
    m = (run.get("config") or {}).get("model") or {}
    if run["kind"] != "serve" or not tr or not tail \
            or not run.get("peaks") or "kv_lora_rank" not in m:
        return None
    seconds = sum(t for name, t in tr["device_ops"] if ROWS.match(name))
    work = decode_work(run["events"], tail["k0"], tail["k1"])
    if not seconds or not work["context_positions"]:
        return None
    return 100.0 * mla_decode_needed_seconds(
        m, work["context_positions"], run["peaks"]) / seconds
