"""Kernels, serving: the share of its memory roofline the grouped paged
decode read reaches while it runs.  The K/V bytes every decode token of
the traced tail must read — ``min(ctx, sliding_window)`` positions in each
window layer, ``ctx`` in each full layer, 4 096 B a position a layer at
the published sizes (from the load generator's log;
``benchmark/flops_window.py``) — over the HBM peak, over the device
seconds of the trace rows whose name starts ``paged_gqa_decode``."""

import re

from benchmark.flops_window import decode_contexts, decode_kv_bytes

ROWS = re.compile(r"^paged_gqa_decode")


def read(run):
    tr, tail = run.get("trace"), run.get("tail")
    m = (run.get("config") or {}).get("model") or {}
    if run["kind"] != "serve" or not tr or not tail \
            or not run.get("peaks") or "sliding_window" not in m:
        return None
    seconds = sum(t for name, t in tr["device_ops"] if ROWS.match(name))
    ctx = decode_contexts(run["events"], tail["k0"], tail["k1"])
    if not seconds or not ctx.size:
        return None
    n = m["num_hidden_layers"]
    m = dict(m, layer_types=run["config"]["layer_types"][:n])
    return 100.0 * decode_kv_bytes(m, ctx) \
        / run["peaks"]["hbm_bytes_per_s"] / seconds
