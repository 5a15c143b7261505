"""Kernels, serving: the share of its memory roofline the recurrent
delta-rule kernel reaches while it runs.  The float32 state S every decode
token of the traced tail must read and write once a linear layer (from
the load generator's log: ``decode_work``; ``benchmark/flops_gdn.py``),
over the HBM peak, over the device seconds of the trace rows whose name
starts ``gdn_decode``.  The convolution's tail is not in it: the kernel
does not move it."""

import re

from benchmark.decode_book import decode_work
from benchmark.flops_gdn import matrix_state_bytes_per_row

ROWS = re.compile(r"^gdn_decode")


def read(run):
    tr, tail = run.get("trace"), run.get("tail")
    m = (run.get("config") or {}).get("model") or {}
    if run["kind"] != "serve" or not tr or not tail \
            or not run.get("peaks") or "linear_key_head_dim" not in m:
        return None
    m = dict(m, layer_types=run["config"]["layer_types"])
    seconds = sum(t for name, t in tr["device_ops"] if ROWS.match(name))
    work = decode_work(run["events"], tail["k0"], tail["k1"])
    if not seconds or not work["tokens"]:
        return None
    need = 2 * work["tokens"] * matrix_state_bytes_per_row(m)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
