"""Kernels, serving: the share of device busy time the decode steps'
NEEDED memory traffic would take at the HBM peak.  Over the traced tail:
(decode steps x weight bytes + context positions read x KV bytes a
position), from the load generator's own bookkeeping, over the table's
bytes/s, over the device's busy seconds.  Bound by memory bandwidth."""

from benchmark.decode_book import decode_work


def read(run):
    tr, tail = run.get("trace"), run.get("tail")
    if run["kind"] != "serve" or not tr or not tail or not run.get("peaks"):
        return None
    work = decode_work(run["events"], tail["k0"], tail["k1"])
    if not work["steps"] or not tr["busy_s"]:
        return None
    need = work["steps"] * run["weight_bytes"] \
        + work["context_positions"] * run["kv_bytes_per_token"]
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / tr["busy_s"]
