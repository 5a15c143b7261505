"""Kernels, training: model FLOP/s utilization of the whole step — the
benchmark's own FLOP count (``benchmark/flops.py``) times steps per
second of the window, over chips times the table's bf16 peak.  A
compute-roofline share of the step, not of one kernel."""


def read(run):
    if run["kind"] != "train" or not run.get("peaks") or not run["span_s"]:
        return None
    rate = run["flops_per_step"] * run["steps"] / run["span_s"]
    return 100.0 * rate / (run["chips"] * run["peaks"]["bf16_flops"])
