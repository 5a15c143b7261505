"""Kernels, training: the share of the bf16 peak that the experts'
grouped matrix products reach while they run.  FLOPs of the gate, up and
down products over the assignments the router really made, forward and
backward (``benchmark/flops_lm.py``; the recomputed hidden projections
are not counted), over the device time of the trace rows whose name
starts ``moe_gmm`` — the same work whatever implements it.  Bound by
compute: an expert's 12.4 MB of weights serve ~1 000 tokens."""

import re

from benchmark.flops_lm import kernel_peak_share_pct

ROWS = re.compile(r"^moe_gmm")


def read(run):
    return kernel_peak_share_pct(run, ROWS, "experts")
