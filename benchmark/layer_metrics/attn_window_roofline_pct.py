"""Kernels, training: the share of the bf16 peak that the grouped-head
attention kernels reach while they run, window and full layers together.
FLOPs of QK^T and PV over the window-exact causal pairs of every layer,
forward and backward (``benchmark/flops_lm.py``; the backward kernels'
recomputed scores are not counted), over the device time of the trace
rows whose name starts ``flash_gqa`` — the same work whatever implements
it.  Bound by compute at head size 128."""

import re

from benchmark.flops_lm import kernel_peak_share_pct

ROWS = re.compile(r"^flash_gqa")


def read(run):
    return kernel_peak_share_pct(run, ROWS, "attention")
