"""Decode scheduler: the 95th percentile (nearest rank) of time per
output token, ``(t_last - t_first) / (n_out - 1)``, over the requests
submitted and finished inside the window, ms.  Both stamps fall on a
chain's sync, so the number carries the chain's quantum (PERF.md)."""

from benchmark.estimators import percentile


def read(run):
    tpot = run.get("tpot_ms")
    if tpot is None or not len(tpot):
        return None
    return percentile(tpot, 95)
