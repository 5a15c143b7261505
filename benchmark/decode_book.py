"""The load generator's own bookkeeping of decode work, from its event
log: which output token of which request each event is, how much context
that decode step had to read, and how many decode steps a span of the
log holds.  Used by the per-layer readers of the serving cells."""

from __future__ import annotations

import numpy as np

from .estimators import SYNC_GAP_S


def token_index(req: np.ndarray) -> np.ndarray:
    """For each event, its 0-based output-token index within its request
    (the log is in emission order)."""
    order = np.argsort(req, kind="stable")
    sorted_req = req[order]
    first = np.flatnonzero(np.concatenate(([True], np.diff(sorted_req) != 0)))
    start_of = np.repeat(first, np.diff(np.concatenate((first,
                                                        [req.size]))))
    idx = np.empty(req.size, np.int64)
    idx[order] = np.arange(req.size) - start_of
    return idx


def decode_work(events: dict, lo: int, hi: int) -> dict:
    """Decode steps, decode tokens and the context positions those
    tokens' steps read, over events ``[lo, hi)`` of the log.  Token 0 of
    a request comes from prefill and is not decode work.  A decode step
    that produced output token ``i`` of a request with a prompt of ``p``
    tokens attended over ``p + i`` positions."""
    req = np.asarray(events["req"])
    stamps = np.asarray(events["stamps"])
    idx = token_index(req)
    sel = np.arange(lo, hi)
    dec = sel[idx[sel] >= 1]
    ctx = np.asarray(events["plen"])[req[dec]] + idx[dec]
    # steps: within one sync, the request that got most tokens rode
    # every step of the chain
    steps = 0
    if dec.size:
        cuts = np.flatnonzero(np.diff(stamps[dec]) >= SYNC_GAP_S) + 1
        for group in np.split(dec, cuts):
            steps += int(np.bincount(req[group]).max())
    return {"steps": steps, "tokens": int(dec.size),
            "context_positions": int(ctx.sum()),
            "mean_context": float(ctx.mean()) if dec.size else 0.0}


def window_range(events: dict) -> tuple:
    stamps = np.asarray(events["stamps"])
    lo = int(np.searchsorted(stamps, events["t_start"], "left"))
    hi = int(np.searchsorted(stamps, events["t_end"], "right"))
    return lo, hi
