"""Arithmetic from stamps to end-to-end numbers.  Pure functions of
arrays, so the tests can drive them with synthetic streams.

The decode engine syncs with the device once per chain of decode steps
and then hands every token of the chain to ``on_token`` in a tight host
loop, so tokens arrive in bursts that share an instant.  A count of
tokens inside a fixed wall-clock window therefore jumps by a whole burst
with the window's phase.  ``sync_rate`` removes the phase: it measures
from one burst to another, and what it divides is exactly the work done
between them.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import numpy as np

#: events closer together than this are one sync (the host loop that
#: emits a chain's tokens takes microseconds a token; two device syncs
#: are a decode step, tens of milliseconds, apart)
SYNC_GAP_S = 1e-3


def sync_groups(stamps: np.ndarray, gap_s: float = SYNC_GAP_S
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Group a non-decreasing array of event stamps into syncs.

    Returns ``(times, counts)``: the instant of each sync (its last
    event — the moment all of its tokens are out) and how many events it
    holds."""
    stamps = np.asarray(stamps, np.float64)
    if stamps.size == 0:
        return np.zeros(0), np.zeros(0, np.int64)
    starts = np.flatnonzero(np.diff(stamps) >= gap_s) + 1
    bounds = np.concatenate(([0], starts, [stamps.size]))
    return stamps[bounds[1:] - 1], np.diff(bounds)


def sync_rate(stamps: np.ndarray, start: float, end: float,
              gap_s: float = SYNC_GAP_S) -> Optional[dict]:
    """Events per second from sync to sync.

    ``t_a`` is the first sync at or after ``start`` and ``t_b`` the last
    sync at or before ``end``.  A sync's events are the work done since
    the sync before it, so the events of the syncs in ``(t_a, t_b]`` are
    exactly the work of that interval.  Never divides by the nominal
    ``end - start``.  None when the window holds fewer than two syncs."""
    times, counts = sync_groups(stamps, gap_s)
    inside = np.flatnonzero((times >= start) & (times <= end))
    if inside.size < 2:
        return None
    a, b = inside[0], inside[-1]
    n = int(counts[a + 1:b + 1].sum())
    span = float(times[b] - times[a])
    return {"rate": n / span, "events": n, "span_s": span,
            "t_a": float(times[a]), "t_b": float(times[b]),
            "syncs": int(b - a)}


def fixed_window_rate(stamps: np.ndarray, start: float, end: float) -> float:
    """The estimator ``sync_rate`` replaces (kept for the tests and for
    the earlier line that shows both): events stamped in
    ``[start, end)`` over the nominal length."""
    stamps = np.asarray(stamps, np.float64)
    return float(((stamps >= start) & (stamps < end)).sum()) / (end - start)


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile by the nearest-rank rule: the smallest
    value with at least ``q`` percent of the sample at or below it.  A
    value of the sample itself, never an interpolation, so a tail over
    whole requests is a request's own number."""
    vals = np.sort(np.asarray(values, np.float64))
    if vals.size == 0:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * vals.size))
    return float(vals[rank - 1])


def whole_requests(t_submit: np.ndarray, t_last: np.ndarray, start: float,
                   end: float) -> np.ndarray:
    """Indices of the requests whose whole life lies in ``[start, end]``:
    submitted at or after ``start`` and finished at or before ``end``.
    ``t_last`` is NaN for a request that never finished."""
    t_submit = np.asarray(t_submit, np.float64)
    t_last = np.asarray(t_last, np.float64)
    with np.errstate(invalid="ignore"):
        ok = (t_submit >= start) & (t_last <= end)
    return np.flatnonzero(ok)


def tpot_ms(t_first: np.ndarray, t_last: np.ndarray, n_out: np.ndarray
            ) -> np.ndarray:
    """Time per output token of each request, in ms:
    ``(t_last - t_first) / (n_out - 1)``; requests with one token have
    none and are dropped."""
    t_first = np.asarray(t_first, np.float64)
    t_last = np.asarray(t_last, np.float64)
    n_out = np.asarray(n_out, np.int64)
    ok = n_out > 1
    return 1e3 * (t_last[ok] - t_first[ok]) / (n_out[ok] - 1)
