import numpy as np
import pytest

from benchmark import estimators as est
from benchmark.decode_book import decode_work, token_index


def burst_stream(chains=120, chain_s=0.53, tokens=1000, jitter=None):
    """A decode engine's token stream as the load generator sees it: every
    ``chain_s`` one burst of ``tokens`` events a few microseconds apart."""
    t, out = 100.0, []
    rng = np.random.default_rng(0)
    for _ in range(chains):
        t += chain_s if jitter is None else chain_s * (1 + jitter *
                                                       rng.uniform(-1, 1))
        out.append(t + 2e-6 * np.arange(tokens))
    return np.concatenate(out)


def test_sync_groups_split_on_gaps_only():
    stamps = np.array([0.0, 1e-5, 2e-5, 0.5, 0.50001, 1.2])
    times, counts = est.sync_groups(stamps)
    assert counts.tolist() == [3, 2, 1]
    assert times.tolist() == [2e-5, 0.50001, 1.2]


def test_sync_rate_does_not_move_with_the_window_but_the_count_jumps():
    stamps = burst_stream()
    true_rate = 1000 / 0.53
    naive, sync = [], []
    for shift in np.linspace(0.0, 0.53, 23):
        start, end = 105.0 + shift, 145.0 + shift
        naive.append(est.fixed_window_rate(stamps, start, end))
        sync.append(est.sync_rate(stamps, start, end)["rate"])
    # whatever the window's phase, sync to sync reads the system's rate
    assert np.ptp(sync) / true_rate < 1e-9
    assert sync[0] == pytest.approx(true_rate, rel=1e-9)
    # the fixed window gains or loses a whole chain: 1000 tokens in 40 s
    assert np.ptp(naive) == pytest.approx(1000 / 40.0, rel=1e-6)


def test_sync_rate_counts_exactly_the_work_between_its_two_syncs():
    stamps = burst_stream(chains=10, tokens=7)
    r = est.sync_rate(stamps, 100.0, 200.0)
    assert r["syncs"] == 9 and r["events"] == 9 * 7
    assert r["span_s"] == pytest.approx(9 * 0.53)
    assert est.sync_rate(stamps, 100.0, 100.6) is None     # one sync


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert est.percentile(vals, 95) == 95
    assert est.percentile(vals, 50) == 50
    assert est.percentile([3.0], 95) == 3.0
    assert est.percentile([1, 2, 3, 4], 95) == 4
    with pytest.raises(ValueError):
        est.percentile([], 95)


def test_whole_request_selection():
    t_submit = np.array([0.5, 1.0, 2.0, 8.0, 9.5])
    t_last = np.array([3.0, 4.0, np.nan, 9.9, 10.2])
    got = est.whole_requests(t_submit, t_last, start=1.0, end=10.0)
    # 0 began before the window, 2 never finished, 4 finished after it
    assert got.tolist() == [1, 3]


def test_tpot_drops_one_token_requests():
    tp = est.tpot_ms(np.array([1.0, 2.0]), np.array([1.9, 2.0]),
                     np.array([10, 1]))
    assert tp.tolist() == pytest.approx([100.0])


def test_decode_bookkeeping():
    # request 0 (prompt 10): tokens 0..3; request 1 (prompt 20): 0..1
    req = np.array([0, 1, 0, 1, 0, 0], np.int32)
    stamps = np.array([0.0, 0.1, 0.2, 0.2 + 1e-6, 0.3, 0.3 + 1e-6])
    assert token_index(req).tolist() == [0, 0, 1, 1, 2, 3]
    ev = {"req": req, "stamps": stamps, "plen": np.array([10, 20])}
    w = decode_work(ev, 0, 6)
    # decode tokens: (r0,1) (r1,1) in one sync = 1 step; (r0,2) (r0,3) in
    # one sync = a chain of 2 steps
    assert w["steps"] == 3 and w["tokens"] == 4
    assert w["context_positions"] == 11 + 21 + 12 + 13
