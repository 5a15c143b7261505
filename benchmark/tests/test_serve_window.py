import numpy as np
import pytest

from benchmark import estimators as est
from benchmark.builders.serve_window import work_end


def two_speed_stream(speed=1.0, cycles=40):
    """A chunked-prefill schedule as the load generator sees it: each
    cycle 12 syncs of 62 tokens 0.1 s apart (a prompt's chunks, each with
    a chain of 1), then 3 syncs of 500 tokens 0.13 s apart (chains of 8);
    ``speed`` scales every interval's length down."""
    t, out = 100.0, []
    for _ in range(cycles):
        for gap, tokens in [(0.1, 62)] * 12 + [(0.13, 500)] * 3:
            t += gap / speed
            out.append(t + 2e-6 * np.arange(tokens))
    return np.concatenate(out)


def test_closing_on_the_work_reads_the_speed_and_the_clock_does_not():
    base, fast = two_speed_stream(), two_speed_stream(speed=1.003)
    # both windows open on the same sync of the schedule
    t0 = est.sync_groups(base)[0][10] - 1e-4
    t0_fast = est.sync_groups(fast)[0][10] - 1e-4
    by_clock = [est.sync_rate(s, a, a + 40.0)["rate"]
                for s, a in ((base, t0), (fast, t0_fast))]
    by_work = [est.sync_rate(s, a, work_end(s, a, a + 40.0, 40000))["rate"]
               for s, a in ((base, t0), (fast, t0_fast))]
    assert by_work[1] / by_work[0] == pytest.approx(1.003, rel=1e-6)
    # closed by the clock the faster run takes in one more large sync:
    # 0.3 % of speed reads 0.86 %
    assert by_clock[1] / by_clock[0] > 1.008


def test_a_run_that_falls_short_closes_on_the_clock():
    stamps = two_speed_stream(cycles=4)
    start = est.sync_groups(stamps)[0][0] - 1e-4
    assert work_end(stamps, start, start + 5.0, 10 ** 6) == start + 5.0
    assert work_end(stamps[:1], start, start + 5.0, 10) == start + 5.0
