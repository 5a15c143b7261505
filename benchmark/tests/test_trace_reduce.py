import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_busy_idle_and_nesting_on_a_hand_made_trace():
    # a while loop [0, 10) holding two fusions, then a gap, then a copy
    events = [["%while.1", 0.0, 10.0], ["%fusion.3", 1.0, 3.0],
              ["%fusion.4", 5.0, 2.0], ["%copy.7", 12.0, 1.0]]
    assert tr.busy_seconds(events, 0.0, 14.0) == pytest.approx(11.0)
    assert tr.idle_gaps(events, 0.0, 14.0) == [(10.0, 12.0), (13.0, 14.0)]
    selfs = dict(tr.self_times(events))
    assert selfs["%while.1"] == pytest.approx(5.0)
    assert dict(tr.top_ops(events)) == pytest.approx(
        {"fusion": 5.0, "while": 5.0, "copy": 1.0})
    assert tr.op_class("%all-reduce-done.12") == "all-reduce-done"
    assert tr.op_class("fusion.123") == "fusion"


def test_idle_gaps_take_the_name_of_the_benchmarks_host_span():
    events = [["a.1", 0.0, 1.0], ["a.2", 2.0, 1.0], ["a.3", 3.00001, 1.0]]
    host = [["bench::feed_next", 0.9, 0.8], ["bench::outer", 0.0, 5.0]]
    gaps = dict(tr.top_gaps(events, host, 0.0, 4.00001))
    assert gaps["bench::feed_next"] == pytest.approx(1.0)
    assert gaps["short_gaps"] == pytest.approx(1e-5)
    assert dict(tr.top_gaps(events, [], 0.0, 4.00001))["unattributed"] \
        == pytest.approx(1.0)


def test_exposed_collective_is_what_no_other_operation_covers():
    # an all-reduce of 4 s of which 1.5 s run beside a fusion on a second
    # line of the same device; the enclosing while does not hide it
    events = sorted([["%while.1", 0.0, 10.0], ["%all-reduce.2", 2.0, 4.0],
                     ["%fusion.9", 4.5, 3.0]], key=lambda e: (e[1], -e[2]))
    assert tr.exposed_collective_seconds(events, 0.0, 10.0) \
        == pytest.approx(2.5)
    assert tr.exposed_collective_seconds(
        [["%fusion.9", 0.0, 1.0]], 0.0, 1.0) == 0.0


def test_summarize_averages_busy_over_the_chips_used():
    trace = {"devices": {"0": [["f.1", 0.0, 1.0], ["f.2", 3.0, 1.0]],
                         "1": [["f.1", 0.0, 3.0]]}, "host": []}
    s = tr.summarize(trace, chips=2)
    assert s["window_s"] == pytest.approx(4.0)
    assert s["busy_s"] == pytest.approx(2.5)
    assert s["device0_busy_s"] == pytest.approx(2.0)
    with pytest.raises(ValueError):
        tr.summarize({"devices": {"0": []}, "host": []}, 1)


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.endswith(".json"))
    if os.path.isdir(DATA) else [])
def test_recorded_trace_reduces(name):
    """A trimmed recording of a real run on the chip: the reduction finds
    operations, a busy share in (0, 1], and classes without instance
    numbers."""
    with open(os.path.join(DATA, name)) as f:
        rec = json.load(f)
    s = tr.summarize(rec["trace"], chips=rec["chips"])
    assert 0.0 < s["busy_s"] <= s["window_s"]
    assert s["op_events"] > 10
    assert all(not k[-1].isdigit() for k, _ in s["device_ops"])
    total_self = sum(t for _, t in tr.self_times(
        rec["trace"]["devices"]["0"]))
    assert total_self == pytest.approx(s["device0_busy_s"], rel=0.02)
    for key, want in rec["expect"].items():
        assert s[key] == pytest.approx(want, rel=1e-6), key
