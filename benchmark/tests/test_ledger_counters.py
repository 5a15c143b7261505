"""The five readers of the decode worker's in-flight ledger
(``DecodeEngine.stats()["device_ns"|"starved_ns"|"slow_phase_ns"]``):
each gives its number on the recorded window and traced tail of a chip
run with chunks in its traffic, and nothing — without raising — on a
program that has no such counters (the parent, with these files laid
over it)."""

import json
import os

import pytest

from benchmark.run import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
NEW_KEYS = ("device_ns", "starved_ns", "slow_phase_ns", "slow_phases")

with open(os.path.join(HERE, "counters", "engine_stats_ledger.json")) as f:
    RECORDED = json.load(f)
WINDOW, TAIL, TRACE = RECORDED["window"], RECORDED["tail"], RECORDED["trace"]


def _read(name, run):
    mod = load_module(os.path.join(BENCH, "layer_metrics", name + ".py"),
                      "layer_metric_" + name.replace(".", "_"))
    return mod.read(run)


def _zeros(delta):
    """A ``stats()`` the recorded delta is the gain over."""
    return {k: dict.fromkeys(v, 0) if isinstance(v, dict) else
            0 if isinstance(v, (int, float)) and not isinstance(v, bool)
            else v for k, v in delta.items()}


def _run(window, tail):
    return {"kind": "serve", "engine_stats": window, "trace": TRACE,
            "tail": {"stats0": _zeros(tail), "stats1": tail}}


def _expected():
    dev, ph = WINDOW["device_ns"], WINDOW["phase_ns"]
    idle_s = TRACE["window_s"] - TRACE["device0_busy_s"]
    return {
        "decode_step_device_ms.serve":
            dev["chain"] / WINDOW["decode_steps"] / 1e6,
        "chunk_device_share_pct.serve":
            100.0 * (dev["prefill"] + dev["chunk"]) / sum(dev.values()),
        "device_starved_pct.serve":
            100.0 * sum(WINDOW["starved_ns"].values()) / sum(ph.values()),
        "slow_phase_pct.serve":
            100.0 * sum(WINDOW["slow_phase_ns"].values()) / sum(ph.values()),
        "idle_unexplained_pct.serve":
            abs(100.0 - 100.0 * (sum(TAIL["starved_ns"].values())
                                 + TAIL["phase_ns"]["idle"]) * 1e-9
                / idle_s),
    }


@pytest.mark.parametrize("name", sorted(_expected()))
def test_reader_on_a_recorded_run_and_on_the_parent(name):
    value = _read(name, _run(WINDOW, TAIL))
    assert value == pytest.approx(_expected()[name], rel=1e-12)
    assert value >= 0
    # the parent's stats() has none of the new keys, in the window or
    # around the tail; a trainer's record has no engine_stats at all; an
    # untraced run has neither a trace nor a tail
    def parent(st):
        return {k: v for k, v in st.items() if k not in NEW_KEYS}
    assert _read(name, _run(parent(WINDOW), parent(TAIL))) is None
    assert _read(name, {"kind": "train"}) is None
    untraced = dict(_run(WINDOW, TAIL), trace=None, tail=None)
    if name == "idle_unexplained_pct.serve":
        assert _read(name, untraced) is None
    else:
        assert _read(name, untraced) == value
    empty = dict(WINDOW, decode_steps=0,
                 phase_ns=dict.fromkeys(WINDOW["phase_ns"], 0),
                 device_ns=dict.fromkeys(WINDOW["device_ns"], 0))
    if name != "idle_unexplained_pct.serve":
        assert _read(name, _run(empty, TAIL)) is None


def test_the_recorded_run_reads_as_the_issue_said_it_would():
    """The chip run behind the file: chunks in the traffic, so the
    ledger's split differs from the launch clock's in the direction and
    by the seconds ISSUE 38 gives; the window and the tail tile."""
    dev, launch = WINDOW["device_ns"], WINDOW["launch_ns"]
    assert WINDOW["launches"]["chunk"] > 0
    assert dev["chunk"] > launch["chunk"] and dev["chain"] < launch["chain"]
    # what the chains lose the prompts gain, the same device seconds to
    # 5 % (apart: the dispatches that found the device free, in no device
    # interval, and the host phases a chunk in flight covered, in one)
    moved = launch["chain"] - dev["chain"]
    gained = dev["chunk"] + dev["prefill"] - launch["chunk"] \
        - launch["prefill"]
    assert abs(moved - gained) <= 0.05 * moved
    for part in (WINDOW, TAIL):
        total = sum(part["phase_ns"].values())
        tiled = sum(part["device_ns"].values()) \
            + sum(part["starved_ns"].values()) + part["phase_ns"]["idle"]
        assert abs(tiled - total) <= 0.01 * total
    assert set(WINDOW["starved_ns"]) == {"admit", "feed", "dispatch",
                                         "emit", "retire"}
    busy = TRACE["device0_busy_s"]
    assert abs(sum(TAIL["device_ns"].values()) * 1e-9 - busy) <= 0.05 * busy
