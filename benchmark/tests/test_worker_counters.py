"""The five readers of the decode worker's own counters
(``DecodeEngine.stats()["phase_ns"|"launch_ns"|"queue_wait_ns"]``): each
gives its number on a recorded window of ``engine_stats`` and nothing —
without raising — on a program that has no such counters."""

import json
import os

import pytest

from benchmark.run import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
NEW_KEYS = ("phase_ns", "launch_ns", "launches", "queue_wait_ns",
            "admitted", "first_token_ns", "first_tokens")

with open(os.path.join(HERE, "counters", "engine_stats_window.json")) as f:
    RECORDED = json.load(f)


def _read(name, run):
    mod = load_module(os.path.join(BENCH, "layer_metrics", name + ".py"),
                      "layer_metric_" + name.replace(".", "_"))
    return mod.read(run)


def _expected(st):
    ph, launch = st["phase_ns"], st["launch_ns"]
    host = sum(ph[k] for k in ("admit", "feed", "dispatch", "emit",
                               "retire"))
    return {
        "worker_host_pct.serve": 100.0 * host / sum(ph.values()),
        "emit_us_per_token.serve": ph["emit"] / st["tokens_out"] / 1e3,
        "decode_step_ms.serve": launch["chain"] / st["decode_steps"] / 1e6,
        "prefill_share_pct.serve":
            100.0 * (launch["prefill"] + launch["chunk"])
            / sum(launch.values()),
        "queue_wait_ms.serve":
            st["queue_wait_ns"] / st["admitted"] / 1e6,
    }


@pytest.mark.parametrize("name", sorted(_expected(RECORDED)))
def test_reader_on_a_recorded_window_and_on_the_parent(name):
    value = _read(name, {"kind": "serve", "engine_stats": RECORDED})
    assert value == pytest.approx(_expected(RECORDED)[name], rel=1e-12)
    assert value > 0
    # the parent's stats() has none of the new keys; a trainer's record
    # has no engine_stats at all; an empty window counted nothing
    parent = {k: v for k, v in RECORDED.items() if k not in NEW_KEYS}
    assert _read(name, {"kind": "serve", "engine_stats": parent}) is None
    assert _read(name, {"kind": "train"}) is None
    empty = dict(RECORDED, tokens_out=0, decode_steps=0, admitted=0,
                 phase_ns=dict.fromkeys(RECORDED["phase_ns"], 0),
                 launch_ns=dict.fromkeys(RECORDED["launch_ns"], 0))
    assert _read(name, {"kind": "serve", "engine_stats": empty}) is None


def test_phases_of_the_recorded_window_tile_it():
    """The window was 40 s of a worker that never stopped: the phases of
    the recorded delta add up to the window within a chain's length."""
    total = sum(RECORDED["phase_ns"].values()) / 1e9
    assert RECORDED["window_s"] - 1.0 < total < RECORDED["window_s"] + 1.0
    assert sum(RECORDED["launches"].values()) == (
        RECORDED["prefill_batches"] + RECORDED["chunk_steps"]
        + RECORDED["chains_run"])
