"""BENCHMARK.json against the contract's checkable rules and against the
files it names."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_keys_and_names():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in M[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in M[k]]
        assert len(ns) == len(set(ns))
    metrics = [x["name"] for x in M["end_to_end"] + M["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for x in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
    for x in M["workloads"]:
        assert set(x) == {"name", "config", "traffic", "chips", "why"}
        assert x["chips"] in (1, 4) and len(x["why"]) <= 200
    four = sum(1 for x in M["workloads"] if x["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)


def test_bounds_and_sources():
    e2e = {x["name"]: x for x in M["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for x in M["end_to_end"]:
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for x in M["per_layer"]:
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_name_resolves_to_a_file_and_every_cell_reports_enough():
    bench = os.path.join(ROOT, "benchmark")
    cfgs = {c["name"]: c for c in M["configs"]}
    used = set()
    cells = [w["name"] for w in M["workloads"]]
    for w in M["workloads"]:
        used.add(w["config"])
        assert os.path.exists(os.path.join(ROOT, cfgs[w["config"]]["file"]))
        assert os.path.exists(os.path.join(bench, "traffic",
                                           w["traffic"] + ".json"))
    assert used == set(cfgs)
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    e2e = {x["name"]: x for x in M["end_to_end"]}
    for x in M["end_to_end"] + M["per_layer"]:
        assert cells_of(x) <= set(cells)
    for x in M["per_layer"]:
        assert os.path.exists(os.path.join(bench, "layer_metrics",
                                           x["name"] + ".py"))
        # the metric it should move is reported wherever it is
        assert cells_of(x) <= cells_of(e2e[x["moves"]]), x["name"]
    for c in cells:
        assert sum(1 for x in M["end_to_end"]
                   if c in cells_of(x) and x["name"] != "setup_s") >= 1
        assert sum(1 for x in M["per_layer"] if c in cells_of(x)) >= 1
    layers = {}
    for x in M["per_layer"]:
        layers.setdefault(x["layer"].lower(), set()).add(x["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_check_fits_the_drivers_budget_with_24_cells():
    rs = M["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
