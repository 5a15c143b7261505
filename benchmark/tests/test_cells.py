"""Each cell walked end to end at tiny size on the CPU through the real
command, and the proof that a new cell and a new per-layer metric are
files and manifest entries only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [c["name"] for c in MANIFEST["workloads"]]
DEVICE_METRICS = {m["name"] for m in MANIFEST["end_to_end"]
                  + MANIFEST["per_layer"]}


def rehearse(root, cell, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", "2",
         "--rehearse", *extra],
        capture_output=True, text=True, env=env, cwd=root, timeout=600)
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_counts_and_no_device_metric(cell):
    out = rehearse(ROOT, cell)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["attempted"] > 0
    assert line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"]
    # counts only: no value under any metric's name, anywhere in the line
    assert line["metrics"] == {}
    assert not DEVICE_METRICS & set(line["counts"])
    assert line["counts"]["compiles_in_window"] == 0
    assert "set-up breakdown" in out.stdout
    assert "reference comparison" in out.stdout


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert out.returncode != 0
    assert not any(l.startswith("{") for l in out.stdout.splitlines())


def test_a_new_cell_and_metric_are_files_and_entries_only(tmp_path):
    """Copy the benchmark, ADD a traffic file, a reader and two manifest
    entries — editing no file that was there — and run the new cell.  The
    cell added is the Fleet dp4 one (four virtual devices here), so this
    is also the rehearsal of the mesh path and of its reference
    comparison, which has to run the mesh program itself."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()
    with open(os.path.join(root, "benchmark", "traffic",
                           "stream_b96_s128.json")) as f:
        traffic = json.load(f)
    traffic.update(global_batch=384, mesh={"dp": 4}, double_buffer=False)
    traffic["rehearsal"]["global_batch"] = 16
    with open(os.path.join(root, "benchmark", "traffic",
                           "throwaway_stream.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "throwaway_steps.py"), "w") as f:
        f.write("def read(run):\n    return float(run['steps'])\n")
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["workloads"].append({
        "name": "throwaway.cell", "config": "bert_base_pretrain",
        "traffic": "throwaway_stream", "chips": 4, "why": "a test"})
    manifest["per_layer"].append({
        "name": "throwaway_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "compiled step",
        "moves": "train_tokens_per_s", "workloads": ["throwaway.cell"]})
    for m in manifest["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("throwaway.cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    out = rehearse(root, "throwaway.cell")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] and line["counts"]["tokens"] \
        == line["counts"]["steps"] * 16 * 32
    assert "throwaway_steps" in line["layer_readers"]
    ref = [l for l in out.stdout.splitlines()
           if l.startswith("reference comparison: ")]
    ref = json.loads(ref[0].split(": ", 1)[1])
    assert ref["mesh"] == {"dp": 4} and ref["ok"]
    for p, content in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == content, f"{p} was edited"
