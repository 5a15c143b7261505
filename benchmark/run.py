#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix or
per-layer metric is data, found by the names in ``BENCHMARK.json``:

* the cell          -> its entry in ``BENCHMARK.json`` (name, config,
                       traffic, chips, why)
* the configuration -> ``benchmark/configs/<config>.json``
* the traffic mix   -> ``benchmark/traffic/<traffic>.json``
* the builder       -> ``benchmark/builders/<kind>.py`` (``kind`` of
                       the configuration), ``run(ctx) -> record``
* a per-layer metric-> ``benchmark/layer_metrics/<name>.py``,
                       ``read(run) -> float | None``

There is no registry and no ``if name ==``: adding any of them is adding
files and one entry in ``BENCHMARK.json``.

The last line of standard output is the result object; everything else
(set-up breakdown, sample counts, generator lateness, the program's
counters whole) is on earlier lines.  Without a TPU the command exits
non-zero and prints no result; ``--rehearse`` is the explicit switch
that walks a cell at tiny size on the CPU and reports counts only.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

META_KEYS = {"name", "kind", "source"}


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise SystemExit(f"{path} does not exist")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_keys(config: dict) -> dict:
    """The model's own settings: the scalar keys at the top level of a
    configuration file, as the published config has them."""
    return {k: v for k, v in config.items()
            if not isinstance(v, (dict, list)) and k not in META_KEYS}


def resolve_cell(manifest: dict, name: str, bench_dir: str = HERE) -> dict:
    """A cell's entry, its two data files and its metric lists — all by
    name."""
    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = cells[name]
    with open(os.path.join(bench_dir, "configs",
                           cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    config["model"] = model_keys(config)

    def mine(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
            "per_layer": [m for m in manifest["per_layer"] if mine(m)]}


def apply_rehearsal(config: dict, traffic: dict):
    """Tiny sizes for the CPU walk-through, from the files' own
    ``rehearsal`` sections."""
    for target in (config, traffic):
        for k, v in target.get("rehearsal", {}).items():
            if isinstance(v, dict) and isinstance(target.get(k), dict):
                target[k] = {**target[k], **v}
            else:
                target[k] = v
    config["model"] = model_keys(config)


class Context:
    def __init__(self, resolved, args, phases, tracer, peaks):
        self.cell = resolved["cell"]
        self.config = resolved["config"]
        self.traffic = resolved["traffic"]
        self.seed = args.seed
        self.seconds = args.seconds
        self.rehearsal = args.rehearse
        self.phases = phases
        self.tracer = tracer
        self.peaks = peaks

    def open_window(self) -> float:
        """Called by the builder at the first instant of the measured
        window: closes set-up and returns its length."""
        from benchmark.harness import process_age_s
        self.phases.mark("last set-up step")
        setup_s = process_age_s()
        self.phases.report()
        return setup_s


def read_layer_metrics(resolved, record, bench_dir: str = HERE) -> dict:
    out = {}
    for metric in resolved["per_layer"]:
        path = os.path.join(bench_dir, "layer_metrics",
                            metric["name"] + ".py")
        reader = load_module(path, "layer_metric_" + metric["name"]
                             .replace(".", "_"))
        value = reader.read(record)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the cell at tiny size on the CPU; reports "
                         "counts only, never a device metric")
    args = ap.parse_args(argv)

    manifest = load_manifest()
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    resolved = resolve_cell(manifest, args.workload)
    chips = resolved["cell"]["chips"]

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={max(chips, 1)}"
        if flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " " + flag).strip()
        apply_rehearsal(resolved["config"], resolved["traffic"])

    from benchmark import harness, peaks as peaks_mod
    phases = harness.Phases()
    if args.rehearse:
        device = harness.device_info(chips)
        peaks = None
    else:
        from paddle_tpu.framework.core import require_tpu
        require_tpu()               # no TPU: non-zero exit, no result
        device = harness.device_info(chips)
        if device["count"] < chips:
            raise SystemExit(f"cell {args.workload} needs {chips} chips; "
                             f"JAX sees {device['count']}")
        peaks = peaks_mod.peaks_for(device["kind"])
        from paddle_tpu.flags import enable_compile_cache
        harness.say(f"compile cache: {enable_compile_cache()}")
    harness.say(f"device: {json.dumps(device)}; cell "
                f"{json.dumps(resolved['cell'])}; seed {args.seed}; "
                f"seconds {args.seconds}; trace {args.trace}")
    phases.mark("jax and paddle_tpu imports, device check")

    tracer = harness.Tracer(bool(args.trace) and not args.rehearse,
                            args.workload, chips)
    ctx = Context(resolved, args, phases, tracer, peaks)
    kind = resolved["config"]["kind"]
    builder = load_module(os.path.join(HERE, "builders", kind + ".py"),
                          "benchmark.builders." + kind)
    record = builder.run(ctx)
    record.update(chips=chips, peaks=peaks, config=resolved["config"],
                  traffic=resolved["traffic"], seconds=args.seconds)

    line = {"correct": bool(record["correct"]),
            "attempted": int(record["attempted"]),
            "failed": int(record["failed"])}
    if args.rehearse:
        # a CPU walk-through: counts only, no metric of the device
        # and which per-layer readers found something to read: their
        # names, never their values
        found = sorted(read_layer_metrics(resolved, record))
        line.update(metrics={}, rehearsal=True, counts=record["counts"],
                    layer_readers=found,
                    device={"platform": device["platform"],
                            "kind": device["kind"],
                            "count": device["count"]})
        harness.finish(line, hard=record.get("hard_exit", False))

    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": harness.memory_peak_bytes(
               chips, record["memory_samples"])}
    if args.trace:
        metrics = read_layer_metrics(resolved, record)
        trace = record["trace"]
        dev.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    else:
        values = dict(record["end_to_end"], setup_s=record["setup_s"])
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in resolved["end_to_end"]}
    line.update(metrics=metrics, device=dev)
    # a run that printed its result exits 0; ``correct`` carries the verdict
    harness.finish(line, hard=record.get("hard_exit", False))


if __name__ == "__main__":
    main()
