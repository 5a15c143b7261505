#!/usr/bin/env python
"""The readings a ``train_lm`` cell's tolerances are set from, on the chip.

    python tools/lm_reference_probe.py [--workload mellum2_train.s8k] [--seed N] [--only a,b]

One step of the cell's timed program against its plain reference
(``benchmark/builders/train_lm.py::compare_with_reference``), then
against the same reference computed WRONG in ways the limits must
refuse: the router, the attention softmax or the residual stream in
bfloat16 (the parts the configuration states in f32), the sliding window
one position off, YaRN's attention factor left out.  Each line is the
comparison's own record; ``ok`` of every wrong reading has to be false.
``--only`` names the readings to take (each compiles its own reference).  Results also go
to ``chiprun_out/lm_reference_probe.json``.  ``--rehearse`` walks it at
the cell's tiny size on the CPU and proves nothing about the limits.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="mellum2_train.s8k")
    ap.add_argument("--seed", type=int, default=2900000101)
    ap.add_argument("--only", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark import run as bench
    resolved = bench.resolve_cell(bench.load_manifest(), args.workload)
    if args.rehearse:
        bench.apply_rehearsal(resolved["config"], resolved["traffic"])
    else:
        from paddle_tpu.flags import enable_compile_cache
        from paddle_tpu.framework.core import require_tpu
        require_tpu()
        enable_compile_cache()
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    builder = bench.load_module(
        os.path.join(ROOT, "benchmark", "builders", "train_lm.py"),
        "benchmark.builders.train_lm")
    config = resolved["config"]
    ctx = types.SimpleNamespace(config=config, traffic=resolved["traffic"],
                                seed=args.seed)
    exe = fluid.Executor(fluid.TPUPlace(0))
    startup, loss, program = builder.build(
        config, builder.draw_of(resolved["traffic"], args.seed))
    rope = config["rope_parameters"]
    variants = {
        "as_published": None,
        "router_in_bfloat16": {"router_dtype": jnp.bfloat16},
        "attention_softmax_in_bfloat16": {"softmax_dtype": jnp.bfloat16},
        "residual_stream_in_bfloat16": {"residual_dtype": jnp.bfloat16},
        "window_one_wider": {"sliding_window": config["sliding_window"] + 1},
        "window_one_narrower": {"sliding_window":
                                config["sliding_window"] - 1},
        "no_attention_factor": {"rope_parameters": {
            **rope, "full_attention": {**rope["full_attention"],
                                       "attention_factor": 1.0}}}}
    if args.only:
        variants = {k: variants[k] for k in args.only.split(",")}
    out = {}
    for name, overrides in variants.items():
        print(f"== reference {name} ==", flush=True)
        out[name] = builder.compare_with_reference(
            ctx, exe, startup, loss, program, overrides)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "lm_reference_probe.json"), "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "rehearsal": args.rehearse, "readings": out}, f, indent=1)
    refused = {k: not v["ok"] for k, v in out.items() if k != "as_published"}
    print(json.dumps({"as_published_ok": out.get("as_published",
                                                 {}).get("ok"),
                      "wrong_readings_refused": refused}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
