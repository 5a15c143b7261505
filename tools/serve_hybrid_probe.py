#!/usr/bin/env python
"""The readings a ``serve_hybrid`` cell's limits are set from, on the chip
(``tools/serve_lm_probe.py``'s way, for the hybrid decoder's builder).

    python tools/serve_hybrid_probe.py [--workload olmo_hybrid_serve.doc_closed] [--seed N] [--seconds S] [--only a,b]

One whole run of the cell as ``benchmark/builders/serve_hybrid.py::serve``
makes it (warm-up, ramp, the closed loop at the timed sizes, the sampled
requests served to their end, the engine closed), then the sampled
requests' served logits against the plain reference — every sampled
request as published, and the shortest of them (for the two controls that
break at a chunk's first positions: the shortest whose served rows hold
such a position) against the reference computed WRONG in each way of
``olmo_hybrid_jnp.CONTROLS`` — each through the builder's own ``compare``
and ``judge``.  ``ok`` of every wrong
reading has to be false; a limit belongs between the largest reading as
published and the smallest wrong one.  One request a control is a lower
bound: ``judge`` takes the worst over the sample.  ``--only`` names the
controls to take (each compiles its own reference).  Results also go to
``chiprun_out/serve_hybrid_probe.json``.  ``--rehearse`` walks it at the
cell's tiny size on the CPU and proves nothing about the limits.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="olmo_hybrid_serve.doc_closed")
    ap.add_argument("--seed", type=int, default=3600000201)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--only", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark import estimators as est, harness, run as bench
    manifest = bench.load_manifest()
    if args.seconds is None:
        args.seconds = 2.0 if args.rehearse else float(manifest["run_seconds"])
    resolved = bench.resolve_cell(manifest, args.workload)
    if args.rehearse:
        bench.apply_rehearsal(resolved["config"], resolved["traffic"])
    else:
        from paddle_tpu.flags import enable_compile_cache
        from paddle_tpu.framework.core import require_tpu
        require_tpu()
        enable_compile_cache()
    from benchmark.builders import serve_hybrid
    from benchmark.reference.olmo_hybrid_jnp import CONTROLS
    config = resolved["config"]
    chips = resolved["cell"]["chips"]
    ctx = bench.Context(resolved, args, harness.Phases(),
                        harness.Tracer(False, args.workload, chips), None)
    s = serve_hybrid.serve(ctx)
    rate = est.sync_rate(s["load"].stamps[:s["load"].k], s["t_start"],
                         s["t_end"])
    print("serve_tokens_per_s:", json.dumps(rate), "setup_s:", s["setup_s"],
          "compilations in the window:", s["compiles_in_window"],
          "ran dry:", s["ran_dry"], "memory:", s["memory"], flush=True)
    print("engine stats() at the end:", json.dumps(s["stats_end"]),
          flush=True)

    ref_cfg = config["reference"]
    ref_m = serve_hybrid.reference_model(config)
    served = sorted(zip(s["sample"], s["results"]),
                    key=lambda p: p[1].prompt_len + p[1].tokens.size)

    def readings(pairs, wrong):
        out = [dict(serve_hybrid.compare(
            ref_cfg, ref_m, s["weights"], s["requests"][r].prompt,
            res.tokens, res.logits, wrong=wrong), request=int(r))
            for r, res in pairs]
        return dict(serve_hybrid.judge(ref_cfg, out), readings=out)

    out = {}
    print("== reference as_published ==", flush=True)
    out["as_published"] = readings(served, ())
    print(json.dumps(out["as_published"]), flush=True)
    chunk = ref_cfg["chunk"]
    # a control that breaks at a chunk's first positions shows in rows
    # after such a position: the shortest request whose served rows hold one
    crossing = [p for p in served
                if (p[1].prompt_len - 1) // chunk
                != (p[1].prompt_len - 1 + p[1].tokens.size) // chunk]
    for name in (args.only.split(",") if args.only else CONTROLS):
        print(f"== reference {name} ==", flush=True)
        at_chunk = name in ("conv_tail_dropped", "state_reset_at_chunk")
        out[name] = readings((crossing if at_chunk and crossing
                              else served)[:1], (name,))
        print(json.dumps(out[name]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "serve_hybrid_probe.json"), "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "rehearsal": args.rehearse, "rate": rate,
                   "readings": out}, f, indent=1)
    refused = {k: not v["ok"] for k, v in out.items() if k != "as_published"}
    # hard exit, as the harness leaves a serve cell: the closed loop's
    # threads are daemons and nobody waits for them
    harness.finish({"as_published_ok": out["as_published"]["ok"],
                    "wrong_readings_refused": refused}, hard=True)


if __name__ == "__main__":
    main()
