#!/usr/bin/env python
"""The readings a ``serve_window`` cell's limits are set from, on the chip
(``tools/serve_hybrid_probe.py``'s way, for the window decoder's builder).

    python tools/serve_window_probe.py [--workload laguna_s_serve.code_closed] [--seed N] [--seconds S] [--only a,b] [--all]

One whole run of the cell as ``benchmark/builders/serve_window.py::serve``
makes it (warm-up, ramp, the closed loop at the timed sizes, the sampled
requests served to their end, the engine closed), then the sampled
requests' served logits against the plain reference — every sampled
request as published, and the shortest of them (``--all``: every one)
against the reference computed WRONG in each way of ``laguna_jnp.CONTROLS`` and in the stated
bfloat16 (``laguna_jnp.ROUNDINGS``: a reading, not a fault) — each through
the builder's own ``compare`` and ``judge``.  ``ok`` of every wrong
reading has to be false; a limit belongs between the largest reading as
published and the smallest wrong one.  It also prints the sync timeline
around the ramp's end (tokens out after ``ramp_seconds``, and the syncs
of the seconds after), which ``ramp_tokens`` is read from.  ``--only``
names the controls to take (each compiles its own reference).  Results
also go to ``chiprun_out/serve_window_probe.json``.  ``--rehearse`` walks
it at the cell's tiny size on the CPU and proves nothing about the
limits.
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
    ap.add_argument("--workload", default="laguna_s_serve.code_closed")
    ap.add_argument("--seed", type=int, default=4100000201)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--only", default="")
    ap.add_argument("--all", action="store_true",
                    help="each control on every sampled request")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
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
    from benchmark.builders import serve_window
    from benchmark.reference.laguna_jnp import CONTROLS, ROUNDINGS
    config, tr = resolved["config"], resolved["traffic"]
    chips = resolved["cell"]["chips"]
    ctx = bench.Context(resolved, args, harness.Phases(),
                        harness.Tracer(False, args.workload, chips), None)
    s = serve_window.serve(ctx)
    load = s["load"]
    stamps = load.stamps[:load.k]
    rate = est.sync_rate(stamps, s["t_start"], s["t_end"])
    print("serve_tokens_per_s:", json.dumps(rate), "setup_s:", s["setup_s"],
          "compilations in the window:", s["compiles_in_window"],
          "ran dry:", s["ran_dry"], "memory:", s["memory"], flush=True)
    print("engine stats() at the end:", json.dumps(s["stats_end"]),
          flush=True)
    # the sync timeline around the ramp's end, from the first submit
    t0 = float(np.nanmin(load.t_submit))
    times, counts = est.sync_groups(stamps)
    out_by = np.cumsum(counts)
    ramp = t0 + tr["ramp_seconds"]
    near = (times > ramp - 2) & (times < ramp + 6)
    timeline = {"tokens_at_ramp_seconds": int(out_by[times <= ramp][-1])
                if (times <= ramp).any() else 0,
                "syncs": [[round(float(t - t0), 4), int(c), int(k)]
                          for t, c, k in zip(times[near], counts[near],
                                             out_by[near])],
                "window_opened_at_s": round(s["t_start"] - t0, 4),
                "tokens_at_window": int(out_by[times < s["t_start"]][-1])}
    print("sync timeline [s from the first submit, tokens, tokens out]:",
          json.dumps(timeline), flush=True)

    ref_cfg = config["reference"]
    ref_m = serve_window.reference_model(config)
    held = tuple(config["deployment"]["held_experts"])
    served = sorted(zip(s["sample"], s["results"]),
                    key=lambda p: p[1].prompt_len + p[1].tokens.size)

    def readings(pairs, wrong):
        out = [dict(serve_window.compare(
            ref_cfg, ref_m, held, s["weights"], s["requests"][r].prompt,
            res.tokens, res.logits, wrong=wrong), request=int(r))
            for r, res in pairs]
        return dict(serve_window.judge(ref_cfg, out), readings=out)

    out = {}
    print("== reference as_published ==", flush=True)
    out["as_published"] = readings(served, ())
    print(json.dumps(out["as_published"]), flush=True)
    for name in (args.only.split(",") if args.only
                 else CONTROLS + ROUNDINGS):
        print(f"== reference {name} ==", flush=True)
        out[name] = readings(served if args.all else served[:1], (name,))
        print(json.dumps(out[name]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "serve_window_probe.json"), "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "rehearsal": args.rehearse, "rate": rate,
                   "timeline": timeline, "memory": s["memory"],
                   "readings": out}, f, indent=1)
    refused = {k: not v["ok"] for k, v in out.items()
               if k != "as_published"}
    # hard exit, as the harness leaves a serve cell: the closed loop's
    # threads are daemons and nobody waits for them
    harness.finish({"as_published_ok": out["as_published"]["ok"],
                    "wrong_readings_refused": refused}, hard=True)


if __name__ == "__main__":
    main()
