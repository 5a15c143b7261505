#!/usr/bin/env python
"""Launch-audit census: seed every static deadlock/divergence class and
prove each is caught BEFORE the first collective.

The pod-scale failure mode this guards is the silent cross-rank hang:
ranks whose programs disagree on collective kind/order/peers block
forever in different collectives with no diagnostic.  The probe seeds
one program (or timeline pair) per class and asserts the static auditor
(framework/launch_audit.py) names it with an anchored ``launch-*``
diagnostic — with **0 compiles and 0 live device collectives**, proven
by the executor compile counter — then runs the one drill that must be
dynamic: a real two-process rendezvous where rank 1 arms the
``rank_divergence`` faultline seam (a divergent bucket reorder) and
both ranks must ABORT with exit code 43 naming the op, instead of
hanging.  The classes (each also a live test in
tests/test_launch_audit.py):

1. **control_flow_collective** — a collective under a data-dependent
   branch: ranks taking different arms deadlock
   (``launch-deadlock-cycle`` via the wait-for game, anchored);
2. **stage_crossing_span** — a collective stamped in stage s reading a
   stage-s' value: its mesh peers rendezvous against mismatched 1F1B
   schedules (``launch-deadlock-cycle``);
3. **ppermute_ring_order** — a 3-rank ppermute ring issued with
   inconsistent hop order: the classic cyclic wait
   (``launch-deadlock-cycle`` with the (rank, tick, channel) cycle);
4. **warmup_depth_mismatch** — one rank launched with a different
   1F1B-family schedule: warm-up depths disagree, forward and backward
   hops interleave differently (``launch-schedule-divergence`` +
   ``launch-deadlock-cycle``);
5. **bucket_reorder** — a rank whose grad-bucketing pass emitted the
   same collectives in a different order
   (``launch-schedule-divergence`` naming both ranks' ops);
6. **fingerprint_flag_flip** — a rank launched with one
   lowering-relevant flag flipped: ``launch-fingerprint-drift`` naming
   the drifted component;
7. **rendezvous_divergence_drill** — two real processes: rank 1 arms
   ``rank_divergence``; ``verify_rank_agreement`` on the gloo substrate
   aborts BOTH ranks at rendezvous with exit code 43 and the op named,
   within the timeout (the abort-don't-hang contract).

Usage::

    python tools/launch_probe.py
    python tools/launch_probe.py --selftest   # tmp artifact + assertions
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SCHEMA = "paddle_tpu.launch_audit/1"

#: every statically seeded class and the launch-* code that must catch it
STATIC_CLASSES = {
    "control_flow_collective": "launch-deadlock-cycle",
    "stage_crossing_span": "launch-deadlock-cycle",
    "ppermute_ring_order": "launch-deadlock-cycle",
    "warmup_depth_mismatch": "launch-schedule-divergence",
    "bucket_reorder": "launch-schedule-divergence",
    "fingerprint_flag_flip": "launch-fingerprint-drift",
}


def _flat_allreduce_program(n=2):
    from paddle_tpu.framework.core import Program
    p = Program()
    b = p.global_block()
    for i in range(n):
        b.create_var(name=f"g{i}", shape=(64,), is_data=True)
        b.append_op(type="c_allreduce_sum", inputs={"X": [f"g{i}"]},
                    outputs={"Out": [f"g{i}"]},
                    attrs={"ring_id": 0, "_axis_name": "dp"})
    return p


def _pipelined_program(schedule="1f1b", microbatches=4):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.core import (Program, program_guard,
                                           reset_default_programs)
    from paddle_tpu.framework.pipe import apply_pipeline
    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16])
        h = fluid.layers.fc(x, 16, act="relu")
        h = fluid.layers.fc(h, 16, act="relu")
        y = fluid.layers.fc(h, 4)
        loss = fluid.layers.mean(y)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    apply_pipeline(main, 2, microbatches, schedule=schedule)
    return main


def _seed_control_flow_collective():
    from paddle_tpu.framework.analysis import verify_program
    from paddle_tpu.framework.core import Program
    p = Program()
    b = p.global_block()
    b.create_var(name="x", shape=(8,), is_data=True)
    b.create_var(name="cond", shape=(1,), dtype="bool", is_data=True)
    b.create_var(name="out", shape=(8,))
    sub = p._create_block()
    sub.append_op(type="c_allreduce_sum", inputs={"X": ["x"]},
                  outputs={"Out": ["x"]}, attrs={"ring_id": 0})
    p._rollback()
    b.append_op(type="conditional_block",
                inputs={"Cond": ["cond"], "Closure": ["x"]},
                outputs={"Out": ["out"]},
                attrs={"true_block": sub, "false_block": sub,
                       "closure_names": ["x"], "true_out_names": ["x"],
                       "false_out_names": ["x"]})
    return verify_program(p)


def _seed_stage_crossing_span():
    from paddle_tpu.framework import launch_audit as la
    from paddle_tpu.framework.analysis import VerifyResult
    main = _pipelined_program()
    blk = main.global_block()
    fwd = [op for op in blk.ops
           if op.attrs.get("_pipe_stage") is not None
           and op.type != "pipe_stage_boundary"]
    s0_out = next(n for op in fwd if op.attrs["_pipe_stage"] == 0
                  for n in op.output_names())
    boundary = next(op for op in blk.ops
                    if op.type == "pipe_stage_boundary")
    bidx = blk.ops.index(boundary)
    span = blk.append_op(type="c_allreduce_sum",
                         inputs={"X": [s0_out]},
                         outputs={"Out": [s0_out]},
                         attrs={"ring_id": 7, "_axis_name": "tp",
                                "_pipe_stage": 1})
    blk.ops.remove(span)
    blk.ops.insert(bidx + 1, span)
    result = VerifyResult()
    la.check_deadlock_freedom(la.expand_pipe_timelines(main), result)
    return result


def _seed_ppermute_ring_order():
    from paddle_tpu.framework import launch_audit as la

    def hop(a, b, tick):
        return la.CollEvent("ppermute", ("pp",), 0, ("act",),
                            perm=((a, b),), group=(a, b), tick=tick)

    # each rank issues its outgoing hop before its incoming one — the
    # consistent order would be ring-position order on every rank
    timelines = {0: [hop(0, 1, 0), hop(2, 0, 1)],
                 1: [hop(1, 2, 0), hop(0, 1, 1)],
                 2: [hop(2, 0, 0), hop(1, 2, 1)]}
    return la.check_deadlock_freedom(timelines)


def _seed_warmup_depth_mismatch():
    from paddle_tpu.framework import launch_audit as la
    from paddle_tpu.framework.analysis import VerifyResult
    a = la.expand_pipe_timelines(_pipelined_program("1f1b"))
    b = la.expand_pipe_timelines(_pipelined_program("zero_bubble"))
    merged = {0: a[0], 1: b[1]}       # rank 1 launched the wrong family
    result = VerifyResult()
    la.check_timeline_compatibility(merged, result)
    la.check_deadlock_freedom(merged, result)
    return result


def _seed_bucket_reorder():
    from paddle_tpu.framework import launch_audit as la
    p = _flat_allreduce_program()
    q = p.clone()
    blk = q.global_block()
    blk.ops[0], blk.ops[1] = blk.ops[1], blk.ops[0]
    return la.audit_launch(p, peer_programs=[q]).result


def _seed_fingerprint_flag_flip():
    from paddle_tpu import flags
    from paddle_tpu.framework import launch_audit as la
    p = _flat_allreduce_program()
    fp0 = la.rank_fingerprint(p)
    old = flags.flag("use_flash_attention")
    flags.set_flags({"use_flash_attention": not old})
    try:
        fp1 = la.rank_fingerprint(p)
    finally:
        flags.set_flags({"use_flash_attention": old})
    return la.check_fingerprint_agreement([fp0, fp1])


_CHILD = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
rank = int(sys.argv[1])
from paddle_tpu.testing import faultline
from paddle_tpu.framework import launch_audit as la
from paddle_tpu.framework.core import Program
if rank == 1:
    faultline.arm("rank_divergence", action="nan", mode="bucket_reorder")
p = Program(); b = p.global_block()
for i in range(2):
    b.create_var(name=f"g{{i}}", shape=(64,), is_data=True)
    b.append_op(type="c_allreduce_sum", inputs={{"X": [f"g{{i}}"]}},
                outputs={{"Out": [f"g{{i}}"]}},
                attrs={{"ring_id": 0, "_axis_name": "dp"}})
try:
    la.verify_rank_agreement({ep!r}, rank, 2, program=p, timeout=60)
except la.LaunchDivergenceError as e:
    print(f"rank {{rank}} aborted: {{e}}", flush=True)
    sys.exit(la.EXIT_LAUNCH_DIVERGENCE)
print(f"rank {{rank}} agreed", flush=True)
"""


def _rendezvous_drill(timeout=120):
    """Two real processes; rank 1 arms the seam; both must abort with
    exit 43 naming the op, within the timeout (no hang).  Both are CPU
    processes — the drill needs no chip, and two processes could not
    share one."""
    d = tempfile.mkdtemp(prefix="launch_drill_")
    ep = os.path.join(d, "endpoint")
    script = _CHILD.format(repo=REPO, ep=ep)
    procs = [subprocess.Popen(
        [sys.executable, "-c", script, str(r)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
        for r in range(2)]
    outs, codes, hung = [], [], False
    for pr in procs:
        try:
            out, _ = pr.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            pr.kill()
            out, _ = pr.communicate()
            hung = True
        outs.append(out)
        codes.append(pr.returncode)
    return {
        "ok": (not hung and codes == [43, 43]
               and all("c_allreduce_sum" in o for o in outs)),
        "aborted_not_hung": not hung,
        "exit_codes": codes,
        "named_op": all("c_allreduce_sum" in o for o in outs),
        "named_rank": all("rank 1" in o for o in outs),
        "output_rank0": outs[0].strip().splitlines()[-1:],
        "output_rank1": outs[1].strip().splitlines()[-1:],
    }


def run():
    from paddle_tpu.monitor import stat
    compiles_before = stat("executor_compile_count").get()

    seeders = {
        "control_flow_collective": _seed_control_flow_collective,
        "stage_crossing_span": _seed_stage_crossing_span,
        "ppermute_ring_order": _seed_ppermute_ring_order,
        "warmup_depth_mismatch": _seed_warmup_depth_mismatch,
        "bucket_reorder": _seed_bucket_reorder,
        "fingerprint_flag_flip": _seed_fingerprint_flag_flip,
    }
    classes = {}
    for name, seed in seeders.items():
        result = seed()
        want = STATIC_CLASSES[name]
        hits = result.by_code(want)
        anchored = bool(hits) and all(
            h.severity == "error" and (h.op_type or h.callstack
                                       or "rank" in h.message)
            for h in hits)
        classes[name] = {
            "expected_code": want,
            "caught": bool(hits),
            "anchored": anchored,
            "diagnostic_codes": sorted({d.code for d in result.errors()}),
            "first_message": hits[0].message[:240] if hits else None,
            "ok": bool(hits) and anchored,
        }
    compiles_after = stat("executor_compile_count").get()

    # the clean side: a genuine pipelined program must audit clean
    from paddle_tpu.framework import launch_audit as la
    clean = la.audit_launch(_pipelined_program())
    drill = _rendezvous_drill()

    art = {
        "metric": "launch_audit",
        "schema": SCHEMA,
        "classes": classes,
        "clean_pipelined_ok": clean.ok,
        "clean_fingerprint": clean.fingerprint["digest"],
        "compiles_during_static_census":
            int(compiles_after - compiles_before),
        "live_collectives": 0,     # by construction: no executor runs
        "rendezvous_divergence_drill": drill,
        "accounting": {
            "classes_seeded": len(classes),
            "classes_caught": sum(1 for c in classes.values()
                                  if c["ok"]),
            "exit_code_launch_divergence": la.EXIT_LAUNCH_DIVERGENCE,
        },
    }
    return art


def check(art):
    """What a run has to show."""
    assert art["metric"] == "launch_audit"
    assert art["schema"] == SCHEMA
    assert set(art["classes"]) == set(STATIC_CLASSES)
    for name, c in art["classes"].items():
        assert c["ok"] is True, (name, c)
        assert c["expected_code"] == STATIC_CLASSES[name]
        assert c["expected_code"] in c["diagnostic_codes"], (name, c)
    assert art["compiles_during_static_census"] == 0
    assert art["live_collectives"] == 0
    assert art["clean_pipelined_ok"] is True
    d = art["rendezvous_divergence_drill"]
    assert d["ok"] is True, d
    assert d["aborted_not_hung"] and d["exit_codes"] == [43, 43]
    assert d["named_op"] and d["named_rank"]
    acct = art["accounting"]
    assert acct["classes_caught"] == acct["classes_seeded"] == \
        len(STATIC_CLASSES)
    assert acct["exit_code_launch_divergence"] == 43


def main():
    art = run()
    check(art)
    print(json.dumps(art["accounting"]))
    print("launch_probe OK")


if __name__ == "__main__":
    main()
