"""Static SPMD launch auditor tests (framework/launch_audit.py): one
seeded program (or timeline pair) per deadlock/divergence class with an
anchored ``launch-*`` diagnostic, every static proof run with
``Executor._compile`` monkeypatched to raise (0 compiles, 0 live
collectives), and the two-process rendezvous drill (abort with exit 43
instead of hanging)."""

import os
import sys
import threading

import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import flags
from paddle_tpu.framework import executor as executor_mod
from paddle_tpu.framework import launch_audit as la
from paddle_tpu.framework.analysis import (
    COLLECTIVE_DIVERGENT_CF, LAUNCH_DEADLOCK_CYCLE,
    LAUNCH_FINGERPRINT_DRIFT, LAUNCH_SCHEDULE_DIVERGENCE, VerifyResult,
    verify_program)
from paddle_tpu.framework.core import (Program, program_guard,
                                       reset_default_programs)
from paddle_tpu.framework.pipe import apply_pipeline
from paddle_tpu.testing import faultline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture(autouse=True)
def _no_compiles(monkeypatch):
    """Every static launch proof in this module must run without ONE
    compile — the auditor's whole claim is pre-compile, pre-collective.
    (The subprocess drill does not compile either.)"""

    def boom(*a, **k):
        raise AssertionError("launch audit attempted a compile")

    monkeypatch.setattr(executor_mod.Executor, "_compile", boom)
    yield


def _one(result, code):
    hits = result.by_code(code)
    assert hits, (f"no {code!r} diagnostic; got "
                  f"{[(d.code, d.message) for d in result.diagnostics]}")
    assert all(d.severity == "error" for d in hits)
    return hits[0]


def _flat_allreduce(n=2):
    p = Program()
    b = p.global_block()
    for i in range(n):
        b.create_var(name=f"g{i}", shape=(64,), is_data=True)
        b.append_op(type="c_allreduce_sum", inputs={"X": [f"g{i}"]},
                    outputs={"Out": [f"g{i}"]},
                    attrs={"ring_id": 0, "_axis_name": "dp"})
    return p


def _pipelined(schedule="1f1b", microbatches=4):
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


# ---------------------------------------------------------------------------
# seeded deadlock classes (wait-for progress game)
# ---------------------------------------------------------------------------


def _branch_wrapped_collective():
    """A collective inside a data-dependent branch."""
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
    return p


def test_collective_under_divergent_control_flow_deadlocks():
    """A collective inside a data-dependent branch: the rank taking the
    other arm never issues it — verify_program proves the deadlock
    statically alongside the existing CF-divergence diagnostic."""
    result = verify_program(_branch_wrapped_collective())
    d = _one(result, LAUNCH_DEADLOCK_CYCLE)
    assert "c_allreduce_sum" in d.message
    # rides with (does not replace) the existing control-flow diagnostic
    assert result.by_code(COLLECTIVE_DIVERGENT_CF)


def test_proglint_launch_flag_gates_and_embeds_the_audit():
    """``proglint --launch --json``: a clean pipelined program passes with
    its audit embedded; the branch-wrapped collective exits 1
    naming ``launch-deadlock-cycle``."""
    import io
    import json
    from tools.proglint import lint
    sink = io.StringIO()
    assert lint(_pipelined(), launch=True, as_json=True, out=sink) == 0
    assert json.loads(sink.getvalue())["launch_audit"]["ok"]
    sink = io.StringIO()
    assert lint(_branch_wrapped_collective(), launch=True, as_json=True,
                out=sink) == 1
    codes = {d["code"] for d in json.loads(sink.getvalue())
             ["launch_audit"]["diagnostics"]}
    assert LAUNCH_DEADLOCK_CYCLE in codes


def test_cross_stage_collective_span_deadlocks():
    """A collective stamped on stage 1 reading a stage-0 value: its
    producer-side peer sits behind the boundary hop the owner is
    waiting on — a 2-cycle in the wait-for graph."""
    main = _pipelined()
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
    d = _one(result, LAUNCH_DEADLOCK_CYCLE)
    assert d.op_type == "c_allreduce_sum"


def test_ppermute_ring_inconsistent_hop_order_cycles():
    """3-rank ppermute ring where every rank issues its outgoing hop
    first: the classic cyclic wait, reported with the (rank, tick,
    channel) cycle."""

    def hop(a, b, tick):
        return la.CollEvent("ppermute", ("pp",), 0, ("act",),
                            perm=((a, b),), group=(a, b), tick=tick)

    timelines = {0: [hop(0, 1, 0), hop(2, 0, 1)],
                 1: [hop(1, 2, 0), hop(0, 1, 1)],
                 2: [hop(2, 0, 0), hop(1, 2, 1)]}
    result = la.check_deadlock_freedom(timelines)
    d = _one(result, LAUNCH_DEADLOCK_CYCLE)
    assert "rank 0" in d.message and "rank 1" in d.message \
        and "rank 2" in d.message


def test_consistent_ppermute_ring_is_deadlock_free():
    """The same ring issued in consistent order on every rank drains."""

    def hop(a, b, tick):
        return la.CollEvent("ppermute", ("pp",), 0, ("act",),
                            perm=((a, b),), group=(a, b), tick=tick)

    # every rank lists the ring's hops in ring-position order
    timelines = {r: [hop(0, 1, 0), hop(1, 2, 1), hop(2, 0, 2)]
                 for r in range(3)}
    for r in range(3):
        timelines[r] = [e for e in timelines[r] if e.participates(r)]
    assert la.check_deadlock_freedom(timelines).ok


# ---------------------------------------------------------------------------
# seeded schedule-divergence classes (pairwise timeline compare)
# ---------------------------------------------------------------------------


def test_warmup_depth_mismatch_across_schedule_families():
    """Rank 1 launched with zero_bubble while rank 0 runs 1f1b: the
    warm-up depths disagree, so the boundary hops interleave
    differently — caught as schedule divergence."""
    a = la.expand_pipe_timelines(_pipelined("1f1b"))
    b = la.expand_pipe_timelines(_pipelined("zero_bubble"))
    merged = {0: a[0], 1: b[1]}
    result = VerifyResult()
    la.check_timeline_compatibility(merged, result)
    la.check_deadlock_freedom(merged, result)
    d = _one(result, LAUNCH_SCHEDULE_DIVERGENCE)
    assert "rank 0" in d.message and "rank 1" in d.message


def test_bucket_reorder_names_both_ranks_and_anchors():
    """Two ranks emit the SAME grad-sync collectives in different
    order: the first mismatching event is reported with both ranks'
    ticks and the peer's creation callstack."""
    p = _flat_allreduce()
    q = p.clone()
    blk = q.global_block()
    blk.ops[0], blk.ops[1] = blk.ops[1], blk.ops[0]
    report = la.audit_launch(p, peer_programs=[q])
    assert not report.ok
    d = _one(report.result, LAUNCH_SCHEDULE_DIVERGENCE)
    assert d.op_type == "c_allreduce_sum"
    assert any("test_launch_audit.py" in f for f in d.callstack), \
        d.callstack
    assert "rank 0" in d.message and "rank 1" in d.message


def test_identical_ranks_audit_clean():
    p = _flat_allreduce()
    report = la.audit_launch(p, peer_programs=[p.clone()])
    assert report.ok
    assert not report.result.by_code(LAUNCH_SCHEDULE_DIVERGENCE)


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------


def test_fingerprint_deterministic_and_flag_sensitive():
    p = _flat_allreduce()
    fp0 = la.rank_fingerprint(p)
    assert fp0["digest"] == la.rank_fingerprint(p)["digest"]
    old = flags.flag("use_flash_attention")
    flags.set_flags({"use_flash_attention": not old})
    try:
        fp1 = la.rank_fingerprint(p)
    finally:
        flags.set_flags({"use_flash_attention": old})
    assert fp1["digest"] != fp0["digest"]
    result = la.check_fingerprint_agreement([fp0, fp1])
    d = _one(result, LAUNCH_FINGERPRINT_DRIFT)
    assert "flags" in d.message and "rank 1" in d.message


def test_fingerprint_schedule_drift_names_event():
    p = _flat_allreduce()
    q = p.clone()
    blk = q.global_block()
    blk.ops[0], blk.ops[1] = blk.ops[1], blk.ops[0]
    div = la.fingerprint_divergence(
        [la.rank_fingerprint(p), la.rank_fingerprint(q)])
    assert div is not None and div["rank"] == 1
    assert "schedule" in div["components"]
    assert div["event"]["index"] == 0


# ---------------------------------------------------------------------------
# clean pipelined expansion + verify_program integration
# ---------------------------------------------------------------------------


def test_clean_pipelined_program_audits_clean():
    """A genuine 2-stage 1F1B program expands through the schedule
    table and drains: no launch-* diagnostics, no errors."""
    report = la.audit_launch(_pipelined())
    assert report.ok, [d.format() for d in report.result.errors()]
    timelines = la.expand_pipe_timelines(_pipelined())
    assert set(timelines) == {0, 1}
    # both ranks see the boundary hops + the grad-sync tail
    assert all(len(t) >= 3 for t in timelines.values())


def test_verify_program_runs_launch_audit_on_pipelined():
    """verify_program picks up the pipe schedule table and runs the
    expansion proofs for free — clean program stays clean."""
    result = verify_program(_pipelined())
    assert not result.by_code(LAUNCH_DEADLOCK_CYCLE)
    assert not result.by_code(LAUNCH_SCHEDULE_DIVERGENCE)


# ---------------------------------------------------------------------------
# rendezvous: the one dynamic leg
# ---------------------------------------------------------------------------


def test_rank_divergence_seam_registered():
    assert "rank_divergence" in faultline.seams()


def test_verify_rank_agreement_in_process_agree_and_abort(tmp_path):
    """Two threads rendezvous through the gloo hub: identical
    fingerprints agree; an armed rank-1 bucket reorder makes BOTH
    ranks raise LaunchDivergenceError naming rank 1 — nobody hangs."""
    p = _flat_allreduce()
    fp = la.rank_fingerprint(p)

    def drive(endpoint_file):
        errs = {}

        def runner(r):
            try:
                la.verify_rank_agreement(str(endpoint_file), r, 2,
                                         fingerprint=fp, timeout=30)
            except la.LaunchDivergenceError as e:
                errs[r] = str(e)

        ts = [threading.Thread(target=runner, args=(r,))
              for r in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts), "rendezvous hung"
        return errs

    assert drive(tmp_path / "ep_agree") == {}
    faultline.arm("rank_divergence", action="nan", mode="bucket_reorder",
                  match={"rank": 1})
    try:
        errs = drive(tmp_path / "ep_diverge")
    finally:
        faultline.disarm()
    assert set(errs) == {0, 1}
    assert all("rank 1" in m for m in errs.values())
    assert la.EXIT_LAUNCH_DIVERGENCE == 43
    assert la.LaunchDivergenceError("x").exit_code == 43


def test_two_process_rendezvous_drill_aborts_not_hangs():
    """The acceptance drill: two REAL processes, rank 1 arms the seam,
    both abort at rendezvous with exit code 43 naming the op."""
    from tools.launch_probe import _rendezvous_drill
    res = _rendezvous_drill(timeout=120)
    assert res["aborted_not_hung"], res
    assert res["exit_codes"] == [43, 43], res
    assert res["named_op"] and res["named_rank"], res
