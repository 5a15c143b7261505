"""Preemption-safe training: kill mid-run with SIGTERM, resume, and the
final model must be BIT-EXACT vs an uninterrupted run (SURVEY §5 — the
first-class TPU story; ref baseline: fleet checkpoint-resume,
incubate/fleet/collective/__init__.py:236)."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from paddle_tpu.distributed.preemption import PREEMPTED_EXIT_CODE

RUNNER = os.path.join(os.path.dirname(__file__), "preemption_runner.py")
DRILL_RUNNER = os.path.join(os.path.dirname(__file__),
                            "reshard_drill_runner.py")
MAX_STEPS = 40


def _launch(ckpt_dir, slow=False):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env["JAX_PLATFORMS"] = "cpu"
    args = [sys.executable, RUNNER, ckpt_dir, str(MAX_STEPS)]
    if slow:
        args.append("slow")
    return subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _result(out):
    for line in out.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"no RESULT in output:\n{out[-2000:]}")


def test_sigterm_checkpoint_and_bitexact_resume(tmp_path):
    # uninterrupted reference run
    ref_dir = str(tmp_path / "ref")
    p = _launch(ref_dir)
    out, err = p.communicate(timeout=420)
    assert p.returncode == 0, err[-2000:]
    ref = _result(out)
    assert ref["first_step"] == 0

    # interrupted run: SIGTERM mid-training, synchronized on step markers
    ckpt_dir = str(tmp_path / "preempt")
    p = _launch(ckpt_dir, slow=True)
    deadline = time.time() + 300
    while time.time() < deadline:
        line = p.stdout.readline()
        if line.startswith("STEP ") and int(line.split()[1]) >= 5:
            break
    else:
        p.kill()
        raise AssertionError("never reached step 5")
    p.send_signal(signal.SIGTERM)
    out, err = p.communicate(timeout=420)
    assert p.returncode == PREEMPTED_EXIT_CODE, (p.returncode, err[-2000:])

    # relaunch: resumes from the checkpoint and completes
    p = _launch(ckpt_dir)
    out, err = p.communicate(timeout=420)
    assert p.returncode == 0, err[-2000:]
    res = _result(out)
    assert 0 < res["first_step"] < MAX_STEPS, res  # really resumed
    # the resumed model is bit-exact vs uninterrupted training
    assert res["digest"] == ref["digest"], (res, ref)
    assert res["losses_tail"] == ref["losses_tail"]


# ---------------------------------------------------------------------------
# elasticity drill: SIGTERM on 8 devices → relaunch on the 2 survivors
# ---------------------------------------------------------------------------

DRILL_STEPS = 10


def _launch_drill(ckpt_dir, ndev, slow=False):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env.pop("XLA_FLAGS", None)           # runner pins its own device count
    env["JAX_PLATFORMS"] = "cpu"
    args = [sys.executable, DRILL_RUNNER, ckpt_dir, str(DRILL_STEPS),
            str(ndev)]
    if slow:
        args.append("slow")
    return subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def test_preemption_drill_shrink_to_surviving_devices(tmp_path):
    """The full elastic loop: auto_shard picks a ZeRO-3 layout on 8
    devices, SIGTERM mid-run → clean layout-stamped checkpoint + exit
    42, relaunch on 2 surviving devices → the planner replans, the
    restore RESHARDS (grouped all_gathers, 0 compiles on rejected
    candidates), and the loss curve continues within 1e-6 of the
    uninterrupted 8-device run."""
    import numpy as np

    ref_dir = str(tmp_path / "ref")
    p = _launch_drill(ref_dir, 8)
    out, err = p.communicate(timeout=420)
    assert p.returncode == 0, err[-2000:]
    ref = _result(out)
    assert ref["layout"]["fsdp"] > 1, ref      # budget forced ZeRO-3

    ckpt_dir = str(tmp_path / "drill")
    p = _launch_drill(ckpt_dir, 8, slow=True)
    deadline = time.time() + 300
    while time.time() < deadline:
        line = p.stdout.readline()
        if line.startswith("STEP ") and int(line.split()[1]) >= 3:
            break
    else:
        p.kill()
        raise AssertionError("never reached step 3")
    p.send_signal(signal.SIGTERM)
    out, err = p.communicate(timeout=420)
    assert p.returncode == PREEMPTED_EXIT_CODE, (p.returncode, err[-2000:])

    # relaunch on 2 surviving devices: replan + resharded restore
    p = _launch_drill(ckpt_dir, 2)
    out, err = p.communicate(timeout=420)
    assert p.returncode == 0, err[-2000:]
    res = _result(out)
    assert 0 < res["first_step"] < DRILL_STEPS, res
    assert res["layout"] != ref["layout"], res          # really replanned
    assert res["resharded"] is True
    assert res["reshard_steps"].get("all_gather", 0) >= 1, res
    assert res["reshard_compiles"] == 0
    # loss curve continues as if never interrupted
    np.testing.assert_allclose(res["losses"],
                               ref["losses"][res["first_step"]:],
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# PreemptionHandler robustness (in-process)
# ---------------------------------------------------------------------------


def _noop_exe():
    import paddle_tpu.fluid as fluid
    return fluid.Executor(fluid.CPUPlace())


def test_handler_chains_preexisting_signal_handler(tmp_path):
    """Installing a PreemptionHandler must not clobber a handler the
    launcher already registered — both run."""
    from paddle_tpu.distributed.preemption import PreemptionHandler
    hits = []
    prev = signal.signal(signal.SIGUSR1, lambda s, f: hits.append(s))
    try:
        handler = PreemptionHandler(_noop_exe(), str(tmp_path), None,
                                    signals=(signal.SIGUSR1,),
                                    exit_on_preempt=False)
        os.kill(os.getpid(), signal.SIGUSR1)
        time.sleep(0.05)
        assert handler.preempted
        assert hits == [signal.SIGUSR1]        # chained, not clobbered
    finally:
        signal.signal(signal.SIGUSR1, prev)


def test_handler_sigint_is_opt_in(tmp_path):
    from paddle_tpu.distributed.preemption import PreemptionHandler
    prev = signal.getsignal(signal.SIGINT)
    try:
        h = PreemptionHandler(_noop_exe(), str(tmp_path), None,
                              signals=(), exit_on_preempt=False)
        assert signal.getsignal(signal.SIGINT) is prev   # default: no
        h2 = PreemptionHandler(_noop_exe(), str(tmp_path), None,
                               signals=(), catch_sigint=True,
                               exit_on_preempt=False)
        assert signal.getsignal(signal.SIGINT) == h2._on_signal
    finally:
        signal.signal(signal.SIGINT, prev)


def test_handler_drains_inflight_async_write_before_exit(tmp_path,
                                                         monkeypatch):
    """A preemption with an async checkpoint write in flight must join
    the write BEFORE saving + exiting — a SIGTERM can never tear a
    half-written checkpoint."""
    from paddle_tpu.distributed.preemption import PreemptionHandler

    order = []

    class FakeCheckpointer:
        def drain(self):
            order.append("drain")
            return True

    handler = PreemptionHandler(_noop_exe(), str(tmp_path), None,
                                signals=(), exit_on_preempt=False,
                                checkpointer=FakeCheckpointer())
    monkeypatch.setattr(handler, "save",
                        lambda step: order.append("save"))
    handler._preempted = True
    assert handler.step_done(7) is True
    assert order == ["drain", "save"]
