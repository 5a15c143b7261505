"""Multi-process collective data parallelism, proven end-to-end with real
localhost subprocesses — the reference's distributed test contract
(ref: test_dist_base.py:506 _run_cluster, test_collective_base.py:34)
translated to jax.distributed: 2 worker processes × 2 virtual CPU devices
each = a dp4 mesh spanning processes, grad-allreduce riding the
coordination backend, losses compared to single-process full-batch
training."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_cluster(nproc=2, timeout=420):
    """Spawn nproc copies of dist_collective_runner.py wired together."""
    runner = os.path.join(os.path.dirname(__file__),
                          "dist_collective_runner.py")
    coordinator = f"127.0.0.1:{_free_port()}"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for pid in range(nproc):
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root
        # workers are CPU processes: a chip belongs to one process, so
        # nproc workers on one host could not share it
        env["JAX_PLATFORMS"] = "cpu"
        env.update({
            "JAX_COORDINATOR_ADDRESS": coordinator,
            "JAX_NUM_PROCESSES": str(nproc),
            "JAX_PROCESS_ID": str(pid),
            "PADDLE_TRAINER_ID": str(pid),
            "PADDLE_TRAINERS_NUM": str(nproc),
        })
        procs.append(subprocess.Popen(
            [sys.executable, runner], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    # drain all workers CONCURRENTLY — collectively-coupled processes can
    # deadlock on a full pipe if drained one at a time
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(len(procs)) as pool:
        futs = [pool.submit(p.communicate, timeout=timeout) for p in procs]
        try:
            outs = [f.result() for f in futs]
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
    results, errs = [], []
    for p, (out, err) in zip(procs, outs):
        errs.append(err)
        for line in out.splitlines():
            if line.startswith("DIST_LOSSES "):
                results.append(json.loads(line[len("DIST_LOSSES "):]))
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
    assert len(results) == nproc, f"missing worker output; stderr: {errs}"
    return results


def _single_process_losses():
    """Same model/optimizer/batches on the full global batch, one process."""
    from tests.dist_collective_runner import build_model, global_batches
    import paddle_tpu.fluid as fluid
    main, startup, loss = build_model()
    with fluid.program_guard(main, startup):
        fluid.optimizer.SGD(0.2).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for xs, ys in global_batches():
            l, = exe.run(main, feed={"x": xs, "label": ys},
                         fetch_list=[loss])
            losses.append(float(l))
    return losses


def test_two_process_collective_dp_matches_single():
    results = _run_cluster(nproc=2)
    by_pid = {r["pid"]: r for r in results}
    assert set(by_pid) == {0, 1}
    # both workers saw the global dp4 mesh
    assert by_pid[0]["ndev"] == 4
    # replicated training: every worker reports identical (pmean'd) losses
    np.testing.assert_allclose(by_pid[0]["losses"], by_pid[1]["losses"],
                               rtol=1e-6)
    # and they match single-process full-batch training
    single = _single_process_losses()
    np.testing.assert_allclose(single, by_pid[0]["losses"], rtol=2e-3,
                               err_msg="multi-process dp diverged from "
                                       "single-process")
    # training is actually learning
    assert by_pid[0]["losses"][-1] < by_pid[0]["losses"][0]
