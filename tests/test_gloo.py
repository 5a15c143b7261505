"""Host collective service tests (GlooWrapper analog,
ref: framework/fleet/gloo_wrapper.h; test pattern: thread-per-rank in
one process — the transport is identical across processes, proven by the
subprocess case)."""

import os
import subprocess
import sys
import threading

import numpy as np

from paddle_tpu.distributed.gloo import GlooContext


def _run_world(world, fn):
    """fn(ctx, rank) on one thread per rank; returns per-rank results."""
    ep = "127.0.0.1:0"
    ctxs = [None] * world
    ctxs[0] = GlooContext(0, world, ep, timeout=30.0)
    resolved = ctxs[0].endpoint
    for r in range(1, world):
        ctxs[r] = GlooContext(r, world, resolved, timeout=30.0)
    results = [None] * world
    errors = []

    def worker(r):
        try:
            results[r] = fn(ctxs[r], r)
        except Exception as e:   # noqa: BLE001
            errors.append((r, e))

    ts = [threading.Thread(target=worker, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    ctxs[0].close()
    assert not errors, errors
    return results


def test_gloo_allreduce_and_gather():
    def body(ctx, r):
        s = ctx.all_reduce(np.asarray([float(r + 1)]), op="sum")
        m = ctx.all_reduce(np.asarray(float(r)), op="max")
        g = ctx.all_gather(f"rank{r}")
        return s, m, g

    out = _run_world(4, body)
    for s, m, g in out:
        np.testing.assert_allclose(np.asarray(s), [10.0])
        assert float(np.asarray(m)) == 3.0
        assert g == ["rank0", "rank1", "rank2", "rank3"]


def test_gloo_broadcast_and_barrier():
    def body(ctx, r):
        ctx.barrier()
        v = ctx.broadcast({"vocab": 123} if r == 1 else None, root=1)
        ctx.barrier()
        return v

    out = _run_world(3, body)
    assert all(v == {"vocab": 123} for v in out)


def test_gloo_prod_handles_zeros_and_negatives():
    def body(ctx, r):
        vals = [2.0, -3.0, 0.0][r]
        return ctx.all_reduce(np.asarray(vals), op="prod")

    out = _run_world(3, body)
    for v in out:
        assert float(np.asarray(v)) == 0.0


_CHILD = r"""
import os
import sys
import time
import numpy as np
from paddle_tpu.distributed.gloo import GlooContext
rank, world, ep_file = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
if rank == 0:
    # bind an EPHEMERAL port (0) — a fixed port is a flake under suite
    # ordering: an earlier test's socket in TIME_WAIT (or a stray child)
    # makes the bind fail only when the whole suite runs.  The resolved
    # endpoint is published through an atomic file rename.
    ctx = GlooContext(0, world, "127.0.0.1:0", timeout=60.0)
    tmp = ep_file + ".tmp"
    with open(tmp, "w") as f:
        f.write(ctx.endpoint)
    os.replace(tmp, ep_file)
else:
    deadline = time.monotonic() + 60.0
    while not os.path.exists(ep_file):
        if time.monotonic() > deadline:
            raise TimeoutError("rank0 never published its endpoint")
        time.sleep(0.05)
    with open(ep_file) as f:
        ep = f.read().strip()
    ctx = GlooContext(rank, world, ep, timeout=60.0)
s = ctx.all_reduce(np.asarray([rank + 1.0]))
# the barrier both proves the rendezvous AND sequences the teardown:
# every rank has its result before rank 0 may stop the hub, so no rank
# can race a collective against server shutdown
ctx.barrier()
print("RESULT", float(np.asarray(s)[0]))
if rank == 0:
    ctx.close()
"""


def test_gloo_across_real_processes(tmp_path):
    """Two real processes rendezvous over TCP (the DCN-tier proof,
    pattern: ref test_collective_base.py launches localhost workers).
    Deterministic under suite load: ephemeral port + file handshake, no
    fixed port to collide on."""
    script = tmp_path / "gloo_child.py"
    script.write_text(_CHILD)
    ep_file = tmp_path / "gloo_endpoint"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "/root/repo"
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), "2", str(ep_file)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd="/root/repo")
        for r in range(2)]
    try:
        outs = [p.communicate(timeout=120) for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    for p, (o, e) in zip(procs, outs):
        assert p.returncode == 0, (o, e)
        assert "RESULT 3.0" in o, (o, e)
