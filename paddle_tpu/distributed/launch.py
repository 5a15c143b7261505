"""Multi-process launcher (ref: python/paddle/distributed/launch.py).

The reference spawns one process per GPU and wires PADDLE_* env vars.  On
TPU the launcher's job is per-HOST (one jax process per host, all chips of
the host attached): set the jax.distributed coordination env and exec the
training script on every host.  On Cloud TPU pods the platform runner
already does this; this module covers manual multi-host bring-up and
single-host multi-process CPU testing.  A chip belongs to one process, so
``nproc > 1`` on one host is refused unless the children are pinned to the
CPU backend (``JAX_PLATFORMS=cpu``): one process drives every local chip
through the mesh."""

from __future__ import annotations

import os
import subprocess
import sys


def launch(script_args=None, nproc: int = 1, coordinator: str = "127.0.0.1:12355"):
    """Spawn ``nproc`` worker processes running the given script, each with
    JAX_COORDINATOR/NUM_PROCESSES/PROCESS_ID env wired (the analog of the
    reference's PADDLE_TRAINER_ID / PADDLE_TRAINER_ENDPOINTS)."""
    script_args = script_args if script_args is not None else sys.argv[1:]
    if not script_args:
        raise SystemExit("usage: python -m paddle_tpu.distributed.launch "
                         "[--nproc N] script.py [args...]")
    if nproc > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"launch: --nproc {nproc} on one host would have {nproc} "
            f"processes claim the same chips; run ONE process per host "
            f"(it drives every local chip through the mesh), or set "
            f"JAX_PLATFORMS=cpu for multi-process CPU testing")
    procs = []
    for pid in range(nproc):
        env = dict(os.environ)
        env.update({
            "JAX_COORDINATOR_ADDRESS": coordinator,
            "JAX_NUM_PROCESSES": str(nproc),
            "JAX_PROCESS_ID": str(pid),
            # reference-compatible names some scripts read:
            "PADDLE_TRAINER_ID": str(pid),
            "PADDLE_TRAINERS_NUM": str(nproc),
        })
        procs.append(subprocess.Popen([sys.executable] + list(script_args),
                                      env=env))
    rc = 0
    for p in procs:
        rc |= p.wait()
    return rc


def main():
    args = sys.argv[1:]
    nproc = 1
    coordinator = "127.0.0.1:12355"
    while args and args[0].startswith("--"):
        if args[0] == "--nproc":
            nproc = int(args[1]); args = args[2:]
        elif args[0] == "--coordinator":
            coordinator = args[1]; args = args[2:]
        else:
            raise SystemExit(f"unknown flag {args[0]}")
    raise SystemExit(launch(args, nproc, coordinator))


if __name__ == "__main__":
    main()
