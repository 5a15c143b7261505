"""Pipeline-parallel meta-op.

The reference runs pipeline parallelism with a thread per stage
(`SectionWorker`) pushing microbatch scopes through queues
(ref: framework/pipeline_trainer.cc:24, section_worker.cc:82,109,150;
built by PipelineOptimizer._split_program, ref: optimizer.py:3628,3751).

TPU-natively the whole pipeline is ONE SPMD program over the `pp` mesh
axis: every device runs `lax.switch` on its stage index to execute its
stage's op segment, activations hop stage→stage+1 with `lax.ppermute`,
and the GPipe microbatch schedule is a `lax.scan` over M + S - 1 ticks.
XLA differentiates the scan/switch/ppermute composition, replacing the
reference's separate backward sections.  Without a `pp` axis the op runs
the stages sequentially per microbatch (single-device semantics — the
reference's num_microbatches-loop on one worker).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, LoweringContext


def _run_segment(seg_ops, env, ctx):
    from ..framework.executor import run_ops
    return run_ops(seg_ops, env, ctx)


@register("pipeline")
def _pipeline_op(ctx, ins, attrs):
    feeds = dict(zip(attrs["feed_names"], ins.get("Feeds") or []))
    closure = dict(zip(attrs["closure_names"], ins.get("Closure") or []))
    stages = attrs["stage_blocks"]          # list of op-lists
    boundaries = attrs["boundary_names"]    # len S-1, var name between stages
    loss_name = attrs["loss_name"]
    M = int(attrs["num_microbatches"])
    axis = attrs.get("_axis_name", "pp")
    S = len(stages)

    # microbatch the feeds: [B, ...] -> [M, B//M, ...]
    mb_feeds = {}
    for n, v in feeds.items():
        if v.shape[0] % M:
            raise ValueError(
                f"batch {v.shape[0]} not divisible by num_microbatches {M}")
        mb_feeds[n] = v.reshape((M, v.shape[0] // M) + v.shape[1:])

    def seg_env(extra):
        env = dict(closure)
        env.update(extra)
        return env

    if axis not in ctx.axis_names:
        # single-device fallback: scan microbatches through all stages
        def body(key, mb):
            sub = LoweringContext(key, ctx.mesh, ctx.axis_names, ctx.is_test)
            env = seg_env(mb)
            for seg in stages:
                env = _run_segment(seg, env, sub)
            k_next = jax.random.split(sub.key, 1)[0]
            return k_next, jnp.mean(env[loss_name])
        _, losses = lax.scan(body, ctx.next_key(), mb_feeds)
        return {"Loss": jnp.mean(losses)}

    idx = lax.axis_index(axis)
    n_pp = lax.axis_size(axis)
    if n_pp != S:
        raise ValueError(f"pipeline has {S} stages but pp axis size {n_pp}")
    perm = [(i, i + 1) for i in range(S - 1)]     # no wrap: stage0 gets zeros

    # boundary buffer: dim0 is the microbatch size, rest from the declared
    # boundary var shape (uniform across stage cuts — the GPipe contract)
    mb_size = next(iter(mb_feeds.values())).shape[1]
    bshape = (mb_size,) + tuple(attrs["boundary_shape"])[1:]
    bdtype = attrs.get("boundary_dtype", "float32")

    def make_branch(si, seg):
        def branch(state, f0, fl, key):
            sub = LoweringContext(key, ctx.mesh, ctx.axis_names, ctx.is_test)
            if si == 0:
                env = seg_env(f0)
            else:
                env = seg_env(fl if si == S - 1 else {})
                env[boundaries[si - 1]] = state
            env = _run_segment(seg, env, sub)
            if si == S - 1:
                return (jnp.zeros(bshape, bdtype),
                        jnp.mean(env[loss_name]).astype(jnp.float32))
            return (env[boundaries[si]].astype(bdtype),
                    jnp.asarray(0.0, jnp.float32))
        return branch

    branches = [make_branch(i, seg) for i, seg in enumerate(stages)]
    T = M + S - 1

    def tick(carry, t):
        state, loss_sum, key = carry
        k_step, k_next = jax.random.split(key)
        t0 = jnp.clip(t, 0, M - 1)                 # stage-0 microbatch index
        tl = jnp.clip(t - (S - 1), 0, M - 1)       # last-stage microbatch
        f0 = {n: v[t0] for n, v in mb_feeds.items()}
        fl = {n: v[tl] for n, v in mb_feeds.items()}
        out_state, loss = lax.switch(idx, branches, state, f0, fl, k_step)
        valid = jnp.logical_and(t - (S - 1) >= 0, t - (S - 1) < M)
        loss_sum = loss_sum + jnp.where(valid, loss, 0.0)
        state = lax.ppermute(out_state, axis, perm)
        return (state, loss_sum, k_next), None

    init = (jnp.zeros(bshape, bdtype), jnp.asarray(0.0, jnp.float32),
            ctx.next_key())
    (_, loss_sum, _), _ = lax.scan(tick, init, jnp.arange(T))
    # only the last stage accumulated loss; broadcast to all pp ranks.
    # MUST be the g-collective (psum fwd, identity bwd): jax transposes a
    # raw psum to psum, which would double-count every stage's grads S×.
    from .tp_ops import _mp_reduce
    return {"Loss": _mp_reduce(loss_sum, axis) / M}
