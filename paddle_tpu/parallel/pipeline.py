"""Pipeline parallelism: program-level PipelineOptimizer (device_guard
stage annotations, ref: optimizer.py:3628 PipelineOptimizer + fluid
device_guard) and a functional SPMD GPipe for homogeneous stacks.

Two tiers:

1. ``PipelineOptimizer`` — API parity with the reference: split the
   forward by `fluid.device_guard("tpu:k")` annotations, collapse it into
   one `pipeline` meta-op (ops/pipeline_op.py) that runs the GPipe
   schedule over the `pp` mesh axis.  Params stay replicated across pp
   (every device traces every `lax.switch` branch); grads psum over pp.

2. ``gpipe_spmd`` — the memory-efficient TPU-native form for homogeneous
   stages (transformer stacks): stage params are STACKED on a leading
   axis sharded over pp, so each device materialises only its own stage's
   weights; activations rotate with ppermute.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.core import (default_main_program, Variable)
from ..framework import core as _core
from ..optimizer import Optimizer


# ---------------------------------------------------------------------------
# functional SPMD GPipe (homogeneous stages, stage-sharded params)
# ---------------------------------------------------------------------------


def gpipe_spmd(stage_fn: Callable, stage_params, microbatches,
               axis_name: str = "pp"):
    """Run `y_m = stage_{S-1}(... stage_0(x_m))` for M microbatches with the
    GPipe schedule, inside shard_map over `axis_name`.

    Args:
      stage_fn: (params, x) -> y with x/y the SAME shape (uniform boundary).
      stage_params: THIS device's stage params (from a [S, ...]-stacked tree
        sharded P('pp') outside shard_map).
      microbatches: [M, mb, ...] — full input stream (only stage 0 reads it).
    Returns [M, mb, ...] outputs, replicated over the pp axis.
    """
    S = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    T = M + S - 1
    perm = [(i, i + 1) for i in range(S - 1)]
    state0 = jnp.zeros_like(microbatches[0])
    outs0 = jnp.zeros_like(microbatches)

    def tick(carry, t):
        state, outs = carry
        inp = jnp.where(idx == 0, microbatches[jnp.clip(t, 0, M - 1)], state)
        y = stage_fn(stage_params, inp)
        tl = t - (S - 1)
        write = jnp.logical_and(idx == S - 1,
                                jnp.logical_and(tl >= 0, tl < M))
        outs = jnp.where(write,
                         lax.dynamic_update_index_in_dim(
                             outs, y, jnp.clip(tl, 0, M - 1), 0),
                         outs)
        state = lax.ppermute(y, axis_name, perm)
        return (state, outs), None

    (_, outs), _ = lax.scan(tick, (state0, outs0), jnp.arange(T))
    # g-collective, not raw psum: raw psum transposes to psum and would
    # inflate grads by the pp size when a loss is taken downstream
    from ..ops.tp_ops import _mp_reduce
    return _mp_reduce(outs, axis_name)  # only last stage nonzero → broadcast


# ---------------------------------------------------------------------------
# program-level PipelineOptimizer
# ---------------------------------------------------------------------------


def _stage_of(op) -> int:
    dev = op.attrs.get("op_device") or ""
    if ":" in str(dev):
        try:
            return int(str(dev).rsplit(":", 1)[1])
        except ValueError:
            return 0
    return 0


class PipelineOptimizer:
    """ref: optimizer.py:3628 — wraps an optimizer; `minimize` splits the
    forward by device_guard stage annotations into the `pipeline` meta-op,
    then delegates backward+update to the inner optimizer.  Use with a mesh
    whose `pp` axis size equals the number of stages."""

    def __init__(self, optimizer: Optimizer, num_microbatches: int = 1,
                 start_cpu_core_id: int = 0):
        self._inner = optimizer
        self.num_microbatches = num_microbatches

    def minimize(self, loss: Variable, startup_program=None,
                 parameter_list=None, no_grad_set=None):
        main = loss.block.program
        block = main.global_block()
        ops = [op for op in block.ops if op.type not in ("feed", "fetch")]

        n_stages = max(_stage_of(op) for op in ops) + 1
        if n_stages < 2:
            raise ValueError(
                "PipelineOptimizer needs >=2 device_guard stages "
                "(with fluid.device_guard('tpu:k'):)")
        stages = [[] for _ in range(n_stages)]
        for op in ops:
            stages[_stage_of(op)].append(op)

        # boundary var between consecutive stages: produced in stage i,
        # consumed in stage i+1 (single-var contract, like the reference's
        # section in/out queues)
        boundaries = []
        for i in range(n_stages - 1):
            produced = set()
            for op in stages[i]:
                produced |= set(op.output_names())
            consumed = set()
            for op in stages[i + 1]:
                consumed |= set(op.input_names())
                produced -= set(op.output_names())
            cross = [n for n in produced if n in consumed]
            # later stages may also read it (e.g. residual) — disallowed
            cross = [n for n in cross
                     if block._find_var_recursive(n) is not None]
            if len(cross) != 1:
                raise ValueError(
                    f"stage {i}->{i + 1} must hand off exactly one var, "
                    f"got {cross}")
            boundaries.append(cross[0])
        bvar = block._find_var_recursive(boundaries[0])

        # feeds = non-persistable vars nobody produces
        produced_all = set()
        for op in ops:
            produced_all |= set(op.output_names())
        feed_names, closure_names = [], []
        for op in ops:
            for n in op.input_names():
                if n in produced_all or n in feed_names or \
                        n in closure_names:
                    continue
                v = block._find_var_recursive(n)
                if v is not None and not v.persistable and \
                        not isinstance(v, _core.Parameter):
                    feed_names.append(n)
                else:
                    closure_names.append(n)

        loss_out = block.create_var(name=loss.name + "@pipeline",
                                    shape=(), dtype="float32")
        pipe_op = _core.Operator(
            block, "pipeline",
            {"Feeds": feed_names, "Closure": closure_names},
            {"Loss": [loss_out.name]},
            {"feed_names": feed_names, "closure_names": closure_names,
             "stage_blocks": stages, "boundary_names": boundaries,
             "boundary_shape": tuple(bvar.shape),
             "boundary_dtype": bvar.dtype,
             "loss_name": loss.name,
             "num_microbatches": self.num_microbatches,
             "_axis_name": "pp"})
        block.ops = [pipe_op]
        main._bump_version()

        result = self._inner.minimize(loss_out,
                                      startup_program=startup_program,
                                      parameter_list=parameter_list,
                                      no_grad_set=no_grad_set)
        self._insert_pp_grad_allreduce(block)
        return result

    def _insert_pp_grad_allreduce(self, block):
        """Each device only produced grads for its own stage's params (other
        switch branches contribute zeros) — sum over pp replicates the full
        grads, the analog of the reference's cross-section param sync
        (ref: pipeline_trainer.cc section param sync per sync_steps)."""
        from ..framework.core import grad_var_name
        bw_idx = next((i for i, op in enumerate(block.ops)
                       if op.type == "backward"), None)
        if bw_idx is None:
            return
        bw = block.ops[bw_idx]
        at = bw_idx + 1
        for pname in bw.attrs["param_names"]:
            g = grad_var_name(pname)
            block._insert_op(at, type="c_allreduce_sum",
                             inputs={"X": [g]}, outputs={"Out": [g]},
                             attrs={"_axis_name": "pp"})
            at += 1
        block.program._bump_version()

    def __getattr__(self, item):
        return getattr(self._inner, item)
