"""Builder for ``kind: train`` configurations: a BERT pretraining step
through ``fluid.Executor.prepare`` fed by ``DataLoader.from_generator``,
on one chip or under ``fleet.distributed_optimizer`` on a mesh.

``run(ctx)`` builds, compares with the plain reference, warms up, measures
the window and (traced runs) a traced tail of the same load, and returns
the record the end-to-end arithmetic and the per-layer readers take
their numbers from."""

from __future__ import annotations

import dataclasses
import itertools
import json
import time

import numpy as np

from .. import flops, traffic as traffic_mod
from ..harness import annotate, compile_count, memory_now, say
from ..reference import bert_jnp


def _bert_config(config: dict, dropout: bool):
    from paddle_tpu.models.bert import BertConfig
    keys = {f.name for f in dataclasses.fields(BertConfig)}
    kw = {k: v for k, v in config["model"].items() if k in keys}
    if not dropout:
        kw["hidden_dropout_prob"] = 0.0
        kw["attention_probs_dropout_prob"] = 0.0
    return BertConfig(**kw)


def _build(config: dict, seed: int, dropout: bool, mesh_axes=None):
    """(startup, loss, program to run).  One chip: pure-bf16 AMP
    around Adam, as ``bench.py`` builds it.  A mesh: the same through
    ``fleet.distributed_optimizer`` (``strategy.amp`` is pure bf16)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert
    b = config["builder"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed % (2 ** 31 - 1) + 1
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, total, _, _ = bert.build_pretrain_network(
            _bert_config(config, dropout))
        opt = fluid.optimizer.Adam(b["learning_rate"])
        if mesh_axes:
            from paddle_tpu.distributed.fleet import (
                DistributedStrategy, UserDefinedRoleMaker,
                distributed_optimizer, fleet)
            from paddle_tpu.parallel import build_mesh
            n = int(np.prod(list(mesh_axes.values())))
            fleet.init(UserDefinedRoleMaker(0, 1))
            strategy = DistributedStrategy()
            strategy.amp = True
            strategy.mesh = build_mesh(dict(mesh_axes), jax.devices()[:n])
            distributed_optimizer(opt, strategy).minimize(total)
            return startup, total, fleet.main_program
        from paddle_tpu.contrib.mixed_precision import decorate
        decorate(opt, use_pure_bf16=True).minimize(total)
    return startup, total, main


def _rel_l2(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def compare_with_reference(ctx, exe, mesh_axes=None) -> dict:
    """The trainer against ``reference/bert_jnp.py`` at the published
    widths: a dropout-off build of the same program (same AMP, same
    seed, so the same initial weights, and on a mesh the same Fleet
    program over the same chips, so the loss and gradients compared are
    those AFTER the all-reduce), one batch of ``reference.batch``
    sequences; the loss and the gradients of the named parameters."""
    import paddle_tpu.fluid as fluid
    config, ref = ctx.config, ctx.config["reference"]
    m = ctx.config["model"]
    startup, total, program = _build(config, ctx.seed, dropout=False,
                                     mesh_axes=mesh_axes)
    small = dict(ctx.traffic, global_batch=ref["batch"], distinct_batches=1)
    batch = traffic_mod.stream_batches(small, m, ctx.seed + 1)[0]
    names = list(ref["grad_params"])
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        weights = {n: np.asarray(scope.find_var(n))
                   for n in scope.var_names() if _is_param(n)}
        out = exe.run(program, feed=batch,
                      fetch_list=[total] + [n + "@GRAD" for n in names])
    loss, grads = bert_jnp.pretrain_loss_and_grads(
        weights, batch, names, n_layer=m["num_hidden_layers"],
        n_head=m["num_attention_heads"], eps=m["layer_norm_eps"])
    loss = float(loss)
    got = float(np.mean(out[0]))
    res = {"mesh": mesh_axes, "loss": got, "reference_loss": loss,
           "loss_rel_err": abs(got - loss) / abs(loss), "grad_rel_err": {}}
    ok = np.isfinite(got) and res["loss_rel_err"] <= ref["loss_rel_tol"]
    for n, g in zip(names, out[1:]):
        err = _rel_l2(g, grads[n])
        res["grad_rel_err"][n] = err
        ok = ok and err <= ref["grad_rel_tol"]
    res["ok"] = bool(ok)
    say("reference comparison: " + json.dumps(res))
    return res


def _is_param(name: str) -> bool:
    return not (name.startswith("@") or "_moment" in name
                or "_pow_acc" in name or name.startswith("learning_rate")
                or "loss_scaling" in name)


def run(ctx) -> dict:
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.dataloader import DataLoader

    config, tr, m = ctx.config, ctx.traffic, ctx.config["model"]
    mesh_axes = tr.get("mesh")
    chips = ctx.cell["chips"]
    if mesh_axes and int(np.prod(list(mesh_axes.values()))) != chips:
        raise SystemExit(f"traffic mesh {mesh_axes} does not span the "
                         f"cell's {chips} chips")
    exe = fluid.Executor(fluid.TPUPlace(0))
    startup, total, program = _build(config, ctx.seed, dropout=True,
                                     mesh_axes=mesh_axes)
    ctx.phases.mark("program build")
    scope = fluid.Scope()
    guard = fluid.scope_guard(scope)
    guard.__enter__()
    exe.run(startup)
    jax.block_until_ready([scope.find_var(n) for n in scope.var_names()])
    ctx.phases.mark("startup program: weights made on the device")

    ref = compare_with_reference(ctx, exe, mesh_axes)
    ctx.phases.mark("reference comparison (dropout-off build, compile or "
                    "cache load, plain jnp forward and backward)")

    batches = traffic_mod.stream_batches(tr, m, ctx.seed)
    ctx.phases.mark("batches drawn on the host")
    loader = DataLoader.from_generator(
        capacity=8, use_double_buffer=bool(tr["double_buffer"]))
    loader.set_batch_generator(lambda: itertools.cycle(batches),
                               places=fluid.TPUPlace(0))
    prepared = exe.prepare(program, fetch_list=[total])
    it = iter(loader)
    handles = prepared.run(next(it))
    first_loss = float(np.mean(handles[0].numpy()))
    ctx.phases.mark("first step: trace, lower, compile or cache load")
    for _ in range(tr["warmup_steps"]):
        handles = prepared.run(next(it))
    prepared.wait()
    ctx.phases.mark(f"warm-up: {tr['warmup_steps']} steps")

    def loop(seconds):
        """Whole steps for about ``seconds``, between two blocking
        points; returns (steps, host seconds, feed-wait ns)."""
        prepared.wait()
        steps = wait_ns = 0
        memory = [0] * chips
        t0 = time.monotonic()
        while time.monotonic() - t0 < seconds:
            if steps % 16 == 8:     # the device is mid-step: two are queued
                memory = [max(a, b)
                          for a, b in zip(memory, memory_now(chips))]
            with annotate("feed_next"):
                w0 = time.perf_counter_ns()
                feed = next(it)
                wait_ns += time.perf_counter_ns() - w0
            with annotate("prepared_run"):
                out = prepared.run(feed)
            steps += 1
        with annotate("final_wait"):
            prepared.wait()
        return steps, time.monotonic() - t0, wait_ns, out, memory

    compiles0 = compile_count()
    stats0 = dict(prepared.stats)
    setup_s = ctx.open_window()
    steps, span, wait_ns, out, memory = loop(ctx.seconds)
    stats1 = dict(prepared.stats)
    compiles_in_window = compile_count() - compiles0
    last_loss = float(np.mean(out[0].numpy()))
    say(f"window: {steps} whole steps in {span:.6f} s between two blocking "
        f"points; loss {first_loss:.4f} (first step) -> {last_loss:.4f}; "
        f"compilations inside the window: {compiles_in_window}")
    say("PreparedStep.stats: " + json.dumps(prepared.stats))

    trace = None
    traced_steps = 0
    if ctx.tracer.enabled:
        ctx.tracer.start()
        traced_steps = loop(tr["trace_seconds"])[0]
        trace = ctx.tracer.stop()
    close = getattr(it, "close", None)
    if close is not None:
        close()
    guard.__exit__(None, None, None)

    tokens = steps * tr["global_batch"] * tr["seq_len"]
    ok = ref["ok"] and np.isfinite(last_loss) and compiles_in_window == 0
    return {
        "kind": "train", "correct": bool(ok), "attempted": steps,
        "failed": 0 if np.isfinite(last_loss) else steps,
        "setup_s": setup_s, "compiles_in_window": compiles_in_window,
        "end_to_end": {"train_tokens_per_s": tokens / span},
        "steps": steps, "span_s": span, "tokens": tokens,
        "feed_wait_ns": wait_ns,
        "prepared_stats": {k: stats1[k] - stats0.get(k, 0) for k in stats1
                           if isinstance(stats1[k], (int, float))},
        "flops_per_step": flops.bert_flops_per_step(
            m, tr["global_batch"], tr["seq_len"], tr["num_masks"]),
        "trace": trace, "traced_steps": traced_steps, "reference": ref,
        "memory_samples": memory,
        "counts": {"steps": steps, "tokens": tokens,
                   "compiles_in_window": compiles_in_window},
    }
