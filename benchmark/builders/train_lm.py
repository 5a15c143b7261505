"""Builder for ``kind: train_lm`` configurations: a decoder language
model's training step (``paddle_tpu.models.decoder_lm``: pre-norm
RoPE/GQA attention with a per-layer window, dropless sparse experts,
one chip's share of an expert-parallel deployment) through
``fluid.Executor.prepare`` fed by ``DataLoader.from_generator``.

``run(ctx)`` builds the timed program, compares ONE prepared step of it
at the timed sizes with the plain reference (loss, two gradients, what
Adam then did to those two parameters, every layer's routing, the
assignment counter as ``PreparedStep.stats`` reports it), frees that,
makes the training state, warms up, measures the window and (traced
runs) a traced tail of the same load.  The record carries the keys
``builders/train.py`` returns, so the trainer's readers take it too.
``setup_s`` leaves the comparison out: it is the benchmark's check, not
the program's set-up, and its cold compile varies by tens of seconds."""

from __future__ import annotations

import dataclasses
import itertools
import json
import time

import numpy as np

from .. import flops_lm, traffic as traffic_mod
from ..harness import annotate, compile_count, memory_now, say
from ..reference import mellum_jnp


def lm_config(config: dict):
    """The program's config from the file: published keys at the top
    level, the router's width and the held experts from ``deployment``."""
    from paddle_tpu.models.decoder_lm import DecoderLMConfig
    keys = {f.name for f in dataclasses.fields(DecoderLMConfig)}
    kw = {k: v for k, v in config["model"].items() if k in keys}
    dep = config["deployment"]
    kw.update(layer_types=tuple(config["layer_types"]),
              rope_parameters=config["rope_parameters"],
              num_experts=dep["router_experts"],
              held_experts=tuple(dep["held_experts"]))
    return DecoderLMConfig(**kw)


def reference_model(config: dict) -> dict:
    """The reference's view of the same file: the published keys, with
    ``num_experts`` the router's width."""
    m = dict(config["model"], layer_types=list(config["layer_types"]),
             rope_parameters=config["rope_parameters"])
    m["num_experts"] = config["deployment"]["router_experts"]
    return m


def draw_of(traffic: dict, seed: int) -> int:
    """The draw ``seed`` picks: the traffic file lists ``draws``, each
    the seed of eight sequences AND of the weights.  A dropless router's
    work is the assignments it makes, which follow ids and weights, so
    the file fixes the work (as benchmark/traffic.py fixes a serving
    mix's multiset) and the seed picks among draws of like work."""
    return traffic["draws"][seed % len(traffic["draws"])]


def build(config: dict, seed: int):
    """(startup, loss, program): pure-bf16 AMP around Adam, as
    ``builders/train.py`` builds BERT; the weights are drawn from
    ``seed`` (the cell passes its draw)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.models import decoder_lm
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed % (2 ** 31 - 1) + 1
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, loss, _ = decoder_lm.build_lm_network(lm_config(config))
        opt = fluid.optimizer.Adam(config["builder"]["learning_rate"])
        decorate(opt, use_pure_bf16=True).minimize(loss)
    return startup, loss, main


def draw_batches(traffic: dict, vocab: int, seed: int, count: int):
    """``count`` batches from ``seed``: sequences of ``seq_len + 1`` ids
    uniform over the vocabulary held, the first ``seq_len`` fed, the last
    ``seq_len`` the labels.  Full length, no padding."""
    rng = traffic_mod.rng_for(seed, "stream")
    b, s = traffic["global_batch"], traffic["seq_len"]
    out = []
    for _ in range(count):
        tokens = rng.integers(0, vocab, (b, s + 1), dtype=np.int64)
        out.append({"src_ids": np.ascontiguousarray(tokens[:, :-1]),
                    "labels": np.ascontiguousarray(tokens[:, 1:])})
    return out


def lm_batches(traffic: dict, vocab: int, seed: int):
    """The training batches: the ``distinct_batches`` sequences of the
    draw ``seed`` picks, in an order ``seed`` decides."""
    out = draw_batches(traffic, vocab, draw_of(traffic, seed),
                       traffic["distinct_batches"])
    order = traffic_mod.rng_for(seed, "order").permutation(len(out))
    return [out[i] for i in order]


def _rel_l2(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _is_param(name: str) -> bool:
    return not (name.startswith("@") or "_moment" in name
                or "_pow_acc" in name or name.startswith("learning_rate")
                or "loss_scaling" in name or name.endswith(".load_stats"))


def _router_ops(program):
    return [op for op in program.global_block().ops
            if op.type == "moe_topk_router"]


def _set_mismatch(a, b) -> float:
    """Share of tokens whose SET of experts differs."""
    return float(np.mean(np.any(np.sort(a, -1) != np.sort(b, -1), -1)))


def program_step(exe, startup, loss, program, batch, names):
    """One PREPARED step of the timed program on fresh weights, the path
    the window times: (the weights it started from, loss, gradients,
    what the step added to the named parameters, routed ids per layer,
    each router's own input, assignments counted as
    ``PreparedStep.stats`` reports them).  Everything it made on the
    device is dropped."""
    import paddle_tpu.fluid as fluid
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        weights = {n: np.asarray(scope.find_var(n))
                   for n in scope.var_names() if _is_param(n)}
        routers = _router_ops(program)
        prepared = exe.prepare(
            program, fetch_list=[loss] + [n + "@GRAD" for n in names]
            + [op.outputs["TopkIndex"][0] for op in routers]
            + [op.inputs["X"][0] for op in routers])
        out = [np.asarray(h.numpy()) for h in prepared.run(batch)]
        prepared.wait()
        counted = prepared.stats["moe_assignments_local"]
        prepared.close()                # the state back into the scope
        updates = [np.asarray(scope.find_var(n)) - weights[n]
                   for n in names]
        scope.drop_all()
    k, r = len(names), len(routers)
    return weights, float(np.mean(out[0])), out[1:1 + k], updates, \
        out[1 + k:1 + k + r], out[1 + k + r:], counted


def compare_with_reference(ctx, exe, startup, loss, program,
                           reference_overrides=None) -> dict:
    """The timed program against ``reference/mellum_jnp.py`` at the timed
    sizes: one batch of the traffic's own shape, one step; loss, the
    gradients of the named parameters and what Adam made of them, every
    layer's routed sets and the count of assignments to the held
    experts.  ``reference_overrides`` (tools/lm_reference_probe.py)
    changes what the REFERENCE computes — the readings a tolerance has
    to refuse."""
    config, ref = ctx.config, ctx.config["reference"]
    m = reference_model(config)
    held = tuple(config["deployment"]["held_experts"])
    batch = draw_batches(ctx.traffic, m["vocab_size"], ctx.seed + 1, 1)[0]
    names = list(ref["grad_params"])
    weights, got, grads, updates, routed, router_inputs, counted = \
        program_step(exe, startup, loss, program, batch, names)
    kw = dict(reference_overrides or {})
    router_dtype = kw.pop("router_dtype", None)
    m = {**m, **kw}                      # changed model keys
    # the router ALONE, on the program's own router input: nothing before
    # it enters, so an f32 router agrees and a rounded one cannot
    router_mismatch = [
        _set_mismatch(idx, np.asarray(mellum_jnp.router_only(
            x_in, weights[op.inputs["W"][0]], m, router_dtype)))
        for idx, x_in, op in zip(routed, router_inputs,
                                 _router_ops(program))]
    del router_inputs
    want, want_grads, want_routed = mellum_jnp.lm_loss_and_grads(
        weights, batch, names, m, held=held, q_block=ref["q_block"],
        row_block=ref["row_block"], router_dtype=router_dtype)
    want = float(want)
    want_grads = {n: np.asarray(g) for n, g in want_grads.items()}
    want_routed = [np.asarray(r) for r in want_routed]
    want_count = int(np.asarray(mellum_jnp.local_counts(
        want_routed, held, m["num_experts"])).sum())
    del weights

    res = {"loss": got, "reference_loss": want,
           "loss_rel_err": abs(got - want) / abs(want),
           "grad_rel_err": {n: _rel_l2(g, want_grads[n])
                            for n, g in zip(names, grads)},
           # the parameter's change against Adam's first step on the
           # gradient the step fetched: a state left unchanged reads 1
           "update_rel_err": {
               n: _rel_l2(u, mellum_jnp.adam_first_step(
                   g, config["builder"]["learning_rate"]))
               for n, g, u in zip(names, grads, updates)},
           # share of tokens whose SET of experts differs, per layer:
           # against the whole reference, and against its router alone
           "routing_mismatch": [_set_mismatch(a, b)
                                for a, b in zip(routed, want_routed)],
           "router_mismatch": router_mismatch,
           "assignments_local": counted,
           "reference_assignments_local": want_count}
    res["assignments_rel_err"] = abs(counted - want_count) \
        / max(want_count, 1)
    # what a capacity of 1.25 x the uniform share would have dropped of
    # the reference's assignments to the held experts (the other reading
    # of assignments_rel_tol: this layer has no capacity)
    per_expert = np.asarray(mellum_jnp.local_counts(
        want_routed, held, m["num_experts"]))
    cap = 1.25 * want_routed[0].size / m["num_experts"]
    res["capacity_1.25_would_drop"] = float(
        np.maximum(per_expert - cap, 0).sum() / max(per_expert.sum(), 1))
    tol = ref["grad_rel_tol"]           # one limit, or one a parameter
    res["ok"] = bool(
        np.isfinite(got) and res["loss_rel_err"] <= ref["loss_rel_tol"]
        and all(err <= (tol[n] if isinstance(tol, dict) else tol)
                for n, err in res["grad_rel_err"].items())
        and max(res["update_rel_err"].values()) <= ref["update_rel_tol"]
        and max(res["routing_mismatch"]) <= ref["routing_mismatch_tol"]
        and max(router_mismatch) <= ref["router_mismatch_tol"]
        and res["assignments_rel_err"] <= ref["assignments_rel_tol"])
    say("reference comparison: " + json.dumps(res))
    return res


def run(ctx) -> dict:
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.dataloader import DataLoader

    config, tr, m = ctx.config, ctx.traffic, ctx.config["model"]
    if tr.get("mesh"):
        raise SystemExit("train_lm runs one chip's share: no mesh")
    exe = fluid.Executor(fluid.TPUPlace(0))
    startup, loss, program = build(config, draw_of(tr, ctx.seed))
    ctx.phases.mark("program build")

    t0 = time.monotonic()
    ref = compare_with_reference(ctx, exe, startup, loss, program)
    comparison_s = time.monotonic() - t0
    ctx.phases.mark("reference comparison, NOT in setup_s (one prepared "
                    "step of the timed program with gradients and routing "
                    "fetched, the blocked jnp forward and backward, all of "
                    "it freed)")

    scope = fluid.Scope()
    guard = fluid.scope_guard(scope)
    guard.__enter__()
    exe.run(startup)
    jax.block_until_ready([scope.find_var(n) for n in scope.var_names()])
    ctx.phases.mark("startup program: weights made on the device")

    batches = lm_batches(tr, m["vocab_size"], ctx.seed)
    ctx.phases.mark("batches drawn on the host")
    loader = DataLoader.from_generator(
        capacity=8, use_double_buffer=bool(tr["double_buffer"]))
    loader.set_batch_generator(lambda: itertools.cycle(batches),
                               places=fluid.TPUPlace(0))
    prepared = exe.prepare(program, fetch_list=[loss])
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
        points (where the device's load counters are folded too)."""
        prepared.wait()
        steps = wait_ns = 0
        memory = [0]
        t0 = time.monotonic()
        while time.monotonic() - t0 < seconds:
            if steps % 16 == 8:     # the device is mid-step: two are queued
                memory = [max(a, b) for a, b in zip(memory, memory_now(1))]
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
    setup_s = ctx.open_window() - comparison_s
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
    stats2 = dict(prepared.stats)
    close = getattr(it, "close", None)
    if close is not None:
        close()
    guard.__exit__(None, None, None)

    window_stats = {k: stats1[k] - stats0.get(k, 0) for k in stats1
                    if isinstance(stats1[k], (int, float))}

    def flops(assignments, n_steps):
        return flops_lm.lm_flops_per_step(
            m, config["layer_types"],
            config["deployment"]["router_experts"], tr["global_batch"],
            tr["seq_len"], assignments / max(n_steps, 1))

    parts = flops(window_stats["moe_assignments_local"], steps)
    # the traced tail's kernels against the tail's own assignments
    tail_parts = flops(stats2["moe_assignments_local"]
                       - stats1["moe_assignments_local"], traced_steps) \
        if traced_steps else parts
    say("FLOPs a step (benchmark/flops_lm.py): " + json.dumps(parts)
        + "; assignments to the held experts a step "
        f"{window_stats['moe_assignments_local'] / max(steps, 1):.1f}")
    tokens = steps * tr["global_batch"] * tr["seq_len"]
    ok = ref["ok"] and np.isfinite(last_loss) and compiles_in_window == 0
    return {
        "kind": "train", "correct": bool(ok), "attempted": steps,
        "failed": 0 if np.isfinite(last_loss) else steps,
        "setup_s": setup_s, "compiles_in_window": compiles_in_window,
        "end_to_end": {"train_tokens_per_s": tokens / span},
        "steps": steps, "span_s": span, "tokens": tokens,
        "feed_wait_ns": wait_ns, "prepared_stats": window_stats,
        "flops_per_step": parts["step"], "lm_flops": tail_parts,
        "trace": trace, "traced_steps": traced_steps, "reference": ref,
        "memory_samples": memory,
        "counts": {"steps": steps, "tokens": tokens,
                   "compiles_in_window": compiles_in_window,
                   "assignments_to_held_experts":
                       window_stats["moe_assignments_local"]},
    }
