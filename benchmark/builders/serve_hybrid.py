"""Builder for ``kind: serve_hybrid`` configurations: a decoder of
linear-attention (gated delta rule) and full-attention layers
(``paddle_tpu.models.hybrid_decoder``) behind ``serving.DecodeEngine`` —
a paged K/V pool for the full layers beside a pool of per-sequence
recurrent states for the linear ones — under a closed loop.

The load generator, the fixed schedule (the file's ``order_seed``, the
window opened on ``ramp_tokens``), the frozen heap and the judged LOGITS
are ``builders/serve_lm.py``'s, imported; the record's keys are
``builders/serve.py``'s, so the serve readers take it.  What differs: the
model and its reference (``reference/olmo_hybrid_jnp.py``: ONE full causal
pass, the recurrence token by token, over prompt + served tokens on the
same bfloat16-rounded weights); a limit more, over a request's LAST rows
(the error of a recurrent state kept too coarse grows along a request);
and ``weight_bytes``, which counts beside the weights a step reads the
recurrent state the window's mean live rows a step read and write."""

from __future__ import annotations

import dataclasses
import gc
import json
import queue
import threading
import time

import numpy as np

from .. import estimators as est, flops_gdn, traffic as traffic_mod
from ..harness import compile_count, memory_now, say
from ..reference import olmo_hybrid_jnp
from . import serve as serve_mod
from .serve_lm import (LIMITS as LM_LIMITS, Load, close_and_take_weights,
                       requests_for, wait_for_tokens)

#: rows at a request's end the ``last_rows_rel_l2`` reading is over
LAST_ROWS = 64
LIMITS = LM_LIMITS + ("last_rows_rel_l2",)


def decoder_config(config: dict):
    """The program's config from the file: published keys at the top
    level, ``layer_types`` cut to the depth held."""
    from paddle_tpu.models.hybrid_decoder import HybridDecoderConfig
    keys = {f.name for f in dataclasses.fields(HybridDecoderConfig)}
    kw = {k: v for k, v in config["model"].items() if k in keys}
    return HybridDecoderConfig(layer_types=config["layer_types"], **kw)


def reference_model(config: dict) -> dict:
    m = config["model"]
    return dict(m, layer_types=tuple(
        config["layer_types"][:m["num_hidden_layers"]]))


def build_engine(config: dict, seed: int):
    from paddle_tpu.models.hybrid_decoder import HybridDecoder
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config["engine"].items()}
    model = HybridDecoder(decoder_config(config),
                          seed=seed % (2 ** 31 - 1) + 1)
    # started once every client's first request is queued, so that what
    # the worker admits first does not depend on thread timing
    return DecodeEngine(model, DecodeConfig(**kw), auto_start=False)


def sample_of(requests, clients: int, count: int, seed: int):
    """The compared requests: some of the clients' FIRST requests, so that
    each is served whole, chunked prefill and every decode step, under the
    load.  The one with the LONGEST prompt is always among them (a K/V
    block lost at 12k positions is a sixth of what it is at 2k: the
    longest context the schedule has is what can show it); the seed draws
    the rest."""
    longest = int(np.argmax([r.prompt.size for r in requests[:clients]]))
    rest = np.delete(np.arange(clients), longest)
    drawn = traffic_mod.rng_for(seed, "sample").choice(
        rest, size=min(count, clients) - 1, replace=False)
    return np.sort(np.append(drawn, longest))


def compare(ref_cfg: dict, m: dict, weights, prompt, tokens, served,
            wrong=()) -> dict:
    """One request's readings: the engine's ``served`` logits [n, V] (row t
    is what ``tokens[t]`` was chosen from) against the reference's full
    pass over ``prompt + tokens``, padded with zeros past its end to a
    multiple of ``pad_to`` (causal: the pad changes nothing before it)."""
    plen, n = int(prompt.size), int(tokens.size)
    pad_to = ref_cfg["pad_to"]
    seq = np.zeros(-(-(plen + n) // pad_to) * pad_to, np.int64)
    seq[:plen], seq[plen:plen + n] = prompt, tokens
    want = np.asarray(olmo_hybrid_jnp.logits(
        weights, seq, m, layer_prefix=ref_cfg["layer_prefix"], wrong=wrong,
        q_block=ref_cfg["q_block"], chunk=ref_cfg["chunk"],
        rows=(plen - 1, plen - 1 + n)))
    got = np.asarray(served, np.float32)
    err = np.linalg.norm(got - want, axis=1)
    norm = np.linalg.norm(want, axis=1)
    sigma = want.std(axis=1)
    gap = (want.max(axis=1) - want[np.arange(n), tokens]) / sigma
    last = slice(max(0, n - LAST_ROWS), n)
    return {"tokens": n, "prompt": plen,
            "logit_rel_l2": float(np.linalg.norm(err) / np.linalg.norm(norm)),
            "row_rel_l2_max": float((err / norm).max()),
            "row_rel_l2_median": float(np.median(err / norm)),
            "last_rows_rel_l2": float(np.linalg.norm(err[last])
                                      / np.linalg.norm(norm[last])),
            "first_row_rel_l2": float(err[0] / norm[0]),
            "token_gap_sigma_max": float(gap.max()),
            "reference_sigma_mean": float(sigma.mean())}


def judge(ref_cfg: dict, readings: list) -> dict:
    """The worst reading of each limited quantity over the sampled
    requests, beside its limit."""
    worst = {k: max(r[k] for r in readings) for k in LIMITS} \
        if readings else {}
    return {"worst": worst, "limits": {k: ref_cfg[k] for k in LIMITS},
            "ok": bool(readings) and all(worst[k] <= ref_cfg[k]
                                         for k in LIMITS)}


def serve(ctx) -> dict:
    """Build, warm up, ramp, the measured window and (traced) its tail
    under the closed loop; then the sampled requests run to their end
    under the same load and the engine is closed (``serve_lm.serve``'s
    sequence, on this builder's engine)."""
    config, tr, m = ctx.config, ctx.traffic, ctx.config["model"]
    ref_cfg = config["reference"]
    chips = ctx.cell["chips"]
    if tr["kind"] != "closed_loop":
        raise SystemExit(f"builders/serve_hybrid.py drives closed_loop "
                         f"traffic, not {tr['kind']!r}")
    engine = build_engine(config, ctx.seed)
    ctx.phases.mark("engine build: programs, startup (bfloat16 weights "
                    "made on the device), K/V pools, state pools")
    requests = requests_for(tr, m, ctx.seed)
    sample = sample_of(requests, tr["clients"], ref_cfg["sample"], ctx.seed)
    load = Load(engine, requests, sample)
    ctx.phases.mark(f"traffic drawn: {len(requests)} requests")

    compiles_before = compile_count()
    grid = engine.warmup()
    ctx.phases.mark(f"warm-up: the engine's whole grid, {grid} "
                    f"executables, {compile_count() - compiles_before} "
                    f"traced (compiled or loaded from the cache)")

    gc.collect()
    gc.freeze()         # serve_lm.serve: a full collection costs a launch
    free_clients: "queue.SimpleQueue" = queue.SimpleQueue()
    for i in range(tr["clients"]):
        load.submit(i, free_clients.put)
    engine.start()
    t_lead = time.monotonic()
    next_req = tr["clients"]
    ran_dry = False

    def dispatch():
        nonlocal next_req, ran_dry
        while True:
            r = free_clients.get()
            if r is None or load.stopping:
                return
            if next_req >= len(requests):
                load.stopping = ran_dry = True
                say("closed loop ran out of drawn requests")
                return
            load.submit(next_req, free_clients.put)
            next_req += 1

    threading.Thread(target=dispatch, name="bench-dispatcher",
                     daemon=True).start()

    # -- ramp, window, tail -------------------------------------------------
    time.sleep(max(0.0, t_lead + tr["ramp_seconds"] - time.monotonic()))
    k_clock = load.k
    wait_for_tokens(load, tr["ramp_tokens"], t_lead + 10 * tr["ramp_seconds"])
    t_start = time.monotonic()
    t_end = t_start + ctx.seconds
    compiles0 = compile_count()
    stats0 = engine.stats()
    ctx.phases.add(f"ramp: {k_clock} tokens out after {tr['ramp_seconds']} "
                   f"s, {load.k} at the window's start", t_start - t_lead)
    setup_s = ctx.open_window()
    memory = [0] * chips
    for quarter in (0.25, 0.5, 0.75):   # the pools are static: three looks
        time.sleep(max(0.0, t_start + quarter * ctx.seconds
                       - time.monotonic()))
        memory = [max(a, b) for a, b in zip(memory, memory_now(chips))]
    time.sleep(max(0.0, t_end - time.monotonic()))
    stats1 = engine.stats()
    compiles_in_window = compile_count() - compiles0

    trace = tail = None
    if ctx.tracer.enabled:
        tail0 = engine.stats()
        k0 = load.k
        ctx.tracer.start()
        time.sleep(tr["trace_seconds"])
        k1 = load.k
        tail1 = engine.stats()
        trace = ctx.tracer.stop()
        tail = {"k0": k0, "k1": k1, "stats0": tail0, "stats1": tail1,
                "t0": ctx.tracer.t_start, "t1": ctx.tracer.t_stop}

    # -- the sampled requests run to their end under the same load ----------
    t_wait = time.monotonic()
    results = []
    for r in sample:
        try:
            results.append(load.futures[r].result(
                timeout=max(1.0, ref_cfg["wait_seconds"]
                            - (time.monotonic() - t_wait))))
        except Exception as e:      # noqa: BLE001 — reported, then judged
            say(f"sampled request {r} gave no result: {e!r}")
    say(f"waited {time.monotonic() - t_wait:.1f} s past the window for "
        f"{len(results)} of {len(sample)} sampled requests")
    load.stopping = True
    free_clients.put(None)
    gc.unfreeze()
    stats_end = engine.stats()
    failed_exc = sum(1 for f in load.futures
                     if f is not None and f.done() and f.exception())
    # the sampled requests' logits come to the host before the pools go:
    # a request's rows are some hundred MB of the device's memory
    for res in results:
        res.logits
    weights = close_and_take_weights(engine)
    say("engine stats() at the window's end: " + json.dumps(stats1))
    return {"requests": requests, "sample": sample, "results": results,
            "weights": weights, "load": load, "t_start": t_start,
            "t_end": t_end, "setup_s": setup_s, "memory": memory,
            "stats0": stats0, "stats1": stats1, "stats_end": stats_end,
            "compiles_in_window": compiles_in_window, "trace": trace,
            "tail": tail, "failed_exc": failed_exc, "ran_dry": ran_dry,
            "requests_left": len(requests) - next_req}


def run(ctx) -> dict:
    config, m = ctx.config, reference_model(ctx.config)
    ref_cfg = config["reference"]
    s = serve(ctx)
    load, requests = s["load"], s["requests"]
    t_start, t_end = s["t_start"], s["t_end"]
    compiles_in_window = s["compiles_in_window"]

    # -- arithmetic ---------------------------------------------------------
    plen = np.array([r.prompt.size for r in requests])
    stamps = load.stamps[:load.k]
    rate = est.sync_rate(stamps, t_start, t_end)
    naive = est.fixed_window_rate(stamps, t_start, t_end)
    say(f"serve_tokens_per_s: sync to sync {json.dumps(rate)}; the "
        f"fixed-window count it replaces would read {naive:.3f}")
    submitted = np.flatnonzero((load.t_submit >= t_start)
                               & (load.t_submit < t_end))
    attempted = int(submitted.size)
    failed = int(load.refused[submitted].sum())
    say(f"requests: attempted {attempted}, failed or refused {failed}, "
        f"futures with an exception (any phase) {s['failed_exc']}; "
        f"compilations inside the window: {compiles_in_window}; requests "
        f"the dispatcher had left: {s['requests_left']}")

    # -- correctness --------------------------------------------------------
    readings = []
    for r, res in zip(s["sample"], s["results"]):
        readings.append(dict(
            compare(ref_cfg, m, s["weights"], requests[r].prompt,
                    res.tokens, res.logits), request=int(r)))
        say("reference comparison, request: " + json.dumps(readings[-1]))
    ref = judge(ref_cfg, readings)
    ref["requests"] = [int(r) for r in s["sample"]]
    say("reference comparison: " + json.dumps(ref))
    ok = ref["ok"] and len(s["results"]) == len(s["sample"]) \
        and compiles_in_window == 0 and rate is not None \
        and s["failed_exc"] == 0 and not s["ran_dry"] \
        and not s["stats_end"]["unhealthy"]

    win = serve_mod._delta(s["stats0"], s["stats1"])
    steps = max(1, win.get("decode_steps", 0))
    live_rows = win.get("state_rows_live", 0) / steps
    return {
        "kind": "serve", "correct": bool(ok), "attempted": attempted,
        "failed": failed, "setup_s": s["setup_s"],
        "end_to_end": {"serve_tokens_per_s": rate["rate"] if rate else 0.0},
        "compiles_in_window": compiles_in_window,
        "engine_stats": win, "engine_stats_whole": s["stats_end"],
        "events": {"stamps": stamps, "req": load.ev_req[:load.k],
                   "plen": plen, "t_start": t_start, "t_end": t_end},
        # what a decode step NEEDS to move: the weights every step reads
        # plus the recurrent state (S and the conv tail) its live rows,
        # the window's mean, read and write
        "weight_bytes": flops_gdn.step_weight_bytes(m)
        + 2 * flops_gdn.state_bytes_per_row(m) * live_rows,
        "kv_bytes_per_token": flops_gdn.kv_bytes_per_token(m),
        "max_seq_len": config["engine"]["max_seq_len"],
        "trace": s["trace"], "tail": s["tail"], "reference": ref,
        "counts": {"tokens": rate["events"] if rate else 0,
                   "compiles_in_window": compiles_in_window,
                   "attempted": attempted,
                   "logit_rows_compared": sum(r["tokens"]
                                              for r in readings)},
        "memory_samples": s["memory"],
        "hard_exit": True,
    }
