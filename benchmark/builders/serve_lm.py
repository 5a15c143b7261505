"""Builder for ``kind: serve_lm`` configurations: a latent-attention
decoder (``paddle_tpu.models.latent_decoder``: MLA over a paged latent
cache, a shared expert beside sigmoid-scored group-limited routed
experts, one chip's share of an expert-parallel deployment) behind
``serving.DecodeEngine``, under a closed loop.

The load generator, its event log and the record's keys are
``builders/serve.py``'s, so the serve readers take the record too.
``--seed`` draws the weights, the token ids and the requests whose
logits are compared.  The order of the file's pairs is the file's
(``order_seed``) and the window opens at a point of that schedule
(``ramp_tokens``), not of the clock: a window of tens of seconds sees some
ninety arrivals of these prompts, a third of one permutation, so another
order or another stretch of the same order is other work
(:func:`requests_for`, :func:`wait_for_tokens`).  What else differs: the
model is built from the published keys; every prompt of the traffic is
longer than the packed-prefill buckets, so the whole executable grid is
one chunk program and (chain length x batch bucket) chains and
``engine.warmup()`` compiles it; and ``correct`` compares
LOGITS: a seeded sample of the clients' first requests asks the engine
for the logits every token was chosen from — prefill, then every decode
step through the cache, at the timed sizes, co-batched with the rest of
the load — and each is held to the plain reference's one full forward
pass over prompt + served tokens on the same bfloat16-rounded weights.
The engine is closed (its pools freed) before the reference runs: the
chip does not hold both.  :func:`serve` is everything up to there, so
that ``tools/serve_lm_probe.py`` can hold the same served logits to the
reference computed WRONG (``deepseek_v3_jnp.CONTROLS``): the readings the
configuration's limits are set below."""

from __future__ import annotations

import dataclasses
import gc
import json
import queue
import threading
import time

import numpy as np

from .. import estimators as est, flops_mla, traffic as traffic_mod
from ..harness import compile_count, memory_now, say
from ..reference import deepseek_v3_jnp
from . import serve as serve_mod


def decoder_config(config: dict):
    """The program's config from the file: published keys at the top
    level; the router's width and the held experts from ``deployment``."""
    from paddle_tpu.models.latent_decoder import LatentDecoderConfig
    keys = {f.name for f in dataclasses.fields(LatentDecoderConfig)}
    kw = {k: v for k, v in config["model"].items() if k in keys}
    dep = config["deployment"]
    kw.update(rope_scaling=config["rope_scaling"],
              n_routed_experts=dep["router_experts"],
              held_experts=tuple(dep["held_experts"]))
    return LatentDecoderConfig(**kw)


def reference_model(config: dict) -> dict:
    """The reference's view of the same file: the published keys, with
    ``n_routed_experts`` the router's width."""
    return dict(config["model"], rope_scaling=config["rope_scaling"],
                n_routed_experts=config["deployment"]["router_experts"])


def build_engine(config: dict, seed: int):
    from paddle_tpu.models.latent_decoder import LatentDecoder
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config["engine"].items()}
    model = LatentDecoder(decoder_config(config),
                          seed=seed % (2 ** 31 - 1) + 1)
    # started once every client's first request is queued, so that what
    # the worker admits first does not depend on thread timing
    return DecodeEngine(model, DecodeConfig(**kw), auto_start=False)


def requests_for(tr: dict, m: dict, seed: int) -> list:
    """The closed loop's requests: which pair each one is comes from the
    file's ``order_seed``, the same in every run; ``seed`` draws the
    token ids."""
    requests = traffic_mod.closed_loop_requests(tr, m, tr["order_seed"],
                                                tr["max_requests"])
    tok = traffic_mod.rng_for(seed, "tokens")
    for r in requests:
        r.prompt = tok.integers(0, m["vocab_size"], r.prompt.size,
                                dtype=np.int64)
    return requests


def wait_for_tokens(load, count: int, deadline: float, quiet_s=0.004):
    """Block until ``count`` tokens are out and the sync that brought the
    last of them is over: a sync's tokens are stamped microseconds apart,
    two syncs a decode step or more, so ``quiet_s`` without a stamp ends
    one.  The instant after is the same point of the schedule in every
    run, and the first sync of the window is the one that follows."""
    k = -1
    while load.k < count or k != load.k:
        if time.monotonic() > deadline:
            raise SystemExit(f"{load.k} of the ramp's {count} tokens out "
                             f"at its deadline")
        k = load.k
        time.sleep(quiet_s if k >= count else 0.001)


class Load(serve_mod.Load):
    """``serve.Load`` whose sampled requests ask for their logits."""

    def __init__(self, engine, requests, with_logits):
        super().__init__(engine, requests)
        self.with_logits = frozenset(int(r) for r in with_logits)

    def submit(self, r: int, on_done=None):
        req = self.requests[r]
        stamps, ev_req = self.stamps, self.ev_req

        def on_token(_tok, r=r):
            k = self.k
            stamps[k] = time.monotonic()
            ev_req[k] = r
            self.k = k + 1

        self.t_submit[r] = time.monotonic()
        try:
            fut = self.engine.generate(
                {"src_ids": req.prompt}, max_new_tokens=req.max_new,
                on_token=on_token, return_logits=r in self.with_logits)
        except Exception as e:      # noqa: BLE001 — a refusal is a result
            self.refused[r] = True
            say(f"request {r} refused: {e!r}")
            return None
        self.futures[r] = fut
        if on_done is not None:
            fut.add_done_callback(lambda _f, r=r: on_done(r))
        return fut


def compare(ref_cfg: dict, m: dict, held, weights, prompt, tokens, served,
            wrong=()) -> dict:
    """One request's readings: the engine's ``served`` logits [n, V]
    (row t is what ``tokens[t]`` was chosen from) against the reference's
    full forward pass over ``prompt + tokens``, padded with zeros past its
    end to a multiple of ``pad_to`` (causal: the pad changes nothing
    before it)."""
    plen, n = int(prompt.size), int(tokens.size)
    pad_to = ref_cfg["pad_to"]
    seq = np.zeros(-(-(plen + n) // pad_to) * pad_to, np.int64)
    seq[:plen], seq[plen:plen + n] = prompt, tokens
    want = np.asarray(deepseek_v3_jnp.logits(
        weights, seq, m, held=held, layer_prefix=ref_cfg["layer_prefix"],
        wrong=wrong, q_block=ref_cfg["q_block"]))[plen - 1:plen - 1 + n]
    got = np.asarray(served, np.float32)
    err = np.linalg.norm(got - want, axis=1)
    norm = np.linalg.norm(want, axis=1)
    sigma = want.std(axis=1)
    gap = (want.max(axis=1) - want[np.arange(n), tokens]) / sigma
    return {"tokens": n, "prompt": plen,
            "logit_rel_l2": float(np.linalg.norm(err) / np.linalg.norm(norm)),
            "row_rel_l2_max": float((err / norm).max()),
            # half the rows lie below it: a row moved by an expert that
            # rounding flipped (about one in five) does not reach it
            "row_rel_l2_median": float(np.median(err / norm)),
            "first_row_rel_l2": float(err[0] / norm[0]),
            "token_gap_sigma_max": float(gap.max()),
            "reference_sigma_mean": float(sigma.mean())}


LIMITS = ("logit_rel_l2", "row_rel_l2_max", "row_rel_l2_median",
          "token_gap_sigma_max")


def judge(ref_cfg: dict, readings: list) -> dict:
    """The worst reading of each limited quantity over the sampled
    requests, beside its limit."""
    worst = {k: max(r[k] for r in readings) for k in LIMITS} \
        if readings else {}
    return {"worst": worst, "limits": {k: ref_cfg[k] for k in LIMITS},
            "ok": bool(readings) and all(worst[k] <= ref_cfg[k]
                                         for k in LIMITS)}


def close_and_take_weights(engine) -> dict:
    """Close the engine where it is (its pools go back to the device)
    and return its weights by name — the bfloat16 arrays themselves, no
    copy: the reference widens each where it uses it."""
    if not engine.close(timeout=120.0):
        raise SystemExit("the decode worker did not stop")
    scope = engine.scope
    return {n: scope.find_var(n) for n in scope.var_names()
            if not n.startswith("@")}


def serve(ctx) -> dict:
    """Build, warm up, ramp, the measured window and (traced) its tail
    under the closed loop; then the sampled requests run to their end
    under the same load and the engine is closed.  Returns what
    :func:`run` judges and reports."""
    config, tr, m = ctx.config, ctx.traffic, ctx.config["model"]
    ref_cfg = config["reference"]
    chips = ctx.cell["chips"]
    if tr["kind"] != "closed_loop":
        raise SystemExit(f"builders/serve_lm.py drives closed_loop "
                         f"traffic, not {tr['kind']!r}")
    engine = build_engine(config, ctx.seed)
    ctx.phases.mark("engine build: programs, startup (bfloat16 weights "
                    "made on the device), latent pools")
    requests = requests_for(tr, m, ctx.seed)
    # the sample: some of the clients' FIRST requests, so that each is
    # served whole — chunked prefill, then every decode step in a full
    # batch — by the time the window and its tail are over
    sample = np.sort(traffic_mod.rng_for(ctx.seed, "sample").choice(
        tr["clients"], size=min(ref_cfg["sample"], tr["clients"]),
        replace=False))
    load = Load(engine, requests, sample)
    ctx.phases.mark(f"traffic drawn: {len(requests)} requests")

    compiles_before = compile_count()
    grid = engine.warmup()
    ctx.phases.mark(f"warm-up: the engine's whole grid, {grid} "
                    f"executables, {compile_count() - compiles_before} "
                    f"traced (compiled or loaded from the cache)")

    # what is alive now stays alive to the end (programs, compiled code,
    # the drawn requests): out of the collector's sight, or a full
    # collection walks it all in the worker's way — 80-150 ms, a launch
    # or more, at another instant of every run
    gc.collect()
    gc.freeze()
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
    ctx.phases.add(f"ramp: every client's prompt prefilled, the batch full, "
                   f"{k_clock} tokens out after {tr['ramp_seconds']} s, "
                   f"{load.k} at the window's start", t_start - t_lead)
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

    # -- the sampled requests run to their end under the same load; then the
    # engine stops where it is --------------------------------------------
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
    config, m = ctx.config, ctx.config["model"]
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
        f"compilations inside the window: "
        f"{compiles_in_window}; requests the dispatcher had left: "
        f"{s['requests_left']}")

    # -- correctness --------------------------------------------------------
    held = tuple(config["deployment"]["held_experts"])
    ref_m = reference_model(config)
    readings = []
    for r, res in zip(s["sample"], s["results"]):
        readings.append(dict(
            compare(ref_cfg, ref_m, held, s["weights"], requests[r].prompt,
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
    hit = (win.get("moe_experts_hit") or {}).get("chain", 0)
    steps = max(1, win.get("decode_steps", 0))
    dep = config["deployment"]
    return {
        "kind": "serve", "correct": bool(ok), "attempted": attempted,
        "failed": failed, "setup_s": s["setup_s"],
        "end_to_end": {"serve_tokens_per_s": rate["rate"] if rate else 0.0},
        "compiles_in_window": compiles_in_window,
        "engine_stats": win, "engine_stats_whole": s["stats_end"],
        "events": {"stamps": stamps, "req": load.ev_req[:load.k],
                   "plen": plen, "t_start": t_start, "t_end": t_end},
        # what a decode step NEEDS to read: the weights every step reads
        # plus those of the held experts it hit (the window's mean)
        "weight_bytes": flops_mla.step_fixed_weight_bytes(
            m, dep["router_experts"])
        + flops_mla.expert_weight_bytes(m) * hit / steps,
        "kv_bytes_per_token": flops_mla.latent_bytes_per_token(m),
        "expert_slots_per_step": (dep["held_experts"][1]
                                  - dep["held_experts"][0])
        * (m["num_hidden_layers"] - m["first_k_dense_replace"]),
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
