"""Builder for ``kind: serve`` configurations: the causal BERT decoder
behind ``serving.DecodeEngine``, under a closed loop.

The load generator lives here, in the benchmark: one dispatcher thread
submits requests, and the engine's worker thread hands back every token
through ``on_token``.  That callback does nothing but store a stamp and
the request's index in preallocated arrays; everything else is worked
out from those arrays after the run.

Phases of a run: build the engine; warm-up groups derived here from the
engine's buckets and the traffic's length ranges (each compiles one
executable the cell's traffic can reach; the traffic file knows nothing
of the scheduler); the ramp (all clients started and the batch full) —
all of it set-up; the measured window; for a traced run a traced tail
under the same load; the comparison with the plain reference on a sample
of requests that were served through the cache.
"""

from __future__ import annotations

import dataclasses
import json
import queue
import threading
import time
from typing import List, Optional

import numpy as np

from .. import estimators as est, flops, traffic as traffic_mod
from ..harness import compile_count, memory_now, say
from ..reference import bert_jnp


def _build_engine(config: dict, seed: int):
    from paddle_tpu.models.bert import BertConfig
    from paddle_tpu.models.decoder import BertDecoder
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    keys = {f.name for f in dataclasses.fields(BertConfig)}
    cfg = BertConfig(**{k: v for k, v in config["model"].items()
                        if k in keys})
    kw = dict(config["engine"])
    for k in ("prefill_seq_buckets", "chain_lengths", "batch_buckets",
              "prefill_batch_buckets"):
        if kw.get(k) is not None:
            kw[k] = tuple(kw[k])
    model = BertDecoder(cfg, seed=seed % (2 ** 31 - 1) + 1)
    # the worker starts only once the first warm-up group is queued, so
    # that what it admits first does not depend on thread timing
    return DecodeEngine(model, DecodeConfig(**kw), auto_start=False)


def _weights_snapshot(engine) -> dict:
    """The weights the program initialised, read from its scope by name,
    as fresh device copies (the engine donates its own buffers)."""
    import jax.numpy as jnp
    scope = getattr(engine, "scope", None) or engine._scope
    caches = set(engine.model.cache_var_names())
    return {n: jnp.array(scope.find_var(n), copy=True)
            for n in scope.var_names()
            if n not in caches and not n.startswith("@")}


class Load:
    """The load generator's state: requests, their futures, and the
    event log the worker thread writes."""

    def __init__(self, engine, requests: List[traffic_mod.Request]):
        self.engine = engine
        self.requests = requests
        n = len(requests)
        self.futures: List[Optional[object]] = [None] * n
        self.t_submit = np.full(n, np.nan)
        self.refused = np.zeros(n, bool)
        cap = int(sum(r.max_new for r in requests)) + 8
        self.stamps = np.zeros(cap, np.float64)
        self.ev_req = np.zeros(cap, np.int32)
        self.k = 0                      # events so far (worker thread only)
        self.stopping = False

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
            fut = self.engine.generate({"src_ids": req.prompt},
                                       max_new_tokens=req.max_new,
                                       on_token=on_token)
        except Exception as e:      # noqa: BLE001 — a refusal is a result
            self.refused[r] = True
            say(f"request {r} refused: {e!r}")
            return None
        self.futures[r] = fut
        if on_done is not None:
            fut.add_done_callback(lambda _f, r=r: on_done(r))
        return fut

    def per_request(self):
        """(t_first, t_last, n_seen) of every request from the log."""
        n = len(self.requests)
        k = self.k
        stamps, ev = self.stamps[:k], self.ev_req[:k]
        t_first = np.full(n, np.inf)
        t_last = np.full(n, -np.inf)
        np.minimum.at(t_first, ev, stamps)
        np.maximum.at(t_last, ev, stamps)
        n_seen = np.bincount(ev, minlength=n)
        t_first[n_seen == 0] = np.nan
        t_last[n_seen == 0] = np.nan
        return t_first, t_last, n_seen


def _bucket(buckets, n: int) -> int:
    """The smallest bucket that holds ``n`` (the largest if none does)."""
    return next((b for b in buckets if b >= n), buckets[-1])


def _warmup_stages(ecfg, tr: dict, cfg: dict, seed: int):
    """Groups of requests, each submitted whole, that make the engine
    compile (or load from the persistent cache) the executables this
    closed loop can reach — worked out from the live engine's own buckets
    (``DecodeEngine.config``) and the traffic's length ranges, in place
    of ``warmup()``'s whole grid: 41 executables took 240-257 s in every
    run where 22 take 36 s (my chip runs, PR 24; PERF.md).

    ``burst`` is the most requests that can finish inside one chain if
    every one were of the shortest output: so many rows can leave the
    batch, and so many prompts arrive, together.  Decode chains are warmed
    at every batch bucket from the one that holds ``clients - burst`` rows
    up, with one over-long prompt beside them whose chunk rounds make the
    scheduler run the short chain as well as the long one; packed prefill
    at every (batch bucket up to ``burst``) x (sequence bucket a prompt of
    this traffic falls in).  A shape outside this set that compiles
    inside the window fails the run."""
    clients = tr["clients"]
    chain = ecfg.chain_lengths[-1]
    burst = min(clients, clients * chain // tr["output"]["min"])
    seq = sorted({_bucket(ecfg.prefill_seq_buckets, n) for n in
                  range(tr["prompt"]["min"], tr["prompt"]["max"] + 1)})
    rng = traffic_mod.rng_for(seed, "warmup")

    def group(count, length, max_new):
        return [traffic_mod.Request(
            rng.integers(0, cfg["vocab_size"], int(length), dtype=np.int64),
            max_new) for _ in range(count)]

    stages = []
    low = _bucket(ecfg.batch_buckets, clients - burst)
    top = _bucket(ecfg.batch_buckets, clients)
    for bb in sorted((b for b in ecfg.batch_buckets if low <= b <= top),
                     reverse=True):
        # inside the top sequence bucket, not filling it; one slot of the
        # batch is left for the chunked prompt
        stages.append(
            group(min(bb, ecfg.max_batch_size - 1), seq[-1] * 25 // 32,
                  chain + 4)
            + group(1, 2 * ecfg.chunk_width + 1, 1))
    for sb in seq:
        for bb in ecfg.prefill_batch_buckets:
            if bb <= _bucket(ecfg.prefill_batch_buckets, burst):
                stages.append(group(bb, sb, 1))
    return stages


def _run_stages(engine, stages, then):
    """Submit stage 0 now (the worker is not running yet) and each next
    stage from the worker thread's own completion callback of the stage
    before, so that every stage reaches the scheduler whole; ``then()``
    runs the same way after the last stage."""
    state = {"i": 0, "left": 0}

    def submit_stage(i):
        if i == len(stages):
            then()
            return
        state["i"], state["left"] = i, len(stages[i])
        for req in stages[i]:
            engine.generate({"src_ids": req.prompt},
                            max_new_tokens=req.max_new
                            ).add_done_callback(done)

    def done(_f):
        state["left"] -= 1
        if state["left"] == 0:
            submit_stage(state["i"] + 1)

    submit_stage(0)


def _reference_check(ctx, weights, load, sample) -> dict:
    """Teacher forcing: for each sampled request, one full causal forward
    pass of the plain reference over prompt + served tokens; every served
    token's reference logit has to lie within ``margin`` of the
    reference's maximum at its position."""
    m, ref = ctx.config["model"], ctx.config["reference"]
    width = ctx.config["engine"]["max_seq_len"]
    worst, gaps_all, top2 = 0.0, [], []
    checked = 0
    for r in sample:
        req = load.requests[r]
        tokens = np.asarray(load.futures[r].result(timeout=0).tokens)
        plen = int(req.prompt.size)
        seq = np.zeros(width, np.int64)
        seq[:plen] = req.prompt
        seq[plen:plen + tokens.size] = tokens
        logits = np.asarray(bert_jnp.decoder_logits(
            weights, seq, n_layer=m["num_hidden_layers"],
            n_head=m["num_attention_heads"], eps=m["layer_norm_eps"],
            layer_prefix=ctx.config["reference"]["layer_prefix"]))
        rows = logits[plen - 1:plen - 1 + tokens.size]
        gaps = rows.max(axis=1) - rows[np.arange(tokens.size), tokens]
        part = np.partition(rows, -2, axis=1)
        top2.extend((part[:, -1] - part[:, -2]).tolist())
        gaps_all.extend(gaps.tolist())
        worst = max(worst, float(gaps.max()))
        checked += int(tokens.size)
    res = {"requests": [int(r) for r in sample], "tokens_checked": checked,
           "worst_gap": worst, "margin": ref["logit_margin"],
           "tokens_not_argmax": int(np.sum(np.asarray(gaps_all) > 0)),
           "reference_top1_minus_top2_median":
               float(np.median(top2)) if top2 else None,
           "ok": bool(checked > 0 and worst <= ref["logit_margin"])}
    say("reference comparison: " + json.dumps(res))
    return res


def run(ctx) -> dict:
    config, tr, m = ctx.config, ctx.traffic, ctx.config["model"]
    chips = ctx.cell["chips"]
    if tr["kind"] != "closed_loop":
        raise SystemExit(f"builders/serve.py drives closed_loop traffic, "
                         f"not {tr['kind']!r}")
    engine = _build_engine(config, ctx.seed)
    ctx.phases.mark("engine build: programs, startup (weights made on the "
                    "device), cache pools")
    weights = _weights_snapshot(engine)
    ctx.phases.mark("weights snapshot for the reference (device copies)")
    requests = traffic_mod.closed_loop_requests(tr, m, ctx.seed,
                                                tr["max_requests"])
    load = Load(engine, requests)
    ctx.phases.mark(f"traffic drawn: {len(requests)} requests")

    # -- warm-up groups, then every client's first request -------------------
    free_clients: "queue.SimpleQueue" = queue.SimpleQueue()
    warm_done = threading.Event()

    def start_clients():
        # on the worker thread: the first requests reach the scheduler whole
        for i in range(tr["clients"]):
            load.submit(i, free_clients.put)
        warm_done.set()

    stages = _warmup_stages(engine.config, tr, m, ctx.seed)
    compiles_before = compile_count()
    _run_stages(engine, stages, start_clients)
    engine.start()
    if not warm_done.wait(timeout=1100):
        raise SystemExit("warm-up did not finish")
    t_lead = time.monotonic()
    next_req = tr["clients"]
    ctx.phases.mark(f"warm-up: {len(stages)} derived groups, "
                    f"{compile_count() - compiles_before} executables "
                    f"traced (compiled or loaded from the cache)")

    def dispatch():
        nonlocal next_req
        while True:
            r = free_clients.get()
            if r is None or load.stopping:
                return
            if next_req >= len(requests):
                load.stopping = True
                say("closed loop ran out of drawn requests")
                return
            load.submit(next_req, free_clients.put)
            next_req += 1

    threading.Thread(target=dispatch, name="bench-dispatcher",
                     daemon=True).start()

    # -- ramp, window, tail -------------------------------------------------
    t_start = t_lead + tr["ramp_seconds"]
    t_end = t_start + ctx.seconds
    time.sleep(max(0.0, t_start - time.monotonic()))
    compiles0 = compile_count()
    stats0 = engine.stats()
    ctx.phases.add("ramp: every client started, the batch full",
                   tr["ramp_seconds"])
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

    load.stopping = True
    free_clients.put(None)
    stats_end = engine.stats()
    failed_exc = sum(1 for f in load.futures
                     if f is not None and f.done() and f.exception())
    engine.shutdown(drain=False, timeout=0.0)
    say("engine stats() at the window's end: " + json.dumps(stats1))

    # -- arithmetic ---------------------------------------------------------
    t_first, t_last, n_seen = load.per_request()
    want = np.array([r.max_new for r in requests])
    plen = np.array([r.prompt.size for r in requests])
    complete = n_seen == want
    stamps = load.stamps[:load.k]
    rate = est.sync_rate(stamps, t_start, t_end)
    naive = est.fixed_window_rate(stamps, t_start, t_end)
    say(f"serve_tokens_per_s: sync to sync {json.dumps(rate)}; the "
        f"fixed-window count it replaces would read {naive:.3f}")
    whole = est.whole_requests(t_submit=load.t_submit,
                               t_last=np.where(complete, t_last, np.nan),
                               start=t_start, end=t_end)
    tpot = est.tpot_ms(t_first[whole], t_last[whole], n_seen[whole])
    say(f"tpot: {tpot.size} whole requests in the window (median "
        f"{np.median(tpot):.3f} ms)" if tpot.size else
        "tpot: no whole request in the window")
    submitted = np.flatnonzero((load.t_submit >= t_start)
                               & (load.t_submit < t_end))
    attempted = int(submitted.size)
    failed = int(load.refused[submitted].sum())
    say(f"requests: attempted {attempted}, failed or refused {failed}, "
        f"futures with an exception (any phase) {failed_exc}; "
        f"compilations inside the window: {compiles_in_window}")

    # -- correctness: a seeded sample of the requests served, ramp and
    # window alike -----------------------------------------------------------
    served = np.array([r for r in np.flatnonzero(complete)
                       if load.futures[r].done()], np.int64)
    k = min(config["reference"]["sample"], served.size)
    sample = np.sort(traffic_mod.rng_for(ctx.seed, "sample").choice(
        served, size=k, replace=False)) if k else []
    ref = _reference_check(ctx, weights, load, sample)
    ok = ref["ok"] and compiles_in_window == 0 and rate is not None \
        and not stats_end["unhealthy"]
    return {
        "kind": "serve", "correct": bool(ok), "attempted": attempted,
        "failed": failed, "setup_s": setup_s,
        "end_to_end": {"serve_tokens_per_s": rate["rate"] if rate else 0.0},
        "compiles_in_window": compiles_in_window,
        "engine_stats": _delta(stats0, stats1),
        "engine_stats_whole": stats_end,
        "events": {"stamps": stamps, "req": load.ev_req[:load.k],
                   "plen": plen, "t_start": t_start, "t_end": t_end},
        "tpot_ms": tpot,
        "weight_bytes": flops.decoder_weight_bytes(m),
        "kv_bytes_per_token": flops.kv_bytes_per_token(m),
        "max_seq_len": config["engine"]["max_seq_len"],
        "trace": trace, "tail": tail, "reference": ref,
        "counts": {"tokens": rate["events"] if rate else 0,
                   "whole_requests": int(tpot.size),
                   "compiles_in_window": compiles_in_window,
                   "attempted": attempted},
        "memory_samples": memory,
        # the worker may still be running requests nobody waits for
        "hard_exit": True,
    }


def _delta(a: dict, b: dict) -> dict:
    """Counter deltas between two ``stats()`` snapshots; histograms are
    differenced key by key, gauges keep the later value."""
    out = {}
    for k, v in b.items():
        if isinstance(v, dict):
            prev = a.get(k, {})
            out[k] = {kk: vv - prev.get(kk, 0) for kk, vv in v.items()}
        elif isinstance(v, bool) or not isinstance(v, (int, float)):
            out[k] = v
        else:
            out[k] = v - a.get(k, 0)
    return out
