#!/usr/bin/env python
"""Decode-engine contract gate — the ISSUE 16 acceptance artifact (decode
fast path v2).  CPU-ONLY: it pins ``JAX_PLATFORMS=cpu`` (its restart leg
starts child processes after the parent has touched JAX, and a chip
belongs to one process), so its tokens/s ratios are CPU stopwatch
readings next to parity and count contracts, never device numbers.  The
engine's run on the chip is chip_smoke.py leg B.

Six legs on the CPU BERT-tiny-decoder (the "before" shape is the
reference's serving story: a per-request greedy loop that re-scores the
FULL prefix through the cache-free program for every emitted token —
AnalysisPredictor semantics):

* **--throughput** — continuous token-level batching over the paged
  KV-cache vs the per-request greedy loop on one mixed-length request
  stream, both sides fully warm.  Asserts >= 3x tokens/s (the engine
  decodes every live sequence per dispatch and pays O(1) attention
  reads through the block table instead of O(prefix) recompute) and
  EVERY sequence token-for-token equal to its unbatched greedy
  reference.  Honest reporting: on CPU both sides pay real padding
  compute for their buckets, exactly as in SERVE_BENCH;
* **--warm-restart** — the prefill/decode split executable grid through
  the persistent AOT cache: a COLD subprocess traces+compiles+stores
  the whole grid, a WARM subprocess with the same cache dir restarts —
  asserted 0 fresh compiles, every executable a cache hit, and
  generated tokens bit-identical across the restart;
* **--admission** — paged-cache admission: a request whose
  ``blocks_needed(prompt, max_new)`` exceeds the pool is rejected at
  submit with 0 compiles spent; a pool sized below the offered load
  makes later arrivals WAIT (admission_waits > 0, blocks reused) and
  still decode to parity;
* **--chained** — device-chained multi-token decode (the v2 fast path:
  a chain_length-step lax.scan per host round-trip) vs the SAME-RUN
  single-step engine (chain_lengths=(1,), the r19 shape) on one mixed
  stream.  Asserts >= 1.5x tokens/s, host syncs per chained decode
  token <= 1/chain_length, every sequence token-for-token equal to the
  greedy reference, and fixed-seed sampling deterministic;
* **--prefix** — cross-request prefix caching: a shared-prefix stream
  where repeat arrivals hit the content-hash block index, charge
  admission only for the suffix, and prefill ONLY the suffix tokens —
  hits > 0, prefill tokens <= suffix tokens < total prompt tokens,
  bytes saved reported, all to parity;
* **--chunked** — chunked prefill: prompts LONGER than the largest
  prefill bucket stream in fixed-width cache-reading chunks that
  interleave with live decode chains (no head-of-line blocking), to
  parity.

A regression gate compares the chained engine's tokens/s against the
committed r19 artifact (>= 0.95x — the v2 path may not regress the
engine below its r19 throughput).

Emits ``DECODE_BENCH_r20.json`` (asserted by tier-1
tests/test_decode.py::test_decode_bench_artifact_contract).

Usage:
  python tools/decode_bench.py [out.json]      # all legs + artifact
  python tools/decode_bench.py --throughput    # one leg, print JSON
  python tools/decode_bench.py --warm-restart
  python tools/decode_bench.py --admission
  python tools/decode_bench.py --chained
  python tools/decode_bench.py --prefix
  python tools/decode_bench.py --chunked
  python tools/decode_bench.py --selftest      # quick CI gate, no write
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ["JAX_PLATFORMS"] = "cpu"       # see module docstring

SCHEMA = "paddle_tpu.decode_bench/2"
ARTIFACT = "DECODE_BENCH_r20.json"
R19_ARTIFACT = "DECODE_BENCH_r19.json"
REGRESSION_TOLERANCE = 0.95


def _model(selftest):
    from paddle_tpu.models.bert import BertConfig
    from paddle_tpu.models.decoder import BertDecoder
    cfg = BertConfig(vocab_size=1024, hidden_size=128,
                     num_hidden_layers=1 if selftest else 2,
                     num_attention_heads=2, intermediate_size=512,
                     max_position_embeddings=128, type_vocab_size=2,
                     initializer_range=0.5)
    return BertDecoder(cfg, seed=7)


def _config(selftest, **kw):
    from paddle_tpu.serving.decode import DecodeConfig
    base = dict(block_size=8, max_seq_len=64, max_batch_size=8,
                prefill_seq_buckets=(8, 16, 32),
                prefill_batch_buckets=(1, 2, 4),
                pack_max_segments=4, max_new_tokens=16)
    if selftest:
        base.update(max_batch_size=4, prefill_seq_buckets=(8, 16),
                    prefill_batch_buckets=(1, 2), max_seq_len=48)
    base.update(kw)
    return DecodeConfig(**base)


def _prompts(selftest, seed=11):
    rng = np.random.RandomState(seed)
    lens = [4, 7, 11, 6] if selftest else \
        [4, 7, 11, 14, 19, 23, 28, 9, 16, 5, 12, 25]
    return [rng.randint(0, 1024, (n,)).astype(np.int64) for n in lens]


# ---------------------------------------------------------------------------
# leg 1: continuous batching vs the per-request greedy loop
# ---------------------------------------------------------------------------


def leg_throughput(selftest=False):
    from paddle_tpu.serving.decode import DecodeEngine

    max_new = 6 if selftest else 16
    engine = DecodeEngine(_model(selftest), _config(selftest))
    prompts = _prompts(selftest)
    try:
        combos = engine.warmup()

        # warm BOTH sides once (compiles + first-touch costs out of the
        # measured window), and collect the reference tokens
        ref = [engine.greedy_reference({"src_ids": p},
                                       max_new_tokens=max_new)
               for p in prompts]
        futs = [engine.generate({"src_ids": p}, max_new_tokens=max_new)
                for p in prompts]
        warm_results = [f.result(timeout=600) for f in futs]
        engine.drain()

        # measured: engine steady state
        t0 = time.perf_counter()
        futs = [engine.generate({"src_ids": p}, max_new_tokens=max_new)
                for p in prompts]
        results = [f.result(timeout=600) for f in futs]
        engine_s = time.perf_counter() - t0

        # measured: the per-request greedy loop, same stream
        t0 = time.perf_counter()
        ref2 = [engine.greedy_reference({"src_ids": p},
                                        max_new_tokens=max_new)
                for p in prompts]
        baseline_s = time.perf_counter() - t0

        tokens_total = sum(len(r.tokens) for r in results)
        matches = [bool(np.array_equal(r.tokens, g.tokens))
                   for r, g in zip(results, ref)]
        stable = [bool(np.array_equal(a.tokens, b.tokens))
                  for a, b in zip(ref, ref2)] + \
                 [bool(np.array_equal(a.tokens, b.tokens))
                  for a, b in zip(warm_results, results)]
        stats = engine.stats()
    finally:
        engine.shutdown()

    out = {
        "definition": "one mixed-prompt-length request stream, both "
                      "sides fully warm: the decode engine (paged "
                      "KV-cache, continuous token-level batching, "
                      "prefill/decode split executables) vs the "
                      "per-request greedy loop that re-scores the full "
                      "prefix per token (the reference "
                      "AnalysisPredictor serving shape, prefix padded "
                      "to the same seq-bucket ladder)",
        "requests": len(prompts),
        "max_new_tokens": max_new,
        "tokens_generated": tokens_total,
        "engine_s": round(engine_s, 4),
        "baseline_s": round(baseline_s, 4),
        "engine_tokens_per_s": round(tokens_total / engine_s, 2),
        "baseline_tokens_per_s": round(tokens_total / baseline_s, 2),
        "speedup": round(baseline_s / engine_s, 2),
        "token_parity_all_match": all(matches),
        "deterministic_across_passes": all(stable),
        "decode_batch_hist": stats["decode_batch_hist"],
        "peak_cache_occupancy": round(stats["peak_occupancy"], 4),
        "pool_blocks": stats["pool_blocks"],
        "block_reuses": stats["block_reuses"],
        "warmed_combos": combos,
        "compile_count": stats["compile_count"],
        "executable_grid": combos,
    }
    assert out["token_parity_all_match"], out
    assert out["deterministic_across_passes"], out
    assert out["compile_count"] <= combos + len(set(
        (engine.config.prefill_seq_buckets) + (engine.config.max_seq_len,)
    )), out
    if not selftest:
        assert out["speedup"] >= 3.0, out
    return out


# ---------------------------------------------------------------------------
# leg 2: warm restart of the prefill+decode grid through the AOT cache
# ---------------------------------------------------------------------------


def restart_phase(phase, workdir, selftest):
    """Subprocess body: build the engine from scratch under
    FLAGS_aot_cache_dir (set by the parent), warm the whole grid, run a
    fixed prompt set, and write counters + tokens for the parent to
    compare across the simulated restart."""
    from paddle_tpu.framework.aot_cache import cache_stats
    from paddle_tpu.monitor import stat
    from paddle_tpu.serving.decode import DecodeEngine

    c0 = stat("executor_compile_count").get()
    t0 = time.perf_counter()
    engine = DecodeEngine(_model(selftest),
                          _config(selftest, pool_blocks=48))
    combos = engine.warmup()
    warm_s = time.perf_counter() - t0
    fresh = stat("executor_compile_count").get() - c0

    prompts = _prompts(selftest, seed=23)
    max_new = 4 if selftest else 8
    futs = [engine.generate({"src_ids": p}, max_new_tokens=max_new)
            for p in prompts]
    tokens = [f.result(timeout=600).tokens for f in futs]
    engine.shutdown()

    np.savez(os.path.join(workdir, f"tokens_{phase}.npz"),
             **{f"t{i}": t for i, t in enumerate(tokens)})
    report = {"phase": phase, "combos": combos,
              "startup_warmup_s": round(warm_s, 4),
              "fresh_compiles": fresh, "aot": cache_stats()}
    with open(os.path.join(workdir, f"phase_{phase}.json"), "w") as f:
        json.dump(report, f)
    return 0


def leg_warm_restart(selftest=False):
    with tempfile.TemporaryDirectory() as workdir:
        cache_dir = os.path.join(workdir, "aot")
        env = dict(os.environ, FLAGS_aot_cache_dir=cache_dir,
                   JAX_PLATFORMS="cpu")
        phases = {}
        for phase in ("cold", "warm"):
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--restart-phase", phase, "--workdir", workdir]
            if selftest:
                cmd.append("--selftest")
            proc = subprocess.run(cmd, env=env, capture_output=True,
                                  text=True, timeout=900)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"restart {phase} phase failed:\n{proc.stdout}\n"
                    f"{proc.stderr}")
            with open(os.path.join(workdir,
                                   f"phase_{phase}.json")) as f:
                phases[phase] = json.load(f)
        cold_np = np.load(os.path.join(workdir, "tokens_cold.npz"))
        warm_np = np.load(os.path.join(workdir, "tokens_warm.npz"))
        bit_identical = all(np.array_equal(cold_np[k], warm_np[k])
                            for k in cold_np.files)

    cold, warm = phases["cold"], phases["warm"]
    out = {
        "definition": "two fresh processes sharing one aot_cache_dir: "
                      "the cold one traces+compiles+stores the whole "
                      "prefill (batch x seq) grid + per-bucket decode "
                      "steps, the warm 'restarted replica' "
                      "deserializes every executable — fresh compiles, "
                      "cache counters, startup wall-clock and the "
                      "generated token bits compared across the "
                      "restart",
        "combos": cold["combos"],
        "cold_startup_s": cold["startup_warmup_s"],
        "warm_startup_s": warm["startup_warmup_s"],
        "startup_speedup": round(
            cold["startup_warmup_s"] /
            max(warm["startup_warmup_s"], 1e-9), 2),
        "cold_fresh_compiles": cold["fresh_compiles"],
        "warm_fresh_compiles": warm["fresh_compiles"],
        "cold_stores": cold["aot"]["stores"],
        "warm_hits": warm["aot"]["hits"],
        "warm_errors": warm["aot"]["errors"],
        "tokens_bit_identical": bool(bit_identical),
    }
    assert out["warm_fresh_compiles"] == 0, out
    assert out["warm_hits"] >= out["combos"], out
    assert out["warm_errors"] == 0, out
    assert out["tokens_bit_identical"], out
    return out


# ---------------------------------------------------------------------------
# leg 3: cache-block admission
# ---------------------------------------------------------------------------


def leg_admission(selftest=False):
    from paddle_tpu.framework.errors import InvalidArgumentError
    from paddle_tpu.monitor import stat
    from paddle_tpu.serving.decode import DecodeEngine, blocks_needed

    # a pool deliberately smaller than one max-length sequence: a
    # max-span request can never fit (rejected at submit), and a few
    # medium sequences saturate it so later arrivals wait
    pool = 5 if selftest else 6
    cfg = _config(selftest, pool_blocks=pool)
    engine = DecodeEngine(_model(selftest), cfg)
    try:
        engine.warmup()
        rng = np.random.RandomState(5)

        big_prompt = rng.randint(
            0, 1024, (cfg.prefill_seq_buckets[-1],)).astype(np.int64)
        big_new = cfg.max_seq_len - len(big_prompt)
        need = blocks_needed(len(big_prompt), big_new, cfg.block_size)
        assert need > pool
        c0 = stat("executor_compile_count").get()
        rejected, named = False, False
        try:
            engine.generate({"src_ids": big_prompt},
                            max_new_tokens=big_new)
        except InvalidArgumentError as e:
            rejected = True
            named = "blocks" in str(e) and "pool" in str(e)
        compiles_at_reject = stat("executor_compile_count").get() - c0

        # saturate: 3 medium sequences into a pool that fits ~1.5 —
        # later arrivals wait for retirements, blocks recycle, and the
        # delayed/reused-block sequences still match the lone loop
        prompts = [rng.randint(0, 1024, (n,)).astype(np.int64)
                   for n in (6, 9, 5)]
        long_new = 16 if selftest else 22
        refs = [engine.greedy_reference({"src_ids": p},
                                        max_new_tokens=long_new)
                for p in prompts]
        futs = [engine.generate({"src_ids": p}, max_new_tokens=long_new)
                for p in prompts]
        results = [f.result(timeout=600) for f in futs]
        stats = engine.stats()
        parity = all(np.array_equal(r.tokens, g.tokens)
                     for r, g in zip(results, refs))
    finally:
        engine.shutdown()

    out = {
        "definition": "admission prices blocks_needed(prompt, max_new) "
                      "before any compile: a request whose reserved "
                      "span exceeds the pool is rejected at submit "
                      "with 0 compiles spent; a saturated pool makes "
                      "later arrivals wait for retirements (blocks "
                      "freed and reused) and they still decode "
                      "token-for-token equal to the lone greedy loop",
        "rejected_over_pool": rejected,
        "rejection_names_blocks": named,
        "rejected_blocks_needed": int(need),
        "compiles_at_reject": compiles_at_reject,
        "pool_blocks": stats["pool_blocks"],
        "admission_waits": stats["admission_waits"],
        "block_reuses": stats["block_reuses"],
        "peak_cache_occupancy": round(stats["peak_occupancy"], 4),
        "parity_under_churn": bool(parity),
    }
    assert out["rejected_over_pool"], out
    assert out["rejection_names_blocks"], out
    assert out["compiles_at_reject"] == 0, out
    assert out["admission_waits"] >= 1, out
    assert out["block_reuses"] >= 1, out
    assert out["parity_under_churn"], out
    return out


# ---------------------------------------------------------------------------
# leg 4: device-chained multi-token decode (+ sampling determinism,
#        regression gate vs the committed r19 artifact)
# ---------------------------------------------------------------------------


def leg_chained(selftest=False):
    from paddle_tpu.serving.decode import DecodeEngine

    chain = 8 if selftest else 16
    # max_new = chain + 1: prefill emits token 1, one full chain emits
    # the rest — every decode host sync retires chain tokens per row
    max_new = chain + 1
    prompts = _prompts(selftest)

    def run_stream(engine):
        ref = [engine.greedy_reference({"src_ids": p},
                                       max_new_tokens=max_new)
               for p in prompts]
        # warm pass (compiles + first-touch out of the window)
        futs = [engine.generate({"src_ids": p}, max_new_tokens=max_new)
                for p in prompts]
        [f.result(timeout=600) for f in futs]
        engine.drain()
        t0 = time.perf_counter()
        futs = [engine.generate({"src_ids": p}, max_new_tokens=max_new)
                for p in prompts]
        results = [f.result(timeout=600) for f in futs]
        elapsed = time.perf_counter() - t0
        parity = all(np.array_equal(r.tokens, g.tokens)
                     for r, g in zip(results, ref))
        tokens = sum(len(r.tokens) for r in results)
        return tokens, elapsed, parity, engine.stats()

    # the r19 shape: one host round-trip (dispatch + token fetch) per
    # decoded token
    base_engine = DecodeEngine(
        _model(selftest),
        _config(selftest, chain_lengths=(1,), prefix_cache=False))
    try:
        base_engine.warmup()
        base_tok, base_s, base_parity, _ = run_stream(base_engine)
    finally:
        base_engine.shutdown()

    # the v2 fast path: chain-length steps of the SAME decode body
    # scanned on device per round-trip.  Measured greedy (the perf
    # contract is about chaining; sampling chains pay a per-step
    # [batch, vocab] policy sort and get their own engine below)
    engine = DecodeEngine(
        _model(selftest),
        _config(selftest, chain_lengths=(chain,), prefix_cache=False))
    try:
        engine.warmup()
        tok, fast_s, parity, stats = run_stream(engine)
    finally:
        engine.shutdown()

    # seeded sampling on a sampling-enabled chain: a fixed seed draws
    # identical tokens across submissions (no matter how the request
    # is co-batched or chain-scheduled); a different seed draws a
    # different stream; co-batched greedy rows keep bit parity
    s_engine = DecodeEngine(
        _model(selftest),
        _config(selftest, chain_lengths=(chain,), prefix_cache=False,
                sampling=True))
    try:
        s_engine.warmup()
        sp = prompts[0]
        greedy_ref = s_engine.greedy_reference(
            {"src_ids": sp}, max_new_tokens=max_new)
        kw = dict(max_new_tokens=max_new, temperature=0.9, top_k=8,
                  top_p=0.9)
        futs = [s_engine.generate({"src_ids": sp}, seed=123, **kw),
                s_engine.generate({"src_ids": sp}, seed=123, **kw),
                s_engine.generate({"src_ids": sp}, seed=321, **kw),
                s_engine.generate({"src_ids": sp},
                                  max_new_tokens=max_new)]
        s1, s2, s3, g = [f.result(timeout=600) for f in futs]
        deterministic = bool(np.array_equal(s1.tokens, s2.tokens))
        seed_sensitive = not np.array_equal(s1.tokens, s3.tokens)
        greedy_row_parity = bool(
            np.array_equal(g.tokens, greedy_ref.tokens))
    finally:
        s_engine.shutdown()

    decode_syncs = stats["chains_run"]
    decode_tokens = stats["chain_tokens"]
    out = {
        "definition": "the same mixed request stream through the "
                      "single-step engine (chain_lengths=(1,), the r19 "
                      "shape: one host dispatch + one device->host "
                      "token fetch per decoded token) and the chained "
                      "engine (chain_length decode steps scanned on "
                      "device per round-trip; next-token, cache write, "
                      "block-table walk and EOS/length masking all "
                      "inside the scan); tokens/s, host syncs per "
                      "chained decode token, greedy bit parity, and "
                      "fixed-seed sampling determinism",
        "chain_length": chain,
        "requests": len(prompts),
        "max_new_tokens": max_new,
        "tokens_generated": tok,
        "single_step_s": round(base_s, 4),
        "chained_s": round(fast_s, 4),
        "single_step_tokens_per_s": round(base_tok / base_s, 2),
        "chained_tokens_per_s": round(tok / fast_s, 2),
        "speedup": round(base_s / fast_s, 2),
        "decode_host_syncs": decode_syncs,
        "decode_tokens": decode_tokens,
        "syncs_per_decode_token": round(
            decode_syncs / max(decode_tokens, 1), 4),
        "chain_hist": stats["chain_hist"],
        "token_parity_all_match": bool(parity and base_parity),
        "sampling_deterministic_fixed_seed": deterministic,
        "sampling_differs_across_seeds": bool(seed_sensitive),
        "sampling_cobatched_greedy_parity": greedy_row_parity,
    }
    if not selftest:
        r19_path = os.path.join(REPO, R19_ARTIFACT)
        with open(r19_path) as f:
            r19 = json.load(f)
        r19_tps = r19["throughput"]["engine_tokens_per_s"]
        out["regression"] = {
            "definition": "the v2 engine may not regress below the "
                          "committed r19 decode throughput: chained "
                          "tokens/s >= r19 engine tokens/s x tolerance",
            "r19_tokens_per_s": r19_tps,
            "chained_tokens_per_s": out["chained_tokens_per_s"],
            "tolerance": REGRESSION_TOLERANCE,
            "pass": bool(out["chained_tokens_per_s"]
                         >= r19_tps * REGRESSION_TOLERANCE),
        }
        assert out["regression"]["pass"], out
        assert out["speedup"] >= 1.5, out
    assert out["token_parity_all_match"], out
    assert out["sampling_deterministic_fixed_seed"], out
    assert out["sampling_cobatched_greedy_parity"], out
    # one packed [chain, batch] fetch per chain: <= 1/chain_length host
    # syncs per decoded token
    assert out["syncs_per_decode_token"] <= 1.0 / chain, out
    return out


# ---------------------------------------------------------------------------
# leg 5: cross-request prefix caching
# ---------------------------------------------------------------------------


def leg_prefix(selftest=False):
    from paddle_tpu.serving.decode import DecodeEngine

    cfg = _config(selftest, prefix_cache=True)
    engine = DecodeEngine(_model(selftest), cfg)
    bs = cfg.block_size
    rng = np.random.RandomState(17)
    base_len = 16 if selftest else 24
    base = rng.randint(0, 1024, (base_len,)).astype(np.int64)
    max_new = 4 if selftest else 6
    try:
        engine.warmup()

        # phase 1 (cold): one request populates the shared-block index
        # on retire — full prompt blocks content-hashed under the
        # model/layout key, refcount 0 (cached, evictable)
        cold = engine.generate({"src_ids": base},
                               max_new_tokens=max_new).result(timeout=600)
        engine.drain()
        s0 = engine.stats()

        # phase 2 (warm): repeat arrivals share the cached prefix —
        # admission charges only the non-shared suffix and prefill
        # computes ONLY the suffix tokens
        warm_prompts = [base.copy()]
        if not selftest:
            tail = rng.randint(0, 1024, (6,)).astype(np.int64)
            warm_prompts.append(np.concatenate([base, tail]))
        else:
            warm_prompts.append(base.copy())
        refs = [engine.greedy_reference({"src_ids": p},
                                        max_new_tokens=max_new)
                for p in warm_prompts]
        futs = [engine.generate({"src_ids": p}, max_new_tokens=max_new)
                for p in warm_prompts]
        results = [f.result(timeout=600) for f in futs]
        engine.drain()
        s1 = engine.stats()
        parity = all(np.array_equal(r.tokens, g.tokens)
                     for r, g in zip(results, refs)) and \
            np.array_equal(cold.tokens, refs[0].tokens)
    finally:
        engine.shutdown()

    hits = s1["prefix_hits"] - s0["prefix_hits"]
    prefilled = s1["prefill_tokens"] - s0["prefill_tokens"]
    total_prompt = sum(len(p) for p in warm_prompts)
    # a prompt's shareable span is its largest whole-block prefix
    # strictly before the last token (the last prompt token is always
    # recomputed so prefill has a suffix to run)
    suffix = sum(len(p) - (min(len(p), len(base)) - 1) // bs * bs
                 for p in warm_prompts)
    out = {
        "definition": "a shared-prefix request stream: the first "
                      "arrival populates the content-hash block index "
                      "(token-ids x model/layout key) on retire; "
                      "repeat arrivals probe it, acquire refcounts on "
                      "the shared whole-prompt blocks, get admission "
                      "priced on the non-shared suffix only, and "
                      "prefill ONLY the suffix tokens — to parity with "
                      "the lone greedy loop",
        "block_size": bs,
        "base_prompt_tokens": int(base_len),
        "warm_requests": len(warm_prompts),
        "warm_prompt_tokens_total": int(total_prompt),
        "warm_suffix_tokens_max": int(suffix),
        "warm_prefill_tokens": int(prefilled),
        "prefix_hits": int(hits),
        "prefix_misses": int(s1["prefix_misses"]),
        "bytes_saved": int(s1["prefix_bytes_saved"]),
        "indexed_blocks": int(s1["prefix_indexed_blocks"]),
        "cache_blocks_used_after_drain": int(s1["cache_blocks_used"]),
        "token_parity_all_match": bool(parity),
    }
    assert out["prefix_hits"] > 0, out
    assert out["warm_prefill_tokens"] <= out["warm_suffix_tokens_max"], out
    assert out["warm_suffix_tokens_max"] < out["warm_prompt_tokens_total"], \
        out
    assert out["bytes_saved"] > 0, out
    assert out["cache_blocks_used_after_drain"] == 0, out
    assert out["token_parity_all_match"], out
    return out


# ---------------------------------------------------------------------------
# leg 6: chunked prefill interleaved with live decodes
# ---------------------------------------------------------------------------


def leg_chunked(selftest=False):
    from paddle_tpu.serving.decode import DecodeEngine

    chunk = 8 if selftest else 16
    cfg = _config(selftest, chunk_tokens=chunk, prefix_cache=False)
    engine = DecodeEngine(_model(selftest), cfg)
    rng = np.random.RandomState(29)
    bucket = cfg.prefill_seq_buckets[-1]
    long_lens = (24, 20) if selftest else (40, 48)
    long_new = 4 if selftest else 8
    short_lens = (5,) if selftest else (6, 10)
    short_new = 6 if selftest else 16
    longs = [rng.randint(0, 1024, (n,)).astype(np.int64)
             for n in long_lens]
    shorts = [rng.randint(0, 1024, (n,)).astype(np.int64)
              for n in short_lens]
    try:
        engine.warmup()
        refs = [engine.greedy_reference({"src_ids": p},
                                        max_new_tokens=short_new)
                for p in shorts] + \
            [engine.greedy_reference({"src_ids": p},
                                     max_new_tokens=long_new)
             for p in longs]
        # shorts first so live decodes are in flight while the long
        # prompts stream in chunk-width pieces — no head-of-line block
        futs = [engine.generate({"src_ids": p},
                                max_new_tokens=short_new)
                for p in shorts] + \
            [engine.generate({"src_ids": p}, max_new_tokens=long_new)
             for p in longs]
        results = [f.result(timeout=600) for f in futs]
        stats = engine.stats()
        parity = all(np.array_equal(r.tokens, g.tokens)
                     for r, g in zip(results, refs))
    finally:
        engine.shutdown()

    out = {
        "definition": "prompts LONGER than the largest prefill bucket "
                      "admitted alongside live short requests: the "
                      "long prompts prefill in fixed chunk-width "
                      "pieces (cache-reading executables, absolute-"
                      "position causal masking) interleaved round-"
                      "robin with the live decode chains, then join "
                      "decode — to parity with the lone greedy loop",
        "chunk_tokens": chunk,
        "largest_prefill_bucket": int(bucket),
        "long_prompt_lens": [int(n) for n in long_lens],
        "short_prompt_lens": [int(n) for n in short_lens],
        "chunk_steps": int(stats["chunk_steps"]),
        "interleaved_rounds": int(stats["interleaved_rounds"]),
        "token_parity_all_match": bool(parity),
    }
    assert max(out["long_prompt_lens"]) > bucket, out
    assert out["chunk_steps"] >= 2, out
    assert out["interleaved_rounds"] >= 1, out
    assert out["token_parity_all_match"], out
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def check(art):
    """The artifact contract — the same assertions tier-1
    (tests/test_decode.py) applies to the committed file."""
    assert art["metric"] == "decode_engine"
    assert art["schema"] == SCHEMA
    tp = art["throughput"]
    assert tp["requests"] >= 8
    assert tp["speedup"] >= 3.0, tp
    assert tp["token_parity_all_match"] is True
    assert tp["deterministic_across_passes"] is True
    assert tp["tokens_generated"] >= 100
    assert 0 < tp["peak_cache_occupancy"] <= 1.0
    wr = art["warm_restart"]
    assert wr["combos"] > 0
    assert wr["warm_fresh_compiles"] == 0, wr
    assert wr["warm_hits"] >= wr["combos"]
    assert wr["tokens_bit_identical"] is True
    ad = art["admission"]
    assert ad["rejected_over_pool"] is True
    assert ad["rejection_names_blocks"] is True
    assert ad["compiles_at_reject"] == 0
    assert ad["admission_waits"] >= 1
    assert ad["block_reuses"] >= 1
    assert ad["parity_under_churn"] is True
    ch = art["chained"]
    assert ch["chain_length"] > 1
    assert ch["speedup"] >= 1.5, ch
    assert ch["syncs_per_decode_token"] <= 1.0 / ch["chain_length"], ch
    assert ch["token_parity_all_match"] is True
    assert ch["sampling_deterministic_fixed_seed"] is True
    assert ch["regression"]["pass"] is True, ch
    px = art["prefix"]
    assert px["prefix_hits"] > 0
    assert px["warm_prefill_tokens"] <= px["warm_suffix_tokens_max"]
    assert px["warm_suffix_tokens_max"] < px["warm_prompt_tokens_total"]
    assert px["bytes_saved"] > 0
    assert px["token_parity_all_match"] is True
    ck = art["chunked"]
    assert max(ck["long_prompt_lens"]) > ck["largest_prefill_bucket"]
    assert ck["chunk_steps"] >= 2
    assert ck["interleaved_rounds"] >= 1
    assert ck["token_parity_all_match"] is True


ALL_LEGS = ("throughput", "warm_restart", "admission",
            "chained", "prefix", "chunked")


def run_all(selftest=False, legs=ALL_LEGS):
    art = {
        "metric": "decode_engine",
        "schema": SCHEMA,
        "model": "bert_tiny_decoder_cpu",
        "before": "per-request greedy loop re-scoring the full prefix "
                  "per token (the reference AnalysisPredictor serving "
                  "shape; no KV cache, no cross-request batching)",
    }
    if "throughput" in legs:
        art["throughput"] = leg_throughput(selftest=selftest)
    if "warm_restart" in legs:
        art["warm_restart"] = leg_warm_restart(selftest=selftest)
    if "admission" in legs:
        art["admission"] = leg_admission(selftest=selftest)
    if "chained" in legs:
        art["chained"] = leg_chained(selftest=selftest)
    if "prefix" in legs:
        art["prefix"] = leg_prefix(selftest=selftest)
    if "chunked" in legs:
        art["chunked"] = leg_chunked(selftest=selftest)
    return art


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--restart-phase" in argv:       # subprocess worker mode
        i = argv.index("--restart-phase")
        phase = argv[i + 1]
        workdir = argv[argv.index("--workdir") + 1]
        return restart_phase(phase, workdir, "--selftest" in argv)
    selftest = "--selftest" in argv
    if selftest:
        argv.remove("--selftest")
    legs = []
    for flag_name, leg in (("--throughput", "throughput"),
                           ("--warm-restart", "warm_restart"),
                           ("--admission", "admission"),
                           ("--chained", "chained"),
                           ("--prefix", "prefix"),
                           ("--chunked", "chunked")):
        if flag_name in argv:
            argv.remove(flag_name)
            legs.append(leg)
    single = bool(legs)
    art = run_all(selftest=selftest, legs=legs or ALL_LEGS)
    print(json.dumps(art, indent=1))
    if selftest:
        print("decode_bench selftest OK"
              + (f" (legs: {', '.join(sorted(art))})" if single else ""))
        return 0
    if single:
        return 0
    check(art)
    out = argv[0] if argv else os.path.join(REPO, ARTIFACT)
    with open(out, "w") as f:
        json.dump(art, f, indent=1)
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
