#!/usr/bin/env python
"""Serving contract gate — the ISSUE 7 "Serving v2" acceptance artifact.
CPU-ONLY: it pins ``JAX_PLATFORMS=cpu`` (its AOT-cache leg starts child
processes after the parent has touched JAX, and a chip belongs to one
process), so its throughput ratios are CPU stopwatch readings next to
parity and count contracts, never device numbers.

Three legs on the CPU BERT-tiny encoder (before-numbers: the PR 4
artifact ``SERVE_BENCH_r08.json`` — 44.7 % padding waste, steady-state
0.81x vs the naive loop, 9.7 s per-process warmup):

* **--ragged** — ragged sequence packing
  (``ServingConfig(packing=True)``): requests pack along the token axis
  with one-hot segment-channel masks instead of each padding its own
  bucket row.  Measures mixed-stream steady-state throughput vs the
  reference-shaped per-request ``predictor.run`` loop AND vs the padded
  (PR 4) engine, plus packing vs padding waste and raw-run parity;
* **--aot-cache** — the persistent AOT executable cache
  (``flag("aot_cache_dir")``): a COLD subprocess warms the bucket grid
  (tracing+compiling+serializing), then a WARM subprocess with the same
  cache dir restarts from scratch — asserted 0 fresh compiles, every
  bucket a cache hit, and results bit-identical to the cold run;
* **--multi-tenant** — ``ServingFleet`` HBM admission: a model set
  whose combined ``memory_analysis.estimate`` exceeds the budget is
  rejected pre-compile (offending model named, 0 compiles attempted);
  evicting one bucket variant then admits the rest.

Emits ``SERVE_BENCH_r11.json`` (asserted by tier-1
tests/test_serving_v2.py::test_serve_bench_r11_artifact_contract).

Usage:
  python tools/serve_bench.py [out.json]            # all legs + artifact
  python tools/serve_bench.py --ragged              # one leg, print JSON
  python tools/serve_bench.py --aot-cache
  python tools/serve_bench.py --multi-tenant
  python tools/serve_bench.py --selftest            # quick CI gate, no write
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

SEQ_FEEDS = ("src_ids", "pos_ids", "sent_ids", "input_mask")


def _build_model(model_dir, n_layer=2, fetch="pooled"):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.core import Program, program_guard
    from paddle_tpu.models import bert

    cfg = bert.BertConfig(vocab_size=1024, hidden_size=128,
                          num_hidden_layers=n_layer, num_attention_heads=2,
                          intermediate_size=512,
                          max_position_embeddings=128, type_vocab_size=2)
    main, startup = Program(), Program()
    with program_guard(main, startup):
        src = fluid.layers.data("src_ids", shape=[-1, -1], dtype="int64",
                                append_batch_size=False)
        pos = fluid.layers.data("pos_ids", shape=[-1, -1], dtype="int64",
                                append_batch_size=False)
        sent = fluid.layers.data("sent_ids", shape=[-1, -1], dtype="int64",
                                 append_batch_size=False)
        mask = fluid.layers.data("input_mask", shape=[-1, -1, 1],
                                 dtype="float32", append_batch_size=False)
        seq_out, pooled = bert.bert_encoder(src, pos, sent, mask, cfg,
                                            is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    targets = [seq_out] if fetch == "seq" else [pooled]
    fluid.io.save_inference_model(model_dir, list(SEQ_FEEDS), targets,
                                  exe, main)
    return cfg


def _request(rng, cfg, b, s):
    return {
        "src_ids": rng.randint(0, cfg.vocab_size, (b, s)).astype("int64"),
        "pos_ids": np.tile(np.arange(s, dtype="int64"), (b, 1)),
        "sent_ids": rng.randint(0, cfg.type_vocab_size,
                                (b, s)).astype("int64"),
        "input_mask": np.ones((b, s, 1), dtype="float32"),
    }


def _predictor(model_dir):
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
    config = AnalysisConfig(model_dir)
    config.disable_gpu()
    return create_paddle_predictor(config)


def _stream(cfg, shapes, repeats, seed=0):
    rng = np.random.RandomState(seed)
    stream = []
    for _ in range(repeats):
        for b, s in shapes:
            stream.append(_request(rng, cfg, b, s))
    order = np.random.RandomState(1).permutation(len(stream))
    return [stream[i] for i in order]


# ---------------------------------------------------------------------------
# leg 1: ragged packing vs padded vs the naive per-request loop
# ---------------------------------------------------------------------------


def leg_ragged(selftest=False):
    from paddle_tpu.serving import ServingConfig, ServingEngine

    if selftest:
        n_layer = 1
        shapes = [(1, 5), (1, 9), (1, 13), (2, 7), (1, 16), (2, 12)]
        repeats = 2
        seq_buckets, batch_buckets, max_batch = (8, 16), (1, 2, 4), 4
    else:
        n_layer = 2
        shapes = [(b, s) for b in (1, 2, 3)
                  for s in (9, 17, 25, 33, 41, 49, 57, 64)]   # 24 distinct
        repeats = 3
        seq_buckets, batch_buckets, max_batch = \
            (16, 32, 48, 64), (1, 2, 4, 8), 8

    with tempfile.TemporaryDirectory() as model_dir:
        cfg = _build_model(model_dir, n_layer=n_layer, fetch="seq")
        stream = _stream(cfg, shapes, repeats)

        # -- naive per-request loop (the reference's serving shape) -------
        baseline = _predictor(model_dir)
        baseline_outs = [baseline.run([r[n] for n in SEQ_FEEDS])[0]
                         for r in stream]          # cold pass: compiles
        t0 = time.perf_counter()
        for r in stream:
            baseline.run([r[n] for n in SEQ_FEEDS])
        baseline_steady_s = time.perf_counter() - t0

        def run_engine(packing):
            pred = _predictor(model_dir)
            seq_fetch = pred.get_output_names()[0]
            kw = dict(max_batch_size=max_batch, max_wait_ms=2.0,
                      batch_buckets=batch_buckets, seq_buckets=seq_buckets,
                      seq_feeds=SEQ_FEEDS, seq_fetches=(seq_fetch,))
            if packing:
                kw.update(packing=True, mask_feed="input_mask",
                          pack_max_segments=8)
            engine = ServingEngine(pred, ServingConfig(**kw))
            t0 = time.perf_counter()
            combos = engine.warmup(stream[0])
            warmup_s = time.perf_counter() - t0
            futs = [engine.submit(r) for r in stream]
            outs = [f.result(timeout=600)[0] for f in futs]    # cold pass
            t0 = time.perf_counter()
            futs = [engine.submit(r) for r in stream]
            for f in futs:
                f.result(timeout=600)
            steady_s = time.perf_counter() - t0
            stats = engine.stats()
            engine.shutdown()
            parity = max(float(np.abs(e - b).max())
                         for e, b in zip(outs, baseline_outs))
            return dict(steady_s=steady_s, warmup_s=warmup_s,
                        combos=combos, stats=stats, parity=parity)

        padded = run_engine(packing=False)
        ragged = run_engine(packing=True)

    out = {
        "requests": len(stream),
        "distinct_request_shapes": len(shapes),
        "definition": "steady-state wall-clock for one mixed-shape "
                      "request stream, all sides fully warm: naive "
                      "per-request predictor.run loop vs the padded "
                      "(PR 4) engine vs ragged sequence packing "
                      "(one-hot segment-channel masks, block-diagonal "
                      "attention)",
        "baseline_steady_s": round(baseline_steady_s, 3),
        "padded_steady_s": round(padded["steady_s"], 3),
        "engine_steady_s": round(ragged["steady_s"], 3),
        "steady_state_ratio": round(
            baseline_steady_s / ragged["steady_s"], 2),
        "steady_state_ratio_padded": round(
            baseline_steady_s / padded["steady_s"], 2),
        "padding_waste_padded": round(
            padded["stats"]["padding_waste"], 4),
        "padding_waste": round(ragged["stats"]["padding_waste"], 4),
        "parity_max_abs_diff": ragged["parity"],
        "parity_max_abs_diff_padded": padded["parity"],
        "batches": ragged["stats"]["batches"],
        "compiles": ragged["stats"]["compile_count"],
        "bucket_capacity": len(batch_buckets) * len(seq_buckets),
        "batch_buckets": list(batch_buckets),
        "seq_buckets": list(seq_buckets),
        "pack_max_segments": 8,
        "warmup_s": round(ragged["warmup_s"], 3),
        "spurious_wakeups": ragged["stats"]["spurious_wakeups"],
    }
    # packing is mask-aware: within float noise of the raw unpadded runs
    assert out["parity_max_abs_diff"] <= 2e-5, out
    assert out["compiles"] <= out["bucket_capacity"], out
    # packing must strictly beat padding on waste
    assert out["padding_waste"] < out["padding_waste_padded"], out
    if not selftest:
        assert out["steady_state_ratio"] >= 1.0, out
        assert out["padding_waste"] <= 0.15, out
    return out


# ---------------------------------------------------------------------------
# leg 2: persistent AOT cache — cold/warm restart in subprocesses
# ---------------------------------------------------------------------------

_AOT_GRID = dict(batch_buckets=(1, 2, 4), seq_buckets=(16, 32),
                 max_batch=4)
_AOT_GRID_SELF = dict(batch_buckets=(1, 2), seq_buckets=(16,),
                      max_batch=2)


def aot_phase(phase, workdir, selftest):
    """Subprocess body for one restart phase: load the prebuilt model,
    warm the bucket grid under FLAGS_aot_cache_dir (set by the parent),
    serve a fixed stream, and write counters + outputs for the parent to
    compare across the simulated restart."""
    from paddle_tpu.framework.aot_cache import cache_stats
    from paddle_tpu.monitor import stat
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.models import bert

    grid = _AOT_GRID_SELF if selftest else _AOT_GRID
    model_dir = os.path.join(workdir, "model")
    cfg = bert.BertConfig(vocab_size=1024, hidden_size=128,
                          num_hidden_layers=1 if selftest else 2,
                          num_attention_heads=2, intermediate_size=512,
                          max_position_embeddings=128, type_vocab_size=2)
    pred = _predictor(model_dir)
    engine = ServingEngine(pred, ServingConfig(
        max_batch_size=grid["max_batch"], max_wait_ms=2.0,
        batch_buckets=grid["batch_buckets"],
        seq_buckets=grid["seq_buckets"], seq_feeds=SEQ_FEEDS))
    rng = np.random.RandomState(7)
    example = _request(rng, cfg, 1, grid["seq_buckets"][0])
    c0 = stat("executor_compile_count").get()
    t0 = time.perf_counter()
    combos = engine.warmup(example)
    warmup_s = time.perf_counter() - t0
    fresh_compiles = stat("executor_compile_count").get() - c0

    shapes = [(1, 5), (2, 9), (1, 14)] if selftest else \
        [(1, 5), (2, 9), (1, 14), (4, 25), (2, 30), (1, 32)]
    reqs = [_request(np.random.RandomState(100 + i), cfg, b, s)
            for i, (b, s) in enumerate(shapes)]
    futs = [engine.submit(r) for r in reqs]
    outs = [f.result(timeout=600)[0] for f in futs]
    engine.shutdown()

    np.savez(os.path.join(workdir, f"outs_{phase}.npz"),
             **{f"o{i}": o for i, o in enumerate(outs)})
    report = {"phase": phase, "combos": combos,
              "warmup_s": round(warmup_s, 4),
              "fresh_compiles": fresh_compiles, "aot": cache_stats()}
    with open(os.path.join(workdir, f"phase_{phase}.json"), "w") as f:
        json.dump(report, f)
    return 0


def leg_aot_cache(selftest=False):
    with tempfile.TemporaryDirectory() as workdir:
        _build_model(os.path.join(workdir, "model"),
                     n_layer=1 if selftest else 2, fetch="pooled")
        cache_dir = os.path.join(workdir, "aot")
        env = dict(os.environ, FLAGS_aot_cache_dir=cache_dir,
                   JAX_PLATFORMS="cpu")
        phases = {}
        for phase in ("cold", "warm"):
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--aot-phase", phase, "--workdir", workdir]
            if selftest:
                cmd.append("--selftest")
            proc = subprocess.run(cmd, env=env, capture_output=True,
                                  text=True, timeout=900)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"aot {phase} phase failed:\n{proc.stdout}\n"
                    f"{proc.stderr}")
            with open(os.path.join(workdir, f"phase_{phase}.json")) as f:
                phases[phase] = json.load(f)
        cold_np = np.load(os.path.join(workdir, "outs_cold.npz"))
        warm_np = np.load(os.path.join(workdir, "outs_warm.npz"))
        bit_identical = all(
            np.array_equal(cold_np[k], warm_np[k]) for k in cold_np.files)

    cold, warm = phases["cold"], phases["warm"]
    out = {
        "definition": "two fresh processes sharing one aot_cache_dir: "
                      "the cold one traces+compiles+serializes the "
                      "bucket grid, the warm 'restarted replica' "
                      "deserializes it — fresh compiles, cache "
                      "counters, warmup wall-clock and output bits "
                      "compared across the restart",
        "combos": cold["combos"],
        "cold_warmup_s": cold["warmup_s"],
        "warm_warmup_s": warm["warmup_s"],
        "warmup_speedup": round(cold["warmup_s"] /
                                max(warm["warmup_s"], 1e-9), 2),
        "cold_fresh_compiles": cold["fresh_compiles"],
        "warm_fresh_compiles": warm["fresh_compiles"],
        "cold_stores": cold["aot"]["stores"],
        "warm_hits": warm["aot"]["hits"],
        "warm_errors": warm["aot"]["errors"],
        "bit_identical": bool(bit_identical),
    }
    assert out["cold_fresh_compiles"] == out["combos"], out
    assert out["warm_fresh_compiles"] == 0, out
    assert out["warm_hits"] >= out["combos"], out
    assert out["bit_identical"], out
    assert out["warmup_speedup"] >= (2.0 if selftest else 5.0), out
    return out


# ---------------------------------------------------------------------------
# leg 3: multi-tenant HBM admission (ServingFleet)
# ---------------------------------------------------------------------------


def leg_multi_tenant(selftest=False):
    from paddle_tpu.framework.errors import InvalidArgumentError
    from paddle_tpu.monitor import stat
    from paddle_tpu.serving import ServingConfig, ServingFleet

    n_layer = 1 if selftest else 2
    scfg = dict(max_batch_size=2, max_wait_ms=1.0, batch_buckets=(1, 2),
                seq_buckets=(16, 32), seq_feeds=SEQ_FEEDS)

    with tempfile.TemporaryDirectory() as tmp:
        d1 = os.path.join(tmp, "model_a")
        d2 = os.path.join(tmp, "model_b")
        cfg = _build_model(d1, n_layer=n_layer)
        _build_model(d2, n_layer=n_layer)
        example = _request(np.random.RandomState(3), cfg, 1, 16)

        # size one tenant with admission off, then set the budget so two
        # full tenants exceed it but two-minus-one-variant fits
        probe = ServingFleet(hbm_budget_gb=0)
        probe.add_model("probe", d1, ServingConfig(**scfg),
                        example_feed=example, warmup=False)
        rep = probe.admission_report()["models"]["probe"]
        probe.shutdown(drain=False)
        cost_mb = rep["cost_mb"]
        dyn = sorted(rep["variants"].values())
        budget_mb = 2 * cost_mb - (dyn[-1] - dyn[-2]) / 2
        budget_gb = budget_mb / 1024.0

        fleet = ServingFleet(hbm_budget_gb=budget_gb)
        fleet.add_model("model_a", d1, ServingConfig(**scfg),
                        example_feed=example, warmup=False)
        c0 = stat("executor_compile_count").get()
        rejected, named = False, False
        try:
            fleet.add_model("model_b", d2, ServingConfig(**scfg),
                            example_feed=example, warmup=False)
        except InvalidArgumentError as e:
            rejected = True
            named = "model_b" in str(e)
        compiles_at_reject = stat("executor_compile_count").get() - c0
        evicted = fleet.evict("model_a", (2, 32))
        fleet.add_model("model_b", d2, ServingConfig(**scfg),
                        example_feed=example, warmup=False)
        admitted = sorted(fleet.models())
        f1 = fleet.submit("model_a", _request(
            np.random.RandomState(4), cfg, 1, 9))
        f2 = fleet.submit("model_b", _request(
            np.random.RandomState(5), cfg, 1, 12))
        served = bool(np.isfinite(f1.result(timeout=600)[0]).all() and
                      np.isfinite(f2.result(timeout=600)[0]).all())
        report = fleet.admission_report()
        fleet.shutdown()

    out = {
        "definition": "two tenants whose combined static estimate "
                      "exceeds hbm_budget_gb: the second is rejected "
                      "pre-compile (named, 0 compiles attempted); "
                      "evicting one bucket variant of the first admits "
                      "it, and both then serve",
        "hbm_budget_gb": round(budget_gb, 8),
        "tenant_cost_mb": cost_mb,
        "rejected_model": "model_b" if rejected else None,
        "rejection_names_model": named,
        "compiles_at_reject": compiles_at_reject,
        "evicted_variant": [2, 32] if evicted else None,
        "admitted_after_evict": admitted,
        "served_after_admit": served,
        "total_mb": report["total_mb"],
    }
    assert out["rejected_model"] == "model_b", out
    assert out["rejection_names_model"], out
    assert out["compiles_at_reject"] == 0, out
    assert out["evicted_variant"], out
    assert out["admitted_after_evict"] == ["model_a", "model_b"], out
    assert out["served_after_admit"], out
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_all(selftest=False, legs=("ragged", "aot_cache", "multi_tenant")):
    art = {
        "metric": "serving_v2",
        "model": "bert_tiny_encoder_cpu",
        "before": "SERVE_BENCH_r08.json (padded engine: steady 0.81x, "
                  "padding waste 0.447, warmup 9.7 s/process)",
    }
    if "ragged" in legs:
        art["ragged"] = leg_ragged(selftest=selftest)
    if "aot_cache" in legs:
        art["aot_cache"] = leg_aot_cache(selftest=selftest)
    if "multi_tenant" in legs:
        art["multi_tenant"] = leg_multi_tenant(selftest=selftest)
    return art


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--aot-phase" in argv:           # subprocess worker mode
        i = argv.index("--aot-phase")
        phase = argv[i + 1]
        workdir = argv[argv.index("--workdir") + 1]
        return aot_phase(phase, workdir, "--selftest" in argv)
    selftest = "--selftest" in argv
    if selftest:
        argv.remove("--selftest")
    legs = []
    for flag_name, leg in (("--ragged", "ragged"),
                           ("--aot-cache", "aot_cache"),
                           ("--multi-tenant", "multi_tenant")):
        if flag_name in argv:
            argv.remove(flag_name)
            legs.append(leg)
    single = bool(legs)
    art = run_all(selftest=selftest,
                  legs=legs or ("ragged", "aot_cache", "multi_tenant"))
    print(json.dumps(art, indent=1))
    if selftest:
        print("serve_bench selftest OK"
              + (f" (legs: {', '.join(sorted(art))})" if single else ""))
        return 0
    if single:
        return 0
    out = argv[0] if argv else os.path.join(REPO, "SERVE_BENCH_r11.json")
    with open(out, "w") as f:
        json.dump(art, f, indent=1)
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
