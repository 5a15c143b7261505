"""Decode-engine tests (ISSUE 15): paged KV-cache parity (single /
co-batched / delayed-behind-a-full-pool / resumed-after-block-reuse
sequences all token-for-token equal to the unbatched greedy loop),
cache-block admission rejecting with 0 compiles (monkeypatch-asserted),
the in-process AOT warm restart of the prefill+decode grid, the
``serving_decode`` chaos drill (all in-flight generations fail, blocks
free, no drain() hang), and the ``verify_decode`` static profile.  The decode fast path v2 surface
(device-chained decode, sampling, prefix cache, chunked prefill) is
covered in tests/test_decode_v2.py."""

import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.framework.errors import (InvalidArgumentError,
                                         UnavailableError)
from paddle_tpu.models.bert import BertConfig
from paddle_tpu.models.decoder import BertDecoder
from paddle_tpu.serving import DecodeConfig, DecodeEngine, blocks_needed
from paddle_tpu.testing import faultline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture(autouse=True)
def decode_hygiene(tmp_path):
    keep = get_flags(["flight_dump_dir", "aot_cache_dir",
                      "hbm_budget_gb"])
    set_flags({"flight_dump_dir": str(tmp_path / "flight")})
    faultline.disarm()
    yield
    faultline.disarm()
    set_flags(keep)


def _model(n_layer=1, seed=3):
    cfg = BertConfig(vocab_size=512, hidden_size=64,
                     num_hidden_layers=n_layer, num_attention_heads=2,
                     intermediate_size=128, max_position_embeddings=64,
                     type_vocab_size=2, initializer_range=0.5)
    return BertDecoder(cfg, seed=seed)


def _config(**kw):
    base = dict(block_size=4, max_seq_len=32, max_batch_size=4,
                prefill_seq_buckets=(8, 16), prefill_batch_buckets=(1, 2),
                pack_max_segments=2, max_new_tokens=6)
    base.update(kw)
    return DecodeConfig(**base)


def _prompts(lens, seed=42, vocab=512):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (n,)).astype(np.int64) for n in lens]


@pytest.fixture(scope="module")
def engine():
    eng = DecodeEngine(_model(), _config())
    yield eng
    eng.shutdown()


# ---------------------------------------------------------------------------
# parity: the bit-parity contract, token-for-token vs the greedy loop
# ---------------------------------------------------------------------------


def test_single_sequence_matches_greedy_loop(engine):
    (p,) = _prompts([5])
    res = engine.generate({"src_ids": p}, max_new_tokens=6).result(
        timeout=300)
    ref = engine.greedy_reference({"src_ids": p}, max_new_tokens=6)
    assert np.array_equal(res.tokens, ref.tokens)
    assert res.prompt_len == 5
    assert res.finish_reason == "length"
    assert len(res.tokens) == 6


def test_cobatched_mixed_lengths_parity(engine):
    """Several mixed-length sequences co-batched at token granularity
    each match their LONE greedy reference — co-residents in the same
    decode step (and the same packed prefill rows) cannot perturb a
    sequence's tokens."""
    prompts = _prompts([3, 7, 9, 12], seed=1)
    futs = [engine.generate({"src_ids": p}, max_new_tokens=6)
            for p in prompts]
    results = [f.result(timeout=300) for f in futs]
    for p, r in zip(prompts, results):
        ref = engine.greedy_reference({"src_ids": p}, max_new_tokens=6)
        assert np.array_equal(r.tokens, ref.tokens), \
            (r.tokens, ref.tokens)
    stats = engine.stats()
    # proof they actually shared decode steps
    assert any(k >= 2 for k in stats["decode_batch_hist"])
    assert len({tuple(r.tokens.tolist()) for r in results}) >= 2


def test_churn_block_reuse_and_delay_parity():
    """The satellite drill: a pool that fits ~1.5 sequences forces later
    arrivals to WAIT for retirements and take over freed blocks — a
    sequence decoded into reused blocks (and one delayed behind a full
    pool) still matches the lone greedy loop token-for-token."""
    eng = DecodeEngine(_model(), _config(pool_blocks=10))
    try:
        prompts = _prompts([6, 9, 5], seed=2)
        refs = [eng.greedy_reference({"src_ids": p}, max_new_tokens=16)
                for p in prompts]
        futs = [eng.generate({"src_ids": p}, max_new_tokens=16)
                for p in prompts]
        results = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
        for r, g in zip(results, refs):
            assert np.array_equal(r.tokens, g.tokens), \
                (r.tokens, g.tokens)
        assert stats["admission_waits"] >= 1      # someone waited
        assert stats["block_reuses"] >= 1         # freed blocks reused
        assert stats["cache_blocks_used"] == 0    # all freed at retire
    finally:
        eng.shutdown()


def test_eos_early_stop_frees_blocks(engine):
    (p,) = _prompts([6], seed=9)
    probe = engine.greedy_reference({"src_ids": p}, max_new_tokens=4)
    eos = int(probe.tokens[1])        # stop at the second token
    res = engine.generate({"src_ids": p}, max_new_tokens=8,
                          eos_token_id=eos).result(timeout=300)
    ref = engine.greedy_reference({"src_ids": p}, max_new_tokens=8,
                                  eos_token_id=eos)
    assert np.array_equal(res.tokens, ref.tokens)
    assert res.finish_reason == "eos" == ref.finish_reason
    assert len(res.tokens) == 2 and res.tokens[-1] == eos
    engine.drain()
    assert engine.stats()["cache_blocks_used"] == 0


def test_streaming_on_token_callback(engine):
    (p,) = _prompts([4], seed=13)
    seen = []
    res = engine.generate({"src_ids": p}, max_new_tokens=5,
                          on_token=seen.append).result(timeout=300)
    assert seen == res.tokens.tolist()


# ---------------------------------------------------------------------------
# admission: blocks_needed priced before any compile
# ---------------------------------------------------------------------------


def test_blocks_needed_math():
    assert blocks_needed(1, 1, 4) == 1
    assert blocks_needed(4, 0 + 1, 4) == 2
    assert blocks_needed(5, 11, 4) == 4
    assert blocks_needed(8, 8, 8) == 2


def test_admission_reject_spends_zero_compiles(monkeypatch):
    """A request whose reserved span can never fit the pool is rejected
    at generate() — monkeypatch-asserted that NO compile is even
    attempted on the reject path."""
    eng = DecodeEngine(_model(), _config(pool_blocks=4),
                       auto_start=False)
    try:
        from paddle_tpu.framework.executor import Executor
        calls = []

        def boom(self, *a, **kw):
            calls.append(a)
            raise AssertionError("compile attempted on the reject path")

        monkeypatch.setattr(Executor, "_compile", boom)
        big = _prompts([16], seed=4)[0]
        need = blocks_needed(16, 16, 4)
        assert need > 4
        with pytest.raises(InvalidArgumentError) as ei:
            eng.generate({"src_ids": big}, max_new_tokens=16)
        msg = str(ei.value)
        assert "blocks" in msg and "pool" in msg
        assert str(need) in msg
        assert calls == []
        assert eng.stats()["rejected"] == 1
    finally:
        monkeypatch.undo()
        eng.shutdown()


def test_generate_validation(engine):
    with pytest.raises(InvalidArgumentError):
        engine.generate({"ids": np.arange(3)})           # no src_ids
    with pytest.raises(InvalidArgumentError):
        engine.generate({"src_ids": np.zeros((2, 4), np.int64)})
    with pytest.raises(InvalidArgumentError):
        engine.generate({"src_ids": np.zeros((0,), np.int64)})
    with pytest.raises(InvalidArgumentError):
        engine.generate({"src_ids": np.arange(4)}, max_new_tokens=0)
    with pytest.raises(InvalidArgumentError):   # prompt > largest bucket
        engine.generate({"src_ids": np.arange(17)}, max_new_tokens=2)
    with pytest.raises(InvalidArgumentError):   # prompt+new > max_seq_len
        engine.generate({"src_ids": np.arange(10)}, max_new_tokens=30)


def test_budget_sized_pool_uses_memory_analyzer():
    """pool_blocks=None + a budget sizes the pool through
    memory_analysis.plan_cache_pool; an impossible budget raises at
    engine start, before any compile."""
    model = _model()
    cfgkw = dict(block_size=4, max_seq_len=16, max_batch_size=2,
                 prefill_seq_buckets=(8,), prefill_batch_buckets=(1,),
                 pack_max_segments=2)
    eng = DecodeEngine(model, DecodeConfig(hbm_budget_gb=0.5, **cfgkw),
                       auto_start=False)
    try:
        assert eng.pool_plan["blocks"] == eng.pool_blocks
        assert eng.pool_blocks >= eng.config.max_blocks_per_seq
        assert eng.pool_plan["block_bytes"] == \
            model.cache_block_bytes(4)
        assert eng.pool_plan["budget_bytes"] == int(0.5 * (1 << 30))
    finally:
        eng.shutdown()
    with pytest.raises(InvalidArgumentError) as ei:
        DecodeEngine(model, DecodeConfig(hbm_budget_gb=1e-6, **cfgkw),
                     auto_start=False)
    assert "cache" in str(ei.value) and "budget" in str(ei.value).lower()


# ---------------------------------------------------------------------------
# warm restart: the prefill/decode grid through the persistent AOT cache
# ---------------------------------------------------------------------------


def test_warm_restart_grid_zero_fresh_compiles(tmp_path):
    """Simulated process restart (fresh engine + fresh Executor, same
    cache dir): every prefill (batch x seq) combo and every decode
    bucket deserializes from the persistent AOT cache — 0 fresh
    compiles, counters asserted, and the restarted engine's tokens are
    bit-identical.  Deterministic program naming (unique_name.guard in
    BertDecoder.build) is what makes the content-hash keys line up."""
    from paddle_tpu.framework.aot_cache import cache_stats
    from paddle_tpu.monitor import stat
    set_flags({"aot_cache_dir": str(tmp_path / "aot")})
    prompts = _prompts([5, 9], seed=21)

    def run_once():
        eng = DecodeEngine(_model(), _config())
        try:
            c0 = stat("executor_compile_count").get()
            combos = eng.warmup()
            fresh_warm = stat("executor_compile_count").get() - c0
            futs = [eng.generate({"src_ids": p}, max_new_tokens=5)
                    for p in prompts]
            toks = [f.result(timeout=300).tokens for f in futs]
            fresh_total = stat("executor_compile_count").get() - c0
        finally:
            eng.shutdown()
        return combos, fresh_warm, fresh_total, toks

    combos, cold_fresh, cold_total, cold_toks = run_once()
    assert combos == _config().executable_grid
    assert cold_fresh >= combos          # cold: everything traced
    s0 = cache_stats()
    warm_combos, warm_fresh, warm_total, warm_toks = run_once()
    s1 = cache_stats()
    assert warm_combos == combos
    assert warm_fresh == 0, "warm restart paid fresh compiles"
    assert warm_total == 0, "live traffic after warmup paid a compile"
    assert s1["hits"] - s0["hits"] >= combos
    assert s1["errors"] == s0["errors"]
    for a, b in zip(cold_toks, warm_toks):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# chaos: the serving_decode seam
# ---------------------------------------------------------------------------


def test_decode_fatal_chaos_drill():
    """A fatal error in the decode worker fails ALL in-flight generation
    futures with the error, frees their cache blocks, marks the engine
    unhealthy (submit raises immediately) and drain() returns instead
    of hanging."""
    eng = DecodeEngine(_model(), _config(), auto_start=False)
    try:
        prompts = _prompts([4, 6], seed=31)
        futs = [eng.generate({"src_ids": p}, max_new_tokens=8)
                for p in prompts]
        faultline.arm("serving_decode", action="raise", at=0, times=1)
        eng.start()
        for f in futs:
            with pytest.raises(UnavailableError) as ei:
                f.result(timeout=60)
            assert "flight bundle" in str(ei.value)
        stats = eng.stats()
        assert stats["unhealthy"] is True
        assert stats["failed"] == 2
        assert stats["cache_blocks_used"] == 0     # blocks freed
        assert eng.drain(timeout=5) is True        # no hang
        with pytest.raises(UnavailableError):
            eng.generate({"src_ids": prompts[0]})
    finally:
        faultline.disarm()
        eng.shutdown(drain=False)


def test_serving_decode_seam_registered():
    assert "serving_decode" in faultline.seams()
    from tools.chaos_probe import DOCUMENTED_SEAMS
    assert sorted(faultline.seams()) == list(DOCUMENTED_SEAMS)


# ---------------------------------------------------------------------------
# static layer: verify_decode + cache op specs
# ---------------------------------------------------------------------------


def test_verify_decode_profile():
    from paddle_tpu.framework.analysis import (DECODE_CACHE_UNDECLARED,
                                               DECODE_STATE_WRITE,
                                               verify_decode)
    model = _model()
    progs = model.build(8, 4, 8, pack_max_segments=2)
    # the genuine decode program verifies clean with its pool declared
    res = verify_decode(progs.decode, feed_names=progs.decode_feeds,
                        fetch_names=progs.fetch_names,
                        cache_vars=progs.cache_vars)
    assert not res.errors(), res.report()
    # withholding a pool name flags its writes as decode-state-write
    res = verify_decode(progs.decode, feed_names=progs.decode_feeds,
                        fetch_names=progs.fetch_names,
                        cache_vars=progs.cache_vars[:-1])
    codes = [d.code for d in res.errors()]
    assert DECODE_STATE_WRITE in codes
    # a typo'd cache var is itself an error
    res = verify_decode(progs.decode, feed_names=progs.decode_feeds,
                        fetch_names=progs.fetch_names,
                        cache_vars=list(progs.cache_vars) + ["nope_pool"])
    assert DECODE_CACHE_UNDECLARED in [d.code for d in res.errors()]
    # the prefill program also holds the contract
    res = verify_decode(progs.prefill, feed_names=progs.prefill_feeds,
                        fetch_names=progs.fetch_names,
                        cache_vars=progs.cache_vars)
    assert not res.errors(), res.report()


def test_cached_attention_matches_full_attention():
    """Numeric spec of the cache-read path: writing K/V through
    cache_write and attending through a (shuffled!) block table equals
    full attention over the same prefix — block identity is
    transparent, masked slots contribute exactly nothing."""
    import jax.numpy as jnp
    from paddle_tpu.ops.attention_ops import reference_attention
    from paddle_tpu.ops.cache_ops import ctx_len_bias, gather_cache
    from paddle_tpu.ops.registry import LoweringContext
    import jax

    rng = np.random.RandomState(0)
    B, S, H, bs, nb = 2, 6, 8, 4, 10
    q1 = rng.randn(B, 1, H).astype(np.float32)
    k = rng.randn(B, S, H).astype(np.float32)
    v = rng.randn(B, S, H).astype(np.float32)
    # scatter the prefix into non-contiguous, per-row-different blocks
    tables = np.array([[7, 2], [4, 9]], np.int32)
    pool_k = jnp.asarray(rng.randn(nb, bs, H).astype(np.float32))
    pool_v = jnp.asarray(rng.randn(nb, bs, H).astype(np.float32))
    from paddle_tpu.ops.cache_ops import _cache_write
    slots = np.stack([[tables[b][p // bs] * bs + p % bs
                       for p in range(S)] for b in range(B)])
    out = _cache_write(None, {"KPool": [pool_k], "VPool": [pool_v],
                              "K": [jnp.asarray(k)],
                              "V": [jnp.asarray(v)],
                              "Slots": [jnp.asarray(slots, jnp.int32)]},
                       {})
    pk, pv = out["KPoolOut"], out["VPoolOut"]
    gk = gather_cache(pk, jnp.asarray(tables))
    gv = gather_cache(pv, jnp.asarray(tables))
    # gathered valid positions are bitwise the written rows
    assert np.array_equal(np.asarray(gk)[:, :S], k)
    bias = ctx_len_bias(jnp.full((B,), S, jnp.int32), gk.shape[1])
    ctx = LoweringContext(jax.random.PRNGKey(0), is_test=True)
    cached = reference_attention(jnp.asarray(q1), gk, gv, bias, 2,
                                 0.0, ctx, True)
    full = reference_attention(jnp.asarray(q1), jnp.asarray(k),
                               jnp.asarray(v), None, 2, 0.0, ctx, True)
    np.testing.assert_allclose(np.asarray(cached), np.asarray(full),
                               rtol=1e-6, atol=1e-6)


def test_cache_op_specs_and_routing():
    """The static layer knows the cache ops: infer propagates shapes,
    SpecMismatch anchors bad widths, and the cached_flash_attention
    route gates exactly like the kernel (tiles → supported, a
    one-token decode query → fallback with the shape reason)."""
    from paddle_tpu.ops.registry import OP_SPECS, VarSig
    spec = OP_SPECS["cache_write"]
    sigs = {"KPool": [VarSig((8, 4, 16), "float32")],
            "VPool": [VarSig((8, 4, 16), "float32")],
            "K": [VarSig((2, 3, 16), "float32")],
            "V": [VarSig((2, 3, 16), "float32")],
            "Slots": [VarSig((2, 3), "int32")]}
    out = spec.infer(sigs, {})
    assert out["KPoolOut"][0].shape == (8, 4, 16)
    from paddle_tpu.ops.registry import SpecMismatch
    bad = dict(sigs, K=[VarSig((2, 3, 8), "float32")])
    with pytest.raises(SpecMismatch):
        spec.infer(bad, {})

    aspec = OP_SPECS["fused_attention"]
    routes = {r.kernel: r for r in aspec.pallas}
    cached = routes["cached_flash_attention"]
    # applicability is the builder-stamped attr: non-cached instances
    # skip the route silently (their fallback counters stay clean)
    assert cached.match({"_cached": True}, None)
    assert not cached.match({}, None)
    assert not routes["flash_attention"].match({"_cached": True}, None)
    assert routes["flash_attention"].match({}, None)
    ins128 = {"Q": [VarSig((1, 128, 128), "float32")],
              "KPool": [VarSig((16, 128, 128), "float32")],
              "VPool": [VarSig((16, 128, 128), "float32")],
              "BlockTable": [VarSig((1, 1), "int32")],
              "CtxLen": [VarSig((1,), "int32")]}
    ok, why = cached.supported(ins128, {"n_head": 2}, None)
    assert ok, why
    ins1 = dict(ins128, Q=[VarSig((1, 1, 128), "float32")])
    ok, why = cached.supported(ins1, {"n_head": 2}, None)
    assert not ok and "128" in why
    nocache = {"Q": [VarSig((1, 128, 128), "float32")],
               "K": [VarSig((1, 128, 128), "float32")],
               "V": [VarSig((1, 128, 128), "float32")]}
    ok, why = cached.supported(nocache, {"n_head": 2}, None)
    assert not ok and why == "not-cached"
    # cached-variant shape inference + flops channel
    out = aspec.infer(ins1, {"n_head": 2})
    assert out["Out"][0].shape == (1, 1, 128)
    fl = aspec.flops(ins1, None, {"n_head": 2})
    assert fl == 4.0 * 1 * 1 * 128 * 128


def test_cached_flash_route_cross_lowers_as_tpu_custom_call():
    """At flash-tiling shapes the cache-read route places the blockwise
    flash kernel in a TPU-cross-lowered module (the KERNEL_CENSUS
    idiom) — the gather feeds the same ``tpu_custom_call`` the plain
    flash path uses; CPU tier-1 proves it with no TPU attached."""
    import jax
    import jax.numpy as jnp
    from jax import export as jexport
    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.ops.registry import LoweringContext, pallas_route

    pool = jnp.zeros((4, 128, 128), jnp.float32)
    ins = {"Q": [jnp.zeros((1, 128, 128))], "KPool": [pool],
           "VPool": [pool],
           "BlockTable": [jnp.zeros((1, 1), jnp.int32)],
           "CtxLen": [jnp.full((1,), 128, jnp.int32)]}
    attrs = {"n_head": 2, "_cached": True, "is_test": True}
    with lowering_target("tpu"):
        route, reason = pallas_route("fused_attention", ins, attrs,
                                     kernel="cached_flash_attention")
        assert route is not None, reason

        def f(q, kp, vp, tb, cl):
            ctx = LoweringContext(jax.random.PRNGKey(0), is_test=True)
            i = {"Q": [q], "KPool": [kp], "VPool": [vp],
                 "BlockTable": [tb], "CtxLen": [cl]}
            return route.lower(ctx, i, attrs)["Out"]

        exported = jexport.export(jax.jit(f), platforms=("tpu",))(
            ins["Q"][0], pool, pool, ins["BlockTable"][0],
            ins["CtxLen"][0])
    assert "tpu_custom_call" in exported.mlir_module()


# ---------------------------------------------------------------------------
# observability + artifact + wiring contracts
# ---------------------------------------------------------------------------


def test_decode_metrics_and_spans(engine):
    """The decode engine's numbers are PULLED from ``stats()`` at scrape
    time (the worker pushes no gauge): block, sequence and prefix-cache
    series, and the phase clock one series per phase."""
    from paddle_tpu.observability import metrics
    (p,) = _prompts([5], seed=55)
    engine.generate({"src_ids": p}, max_new_tokens=4).result(timeout=300)
    engine.drain()
    snap = metrics.metrics_snapshot()
    mine = [st for st in snap["serving"] if "cache_blocks_used" in st
            and st["pool_blocks"] == engine.pool_blocks]
    assert mine and mine[0]["active"] == 0
    assert not {m["name"] for m in snap["metrics"]
                if m["name"].startswith("decode::")}
    text = metrics.prometheus_text()
    for series in ("cache_blocks_used", "active", "prefix_hits",
                   "prefix_misses", "prefix_bytes_saved", "queue_wait_ns"):
        assert f"paddle_tpu_serving_{series}{{engine=" in text, series
    assert 'paddle_tpu_serving_phase_ns{engine="' in text
    assert 'key="sync"} ' in text
    # the ledger's dicts ride the same path, one series a key; the ring
    # of slow phases is a list and no series
    for series, key in (("device_ns", "chain"), ("starved_ns", "retire"),
                        ("slow_phase_ns", "sync")):
        assert any(line.startswith(f"paddle_tpu_serving_{series}{{")
                   and f'key="{key}"' in line
                   for line in text.splitlines()), series
    assert "paddle_tpu_serving_slow_phases{" not in text
    stats = engine.stats()
    assert stats["tokens_per_s"] > 0
    assert 0 < stats["peak_occupancy"] <= 1
    assert stats["compile_count"] >= 2


# ---------------------------------------------------------------------------
# the worker's timeline: phase clock, launch counters, request stamps
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def timeline_run():
    """One engine through packed prefill, chunked prefill and chains,
    with a pool small enough that later requests queue: its results, its
    ``stats()`` taken while idle, and the test's own clock around the
    worker's whole life so far."""
    from paddle_tpu.serving.decode import PHASES
    eng = DecodeEngine(_model(), _config(chunk_tokens=4, pool_blocks=14),
                       auto_start=False)
    prompts = _prompts([5, 20, 7, 18, 3, 9], seed=77)
    try:
        t0 = time.monotonic_ns()
        eng.start()
        futs = [eng.generate({"src_ids": p}, max_new_tokens=6)
                for p in prompts]
        results = [f.result(timeout=300) for f in futs]
        assert eng.drain(timeout=60)
        time.sleep(0.05)                     # some idle time on the clock
        stats = eng.stats()
        t1 = time.monotonic_ns()
        assert eng.shutdown(timeout=60)
        after = [eng.stats()["phase_ns"], None]
        time.sleep(0.02)
        after[1] = eng.stats()["phase_ns"]
    finally:
        eng.shutdown()
    return {"results": results, "stats": stats, "wall_ns": t1 - t0,
            "after_shutdown": after, "phases": PHASES}


def test_phase_ns_tiles_the_workers_wall_time(timeline_run):
    """Every instant of the worker is in exactly one phase: the phases
    add up to the wall time since ``start()`` within 1 %, each of them
    was visited, and the clock stops with the worker."""
    ph = timeline_run["stats"]["phase_ns"]
    assert tuple(ph) == timeline_run["phases"]
    wall = timeline_run["wall_ns"]
    assert abs(sum(ph.values()) - wall) <= 0.01 * wall, (ph, wall)
    assert all(v > 0 for v in ph.values()), ph
    first, second = timeline_run["after_shutdown"]
    assert first == second
    assert sum(first.values()) >= sum(ph.values())


def test_ledger_tiles_the_workers_wall_time_as_the_phases_do(timeline_run):
    """With chunks in the traffic and the ledger empty (the engine is
    drained): device time by kind, the host phases that opened with
    nothing in flight and the idle phase add up to the phases' sum —
    to the nanosecond, since every interval ends on a stamp the next one
    starts on; ISSUE 38 asks for 1 %."""
    from paddle_tpu.serving.decode import LAUNCH_KINDS, STARVABLE
    st = timeline_run["stats"]
    ph, dev, starved = st["phase_ns"], st["device_ns"], st["starved_ns"]
    assert tuple(dev) == LAUNCH_KINDS and tuple(starved) == STARVABLE
    total = sum(ph.values())
    tiled = sum(dev.values()) + sum(starved.values()) + ph["idle"]
    assert abs(tiled - total) <= 0.01 * total, (dev, starved, ph)
    assert tiled == total
    # every launch was booked (the ledger tiles only when it is empty)
    # and each kind held the device; a starved phase is a part of its
    # phase
    assert all(v > 0 for v in dev.values()), dev
    assert all(0 <= starved[k] <= ph[k] for k in starved)
    # a sync waits on the device: what it waited out is device time
    assert sum(dev.values()) >= ph["sync"]
    # non-final chunks were waited out in later launches' syncs, so the
    # chunks' device time is more than the dispatches the worker paid
    # for them and the launch clock's split differs from the ledger's
    assert st["chunk_steps"] > st["host_syncs"] - st["prefill_batches"] \
        - st["chains_run"]
    assert dev != st["launch_ns"]
    # a compile inside a dispatch is no slow phase: that launch is not
    # judged; the ring and the sums are one record
    rows = st["slow_phases"]
    assert not any(r[0] in ("idle", "dispatch") and r[3] > 1e9 for r in rows)
    if len(rows) < 32:
        assert sum(r[3] for r in rows) == sum(st["slow_phase_ns"].values())


def test_launch_counters_agree_with_the_schedulers(timeline_run):
    st = timeline_run["stats"]
    assert st["launches"] == {"prefill": st["prefill_batches"],
                              "chunk": st["chunk_steps"],
                              "chain": st["chains_run"]}
    assert all(n > 0 for n in st["launches"].values()), st["launches"]
    assert all(st["launch_ns"][k] > 0 for k in st["launches"])
    # every dispatch and every sync belongs to exactly one launch
    assert sum(st["launch_ns"].values()) == \
        st["phase_ns"]["dispatch"] + st["phase_ns"]["sync"]


def test_request_timing_is_ordered_and_sums_to_the_counters(timeline_run):
    results, st = timeline_run["results"], timeline_run["stats"]
    timings = [r.timing for r in results]
    assert sorted(t["rid"] for t in timings) == list(range(len(results)))
    for t in timings:
        assert t["submit"] <= t["admit"] <= t["first_token"] <= t["done"]
    assert st["admitted"] == st["first_tokens"] == len(results)
    assert st["queue_wait_ns"] == sum(t["admit"] - t["submit"]
                                      for t in timings)
    assert st["first_token_ns"] == sum(t["first_token"] - t["admit"]
                                       for t in timings)
    # the pool held back the later arrivals: their wait is on the record
    assert st["admission_waits"] > 0
    assert max(t["admit"] - t["submit"] for t in timings) > \
        min(t["first_token"] - t["admit"] for t in timings)


def test_decode_spans_reach_the_xplane_under_a_bare_jax_trace(tmp_path):
    """With ``jax.profiler.start_trace`` alone — no ``start_profiler``,
    the program's own tracing off — the worker's phases land in the
    xplane's host plane under their own names, children inside the
    launch's ``decode::chain`` span; every phase span says whether the
    device was starved in it (a short prompt decoding beside a long one
    mid-chunk: the chain's dispatch opens with the chunk in flight)."""
    import glob

    import jax
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving.decode import PHASES
    assert not tracing.is_enabled()
    eng = DecodeEngine(_model(), _config(chunk_tokens=4), auto_start=False)
    short, long_ = _prompts([6, 19], seed=91)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        futs = [eng.generate({"src_ids": p}, max_new_tokens=5)
                for p in (short, long_)]
        eng.start()
        for f in futs:
            f.result(timeout=300)
        eng.drain()
    finally:
        jax.profiler.stop_trace()
        eng.shutdown()
    assert not tracing.get_events()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               {k: str(v) for k, v in ev.stats})
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("decode::")]
    names = {e[0] for e in events}
    assert {"decode::admit", "decode::feed", "decode::dispatch",
            "decode::sync", "decode::emit", "decode::retire",
            "decode::prefill", "decode::chunk",
            "decode::chain"} <= names, names
    chains = [e for e in events if e[0] == "decode::chain"]
    for name, a, b, _ in events:
        if name in ("decode::sync", "decode::emit"):
            assert any(pa <= a and b <= pb for n, pa, pb, _ in events
                       if n in ("decode::chain", "decode::prefill",
                                "decode::chunk")), name
    assert chains
    phase_spans = [e for e in events
                   if e[0].split("::")[1] in PHASES[1:]]
    assert all(e[3].get("starved") in ("0", "1") for e in phase_spans), \
        [e for e in phase_spans if "starved" not in e[3]][:3]
    syncs = [e for e in events if e[0] == "decode::sync"]
    assert syncs and all(e[3]["starved"] == "0" for e in syncs)
    # a dispatch with nothing in flight is exposed; one behind a chunk
    # in flight is not
    dispatches = {e[3]["starved"] for e in events
                  if e[0] == "decode::dispatch"}
    assert dispatches == {"0", "1"}
