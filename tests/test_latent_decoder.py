"""The served latent-attention decoder (models/latent_decoder.py, ISSUE
33) at its tiny size on the CPU, float32: hidden 64, 4 heads of 16+8 /
v 16, latent 32, q-latent 48, 16 experts in 4 groups (top-2 groups,
top-4), 3 layers of which 1 dense.

The yardstick is ``benchmark/reference/deepseek_v3_jnp.py``: the
expanded forward pass in plain ``jax.numpy``, no cache, no absorbed
form, nothing imported from ``paddle_tpu``.  ``chip_smoke.py`` leg L and
the benchmark cell repeat the comparisons on the chip at the published
widths."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark.reference import deepseek_v3_jnp as ref
from paddle_tpu.models.latent_decoder import (LatentDecoder,
                                              LatentDecoderConfig)
from paddle_tpu.ops import mla_ops
from paddle_tpu.ops.decoder_lm_ops import (_rotary_embedding,
                                           _sigmoid_group_topk)
from paddle_tpu.ops.pallas import mla_paged
from paddle_tpu.serving import DecodeConfig, DecodeEngine

INTERPRET = pltpu.InterpretParams()      # uninitialised memory reads NaN
CFG = LatentDecoderConfig.tiny()
M = dataclasses.asdict(CFG)


def _weights(engine):
    caches = set(engine.model.cache_var_names())
    return {n: np.asarray(engine.scope.find_var(n))
            for n in engine.scope.var_names()
            if n not in caches and not n.startswith("@")}


def _engine(**kw):
    args = dict(block_size=8, max_seq_len=128, max_batch_size=4,
                prefill_seq_buckets=(16, 32), chain_lengths=(1, 4),
                chunk_tokens=16, prefix_cache=True)
    args.update(kw)
    return DecodeEngine(LatentDecoder(CFG, seed=5), DecodeConfig(**args))


@pytest.fixture(scope="module")
def served():
    """One engine, its weights, and five requests served together: a
    prompt inside the packed-prefill buckets, chunked ones, and one that
    repeats another's first blocks (a prefix hit, served after it)."""
    engine = _engine()
    try:
        w = _weights(engine)
        rng = np.random.default_rng(0)
        shared = rng.integers(0, CFG.vocab_size, 40)
        prompts = [rng.integers(0, CFG.vocab_size, n) for n in (5, 16, 23)]
        prompts.append(shared)
        results = [engine.generate({"src_ids": p}, max_new_tokens=10,
                                   return_logits=True) for p in prompts]
        results = [f.result(timeout=300) for f in results]
        prompts.append(np.concatenate(
            [shared, rng.integers(0, CFG.vocab_size, 9)]))
        results.append(engine.generate(
            {"src_ids": prompts[-1]}, max_new_tokens=10,
            return_logits=True).result(timeout=300))
        yield engine, w, prompts, results
    finally:
        engine.shutdown(drain=False)


def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(
        served):
    engine, w, prompts, results = served
    stats = engine.stats()
    assert stats["prefix_hits"] > 0 and stats["chunk_steps"] > 0 \
        and stats["prefill_batches"] > 0 and stats["chain_hist"].get(4)
    for p, res in zip(prompts, results):
        assert res.logits.shape == (10, CFG.vocab_size)
        seq = np.concatenate([p, res.tokens])
        want = np.asarray(ref.logits(w, seq, M, q_block=1))[
            p.size - 1:seq.size - 1]
        assert np.abs(res.logits - want).max() < 1e-4 * np.abs(want).max()
        assert (want.argmax(-1) == res.tokens).all()
        assert (engine.greedy_reference({"src_ids": p}, 4).tokens
                == res.tokens[:4]).all()


def test_device_counters_by_launch_kind(served):
    engine, _, _, _ = served
    stats = engine.stats()
    sparse = CFG.num_hidden_layers - CFG.first_k_dense_replace
    for kind in ("prefill", "chunk", "chain"):
        assert stats["moe_assignments_local"][kind] > 0
        assert 0 < stats["moe_experts_hit"][kind] \
            <= stats["moe_assignments_local"][kind]
    # every chain step routes every row of its bucket, all layers
    steps = stats["decode_steps"]
    assert stats["moe_experts_hit"]["chain"] \
        <= steps * sparse * CFG.n_routed_experts
    assert stats["moe_assignments_local"]["chain"] \
        % (sparse * CFG.num_experts_per_tok) == 0


def test_chunked_prefill_equals_whole_prefill():
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, CFG.vocab_size, 45)
    out = []
    for kw in (dict(prefill_seq_buckets=(64,), chunk_tokens=None,
                    prefix_cache=False),
               dict(prefill_seq_buckets=(8,), chunk_tokens=16)):
        engine = _engine(**kw)
        try:
            out.append(engine.generate({"src_ids": prompt},
                                       max_new_tokens=6, return_logits=True
                                       ).result(timeout=300))
            chunks = engine.stats()["chunk_steps"]
            assert chunks == (3 if kw["chunk_tokens"] else 0)
        finally:
            engine.shutdown(drain=False)
    assert (out[0].tokens == out[1].tokens).all()
    np.testing.assert_allclose(out[0].logits, out[1].logits, atol=2e-5)


def test_logits_need_a_model_whose_chains_return_them():
    from paddle_tpu.framework.errors import InvalidArgumentError
    from paddle_tpu.models.bert import BertConfig
    from paddle_tpu.models.decoder import BertDecoder
    engine = DecodeEngine(BertDecoder(BertConfig.tiny(), seed=3),
                          DecodeConfig(block_size=8, max_seq_len=32,
                                       max_batch_size=2,
                                       prefill_seq_buckets=(16,)),
                          auto_start=False)
    with pytest.raises(InvalidArgumentError, match="return_logits"):
        engine.generate({"src_ids": np.arange(4)}, max_new_tokens=2,
                        return_logits=True)


def test_close_fails_what_is_in_flight_and_frees_the_pools():
    engine = _engine()
    futs = [engine.generate({"src_ids": np.arange(1, 20)},
                            max_new_tokens=100) for _ in range(3)]
    assert engine.close(timeout=120.0)
    assert any(f.exception() is not None for f in futs)
    left = set(engine.scope.var_names())
    assert not left & set(engine.model.cache_var_names())
    assert "lm_head_w" in left


# ---------------------------------------------------------------------------
# the ops
# ---------------------------------------------------------------------------

def _mla_problem(seed=0, b=2, s=24, dtype=jnp.float32):
    h, dn, dr, dv, dc = 4, 16, 8, 16, 32
    rng = np.random.default_rng(seed)
    attrs = {"n_head": h, "nope_dim": dn, "rope_dim": dr, "v_dim": dv,
             "scale": 0.3}
    q = jnp.asarray(rng.normal(size=(b, s, h * (dn + dr))), dtype)
    latent = jnp.asarray(rng.normal(size=(b, s, 128)), dtype) \
        .at[..., dc + dr:].set(0)
    wkvb = jnp.asarray(rng.normal(size=(dc, h * (dn + dv))) * 0.2, dtype)
    return attrs, q, latent, wkvb


def _paged(latent, block, rng, pages):
    """Scatter ``latent`` [B, S, W] into a pool of NaN through a
    permuted block table."""
    b, s, w = latent.shape
    need = -(-s // block)
    nb = b * need + 3
    table = np.zeros((b, pages), np.int32)
    table[:, :need] = (rng.permutation(nb - 1)[:b * need] + 1) \
        .reshape(b, need)
    pool = np.full((nb, block, w), np.nan, np.float32)
    for i in range(b):
        for t in range(s):
            pool[table[i, t // block], t % block] = latent[i, t]
    return jnp.asarray(pool, latent.dtype), jnp.asarray(table)


def test_absorbed_decode_and_chunked_read_equal_the_expanded_form():
    attrs, q, latent, wkvb = _mla_problem()
    b, s, _ = q.shape
    whole = mla_ops.fresh_attention(q, latent, wkvb, None, attrs)
    pool, table = _paged(latent, 8, np.random.default_rng(1), pages=6)
    ctx = jnp.full((b,), s, jnp.int32)
    # chunked: the last 10 queries read everything through the table
    pos = jnp.broadcast_to(jnp.arange(s - 10, s)[None], (b, 10))
    chunk = mla_ops.chunk_attention(q[:, -10:], pool, table, ctx, pos,
                                    wkvb, attrs)
    np.testing.assert_allclose(chunk, whole[:, -10:], atol=2e-5)
    # absorbed: the last query alone
    o_lat = mla_ops.gathered_decode(
        mla_ops.absorb_query(q[:, -1:], wkvb, attrs, pool.shape[-1]),
        pool, table, ctx, wkvb.shape[0], attrs["scale"])
    out = mla_ops.project_value(o_lat, wkvb, attrs, q.dtype)
    np.testing.assert_allclose(out, whole[:, -1:], atol=2e-5)


@pytest.mark.parametrize("ctx,pps", [
    ([1, 16, 17, 80], 2), ([1, 16, 17, 80], 32), ([0, 33, 5, 64], 3),
    ([128, 2, 127, 1], 4)], ids=str)
def test_mla_paged_decode_kernel_equals_the_gather(ctx, pps):
    """The kernel in Pallas' TPU interpret mode (scratch starts as NaN,
    a read outside a buffer raises) with NaN in every slot no live
    context owns: a partly live last page, rows of very different page
    counts side by side, a row with nothing live."""
    rng = np.random.default_rng(0)
    b, h, dc, w, bs, pages = len(ctx), 8, 128, 256, 16, 8
    ctx = np.asarray(ctx, np.int32)
    nb = b * pages + 1
    table = (rng.permutation(nb - 1)[:b * pages] + 1).reshape(b, pages) \
        .astype(np.int32)
    pool = rng.normal(size=(nb, bs, w)).astype(np.float32)
    owned = np.zeros((nb, bs), bool)
    for i in range(b):
        for t in range(ctx[i]):
            owned[table[i, t // bs], t % bs] = True
    pool[~owned] = np.nan
    pool = jnp.asarray(pool, jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(b, h, w)), jnp.bfloat16)
    want = mla_ops.gathered_decode(q, pool, jnp.asarray(table),
                                   jnp.asarray(ctx), dc, 0.1)
    got = mla_paged.mla_paged_decode(
        q, pool, jnp.asarray(table), jnp.asarray(ctx), latent_dim=dc,
        scale=0.1, pages_per_step=pps, interpret=INTERPRET)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, atol=4e-3)
    assert not np.asarray(got)[ctx == 0].any()


def test_mla_paged_route_takes_decode_steps_only():
    ok = mla_paged.supported(1, 128, 512, 64, 16, 640)
    assert ok == (True, "")
    for kw, why in ((dict(sq=2), "sq"), (dict(has_qpos=True), "qpos"),
                    (dict(dtype="float32"), "dtype"),
                    (dict(block_size=8), "block-size"),
                    (dict(width=576), "latent")):
        args = dict(sq=1, n_head=128, latent_dim=512, rope_dim=64,
                    block_size=16, width=640)
        args.update(kw)
        good, reason = mla_paged.supported(**args)
        assert not good and why in reason


def test_router_matches_the_reference_and_the_bias_only_selects():
    rng = np.random.default_rng(0)
    n, e = 64, 16
    logits = jnp.asarray(rng.normal(size=(n, e)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(e,)) * 0.5, jnp.float32)
    attrs = {"top_k": 4, "n_group": 4, "topk_group": 2, "routed_scale": 2.5}
    vals, idx = _sigmoid_group_topk(logits, bias, attrs)
    m = dict(M, routed_scaling_factor=2.5)
    wts, ridx = ref.route(jax.nn.sigmoid(logits), bias, m)
    assert (np.sort(idx, -1) == np.sort(ridx, -1)).all()
    np.testing.assert_allclose(np.sort(vals, -1), np.sort(wts, -1),
                               rtol=1e-6)
    # the chosen experts lie in at most topk_group groups
    assert (np.array([len(set(r // 4)) for r in np.asarray(idx)]) <= 2).all()
    # the bias chooses (the sets differ from the unbiased ones) and never
    # weighs: a chosen expert's weight is its own sigmoid over the chosen
    # sigmoids' sum, times the scale
    _, idx0 = _sigmoid_group_topk(logits, None, attrs)
    assert (np.sort(idx, -1) != np.sort(idx0, -1)).any()
    s = np.take_along_axis(np.asarray(jax.nn.sigmoid(logits)),
                           np.asarray(idx), -1)
    np.testing.assert_allclose(vals, 2.5 * s / s.sum(-1, keepdims=True),
                               rtol=1e-6)


def test_interleaved_rotary_at_fed_positions_matches_the_reference():
    rng = np.random.default_rng(0)
    b, s, heads, d, r = 2, 6, 3, 24, 8
    xv = jnp.asarray(rng.normal(size=(b, s, heads * d)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 4000, (b, s)))
    attrs = dict(CFG.rope_attrs(), interleaved=True, rotary_dim=r,
                 head_dim=d)
    got = _rotary_embedding(None, {"X": [xv], "Pos": [pos]},
                            attrs)["Out"].reshape(b, s, heads, d)
    inv_freq = ref.yarn_inv_freq(r, M)
    for i in range(b):
        want = ref._rotary(xv[i].reshape(s, heads, d)[..., d - r:], pos[i],
                           inv_freq, ref.rope_factor(M))
        np.testing.assert_allclose(got[i][..., d - r:], want, atol=1e-5)
        np.testing.assert_array_equal(
            got[i][..., :d - r], xv[i].reshape(s, heads, d)[..., :d - r])


def test_gmm_cuts_a_wide_expert_into_column_blocks():
    from paddle_tpu.ops.pallas.grouped_matmul import (column_block, gmm,
                                                      row_tile, tile_layout)
    assert column_block(2304, 896, 2) == 896          # s8k: unchanged
    assert column_block(7168, 2048, 2) == 512
    assert column_block(2048, 7168, 2) == 1792
    # s8k's step, the served decoder's chunk and its decode buckets
    assert [row_tile(a * 8, e) for a, e in
            ((8192, 64), (1024, 256), (128, 256), (32, 256))] \
        == [256, 128, 16, 16]
    rng = np.random.default_rng(0)
    counts = jnp.asarray([3, 0, 9, 1], jnp.int32)
    tile_m, k, n = 8, 512, 8192         # 16 MiB an expert in f32: 2 blocks
    assert column_block(k, n, 4) == 4096
    m = 8 * 8
    _, tg, na = tile_layout(counts, tile_m, m // tile_m)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, k, n)), jnp.float32)
    got = np.asarray(gmm(lhs, rhs, tg, na, tile_m=tile_m, interpret=True))
    for t in range(int(na[0])):
        rows = slice(t * tile_m, (t + 1) * tile_m)
        np.testing.assert_allclose(
            got[rows], np.asarray(lhs[rows]) @ np.asarray(rhs[int(tg[t])]),
            rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# one chip's share of an expert-parallel layer
# ---------------------------------------------------------------------------

def _run_layer(held, w, x):
    """The sparse layer as the program builds it, holding ``held``."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.layer_helper import ParamAttr
    from paddle_tpu.parallel import moe_dropless_ffn
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xin = fluid.layers.data("x", shape=list(x.shape),
                                append_batch_size=False)
        out = moe_dropless_ffn(
            xin, CFG.n_routed_experts, CFG.moe_intermediate_size,
            CFG.num_experts_per_tok, held_experts=held,
            param_attr=ParamAttr(name="L"), name="L_moe",
            scoring="sigmoid", n_group=CFG.n_group,
            topk_group=CFG.topk_group,
            routed_scale=CFG.routed_scaling_factor,
            shared_hidden=CFG.moe_intermediate_size, counter_tag=False)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        lo, hi = held
        for name, v in w.items():
            scope.set_var(name, jnp.asarray(
                v[lo:hi] if name.startswith("L_expert_") else v))
        return exe.run(main, feed={"x": x}, fetch_list=[out])[0]


def test_the_shares_routed_parts_and_one_shared_expert_sum_to_the_layer():
    rng = np.random.default_rng(0)
    d, f, e = CFG.hidden_size, CFG.moe_intermediate_size, \
        CFG.n_routed_experts

    def rnd(*shape):
        return (rng.normal(size=shape) * 0.3).astype(np.float32)
    w = {"L_router_w": rnd(d, e), "L_router_bias": rnd(e) * 0.2,
         "L_expert_gate_w": rnd(e, d, f), "L_expert_up_w": rnd(e, d, f),
         "L_expert_down_w": rnd(e, f, d), "L_shared_gate_w": rnd(d, f),
         "L_shared_up_w": rnd(d, f), "L_shared_down_w": rnd(f, d)}
    x = rnd(2, 12, d)
    flat = jnp.asarray(x.reshape(-1, d))
    uncut = np.asarray(ref.moe(flat, w, "L", M)).reshape(x.shape)
    shared = np.asarray(ref._swiglu(
        flat, w["L_shared_gate_w"], w["L_shared_up_w"],
        w["L_shared_down_w"])).reshape(x.shape)
    shares = 4
    total = np.zeros_like(uncut)
    for i in range(shares):
        held = (i * e // shares, (i + 1) * e // shares)
        part = np.asarray(_run_layer(held, w, x))
        wi = dict(w, **{k: v[held[0]:held[1]] for k, v in w.items()
                        if k.startswith("L_expert_")})
        np.testing.assert_allclose(
            part, np.asarray(ref.moe(flat, wi, "L", M, held=held))
            .reshape(x.shape), atol=2e-5)
        total += part - shared          # the chip's ROUTED part
    np.testing.assert_allclose(total + shared, uncut, atol=5e-5)
    assert np.abs(uncut - shared).max() > 0.01      # the experts matter
