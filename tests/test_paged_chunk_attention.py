"""The paged chunk attention kernel (ops/pallas/paged_chunk.py) against
the composition it replaces for a prefill chunk's cache read,
``gather_cache`` + ``reference_attention`` under the ``CtxLen`` and
``QPos`` masks, over what a read bounded by each query block's causal
frontier can get wrong and a gather of the whole table cannot: chunks at
the table's start, in its middle and ending at ``max_seq_len``; contexts
that end inside a page; a final chunk whose query rows past the prompt
are padding (position 0); query blocks of different frontiers; heads in
more than one group a grid step; float32 and bfloat16 pools; every pool
slot no context owns holding NaN.

Then the route (which programs pick it: the hybrid and the window
decoders' chunks, not ``bert_base_decoder``'s or
``deepseek_v3_ep16_serve``'s), a census that every serving
configuration's programs keep their routes, the engine's tokens through
it, and the engine's count of the pages the chunks read.

The kernel runs in Pallas' TPU interpret mode (scratch VMEM starts as
NaN, a read outside a buffer raises); ``chip_smoke.py`` leg H repeats the
comparison on the chip at ``olmo_hybrid_serve.doc_closed``'s shapes."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.attention_ops import reference_attention
from paddle_tpu.ops.cache_ops import ctx_len_bias, gather_cache
from paddle_tpu.ops.pallas import lowering_target
from paddle_tpu.ops.pallas import paged_chunk as pc
from tests.test_paged_decode_attention import (  # noqa: F401 - a fixture
    _poisoned, tpu_routes_interpreted)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INTERPRET = pltpu.InterpretParams()      # uninitialised memory reads NaN


def _gathered(q, k_pool, v_pool, table, ctx, q_pos, n_head):
    """``lower_cached_attention``'s einsum composition."""
    keys, vals = gather_cache(k_pool, table), gather_cache(v_pool, table)
    bias = ctx_len_bias(ctx, keys.shape[1])
    t = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, None, :]
    causal = jnp.where(t <= jnp.asarray(q_pos)[:, :, None], 0.0, -1e9)
    return reference_attention(q, keys, vals, bias + causal[:, None],
                               n_head, 0.0, None, True)


def _problem(chunks, sq, hidden=256, block=16, pages=16, seed=0,
             dtype="float32"):
    """Rows, one a ``(start, tokens)`` chunk: the row's context is
    ``start + tokens`` positions over scattered pool blocks, its queries
    the chunk's ``tokens`` positions and ``sq - tokens`` padded rows at
    position 0 (as the engine feeds a final chunk).  ``dtype`` is the
    pools' (and the queries'): values are rounded to it and handed out as
    float32 NumPy arrays, which :func:`_run` stores in it again,
    exactly."""
    rng = np.random.RandomState(seed)
    ctx = np.array([s + n for s, n in chunks], np.int32)
    need = np.clip(-(-ctx // block), 1, pages)
    num_blocks = int(need.sum()) + 9
    order = rng.permutation(num_blocks - 1) + 1      # block 0: nobody's
    table = np.zeros((len(chunks), pages), np.int32)
    at = 0
    for b, n in enumerate(need):
        table[b, :n] = order[at:at + n]
        at += n
    q_pos = np.zeros((len(chunks), sq), np.int32)
    for b, (s, n) in enumerate(chunks):
        q_pos[b, :n] = np.arange(s, s + n)

    def draw(*shape):
        return np.array(jnp.asarray(rng.randn(*shape), dtype).astype(
            jnp.float32))
    return (draw(len(chunks), sq, hidden), draw(num_blocks, block, hidden),
            draw(num_blocks, block, hidden), table, ctx, q_pos)


def _run(q, k_pool, v_pool, table, ctx, q_pos, n_head, dtype="float32",
         q_dtype=None):
    return np.asarray(pc.paged_chunk_attention(
        jnp.asarray(q, q_dtype or dtype), jnp.asarray(k_pool, dtype),
        jnp.asarray(v_pool, dtype), jnp.asarray(table), jnp.asarray(ctx),
        jnp.asarray(q_pos), n_head=n_head, interpret=INTERPRET
    ).astype(jnp.float32))


#: the table holds 16 pages of 16: 256 positions
CASES = {
    # the prompt's first chunk: the table's first pages
    "at-the-table-start": dict(chunks=[(0, 32)], sq=32),
    # a chunk starting mid-page, after a whole chunk
    "mid-table": dict(chunks=[(40, 32)], sq=32),
    # the last chunk of a prompt that fills the table
    "ending-at-max-seq-len": dict(chunks=[(224, 32)], sq=32),
    # a final chunk: 13 tokens, 19 padded query rows, the context ending
    # mid-page
    "final-chunk-padded-rows": dict(chunks=[(37, 13)], sq=32),
    # three query blocks of 16 (48 is no multiple of 32), frontiers a
    # page apart; beside it a chunk whose last two blocks are padding
    "query-blocks-of-16": dict(chunks=[(100, 48), (5, 11)], sq=48),
    # a long context: the first steps of every block lie wholly below its
    # queries and run without the mask
    "query-blocks-past-a-long-context": dict(chunks=[(200, 48)], sq=48),
    # rows of very different frontiers side by side
    "rows-of-different-frontiers": dict(chunks=[(0, 16), (208, 16),
                                                (65, 9)], sq=16),
    # one head of 256 lanes; twenty heads of 128 (two groups of ten a grid
    # step: the group's lanes are a dynamic slice of every page)
    "one-head-of-256": dict(chunks=[(40, 32)], sq=32, n_head=1),
    "twenty-heads-of-128": dict(chunks=[(150, 32)], sq=32, hidden=2560,
                                n_head=20),
    # a float32 query over bfloat16 pools (the wider dtype sizes the
    # groups: four of five heads)
    "float32-query-bfloat16-pools": dict(chunks=[(150, 32)], sq=32,
                                         hidden=2560, n_head=20,
                                         q_dtype="float32"),
    # pages of 32 (whole tiles of both dtypes; 4 pages a step)
    "pages-of-32": dict(chunks=[(70, 32)], sq=32, block=32, pages=8),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_gather_composition(case, dtype):
    kw = dict(CASES[case])
    n_head = kw.pop("n_head", 2)
    q_dtype = kw.pop("q_dtype", dtype)
    q, kp, vp, table, ctx, q_pos = _problem(dtype=dtype, **kw)
    got = _run(q, kp, vp, table, ctx, q_pos, n_head, dtype, q_dtype)
    want = np.asarray(_gathered(jnp.asarray(q, q_dtype),
                                jnp.asarray(kp, dtype),
                                jnp.asarray(vp, dtype), table, ctx, q_pos,
                                n_head).astype(jnp.float32))
    assert got.shape == q.shape
    # bfloat16: the output is rounded to it, from float32 sums that may
    # differ in their last bits
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" \
        else dict(rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(got, want, **tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunks,sq", [
    ([(40, 32)], 32), ([(37, 13), (224, 32)], 32), ([(200, 48)], 48)],
    ids=["mid-table", "padded-and-at-the-end", "three-query-blocks"])
def test_nan_in_every_slot_no_context_owns(chunks, sq, dtype):
    """What the kernel must not read into the result is NaN here: the
    slots past ``ctx_len`` of every last page and every block no row
    owns.  The gather composition cannot survive it (0 x NaN); the
    kernel must, and must agree with the composition on clean pools.
    Pages between a query block's frontier and the context's end are
    clean here: they are the chunk's own, and never fetched for that
    block; a block whose buffer still holds them from a block before is
    held by the same assertion."""
    q, kp, vp, table, ctx, q_pos = _problem(chunks, sq, dtype=dtype)
    want = np.asarray(_gathered(jnp.asarray(q, dtype), jnp.asarray(kp, dtype),
                                jnp.asarray(vp, dtype), table, ctx, q_pos,
                                2).astype(jnp.float32))
    bad_k, bad_v = _poisoned(kp, table, ctx), _poisoned(vp, table, ctx)
    assert np.isnan(bad_k[0]).all() and np.isnan(bad_v).any()
    assert not np.isfinite(np.asarray(
        _gathered(q, bad_k, bad_v, table, ctx, q_pos, 2))).all()
    got = _run(q, bad_k, bad_v, table, ctx, q_pos, 2, dtype)
    assert np.isfinite(got).all()
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" \
        else dict(rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(got, want, **tol)


def test_pages_past_a_query_blocks_frontier_are_never_fetched():
    """The first query block (16 queries) of a chunk at position 0 sees
    its own page alone: NaN in the row's second page (live, the later
    blocks') cannot reach the first block's rows, and the later blocks
    read it."""
    q, kp, vp, table, ctx, q_pos = _problem([(0, 48)], 48)
    want = np.asarray(_gathered(q, kp, vp, table, ctx, q_pos, 2))
    bad_k, bad_v = kp.copy(), vp.copy()
    bad_k[table[0, 1]] = bad_v[table[0, 1]] = np.nan
    got = _run(q, bad_k, bad_v, table, ctx, q_pos, 2)
    np.testing.assert_allclose(got[:, :16], want[:, :16], rtol=2e-5,
                               atol=2e-6)
    assert np.isnan(got[:, 16:]).all()


def test_frontiers_and_pages_read():
    """Per query block: the positions it reads, the positions all of its
    queries see, and the pages fetched (page 0 at least, the table at
    most) — on NumPy arrays, as the engine counts them."""
    q_pos = np.zeros((2, 48), np.int64)
    q_pos[0] = np.arange(200, 248)
    q_pos[1, :5] = np.arange(30, 35)
    ctx = np.array([248, 35], np.int32)
    hi, lo, bound = pc.frontiers(q_pos, ctx, 48, 256)
    assert pc.q_block(48) == 16 and hi.shape == (2, 3)
    np.testing.assert_array_equal(hi, [[216, 232, 248], [35, 1, 1]])
    np.testing.assert_array_equal(lo, [[201, 217, 233], [1, 1, 1]])
    np.testing.assert_array_equal(bound[1, :6, 0], [31, 32, 33, 34, 35, 1])
    np.testing.assert_array_equal(pc.pages_read(hi, 16, 16),
                                  [[14, 15, 16], [3, 1, 1]])
    # the engine's chunk of 1 024 tokens: four blocks of 256
    assert pc.q_block(1024) == 256 and pc.q_block(3) == 1


@pytest.mark.parametrize("args,reason", [
    ((1024, 3840, 30, 16, "bfloat16"), ""),
    ((32, 256, 2, 16, "float32"), ""),
    ((16, 256, 1, 8, "float32"), ""),
    ((1, 3840, 30, 16, "bfloat16"), "sq:1"),
    ((1024, 768, 12, 16, "float32"), "head-dim:64"),
    ((1024, 384, 2, 16, "bfloat16"), "head-dim:192"),
    ((1024, 3840, 30, 8, "bfloat16"), "block-size:8"),
    ((1024, 3840, 30, 16, "float16"), "dtype:float16"),
    ((8, 256, 2, 16, "float32"), "q-block:8"),
    ((1000, 256, 2, 16, "float32"), "q-block:1000"),
])
def test_shape_rule(args, reason):
    ok, why = pc.supported(*args)
    assert ok == (not reason) and why == (reason and f"paged-chunk:{reason}")


def test_shape_rule_needs_qpos():
    assert pc.supported(1024, 3840, 30, 16, "bfloat16", has_qpos=False) \
        == (False, "paged-chunk:no-qpos")
    with pytest.raises(ValueError, match="head-dim:64"):
        pc.paged_chunk_attention(
            jnp.zeros((1, 32, 128)), jnp.zeros((2, 16, 128)),
            jnp.zeros((2, 16, 128)), jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.zeros((1, 32), jnp.int32),
            n_head=2)


def test_head_groups_fit_their_lanes():
    # bfloat16: 2 groups of 1 920 lanes at the hybrid cell's heads
    assert pc.group_heads(30, 128, 2) == 15
    assert pc.group_heads(30, 128, 4) == 6
    assert pc.group_heads(20, 128, 2) == 10
    assert pc.group_heads(20, 128, 4) == 5
    assert pc.group_heads(2, 128, 4) == 2
    assert pc.group_heads(3, 1024, 2) == 1
    assert pc.group_heads(7, 4096, 2) == 1


# ---------------------------------------------------------------------------
# through the op, the programs and the engine
# ---------------------------------------------------------------------------


def _cached_op(q, kp, vp, table, ctx, q_pos, n_head):
    from paddle_tpu.ops.registry import LoweringContext, get_op
    ins = {"Q": [jnp.asarray(q)], "KPool": [jnp.asarray(kp)],
           "VPool": [jnp.asarray(vp)], "BlockTable": [jnp.asarray(table)],
           "CtxLen": [jnp.asarray(ctx)], "QPos": [jnp.asarray(q_pos)]}
    lctx = LoweringContext(jax.random.PRNGKey(0), is_test=True)
    return np.asarray(get_op("fused_attention")(
        lctx, ins, {"n_head": n_head, "_cached": True,
                    "is_test": True})["Out"])


@pytest.mark.parametrize("n_head,route,outcome", [
    (2, "paged_chunk_attention", "hit"),
    (4, "cached_flash_attention", "fallback")], ids=["heads-of-128",
                                                      "heads-of-64"])
def test_op_takes_the_chunk_route_at_heads_of_whole_lane_tiles(
        tpu_routes_interpreted, n_head, route, outcome):
    """A chunk's query with ``QPos``: the kernel at heads of 128, the
    gather at heads of 64 (which share a lane tile) — the same numbers."""
    q, kp, vp, table, ctx, q_pos = _problem([(37, 13), (100, 32)], 32)
    want = np.asarray(_gathered(q, kp, vp, table, ctx, q_pos, n_head))
    got = _cached_op(q, kp, vp, table, ctx, q_pos, n_head)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert tpu_routes_interpreted(outcome) == {route: 1}


def _config(name, rehearse):
    from benchmark import run as bench
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    if rehearse:
        bench.apply_rehearsal(config, {})
    config["model"] = bench.model_keys(config)
    return config


def _model(name, config):
    if name == "bert_base_decoder":
        from paddle_tpu.models.bert import BertConfig
        from paddle_tpu.models.decoder import BertDecoder
        keys = {f.name for f in dataclasses.fields(BertConfig)}
        return BertDecoder(BertConfig(**{
            k: v for k, v in config["model"].items() if k in keys}))
    if name == "deepseek_v3_ep16_serve":
        from benchmark.builders.serve_lm import decoder_config
        from paddle_tpu.models.latent_decoder import LatentDecoder
        return LatentDecoder(decoder_config(config))
    if name == "laguna_s21_ep4_serve":
        from benchmark.builders.serve_window import decoder_config
        from paddle_tpu.models.window_decoder import WindowDecoder
        return WindowDecoder(decoder_config(config))
    from benchmark.builders.serve_hybrid import decoder_config
    from paddle_tpu.models.hybrid_decoder import HybridDecoder
    return HybridDecoder(decoder_config(config))


def _census(name, rehearse):
    """``{program: sorted (op, route or "fallback")}`` of every op with a
    Pallas channel in the configuration's serving programs, at the
    engine's largest buckets, for a TPU — 0 compiles, 0 traces
    (``analysis.kernel_routing_report``)."""
    from paddle_tpu.framework.analysis import kernel_routing_report
    from paddle_tpu.serving import DecodeConfig
    config = _config(name, rehearse)
    cfg = DecodeConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in config["engine"].items()})
    model = _model(name, config)
    mbps = cfg.max_blocks_per_seq
    kw = {"state_slots": 2} if "hybrid" in name else {}
    if "laguna" in name:
        kw = {"state_slots": 2, "ring_pages": model.window_ring_pages(
            cfg.block_size, max(cfg.prefill_seq_buckets[-1],
                                cfg.chunk_width))}
    progs = model.build(mbps, cfg.block_size, mbps, 1,
                        chain_lengths=cfg.chain_lengths,
                        chunk_tokens=cfg.chunk_width, **kw)
    rows = max(cfg.batch_buckets or (cfg.max_batch_size,))
    kinds = {"prefill": (progs.prefill, progs.prefill_feeds,
                         (1, cfg.prefill_seq_buckets[-1])),
             "chunk": (progs.chunk, progs.chunk_feeds, (1, cfg.chunk_width)),
             "decode": (progs.decode, progs.decode_feeds, (rows, 1))}
    for length, prog in progs.chains.items():
        kinds[f"chain{length}"] = (prog, progs.chain_feeds, (rows, 1))
    out = {}
    for kind, (prog, feeds, dims) in kinds.items():
        block, shapes = prog.global_block(), {}
        for f in feeds:
            var = block.var(f)
            fill = list(dims)
            shape = tuple(fill.pop(0) if d == -1 and fill else max(d, 1)
                          for d in var.shape)
            shapes[f] = (shape, str(var.dtype))
        rep = kernel_routing_report(prog, feed_shapes=shapes, backend="tpu")
        out[kind] = sorted({(r["op"], r["kernel"] if r["route"] == "pallas"
                             else "fallback") for r in rep["rows"]})
    return out


_FA, _MLA = "fused_attention", "mla_attention"
_LN = ("layer_norm", "fused_layer_norm")
#: the routes each program picks without this kernel, recorded from the
#: commit before it
CENSUS = {
    ("bert_base_decoder", True): dict(
        prefill=[(_FA, "fallback"), _LN], chunk=[(_FA, "fallback"), _LN],
        decode=[(_FA, "paged_decode_attention"), _LN],
        chain1=[(_FA, "paged_decode_attention"), _LN],
        chain4=[(_FA, "paged_decode_attention"), _LN]),
    ("bert_base_decoder", False): dict(
        prefill=[(_FA, "flash_attention"), _LN],
        chunk=[(_FA, "cached_flash_attention"), _LN],
        decode=[(_FA, "paged_decode_attention"), _LN],
        chain1=[(_FA, "paged_decode_attention"), _LN],
        chain8=[(_FA, "paged_decode_attention"), _LN]),
    ("deepseek_v3_ep16_serve", True): {
        k: [(_MLA, "fallback"), ("moe_grouped_ffn", "fallback")]
        for k in ("prefill", "chunk", "decode", "chain1", "chain4")},
    # at the published widths the latent decoder's chunks take a kernel
    # of their own (ops/pallas/mla_chunk.py)
    ("deepseek_v3_ep16_serve", False): dict(
        prefill=[(_MLA, "fallback"),
                 ("moe_grouped_ffn", "moe_grouped_matmul")],
        chunk=[(_MLA, "mla_chunk_attn"),
               ("moe_grouped_ffn", "moe_grouped_matmul")],
        **{k: [(_MLA, "mla_paged_decode"),
               ("moe_grouped_ffn", "moe_grouped_matmul")]
           for k in ("decode", "chain1", "chain8")}),
    ("olmo_hybrid_7b_pp4_serve", True): dict(
        {k: [(_FA, "fallback"), ("gated_delta_rule", "gdn_chunk")]
         for k in ("prefill", "chunk")},
        **{k: [(_FA, "fallback"), ("gated_delta_rule", "gdn_decode")]
           for k in ("decode", "chain1", "chain4")}),
    ("olmo_hybrid_7b_pp4_serve", False): dict(
        prefill=[(_FA, "flash_attention"), ("gated_delta_rule", "gdn_chunk")],
        chunk=[(_FA, "paged_chunk_attention"),
               ("gated_delta_rule", "gdn_chunk")],
        **{k: [(_FA, "paged_decode_attention_wide"),
               ("gated_delta_rule", "gdn_decode")]
           for k in ("decode", "chain1", "chain8")}),
}


@pytest.mark.parametrize("rehearse", [True, False],
                         ids=["rehearsal", "published"])
@pytest.mark.parametrize("name", ["bert_base_decoder",
                                  "deepseek_v3_ep16_serve"])
def test_no_other_serving_configuration_picks_the_chunk_route(name,
                                                              rehearse):
    """``bert_decoder.chat_closed`` and ``deepseek_v3_decode.reason_closed``
    run none of this kernel: every program of their configurations, at
    the rehearsal's sizes and at the published ones, keeps the routes it
    had before the kernel existed (heads of 64 share a lane tile; the
    latent decoder's chunks are another op, with a kernel of their own)."""
    assert _census(name, rehearse) == CENSUS[(name, rehearse)]


@pytest.mark.parametrize("rehearse", [True, False],
                         ids=["rehearsal", "published"])
@pytest.mark.parametrize("name", sorted({n for n, _ in CENSUS}))
def test_every_serving_configuration_keeps_its_routes(name, rehearse):
    """Grouped K/V heads and a window in the paged reads are routed by the
    op's attrs alone: every program of every serving configuration that
    has neither picks the routes it picked before they existed (recorded
    from the commit before; group 1 / window 0 is the same kernel)."""
    assert _census(name, rehearse) == CENSUS[(name, rehearse)]


def test_the_window_decoders_programs_take_the_grouped_routes():
    """``laguna_s_serve.code_closed``'s programs at the published widths
    (48 / 72 heads of 128 on 8 K/V heads, windows of 512, bfloat16 pools):
    decode steps by the wide body's grouped scoring, chunks by the chunk
    kernel with K/V groups, the packed prefill by the windowed flash
    kernels; experts by the grouped matmul."""
    got = _census("laguna_s21_ep4_serve", False)
    moe = ("moe_grouped_ffn", "moe_grouped_matmul")
    assert got["prefill"] == [(_FA, "flash_gqa_attention"), moe]
    assert got["chunk"] == [(_FA, "paged_chunk_attention"), moe]
    for kind in ("decode", "chain1", "chain8"):
        assert got[kind] == [(_FA, "paged_gqa_decode"), moe]


def test_the_hybrid_decoders_chunks_take_the_chunk_route():
    """``olmo_hybrid_serve.doc_closed``'s chunk program, at the published
    widths (30 heads of 128, bfloat16 pools, 1 024-token chunks), is the
    one serving program that picks the route; its other programs keep
    theirs."""
    got = _census("olmo_hybrid_7b_pp4_serve", False)
    assert got["chunk"] == [(_FA, "paged_chunk_attention"),
                            ("gated_delta_rule", "gdn_chunk")]
    assert got["prefill"] == [(_FA, "flash_attention"),
                              ("gated_delta_rule", "gdn_chunk")]
    for kind in ("decode", "chain1", "chain8"):
        assert got[kind] == [(_FA, "paged_decode_attention_wide"),
                             ("gated_delta_rule", "gdn_decode")]


def _hybrid_engine():
    """A hybrid decoder with heads of 128 (two, in a layer of four), the
    tiny linear layers, chunks of 16 over pages of 8."""
    from paddle_tpu.models.hybrid_decoder import (HybridDecoder,
                                                  HybridDecoderConfig)
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    cfg = HybridDecoderConfig.tiny(hidden_size=256, num_attention_heads=2,
                                   num_key_value_heads=2,
                                   num_hidden_layers=4)
    return DecodeEngine(HybridDecoder(cfg, seed=3), DecodeConfig(
        block_size=8, max_seq_len=128, max_batch_size=4,
        prefill_seq_buckets=(16,), prefill_batch_buckets=(1,),
        chain_lengths=(1, 4), chunk_tokens=16, prefix_cache=False,
        pool_blocks=48))


def _served():
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 256, (n,)).astype(np.int64)
               for n in (40, 23, 57)]
    engine = _hybrid_engine()
    try:
        futs = [engine.generate({"src_ids": p}, max_new_tokens=5)
                for p in prompts]
        return [f.result(timeout=900).tokens for f in futs], engine.stats()
    finally:
        engine.shutdown(drain=False)


def test_engine_tokens_through_the_chunk_route_equal_the_gathers(
        tpu_routes_interpreted):
    """Prompts of 40, 23 and 57 tokens, chunked by 16 beside each other's
    decode chains: the tokens of the chunk kernel are the gather's, and
    the route counted hits and no fallback of its own."""
    got, stats = _served()
    hits = tpu_routes_interpreted("hit")
    assert hits.get("paged_chunk_attention", 0) > 0, hits
    assert "cached_flash_attention" not in tpu_routes_interpreted(
        "fallback")
    assert stats["chunk_steps"] == 3 + 2 + 4
    with lowering_target("cpu"):
        want, _ = _served()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_chunk_pages_counters_follow_the_chunks_frontiers():
    """A prompt of 70 tokens on the bert serving cell's rehearsal engine
    (chunks of 32, pages of 8, a 16-page table): chunks at 0, 32 and 64
    of one query block each read 4, 8 and 9 pages of 16 spanned."""
    from paddle_tpu.models.bert import BertConfig
    from paddle_tpu.models.decoder import BertDecoder
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    reh = _config("bert_base_decoder", True)
    keys = {f.name for f in dataclasses.fields(BertConfig)}
    cfg = BertConfig(**{k: v for k, v in reh["model"].items()
                        if k in keys})
    engine = DecodeEngine(BertDecoder(cfg, seed=3),
                          DecodeConfig(**dict(reh["engine"],
                                              chain_lengths=(1, 4))))
    try:
        prompt = np.arange(1, 71, dtype=np.int64)
        engine.generate({"src_ids": prompt},
                        max_new_tokens=2).result(timeout=300)
        st = engine.stats()
    finally:
        engine.shutdown(drain=False)
    assert st["chunk_steps"] == 3
    assert st["chunk_kv_pages_read"] == 4 + 8 + 9
    assert st["chunk_kv_pages_spanned"] == 3 * 16
