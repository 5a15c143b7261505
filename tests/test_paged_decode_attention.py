"""The paged decode attention kernel (ops/pallas/paged_attention.py)
against the composition it replaces, ``gather_cache`` +
``reference_attention``, over what a paged read can get wrong and a
gather cannot (ISSUE 32): the partly live last page, table entries past
the live pages, leftovers in reused blocks, never-written slots, rows of
very different page counts side by side, softmax state carried from one
row into the next, a row with nothing live.

Both bodies of the kernel are held to it: the narrow one (heads that
share a lane tile: the lane butterfly, one buffer) and the wide one
(heads of whole lane tiles: scores on the MXU, two buffers), which adds
its own ways to go wrong — the second buffer holding an earlier row's
pages, the next row's first pages in flight at a row's last step.

The kernel runs in Pallas' TPU interpret mode: scratch VMEM starts as
NaN and a read outside a buffer raises, which is as near as a CPU gets
to what the chip does with such a read; ``chip_smoke.py`` leg K repeats
the comparison on the chip at the serving cell's shapes."""

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
from paddle_tpu.ops.pallas import paged_attention as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INTERPRET = pltpu.InterpretParams()      # uninitialised memory reads NaN


def _gathered(q, k_pool, v_pool, table, ctx, n_head):
    keys, vals = gather_cache(k_pool, table), gather_cache(v_pool, table)
    return reference_attention(q, keys, vals,
                               ctx_len_bias(ctx, keys.shape[1]), n_head,
                               0.0, None, True)


def _problem(ctx, hidden=128, block=16, pages=32, seed=0, shared=0,
             dtype="float32"):
    """Rows of ``ctx`` live positions over scattered pool blocks, every
    slot of the pools holding some sequence's values; with ``shared`` the
    rows' first pages are the same blocks (a prefix hit).  ``dtype`` is
    the pools': the values are rounded to it and handed out as float32
    NumPy arrays (what the composition reads); :func:`_run` stores them
    in ``dtype`` again, exactly."""
    rng = np.random.RandomState(seed)
    ctx = np.asarray(ctx, np.int32)
    need = np.clip(-(-ctx // block), 1, pages)
    num_blocks = int(need.sum()) + 9
    order = rng.permutation(num_blocks - 1) + 1      # block 0: nobody's
    table = np.zeros((len(ctx), pages), np.int32)
    at = 0
    for b, n in enumerate(need):
        table[b, :n] = order[at:at + n]
        at += n
    if shared:
        table[1:, :shared] = table[0, :shared]
    pools = [np.array(jnp.asarray(
        rng.randn(num_blocks, block, hidden), dtype).astype(jnp.float32))
        for _ in range(2)]
    q = rng.randn(len(ctx), 1, hidden).astype(np.float32)
    return q, pools[0], pools[1], table, ctx


def _poisoned(pool, table, ctx):
    """NaN in every slot no row's live context owns: past ``ctx`` in the
    last page, whole blocks nobody's table names (block 0 among them,
    which is where the table's dead entries point)."""
    block = pool.shape[1]
    out = np.full_like(pool, np.nan)
    for b, n in enumerate(ctx):
        for t in range(min(int(n), table.shape[1] * block)):
            blk = table[b, t // block]
            out[blk, t % block] = pool[blk, t % block]
    return out


BODIES = {"narrow": pa.paged_decode_attention,
          "wide": pa.paged_decode_attention_wide}


def _run(q, k_pool, v_pool, table, ctx, n_head, dtype="float32",
         body="narrow", **kw):
    return np.asarray(BODIES[body](
        jnp.asarray(q), jnp.asarray(k_pool, dtype),
        jnp.asarray(v_pool, dtype), jnp.asarray(table), jnp.asarray(ctx),
        n_head=n_head, interpret=INTERPRET, **kw))


#: live contexts a row (block 16, 32 pages): one token, a whole page, a
#: page and one, the cell's mean, the whole table
RAGGED = (1, 16, 17, 143, 512)

CASES = {
    "ragged": dict(ctx=RAGGED),
    "ragged-pages-of-8": dict(ctx=(1, 8, 9, 143, 256), block=8),
    "a-long-row-then-short-ones": dict(ctx=(512, 1, 496, 16)),
    "page-counts-30-apart": dict(ctx=(16, 496, 32, 480)),
    "bucket-of-one": dict(ctx=(143,)),
    "bucket-of-two": dict(ctx=(17, 300)),
    "eight-pages-a-step": dict(ctx=RAGGED, pages_per_step=8),
    "one-head-of-128": dict(ctx=RAGGED, n_head=1),
    "four-heads-of-32": dict(ctx=RAGGED, n_head=4),
    "three-lane-tiles": dict(ctx=(17, 143), hidden=384, n_head=6),
    "shared-prefix-blocks": dict(ctx=(40, 70, 100), shared=2),
    "a-short-table": dict(ctx=(1, 33, 48), pages=3),
    # heads of whole lane tiles (both bodies take a head of 128; only the
    # wide one takes more, or more heads than one MXU pass scores)
    "three-heads-of-128": dict(ctx=RAGGED, hidden=384, n_head=3),
    "ten-heads-of-128": dict(ctx=(17, 143, 300), hidden=1280, n_head=10),
    "two-heads-of-256": dict(ctx=(17, 143, 300), hidden=512, n_head=2),
    # four steps of 256 positions, the last page half live; then a row
    # of one page, whose copies were in flight during that last step
    "sixteen-pages-a-step": dict(ctx=(1000, 9, 515), pages=64, n_head=1,
                                 pages_per_step=16),
    # row 0 fills both buffers with its pages (four steps of 8); row 1's
    # one step has three live pages and five that still hold row 0's
    "dead-pages-after-a-longer-row": dict(ctx=(512, 40, 300, 17), n_head=1,
                                          pages_per_step=8),
    "wide-ragged-two-pages-a-step": dict(ctx=RAGGED, n_head=1,
                                         pages_per_step=2),
}


def _bodies(hidden=128, n_head=2, **_):
    """The bodies whose shape rule takes the case's heads."""
    return [b for b, rule in (("narrow", pa.supported),
                              ("wide", pa.supported_wide))
            if rule(1, hidden, n_head, 16)[0]]


#: a bfloat16 page is whole 16-row tiles: pages of 8 are float32's alone
POOL_DTYPES = [(c, dt, body) for c in sorted(CASES)
               for dt in ("float32", "bfloat16")
               if dt == "float32" or CASES[c].get("block", 16) % 16 == 0
               for body in _bodies(**CASES[c])]

#: (body, hidden) at two heads: heads of 64 and heads of 128
TWO_HEADS = [("narrow", 128), ("wide", 256)]


@pytest.mark.parametrize("case,dtype,body", POOL_DTYPES)
def test_kernel_matches_gather_composition(case, dtype, body):
    kw = dict(CASES[case])
    n_head = kw.pop("n_head", 2)
    run_kw = {k: kw.pop(k) for k in ("pages_per_step",) if k in kw}
    q, kp, vp, table, ctx = _problem(dtype=dtype, **kw)
    got = _run(q, kp, vp, table, ctx, n_head, dtype=dtype, body=body,
               **run_kw)
    want = np.asarray(_gathered(q, kp, vp, table, ctx, n_head))
    assert got.shape == q.shape
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("body,hidden", TWO_HEADS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("pages_per_step", [8, 2])
def test_nan_past_ctx_len_and_in_unowned_blocks(pages_per_step, dtype, body,
                                                hidden):
    """What the kernel must not read into the result is NaN here: the
    slots past ``ctx_len`` of every last page, every block no row owns
    (block 0, where dead table entries point, among them).  The gather
    composition cannot survive this (0 x NaN); the kernel must, and must
    agree with the composition on clean pools."""
    q, kp, vp, table, ctx = _problem((1, 16, 17, 143, 512, 30, 250),
                                     hidden=hidden, dtype=dtype)
    want = np.asarray(_gathered(q, kp, vp, table, ctx, 2))
    bad_k, bad_v = _poisoned(kp, table, ctx), _poisoned(vp, table, ctx)
    assert np.isnan(bad_k[0]).all() and np.isnan(bad_v).any()
    assert not np.isfinite(np.asarray(
        _gathered(q, bad_k, bad_v, table, ctx, 2))).all()
    got = _run(q, bad_k, bad_v, table, ctx, 2, dtype=dtype, body=body,
               pages_per_step=pages_per_step)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("body,hidden", TWO_HEADS)
def test_rows_with_nothing_live_and_with_more_than_the_table(body, hidden):
    """A pad row of a bucket has ``ctx_len`` 0 on a single step: it reads
    page 0 and writes finite numbers (zeros).  A row that finished at
    the table's end inside a chain has ``ctx_len`` one past it: it reads
    the whole table, as the composition's mask does."""
    q, kp, vp, table, ctx = _problem((0, 513, 512, 0, 5), hidden=hidden)
    kp[table[0, 0]] = np.nan            # nothing of row 0 is live
    got = _run(q, kp, vp, table, ctx, 2, body=body)
    assert np.isfinite(got).all()
    assert not got[0].any() and not got[3].any()
    want = np.asarray(_gathered(q, np.nan_to_num(kp), vp, table, ctx, 2))
    np.testing.assert_allclose(got[[1, 2, 4]], want[[1, 2, 4]],
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("body,hidden", TWO_HEADS)
def test_table_entries_out_of_the_pool_are_not_followed(body, hidden):
    """Dead entries are never fetched, whatever they hold; a live one
    that is out of range is clamped into the pool, not followed."""
    q, kp, vp, table, ctx = _problem((17, 40), hidden=hidden)
    want = np.asarray(_gathered(q, kp, vp, table, ctx, 2))
    wild = table.copy()
    wild[0, 2:] = 10 ** 6
    wild[1, 3:] = -7
    got = _run(q, kp, vp, wild, ctx, 2, body=body)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_the_wide_body_without_prefetch_is_the_same_read():
    """``prefetch=False`` (a step's copies waited for before they are
    scored: what chip_smoke.py times the second buffer against) reads the
    same pages."""
    q, kp, vp, table, ctx = _problem(RAGGED, hidden=256, dtype="bfloat16")
    want = np.asarray(_gathered(q, kp, vp, table, ctx, 2))
    got = _run(q, _poisoned(kp, table, ctx), _poisoned(vp, table, ctx),
               table, ctx, 2, dtype="bfloat16", body="wide", prefetch=False)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_the_wide_body_keeps_its_page_buffers_inside_the_budget():
    """A step's pages shrink until the four buffers fit half the default
    scoped VMEM: asked for 16, float32 pools of the hybrid cell's width
    get 8 pages a step where bfloat16 ones get the 16."""
    import re
    for dtype, rows in (("bfloat16", 16 * 16), ("float32", 8 * 16)):
        jaxpr = str(jax.make_jaxpr(lambda *a: pa.paged_decode_attention_wide(
            *a, n_head=30, pages_per_step=16))(
                jnp.zeros((2, 1, 3840)), jnp.zeros((4, 16, 3840), dtype),
                jnp.zeros((4, 16, 3840), dtype),
                jnp.zeros((2, 1024), jnp.int32), jnp.ones((2,), jnp.int32)))
        bufs = set(re.findall(r"\[2,(\d\d+),3840\]", jaxpr))
        assert bufs == {str(rows)}, (dtype, bufs)


def _primitives(jaxpr, out):
    """Every equation's primitive, nested jaxprs (the kernel's body, its
    loops and branches) in place."""
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, out)
    return out


def test_the_narrow_body_at_the_serving_cells_shapes_is_pr_32s():
    """``bert_decoder.chat_closed`` must not move while its traffic file
    cannot feed a faster kernel (PERF.md question 29): the narrow body
    traced at that cell's shapes (128 rows, 32 pages of 16, 12 heads of
    64, float32) is, equation for equation, the one PR 32 landed —
    recorded from the commit before the wide body existed."""
    import hashlib
    jaxpr = jax.make_jaxpr(
        lambda *a: pa.paged_decode_attention(*a, n_head=12))(
            jnp.zeros((128, 1, 768)), jnp.zeros((4096, 16, 768)),
            jnp.zeros((4096, 16, 768)), jnp.zeros((128, 32), jnp.int32),
            jnp.ones((128,), jnp.int32))
    names = _primitives(jaxpr.jaxpr, [])
    assert len(names) == 477
    assert hashlib.sha256(" ".join(names).encode()).hexdigest()[:16] \
        == "6662748e2f0d2f86"
    assert pa.PAGES_PER_STEP == 4


@pytest.mark.parametrize("d", [32, 64, 128])
def test_head_sum_leaves_each_heads_sum_in_all_its_lanes(d):
    """The lane butterfly: every lane of a head holds the head's sum,
    bitwise the same in all of them, to f32 accumulation error."""
    rng = np.random.RandomState(1)
    x = (rng.randn(16, 256) * np.exp(rng.randn(16, 256) * 3)).astype(
        np.float32)
    heads = 256 // d

    def kernel(x_ref, o_ref):
        o_ref[...] = pa._head_sum(x_ref[...], d)
    from jax.experimental import pallas as pl
    got = np.asarray(pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=True)(jnp.asarray(x))).reshape(16, heads, d)
    want = x.astype(np.float64).reshape(16, heads, d).sum(-1)
    scale = np.abs(x).astype(np.float64).reshape(16, heads, d).sum(-1)
    assert np.all(np.abs(got - want[..., None]) <= 1e-6 * scale[..., None])
    assert np.all(got == got[..., :1])


@pytest.mark.parametrize("args,reason", [
    ((1, 768, 12, 16), ""),
    ((1, 128, 1, 8), ""),
    ((128, 768, 12, 16), "sq:128"),
    ((1, 768, 12, 12), "block-size:12"),
    ((1, 192, 3, 16), "hidden:192"),
    ((1, 768, 3, 16), "head-dim:256"),
    ((1, 768, 8, 16), "head-dim:96"),
])
def test_shape_rule(args, reason):
    ok, why = pa.supported(*args)
    assert ok == (not reason) and why == (reason and f"paged-decode:{reason}")


def test_shape_rule_refuses_qpos_and_other_dtypes():
    assert pa.supported(1, 768, 12, 16, has_qpos=True) == (
        False, "paged-decode:qpos")
    assert pa.supported(1, 768, 12, 16, "float16") == (
        False, "paged-decode:dtype:float16")
    # a bfloat16 page is whole 16-row tiles
    assert pa.supported(1, 3840, 30, 16, "bfloat16") == (True, "")
    assert pa.supported(1, 768, 12, 8, "bfloat16") == (
        False, "paged-decode:block-size:8")
    # the wide rule: the narrow one's, for heads of whole lane tiles
    assert pa.supported_wide(1, 3840, 30, 16, "bfloat16") == (True, "")
    assert pa.supported_wide(1, 512, 2, 8) == (True, "")
    assert pa.supported_wide(1, 768, 12, 16) == (
        False, "paged-decode-wide:head-dim:64")
    assert pa.supported_wide(1, 384, 2, 16) == (
        False, "paged-decode-wide:head-dim:192")
    assert pa.supported_wide(8, 3840, 30, 16) == (False, "paged-decode:sq:8")
    assert pa.supported_wide(1, 3840, 30, 8, "bfloat16") == (
        False, "paged-decode:block-size:8")
    assert pa.supported_wide(1, 3840, 30, 16, has_qpos=True) == (
        False, "paged-decode:qpos")
    with pytest.raises(ValueError, match="head-dim:64"):
        pa.paged_decode_attention_wide(
            jnp.zeros((1, 1, 128)), jnp.zeros((2, 8, 128)),
            jnp.zeros((2, 8, 128)), jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32), n_head=2)
    with pytest.raises(ValueError, match="sq:2"):
        pa.paged_decode_attention(
            jnp.zeros((1, 2, 128)), jnp.zeros((2, 8, 128)),
            jnp.zeros((2, 8, 128)), jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32), n_head=2)


# ---------------------------------------------------------------------------
# through the op and through the engine
# ---------------------------------------------------------------------------


@pytest.fixture
def tpu_routes_interpreted():
    """Route as on a TPU, run the kernels in interpret mode: the paged
    route's gate is the only Pallas gate the programs below pass (their
    prefill shapes tile no flash kernel; the fused row kernels are
    switched off)."""
    from paddle_tpu import flags
    from paddle_tpu.observability import metrics
    keep = flags.flag("use_pallas_fused")
    flags.set_flags({"use_pallas_fused": False})
    pltpu.set_tpu_interpret_mode(INTERPRET)
    metrics.reset_metrics()
    try:
        with lowering_target("tpu"):
            yield lambda outcome: {
                m["labels"]["kernel"]: int(m["value"])
                for m in metrics.metrics_snapshot(
                    include_serving=False)["metrics"]
                if m["name"] == "pallas_routes"
                and m["labels"]["outcome"] == outcome}
    finally:
        pltpu.set_tpu_interpret_mode(None)
        flags.set_flags({"use_pallas_fused": keep})


def _cached_op(q, kp, vp, table, ctx, q_pos=None):
    from paddle_tpu.ops.registry import LoweringContext, get_op
    ins = {"Q": [jnp.asarray(q)], "KPool": [jnp.asarray(kp)],
           "VPool": [jnp.asarray(vp)], "BlockTable": [jnp.asarray(table)],
           "CtxLen": [jnp.asarray(ctx)]}
    if q_pos is not None:
        ins["QPos"] = [jnp.asarray(q_pos)]
    lctx = LoweringContext(jax.random.PRNGKey(0), is_test=True)
    return np.asarray(get_op("fused_attention")(
        lctx, ins, {"n_head": 2, "_cached": True, "is_test": True})["Out"])


@pytest.mark.parametrize("hidden,route", [
    (128, "paged_decode_attention"), (256, "paged_decode_attention_wide")])
def test_op_takes_the_paged_route_for_a_decode_step_only(
        tpu_routes_interpreted, hidden, route):
    """Sq == 1 without QPos lowers to the kernel and counts a hit — the
    narrow body's route at two heads of 64, the wide body's at two heads
    of 128; a chunk's query (Sq > 1, QPos) keeps the gather."""
    q, kp, vp, table, ctx = _problem((1, 17, 143), pages=16, hidden=hidden)
    want = np.asarray(_gathered(q, kp, vp, table, ctx, 2))
    got = _cached_op(q, kp, vp, table, ctx)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert tpu_routes_interpreted("hit") == {route: 1}
    assert not tpu_routes_interpreted("fallback")
    chunk_q = np.repeat(q, 8, axis=1)
    q_pos = np.tile(np.arange(8), (3, 1)) + np.maximum(ctx - 8, 0)[:, None]
    _cached_op(chunk_q, kp, vp, table, ctx, q_pos)
    assert tpu_routes_interpreted("hit") == {route: 1}
    (kernel, n), = tpu_routes_interpreted("fallback").items()
    assert kernel == "cached_flash_attention" and n == 1


def _rehearsal_engine(chains):
    """The serving cell's rehearsal configuration, with a pool so small
    that blocks are freed and handed out again while others decode."""
    from paddle_tpu.models.bert import BertConfig
    from paddle_tpu.models.decoder import BertDecoder
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    with open(os.path.join(REPO, "benchmark", "configs",
                           "bert_base_decoder.json")) as f:
        reh = json.load(f)["rehearsal"]
    fields = {f.name for f in dataclasses.fields(BertConfig)}
    cfg = BertConfig(type_vocab_size=2,
                     **{k: v for k, v in reh.items() if k in fields})
    eng = dict(reh["engine"], chain_lengths=chains, pool_blocks=14,
               batch_buckets=(4,), max_batch_size=4)
    return DecodeEngine(BertDecoder(cfg, seed=3), DecodeConfig(**eng))


def _served(chains):
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 1024, (n,)).astype(np.int64)
               for n in (5, 16, 23, 9, 30, 12)]
    engine = _rehearsal_engine(chains)
    try:
        futs = [engine.generate({"src_ids": p}, max_new_tokens=n)
                for p, n in zip(prompts, (9, 17, 6, 20, 10, 14))]
        tokens = [f.result(timeout=900).tokens for f in futs]
        # alone, with 9 tokens left after its prefill: a chain of 8, then
        # a chain of 1, in blocks the others have just given back
        tokens.append(engine.generate(
            {"src_ids": prompts[1]}, max_new_tokens=10).result(
                timeout=900).tokens)
        return tokens, engine.stats()
    finally:
        engine.shutdown(drain=False)


def test_engine_tokens_equal_the_gather_routes(tpu_routes_interpreted):
    """DecodeEngine on the rehearsal configuration, chains of 1 and 8,
    blocks freed and reused after a retire: the tokens of the paged
    route are the gather route's, and the route counted hits and no
    fallback of its own."""
    got, stats = _served((1, 8))
    hits = tpu_routes_interpreted("hit")
    assert hits.get("paged_decode_attention", 0) > 0, hits
    assert stats["block_reuses"] > 0 and stats["admission_waits"] > 0
    assert set(stats["chain_hist"]) == {1, 8}, stats["chain_hist"]
    # the share of the block tables the decode steps' reads touched
    assert 0 < stats["kv_pages_read"] < stats["kv_pages_spanned"]
    assert stats["kv_pages_spanned"] == 16 * 4 * stats["decode_steps"]
    with lowering_target("cpu"):
        want, _ = _served((1, 8))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_kv_pages_counters_follow_the_rows_positions():
    """One request alone in a bucket of 4: each step reads
    ``ceil(ctx / block)`` pages for its row and one page for each pad
    row, of ``max_blocks_per_seq`` spanned a row."""
    engine = _rehearsal_engine((1, 4))
    try:
        prompt = np.arange(1, 14, dtype=np.int64)          # 13 tokens
        engine.generate({"src_ids": prompt},
                        max_new_tokens=10).result(timeout=300)
        st = engine.stats()
    finally:
        engine.shutdown(drain=False)
    # decode steps run at positions 13 .. 21 (the first token is the
    # prefill's); a chain runs its whole length, the finished row frozen
    steps = st["decode_steps"]
    assert steps >= 9
    ctx = [min(13 + s, 22) + 1 for s in range(steps)]
    assert st["kv_pages_read"] == sum(-(-c // 8) + 3 for c in ctx)
    assert st["kv_pages_spanned"] == steps * 4 * 16
