"""Grouped K/V heads and a sliding window in the paged reads: the wide
decode body's grouped scoring (``paged_gqa_decode``) and the paged chunk
kernel with K/V groups, against the gather route they are held to
(``lower_cached_attention`` with ``num_kv_heads`` and ``window``: the
gathered table, each K/V head repeated for its query heads, the causal,
context and window masks).

What a grouped, windowed paged read can get wrong and the gather cannot:
query head ``h`` reading another K/V head than ``h // group``; a window's
first page (``(ctx - window) // block``) and the positions before the
window inside it; contexts shorter than the window; groups of 1, 6 and 9
(rows of a K/V head padded to whole sublane tiles); every pool slot no
context owns holding NaN.  The kernels run in Pallas' TPU interpret mode;
``chip_smoke.py`` leg W repeats the comparison on the chip at
``laguna_s_serve.code_closed``'s shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.attention_ops import lower_cached_attention
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import paged_chunk as pc
from tests.test_paged_decode_attention import (  # noqa: F401 - a fixture
    _poisoned, tpu_routes_interpreted)

INTERPRET = pltpu.InterpretParams()      # uninitialised memory reads NaN
D, BLOCK, PAGES, N_KV = 128, 16, 12, 2
WINDOW = 40


def _problem(ctx, n_head, sq=1, seed=0, dtype="float32"):
    """Rows of contexts ``ctx`` over scattered pool blocks of ``N_KV``
    heads of 128; queries of ``n_head`` heads, ``sq`` a row."""
    rng = np.random.RandomState(seed)
    nb = len(ctx) * PAGES + 1
    table = np.stack([rng.permutation(nb - 1)[:PAGES] + 1
                      for _ in ctx]).astype(np.int32)
    kp, vp = (rng.randn(nb, BLOCK, N_KV * D).astype(dtype)
              .astype(np.float32) for _ in range(2))
    q = rng.randn(len(ctx), sq, n_head * D).astype(dtype).astype(np.float32)
    return q, kp, vp, table, np.asarray(ctx, np.int32)


def _gather(q, kp, vp, table, ctx, q_pos, n_head, window):
    attrs = {"n_head": n_head, "num_kv_heads": N_KV}
    if window:
        attrs["window"] = window
    ins = {"Q": [jnp.asarray(q)], "KPool": [jnp.asarray(kp)],
           "VPool": [jnp.asarray(vp)], "BlockTable": [jnp.asarray(table)],
           "CtxLen": [jnp.asarray(ctx)]}
    if q_pos is not None:
        ins["QPos"] = [jnp.asarray(q_pos)]
    with jax.default_matmul_precision("highest"):
        return np.asarray(lower_cached_attention(None, ins, attrs)["Out"])


#: contexts: a lone position, inside the first page, at the window's
#: edge and past it by several pages, the whole table, nothing live
DECODE_CTX = (1, 7, WINDOW - 1, WINDOW, WINDOW + 1, 137, PAGES * BLOCK, 0)


@pytest.mark.parametrize("window", [0, WINDOW], ids=["full", "window"])
@pytest.mark.parametrize("group", [1, 6, 9])
def test_grouped_decode_matches_the_gather(group, window):
    """A decode step's query (one token a row at ``ctx - 1``): the wide
    body's grouped scoring against the gather route, NaN in every slot no
    context owns; a row with nothing live writes zeros."""
    n_head = group * N_KV
    q, kp, vp, table, ctx = _problem(DECODE_CTX, n_head)
    want = _gather(q, kp, vp, table, ctx, None, n_head, window)
    got = np.asarray(pa.paged_gqa_decode(
        jnp.asarray(q), jnp.asarray(_poisoned(kp, table, ctx)),
        jnp.asarray(_poisoned(vp, table, ctx)), jnp.asarray(table),
        jnp.asarray(ctx), n_head=n_head, num_kv_heads=N_KV, window=window,
        interpret=INTERPRET))
    live = ctx > 0
    # float32 arithmetic throughout, the gather's too
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    assert not got[~live].any()


#: (start, tokens) chunks: the first, one crossing the window's edge, a
#: final one of padded rows, one ending at the table's end
CHUNKS = ((0, 32), (24, 32), (100, 21), (PAGES * BLOCK - 32, 32))


@pytest.mark.parametrize("window", [0, WINDOW], ids=["full", "window"])
@pytest.mark.parametrize("group", [1, 6, 9])
def test_grouped_chunk_matches_the_gather(group, window):
    """A chunk's queries with ``QPos`` (padded rows at position 0): the
    paged chunk kernel with K/V groups against the gather route, NaN in
    every slot no context owns."""
    n_head, sq = group * N_KV, 32
    ctx = [s + n for s, n in CHUNKS]
    q, kp, vp, table, ctx = _problem(ctx, n_head, sq=sq, seed=1)
    q_pos = np.zeros((len(CHUNKS), sq), np.int32)
    for i, (s, n) in enumerate(CHUNKS):
        q_pos[i, :n] = np.arange(s, s + n)
    want = _gather(q, kp, vp, table, ctx, q_pos, n_head, window)
    got = np.asarray(pc.paged_chunk_attention(
        jnp.asarray(q), jnp.asarray(_poisoned(kp, table, ctx)),
        jnp.asarray(_poisoned(vp, table, ctx)), jnp.asarray(table),
        jnp.asarray(ctx), jnp.asarray(q_pos), n_head=n_head,
        num_kv_heads=N_KV, window=window, interpret=INTERPRET))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_bfloat16_pools():
    """bfloat16 pools and queries, both kernels: the chunk's output is
    rounded to bfloat16 (as the query), the decode body keeps float32."""
    n_head = 6 * N_KV
    q, kp, vp, table, ctx = _problem(DECODE_CTX[:-1], n_head,
                                     dtype=jnp.bfloat16)
    want = _gather(q, kp, vp, table, ctx, None, n_head, WINDOW)
    bf = [jnp.asarray(p, jnp.bfloat16) for p in (kp, vp)]
    got = pa.paged_gqa_decode(
        jnp.asarray(q), *bf, jnp.asarray(table), jnp.asarray(ctx),
        n_head=n_head, num_kv_heads=N_KV, window=WINDOW,
        interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    sq = 16
    q_pos = np.stack([np.arange(c - sq, c) for c in (16, 64, 150)])
    ctx = q_pos[:, -1] + 1
    qc = jnp.asarray(np.random.RandomState(2).randn(3, sq, n_head * D),
                     jnp.bfloat16)
    want = _gather(qc, kp, vp, table[:3], ctx, q_pos, n_head, WINDOW)
    got = pc.paged_chunk_attention(
        qc, *bf, jnp.asarray(table[:3]), jnp.asarray(ctx),
        jnp.asarray(q_pos), n_head=n_head, num_kv_heads=N_KV,
        window=WINDOW, interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=1e-2, atol=1e-2)


def test_one_group_and_no_window_keep_the_body_of_one_pool_head_a_query():
    """Group 1 / window 0 is the wide body as it was: an op with neither
    attr takes ``paged_decode_attention_wide`` and its kernel, and only
    ``paged_gqa_decode`` runs the grouped scoring."""
    from paddle_tpu.ops.op_specs import _PL_PAGED_GQA, _PL_PAGED_WIDE
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((8, 1, 512), jnp.float32), ((64, 16, 512), jnp.bfloat16),
        ((64, 16, 512), jnp.bfloat16), ((8, 8), jnp.int32),
        ((8,), jnp.int32))]

    def kernels(fn, **kw):
        return str(jax.make_jaxpr(lambda *a: fn(*a, n_head=4, **kw))(*args))
    plain = kernels(pa.paged_decode_attention_wide)
    assert "paged_decode_attn_wide" in plain
    assert "paged_gqa_decode" not in plain
    grouped = kernels(pa.paged_gqa_decode, num_kv_heads=4, window=0)
    assert "paged_gqa_decode" in grouped
    assert "paged_decode_attn_wide" not in grouped
    attrs = {"n_head": 4, "_cached": True}
    assert _PL_PAGED_WIDE.match(attrs, None)
    assert not _PL_PAGED_GQA.match(attrs, None)
    for stamp in ({"num_kv_heads": 2}, {"window": 64}):
        assert _PL_PAGED_GQA.match(dict(attrs, **stamp), None)
        assert not _PL_PAGED_WIDE.match(dict(attrs, **stamp), None)


@pytest.mark.parametrize("args,reason", [
    ((1, 6 * 128, 6, 2, 16, "bfloat16"), ""),
    ((1, 6 * 128, 6, 4, 16, "bfloat16"), "paged-gqa:heads:6/4"),
    ((1, 6 * 64, 6, 2, 16, "bfloat16"), "paged-decode-wide:head-dim:64"),
    ((2, 6 * 128, 6, 2, 16, "bfloat16"), "paged-decode:sq:2")])
def test_grouped_shape_rule(args, reason):
    ok, why = pa.supported_gqa(*args)
    assert ok == (not reason) and why == reason


@pytest.mark.parametrize("q_pos,sq,first,edge", [
    (np.arange(100, 132), 32, [61], [92]),
    (np.arange(0, 32), 32, [0], [0]),
    (np.concatenate([np.arange(5, 26), np.zeros(11, int)]), 32, [0], [0])])
def test_window_bounds(q_pos, sq, first, edge):
    """A query block's first visible key (its earliest query's, ``p -
    window + 1``) and the first key all its queries see (its latest's);
    padded rows at position 0 pull the first to 0."""
    f, e, lower = pc.window_bounds(np.asarray(q_pos)[None], None, sq, WINDOW)
    assert f.tolist() == [first] and e.tolist() == [edge]
    assert lower[0, :, 0].tolist() == np.maximum(
        np.asarray(q_pos) - WINDOW + 1, 0).tolist()


@pytest.mark.parametrize("sq,route", [(1, "paged_gqa_decode"),
                                      (32, "paged_chunk_attention")])
def test_op_takes_the_grouped_routes(tpu_routes_interpreted, sq, route):
    """``fused_attention`` over the pools with ``num_kv_heads`` and
    ``window`` stamped: a decode step takes the grouped scoring, a chunk
    the chunk kernel — the gather's numbers — and neither plain paged
    body nor the gather + flash composition is tried."""
    from paddle_tpu.ops.registry import LoweringContext, get_op
    n_head = 6 * N_KV
    ctx = [70, 150] if sq == 1 else [64, 150]
    q, kp, vp, table, ctx = _problem(ctx, n_head, sq=sq)
    q_pos = None if sq == 1 else np.stack([np.arange(c - sq, c)
                                           for c in ctx]).astype(np.int32)
    want = _gather(q, kp, vp, table, ctx, q_pos, n_head, WINDOW)
    ins = {"Q": [jnp.asarray(q)], "KPool": [jnp.asarray(kp)],
           "VPool": [jnp.asarray(vp)], "BlockTable": [jnp.asarray(table)],
           "CtxLen": [jnp.asarray(ctx)]}
    if q_pos is not None:
        ins["QPos"] = [jnp.asarray(q_pos)]
    attrs = {"n_head": n_head, "num_kv_heads": N_KV, "window": WINDOW,
             "_cached": True, "is_test": True}
    got = np.asarray(get_op("fused_attention")(
        LoweringContext(jax.random.PRNGKey(0), is_test=True), ins,
        attrs)["Out"])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert tpu_routes_interpreted("hit") == {route: 1}
    assert not tpu_routes_interpreted("fallback")
