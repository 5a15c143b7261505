"""The latent cache's chunk kernel (ops/pallas/mla_chunk.py) against the
composition it replaces for a prefill chunk's read of the paged latent
cache, ``mla_ops.chunk_attention`` (the XLA loop, still the CPU path and
the specification), over what a read bounded by each query block's
causal frontier and an up-projection held in VMEM can get wrong: a first
chunk and a later one; contexts that end inside a page and inside a key
step; a final chunk whose query rows past the prompt are padding
(position 0); several query blocks of different frontiers; float32 and
bfloat16 pools; every pool slot no context owns holding NaN.  At the
smallest lane-aligned widths and at one head group of DeepSeek-V3's
(128 + 64 / 128 a head over a 512-wide latent in a 640-wide row).

Then the shape rule (the rehearsal's widths keep the loop), the route,
the engine's count of the work a chunk's read needs and the reader of
``mla_chunk_roofline_pct``.

The kernel runs in Pallas' TPU interpret mode (scratch VMEM starts as
NaN, a read outside a buffer raises); ``chip_smoke.py`` leg P repeats the
comparison on the chip at ``deepseek_v3_decode.reason_closed``'s
shapes."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import mla_ops
from paddle_tpu.ops.pallas import mla_chunk as mc
from paddle_tpu.serving.decode import causal_pairs
from tests.test_paged_decode_attention import (  # noqa: F401 - a fixture
    tpu_routes_interpreted)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INTERPRET = pltpu.InterpretParams()      # uninitialised memory reads NaN

#: (heads, nope, rope, v, latent, row width)
WIDTHS = {"lanes": (2, 128, 64, 128, 128, 256),
          "published": (4, 128, 64, 128, 512, 640)}


def _problem(chunks, sq, widths, block=16, pages=8, seed=0,
             dtype=jnp.float32):
    """Rows, one a ``(start, tokens)`` chunk: the row's context is
    ``start + tokens`` latent rows ``[c_kv | k_rope | 0]`` over scattered
    pool blocks, NaN in every slot no row owns; its queries the chunk's
    ``tokens`` positions and ``sq - tokens`` padded rows at position 0."""
    h, dn, dr, dv, dc, width = widths
    rng = np.random.RandomState(seed)
    ctx = np.array([s + n for s, n in chunks], np.int32)
    need = np.clip(-(-ctx // block), 1, pages)
    nb = int(need.sum()) + 5
    order = rng.permutation(nb - 1) + 1              # block 0: nobody's
    table = np.zeros((len(chunks), pages), np.int32)
    at = 0
    for b, n in enumerate(need):
        table[b, :n] = order[at:at + n]
        at += n
    q_pos = np.zeros((len(chunks), sq), np.int32)
    for b, (s, n) in enumerate(chunks):
        q_pos[b, :n] = np.arange(s, s + n)
    pool = np.full((nb, block, width), np.nan, np.float32)
    for b in range(len(chunks)):
        for t in range(ctx[b]):
            row = np.zeros(width, np.float32)
            row[:dc + dr] = rng.randn(dc + dr)
            pool[table[b, t // block], t % block] = row
    q = rng.randn(len(chunks), sq, h * (dn + dr))
    wkvb = rng.randn(dc, h * (dn + dv)) * dc ** -0.5
    attrs = {"n_head": h, "nope_dim": dn, "rope_dim": dr, "v_dim": dv,
             "scale": (dn + dr) ** -0.5}
    return attrs, (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
                   jnp.asarray(table), jnp.asarray(ctx),
                   jnp.asarray(q_pos), jnp.asarray(wkvb, dtype))


def _spec(attrs, args):
    with jax.default_matmul_precision("highest"):
        return np.asarray(mla_ops.chunk_attention(*args, attrs), np.float32)


#: chunks of 48 queries (3 query blocks of 16) over an 8-page table:
#: a prompt's first chunk, a later one ending inside a page, and a final
#: chunk of 26 tokens with 22 padded rows
CASES = {"first": [(0, 48)], "later": [(40, 48)], "padded": [(70, 26)],
         "rows": [(0, 48), (40, 48), (70, 26), (80, 48)]}


@pytest.mark.parametrize("pps", [2, None], ids=["steps-of-2-pages",
                                                "default-step"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_equals_the_loop(case, dtype, pps):
    attrs, args = _problem(CASES[case], 48, WIDTHS["lanes"],
                           dtype=jnp.dtype(dtype))
    got = np.asarray(mc.mla_chunk_attention(
        *args, **attrs, pages_per_step=pps, interpret=INTERPRET),
        np.float32)
    want = _spec(attrs, args)
    assert np.isfinite(got).all()
    # bfloat16: both round the up-projection and ``p`` to the pool's
    # dtype, at different block maxima, and return bfloat16
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, want, atol=tol * np.abs(want).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_head_group_at_the_published_widths(dtype):
    """One grid step's four heads of 128 + 64 / 128 over a 512-wide
    latent in 640-wide rows: a later chunk and a padded final one, short
    contexts, steps of two pages."""
    attrs, args = _problem([(40, 48), (70, 26)], 48, WIDTHS["published"],
                           dtype=jnp.dtype(dtype))
    got = np.asarray(mc.mla_chunk_attention(
        *args, **attrs, pages_per_step=2, interpret=INTERPRET), np.float32)
    want = _spec(attrs, args)
    assert np.isfinite(got).all()
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, want, atol=tol * np.abs(want).max())


def test_shape_rule():
    """The published widths at the cell's table are taken; the
    rehearsal's (heads of 16 + 8 / 16 over a 32-wide latent) and every
    other refusal keep the loop."""
    args = dict(sq=1024, n_head=128, nope_dim=128, rope_dim=64, v_dim=128,
                latent_dim=512, block_size=16, width=640, pages_per_seq=512)
    assert mc.supported(**args) == (True, "")
    assert mc.group_heads(128, 1024, 128, 128, 512, 640, 16, 512, 2) == 4
    for kw, why in ((dict(has_qpos=False), "no-qpos"), (dict(sq=1), "sq"),
                    (dict(dtype="float16"), "dtype"),
                    (dict(block_size=8), "block-size"),
                    (dict(width=576), "latent"),
                    (dict(nope_dim=16, v_dim=16, latent_dim=32,
                          rope_dim=8, width=128, n_head=4), "latent"),
                    (dict(nope_dim=64, v_dim=64), "head"),
                    (dict(sq=1000), "q-block"),
                    (dict(pages_per_seq=1 << 16), "vmem")):
        good, reason = mc.supported(**dict(args, **kw))
        assert not good and why in reason, (kw, reason)


def _op(q, pool, table, ctx, q_pos, wkvb, attrs):
    from paddle_tpu.ops.registry import get_op
    ins = {"Q": [q], "Pool": [pool], "BlockTable": [table], "CtxLen": [ctx],
           "QPos": [q_pos], "WKVB": [wkvb]}
    return np.asarray(get_op("mla_attention")(
        None, ins, dict(attrs, _cached=True))["Out"], np.float32)


def test_route_takes_lane_aligned_chunks_and_keeps_the_loop_else(
        tpu_routes_interpreted):
    attrs, args = _problem(CASES["rows"], 48, WIDTHS["lanes"])
    got = _op(*args, attrs)
    assert tpu_routes_interpreted("hit") == {"mla_chunk_attn": 1}
    np.testing.assert_allclose(got, _spec(attrs, args),
                               atol=1e-5 * np.abs(got).max())
    # the rehearsal's widths: the same op, the loop
    h, dn, dr, dv, dc = 4, 16, 8, 16, 32
    rng = np.random.RandomState(1)
    small = {"n_head": h, "nope_dim": dn, "rope_dim": dr, "v_dim": dv,
             "scale": 0.3}
    q = jnp.asarray(rng.randn(1, 16, h * (dn + dr)), jnp.float32)
    pool = jnp.asarray(rng.randn(6, 8, 128), jnp.float32)
    w = jnp.asarray(rng.randn(dc, h * (dn + dv)) * 0.2, jnp.float32)
    rest = (jnp.arange(6, dtype=jnp.int32)[None], jnp.array([30], jnp.int32),
            jnp.arange(14, 30, dtype=jnp.int32)[None])
    got = _op(q, pool, *rest, w, small)
    assert tpu_routes_interpreted("hit") == {"mla_chunk_attn": 1}
    assert tpu_routes_interpreted("fallback").get("mla_chunk_attn")
    np.testing.assert_allclose(
        got, _spec(small, (q, pool, *rest, w)), atol=1e-6)


@pytest.mark.parametrize("start,end", [(0, 1), (0, 1024), (1024, 2048),
                                       (3072, 3100), (17, 18), (5, 5)])
def test_causal_pairs_is_the_brute_force_count(start, end):
    t = np.arange(end + 1)
    brute = sum(int((t <= i).sum()) for i in range(start, end))
    assert causal_pairs(start, end) == brute


def test_the_engine_counts_what_its_chunks_must_read():
    """A tiny latent engine chunking two prompts: the two counters are
    the chunks' causal pairs and contexts, whatever route the read took."""
    from paddle_tpu.models.latent_decoder import (LatentDecoder,
                                                  LatentDecoderConfig)
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    engine = DecodeEngine(
        LatentDecoder(LatentDecoderConfig.tiny(), seed=5),
        DecodeConfig(block_size=8, max_seq_len=128, max_batch_size=2,
                     prefill_seq_buckets=(16,), chain_lengths=(1,),
                     chunk_tokens=16, prefix_cache=False))
    try:
        rng = np.random.default_rng(0)
        lens = (45, 33)
        futs = [engine.generate({"src_ids": rng.integers(0, 64, n)},
                                max_new_tokens=2) for n in lens]
        for f in futs:
            f.result(timeout=300)
        st = engine.stats()
    finally:
        engine.shutdown(drain=False)
    bounds = [(s, min(n, s + 16)) for n in lens for s in range(0, n, 16)]
    assert st["chunk_steps"] == len(bounds)
    assert st["chunk_causal_pairs"] == sum(causal_pairs(s, e)
                                           for s, e in bounds)
    assert st["chunk_ctx_positions"] == sum(e for _, e in bounds)


def _reader():
    path = os.path.join(REPO, "benchmark", "layer_metrics",
                        "mla_chunk_roofline_pct.py")
    spec = importlib.util.spec_from_file_location("mla_chunk_roofline_pct",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_reader_prices_the_needed_work_over_the_kernel_rows():
    import json
    with open(os.path.join(REPO, "benchmark", "configs",
                           "deepseek_v3_ep16_serve.json")) as f:
        cfg = json.load(f)
    model = {k: v for k, v in cfg.items() if not isinstance(v, dict)}
    # one 1 024-token chunk ending at 2 048, in 20 ms of kernel rows
    pairs, ctx = causal_pairs(1024, 2048), 2048
    run = {"kind": "serve", "config": {"model": model},
           "peaks": {"bf16_flops": 197e12},
           "trace": {"device_ops": [["fusion", 1.0],
                                    ["mla_chunk_attn", 0.015],
                                    ["mla_chunk_attn.1", 0.005]]},
           "tail": {"stats0": {"chunk_causal_pairs": 0,
                               "chunk_ctx_positions": 0},
                    "stats1": {"chunk_causal_pairs": pairs,
                               "chunk_ctx_positions": ctx}}}
    need = 5 * (pairs * 81920 + ctx * 33554432)
    assert _reader().read(run) == pytest.approx(
        100 * need / 197e12 / 0.02)
    # the parent: no counters, no kernel rows -> nothing
    run["tail"] = {"stats0": {}, "stats1": {}}
    assert _reader().read(run) is None
