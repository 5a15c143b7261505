"""Decode fast path v2 tests (ISSUE 16): device-chained decode (the
chain_length-step on-device scan — token parity at every chain length,
host-sync accounting, the chain-length scheduler), on-device sampling
(greedy rows bit-par when co-batched, fixed-seed determinism, policy
unit specs), cross-request prefix caching (partial-block boundary,
model/layout identity in the hash key, refcounts across retire/EOS,
eviction never touching referenced blocks, the suffix-priced admission
flip), chunked prefill (long-prompt parity, interleave with live
decodes), and the static layer (DECODE_CHAIN_MISPLACED, the
decode_chain / QPos op specs, plan_cache_pool reserve_blocks)."""

import os
import sys

import numpy as np
import pytest

from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.models.bert import BertConfig
from paddle_tpu.models.decoder import BertDecoder
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.decode import _PrefixIndex
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


# ---------------------------------------------------------------------------
# device-chained decode
# ---------------------------------------------------------------------------


def test_chained_decode_parity_and_sync_accounting():
    """A chain_lengths=(4,) engine emits token-for-token what the
    unbatched greedy loop emits, while fetching tokens from the device
    once per CHAIN (packed [chain, batch]) instead of once per token."""
    eng = DecodeEngine(_model(), _config(chain_lengths=(4,)))
    try:
        prompts = _prompts([5, 9, 3])
        max_new = 9          # prefill emits 1, then two full 4-chains
        refs = [eng.greedy_reference({"src_ids": p},
                                     max_new_tokens=max_new)
                for p in prompts]
        futs = [eng.generate({"src_ids": p}, max_new_tokens=max_new)
                for p in prompts]
        results = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
    finally:
        eng.shutdown()
    for r, g in zip(results, refs):
        assert np.array_equal(r.tokens, g.tokens)
    assert set(stats["chain_hist"]) == {4}
    assert stats["chains_run"] == sum(stats["chain_hist"].values())
    assert stats["chain_tokens"] == 3 * (max_new - 1)
    # the old engine paid one host sync per decoded token; chained
    # decode pays one per chain (+ prefill fetches)
    assert stats["host_syncs"] < stats["chain_tokens"]
    assert stats["chains_run"] <= stats["decode_steps"] / 4
    assert stats["decode_steps"] == \
        sum(k * v for k, v in stats["chain_hist"].items())


def test_chain_scheduler_stays_within_configured_lengths():
    """The scheduler only dispatches configured chain lengths, and its
    accounting ties out: decode_steps is the chain-weighted sum."""
    eng = DecodeEngine(_model(), _config(chain_lengths=(1, 4)))
    try:
        prompts = _prompts([5, 9, 3, 6, 11], seed=7)
        refs = [eng.greedy_reference({"src_ids": p},
                                     max_new_tokens=6)
                for p in prompts]
        futs = [eng.generate({"src_ids": p}, max_new_tokens=6)
                for p in prompts]
        results = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
    finally:
        eng.shutdown()
    for r, g in zip(results, refs):
        assert np.array_equal(r.tokens, g.tokens)
    assert set(stats["chain_hist"]) <= {1, 4}
    assert stats["decode_steps"] == \
        sum(k * v for k, v in stats["chain_hist"].items())


# ---------------------------------------------------------------------------
# on-device sampling
# ---------------------------------------------------------------------------


def test_sampling_params_rejected_without_flag():
    eng = DecodeEngine(_model(), _config(), auto_start=False)
    try:
        with pytest.raises(InvalidArgumentError, match="sampling"):
            eng.generate({"src_ids": _prompts([5])[0]}, temperature=0.7)
    finally:
        eng.shutdown()


def test_sampling_deterministic_and_cobatched_greedy_parity():
    """Co-batched with sampling requests, a greedy request stays
    bit-par with the reference; a fixed seed draws identical tokens
    across submissions; a different seed draws a different stream."""
    eng = DecodeEngine(_model(),
                       _config(chain_lengths=(4,), sampling=True))
    try:
        (p,) = _prompts([6])
        ref = eng.greedy_reference({"src_ids": p}, max_new_tokens=9)
        kw = dict(max_new_tokens=9, temperature=0.9, top_k=8, top_p=0.9)
        futs = [eng.generate({"src_ids": p}, max_new_tokens=9),
                eng.generate({"src_ids": p}, seed=123, **kw),
                eng.generate({"src_ids": p}, seed=123, **kw),
                eng.generate({"src_ids": p}, seed=321, **kw)]
        g, s1, s2, s3 = [f.result(timeout=300) for f in futs]
    finally:
        eng.shutdown()
    assert np.array_equal(g.tokens, ref.tokens)
    assert np.array_equal(s1.tokens, s2.tokens)
    assert list(s1.tokens) != list(s3.tokens)


def test_sample_chain_tokens_policy_unit():
    """Pure-function spec of the sampling kernel: temperature <= 0
    returns the greedy tokens bit-exactly, top_k=1 is argmax under any
    seed, and draws are a function of (seed, position) alone."""
    import jax.numpy as jnp
    from paddle_tpu.ops.sampling_ops import sample_chain_tokens

    rng = np.random.RandomState(0)
    b, v = 4, 32
    logits = jnp.asarray(rng.randn(b, v).astype(np.float32))
    greedy = jnp.argmax(logits, axis=-1)
    seeds = jnp.asarray([0, 1, 2, 3], jnp.int32)
    pos = jnp.asarray([5, 5, 9, 9], jnp.int32)

    z = jnp.zeros((b,), jnp.float32)
    zi = jnp.zeros((b,), jnp.int32)
    out = sample_chain_tokens(logits, greedy, z, zi, z, seeds, pos)
    assert np.array_equal(np.asarray(out), np.asarray(greedy))

    t = jnp.full((b,), 0.8, jnp.float32)
    out = sample_chain_tokens(logits, greedy, t, jnp.full((b,), 1,
                              jnp.int32), z, seeds, pos)
    assert np.array_equal(np.asarray(out), np.asarray(greedy))

    k8 = jnp.full((b,), 8, jnp.int32)
    a = sample_chain_tokens(logits, greedy, t, k8, z, seeds, pos)
    b2 = sample_chain_tokens(logits, greedy, t, k8, z, seeds, pos)
    assert np.array_equal(np.asarray(a), np.asarray(b2))
    # every draw stays inside the top-k set
    topk = np.argsort(-np.asarray(logits), axis=-1)[:, :8]
    for row, tok in enumerate(np.asarray(a)):
        assert tok in topk[row]


# ---------------------------------------------------------------------------
# cross-request prefix caching
# ---------------------------------------------------------------------------


def test_prefix_partial_block_trailing_tokens_never_shared():
    """Only FULL prompt blocks strictly before the last token are
    shareable: a 6-token prompt at block_size=4 indexes exactly one
    block, and a repeat arrival hits it and prefills only the 2-token
    suffix."""
    eng = DecodeEngine(_model(), _config(prefix_cache=True))
    try:
        (p,) = _prompts([6])
        ref = eng.greedy_reference({"src_ids": p}, max_new_tokens=4)
        r1 = eng.generate({"src_ids": p},
                          max_new_tokens=4).result(timeout=300)
        eng.drain()
        s0 = eng.stats()
        r2 = eng.generate({"src_ids": p},
                          max_new_tokens=4).result(timeout=300)
        eng.drain()
        s1 = eng.stats()
    finally:
        eng.shutdown()
    assert np.array_equal(r1.tokens, ref.tokens)
    assert np.array_equal(r2.tokens, ref.tokens)
    assert s0["prefix_indexed_blocks"] == 1      # block 1 stays partial
    assert s1["prefix_hits"] - s0["prefix_hits"] == 1
    assert s1["prefill_tokens"] - s0["prefill_tokens"] == 2
    assert s1["prefix_bytes_saved"] > s0["prefix_bytes_saved"]
    assert s1["cache_blocks_used"] == 0


def test_prefix_key_binds_model_and_layout_identity():
    """Two caches only share bytes when the parameters AND the pool
    geometry agree — the hash key folds in cache_layout_key."""
    (p,) = _prompts([12])
    m_a, m_b = _model(seed=3), _model(seed=4)
    assert m_a.cache_layout_key(4) != m_b.cache_layout_key(4)
    assert m_a.cache_layout_key(4) != m_a.cache_layout_key(8)
    idx_a = _PrefixIndex(m_a.cache_layout_key(4), 4, 128)
    idx_b = _PrefixIndex(m_b.cache_layout_key(4), 4, 128)
    idx_a2 = _PrefixIndex(m_a.cache_layout_key(4), 4, 128)
    assert idx_a._key(p, 0) != idx_b._key(p, 0)
    assert idx_a._key(p, 0) == idx_a2._key(p, 0)
    # same layout, different tokens -> different key
    q = p.copy()
    q[1] += 1
    assert idx_a._key(p, 0) != idx_a._key(q, 0)


def test_prefix_refcounts_release_on_eos_retire():
    """An EOS-stopped sequence retires through the same block-release
    path as a length-stopped one: refcounts drop, blocks promote, and
    a follow-up identical prompt hits the index."""
    eng = DecodeEngine(_model(), _config(prefix_cache=True))
    try:
        (p,) = _prompts([9])
        ref = eng.greedy_reference({"src_ids": p}, max_new_tokens=4)
        eos = int(ref.tokens[0])
        r1 = eng.generate({"src_ids": p}, max_new_tokens=4,
                          eos_token_id=eos).result(timeout=300)
        eng.drain()
        s0 = eng.stats()
        r2 = eng.generate({"src_ids": p}, max_new_tokens=4,
                          eos_token_id=eos).result(timeout=300)
        eng.drain()
        s1 = eng.stats()
    finally:
        eng.shutdown()
    assert r1.finish_reason == "eos" and len(r1.tokens) == 1
    assert np.array_equal(r2.tokens, r1.tokens)
    # EOS retire still promoted the full prompt blocks (9 tokens -> 2)
    assert s0["prefix_indexed_blocks"] == 2
    assert s1["prefix_hits"] - s0["prefix_hits"] == 2
    assert s0["cache_blocks_used"] == 0
    assert s1["cache_blocks_used"] == 0


def test_prefix_eviction_never_frees_referenced_blocks():
    idx = _PrefixIndex("m/x", 4, 128)
    (p,) = _prompts([12])
    assert idx.promote(p, 0, 5)
    assert idx.promote(p, 1, 6)
    assert not idx.promote(p, 0, 7)       # racing twin stays private
    idx.release_block(5)
    idx.release_block(6)
    assert idx.evictable() == 2
    hits = idx.probe(p, 9)                # (9-1)//4 = 2 shareable
    assert hits == [5, 6]
    assert idx.evictable() == 0
    assert idx.evict_one() is None        # everything referenced
    idx.release_block(6)
    assert idx.evict_one() == 6
    assert not idx.contains_block(6)
    assert idx.contains_block(5)
    assert idx.evict_one() is None        # 5 still referenced
    idx.release_block(5)
    assert idx.evict_one() == 5
    assert len(idx) == 0


def test_admission_flip_on_evictable_indexed_blocks():
    """The suffix/evictable-aware admission flip: after a retired
    request leaves 4 indexed (refcount-0) blocks in a 6-block pool, a
    DIFFERENT 5-block request has only 2 free blocks — free-list-only
    pricing would wait forever (nothing in flight to retire) — but
    admission counts the evictable blocks, evicts, and admits."""
    eng = DecodeEngine(_model(),
                       _config(prefix_cache=True, pool_blocks=6))
    try:
        a, b = _prompts([16, 16], seed=9)
        ref_a = eng.greedy_reference({"src_ids": a}, max_new_tokens=4)
        ref_b = eng.greedy_reference({"src_ids": b}, max_new_tokens=4)
        r_a = eng.generate({"src_ids": a},
                           max_new_tokens=4).result(timeout=300)
        eng.drain()
        s0 = eng.stats()
        r_b = eng.generate({"src_ids": b},
                           max_new_tokens=4).result(timeout=300)
        eng.drain()
        s1 = eng.stats()
    finally:
        eng.shutdown()
    assert np.array_equal(r_a.tokens, ref_a.tokens)
    assert np.array_equal(r_b.tokens, ref_b.tokens)
    assert s0["prefix_indexed_blocks"] == 4       # 16 tokens / bs 4
    assert s1["prefix_evictions"] - s0["prefix_evictions"] >= 3
    assert s1["admission_waits"] == 0
    assert s1["cache_blocks_used"] == 0


def test_admission_prices_shared_suffix_only():
    """Shared-prefix arrivals admit without waiting where full-span
    pricing would block: with the pool mostly held by a live sequence,
    a same-prefix request needs only its suffix blocks."""
    eng = DecodeEngine(_model(),
                       _config(prefix_cache=True, pool_blocks=9))
    try:
        (p,) = _prompts([16], seed=13)
        ref4 = eng.greedy_reference({"src_ids": p}, max_new_tokens=4)
        ref12 = eng.greedy_reference({"src_ids": p}, max_new_tokens=12)
        # warm the index
        eng.generate({"src_ids": p},
                     max_new_tokens=4).result(timeout=300)
        eng.drain()
        # A holds most of the pool; B's full span (5 blocks) exceeds
        # what's left, but its 2-block suffix fits
        fa = eng.generate({"src_ids": p}, max_new_tokens=12)
        fb = eng.generate({"src_ids": p}, max_new_tokens=4)
        r_a, r_b = fa.result(timeout=300), fb.result(timeout=300)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert np.array_equal(r_a.tokens, ref12.tokens)
    assert np.array_equal(r_b.tokens, ref4.tokens)
    assert stats["admission_waits"] == 0
    assert stats["prefix_hits"] >= 6          # 3 shared blocks x A + B
    assert stats["cache_blocks_used"] == 0


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_long_prompt_parity():
    """A prompt LONGER than the largest prefill bucket streams in
    chunk-width pieces and still decodes token-for-token equal to the
    greedy loop; only the final chunk syncs a token to the host."""
    eng = DecodeEngine(_model(), _config(chunk_tokens=4))
    try:
        (p,) = _prompts([20], seed=21)
        assert len(p) > eng.config.prefill_seq_buckets[-1]
        ref = eng.greedy_reference({"src_ids": p}, max_new_tokens=6)
        res = eng.generate({"src_ids": p},
                           max_new_tokens=6).result(timeout=300)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert np.array_equal(res.tokens, ref.tokens)
    assert stats["chunk_steps"] == 5              # ceil(20 / 4)
    assert stats["prefill_tokens"] == 20


def test_chunked_prefill_interleaves_with_live_decodes():
    eng = DecodeEngine(_model(), _config(chunk_tokens=4))
    try:
        short, long_a, long_b = _prompts([5, 20, 18], seed=25)
        ref_s = eng.greedy_reference({"src_ids": short}, max_new_tokens=8)
        ref_a = eng.greedy_reference({"src_ids": long_a}, max_new_tokens=4)
        ref_b = eng.greedy_reference({"src_ids": long_b}, max_new_tokens=4)
        fs = eng.generate({"src_ids": short}, max_new_tokens=8)
        fa = eng.generate({"src_ids": long_a}, max_new_tokens=4)
        fb = eng.generate({"src_ids": long_b}, max_new_tokens=4)
        r_s, r_a, r_b = [f.result(timeout=300) for f in (fs, fa, fb)]
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert np.array_equal(r_s.tokens, ref_s.tokens)
    assert np.array_equal(r_a.tokens, ref_a.tokens)
    assert np.array_equal(r_b.tokens, ref_b.tokens)
    assert stats["chunk_steps"] >= 10             # 5 + 5 chunks
    assert stats["interleaved_rounds"] >= 1


# ---------------------------------------------------------------------------
# static layer: verifier, op specs, pool planning
# ---------------------------------------------------------------------------


def test_verify_decode_chain_marker_placement():
    from paddle_tpu.framework.analysis import (DECODE_CHAIN_MISPLACED,
                                               verify_decode)
    from paddle_tpu.framework.core import Program

    model = _model()
    progs = model.build(8, 4, 8, pack_max_segments=2,
                        chain_lengths=(2,))
    prog = progs.chains[2]
    res = verify_decode(prog, feed_names=progs.chain_feeds,
                        fetch_names=progs.chain_fetch_names,
                        cache_vars=progs.cache_vars)
    assert not res.errors(), res.report()

    # an op AFTER the marker is outside the scanned body -> error
    b = prog.global_block()
    b.create_var(name="after_chain", shape=(2, -1))
    b.append_op(type="relu", inputs={"X": ["chain_tokens"]},
                outputs={"Out": ["after_chain"]})
    res = verify_decode(prog, feed_names=progs.chain_feeds,
                        fetch_names=progs.chain_fetch_names,
                        cache_vars=progs.cache_vars)
    assert DECODE_CHAIN_MISPLACED in [d.code for d in res.errors()]

    # more than one marker in a program -> error
    p2 = Program()
    b2 = p2.global_block()
    b2.append_op(type="decode_chain", inputs={}, outputs={}, attrs={})
    b2.append_op(type="decode_chain", inputs={}, outputs={}, attrs={})
    res = verify_decode(p2, feed_names=[], fetch_names=[],
                        cache_vars=[])
    assert DECODE_CHAIN_MISPLACED in [d.code for d in res.errors()]


def test_decode_chain_op_spec():
    from paddle_tpu.ops.registry import OP_SPECS, SpecMismatch, VarSig
    spec = OP_SPECS["decode_chain"]
    sigs = {"TokenIds": [VarSig((4,), "int64")],
            "StepsLeft": [VarSig((4,), "int32")]}
    out = spec.infer(sigs, {"chain_length": 6})
    assert out["Out"][0].shape == (6, 4)
    assert out["Out"][0].dtype == "int64"
    with pytest.raises(SpecMismatch):
        spec.infer(sigs, {"chain_length": 0})
    bad = dict(sigs, StepsLeft=[VarSig((3,), "int32")])
    with pytest.raises(SpecMismatch):
        spec.infer(bad, {"chain_length": 6})


def test_qpos_spec_must_match_query_shape():
    from paddle_tpu.ops.registry import OP_SPECS, SpecMismatch, VarSig
    spec = OP_SPECS["fused_attention"]
    sigs = {"Q": [VarSig((2, 4, 64), "float32")],
            "KPool": [VarSig((8, 4, 64), "float32")],
            "VPool": [VarSig((8, 4, 64), "float32")],
            "BlockTable": [VarSig((2, 2), "int32")],
            "CtxLen": [VarSig((2,), "int32")],
            "QPos": [VarSig((2, 4), "int64")]}
    out = spec.infer(sigs, {"n_head": 2})
    assert out["Out"][0].shape == (2, 4, 64)
    bad = dict(sigs, QPos=[VarSig((2, 3), "int64")])
    with pytest.raises(SpecMismatch):
        spec.infer(bad, {"n_head": 2})


def test_plan_cache_pool_reserve_blocks():
    """reserve_blocks is prefix-cache headroom the budget must afford
    on top of min_blocks — an impossible reserve rejects at engine
    start, a feasible one rides the pool plan."""
    cfgkw = dict(block_size=4, max_seq_len=16, max_batch_size=2,
                 prefill_seq_buckets=(8,), prefill_batch_buckets=(1,),
                 pack_max_segments=2)
    with pytest.raises(InvalidArgumentError, match="reserve_blocks"):
        DecodeEngine(_model(),
                     DecodeConfig(hbm_budget_gb=0.5,
                                  prefix_reserve_blocks=10 ** 9,
                                  **cfgkw),
                     auto_start=False)
    eng = DecodeEngine(_model(),
                       DecodeConfig(hbm_budget_gb=0.5,
                                    prefix_reserve_blocks=3, **cfgkw),
                       auto_start=False)
    try:
        assert eng.pool_plan["reserve_blocks"] == 3
        assert eng.pool_blocks >= eng.config.max_blocks_per_seq
    finally:
        eng.shutdown()


def test_config_validation_v2():
    with pytest.raises(InvalidArgumentError):
        _config(chain_lengths=())
    with pytest.raises(InvalidArgumentError):
        _config(chain_lengths=(0,))
    with pytest.raises(InvalidArgumentError):
        _config(chunk_tokens=-2)
    assert _config(chunk_tokens=0).chunk_tokens is None
    with pytest.raises(InvalidArgumentError):
        _config(prefix_reserve_blocks=-1)
    cfg = _config(chain_lengths=(1, 4), chunk_tokens=8)
    assert cfg.chunk_width == 8
    assert _config().chunk_width == _config().prefill_seq_buckets[-1]
