"""The hybrid linear / full attention decoder (models/hybrid_decoder.py)
behind the decode engine, its op family (ops/linear_attn_ops.py, the two
``gdn_*`` kernels in interpret mode) and the engine's recurrent-state
pool, at tiny size on the CPU in float32.

The yardstick is ``benchmark/reference/olmo_hybrid_jnp.py``: the
recurrence token by token, no cache, no chunked form.  chip_smoke.py leg
H and the benchmark cell repeat the comparisons on the chip at the
published widths.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from benchmark.reference import olmo_hybrid_jnp as ref
from paddle_tpu.models.hybrid_decoder import (FULL, LINEAR, HybridDecoder,
                                              HybridDecoderConfig)
from paddle_tpu.ops import linear_attn_ops as la
from paddle_tpu.ops.pallas import gated_delta as gd
from paddle_tpu.ops.pallas import lowering_target
from paddle_tpu.ops.registry import LoweringContext, get_op
from paddle_tpu.serving import DecodeConfig, DecodeEngine

INTERPRET = pltpu.InterpretParams()
TOL = 1e-4


def _engine(model=None, **kw):
    cfg = dict(block_size=8, max_seq_len=128, max_batch_size=4,
               prefill_seq_buckets=(16,), prefill_batch_buckets=(1, 2, 4),
               chain_lengths=(1, 4), chunk_tokens=16, prefix_cache=False,
               pool_blocks=48)
    cfg.update(kw)
    return DecodeEngine(model or HybridDecoder(HybridDecoderConfig.tiny(),
                                               seed=3),
                        DecodeConfig(**cfg))


def _weights(engine):
    assert engine.close()       # the donated state flows back to the scope
    scope = engine.scope
    return {n: np.asarray(scope.find_var(n)) for n in scope.var_names()
            if not n.startswith("@")}


def _model_keys(cfg):
    import dataclasses
    return dict(dataclasses.asdict(cfg), layer_types=tuple(cfg.layer_types))


def _reference_rows(weights, cfg, prompt, tokens, chunk=16):
    seq = np.concatenate([prompt, tokens])
    pad = -len(seq) % 16
    lg = ref.logits(weights, np.pad(seq, (0, pad)), _model_keys(cfg),
                    q_block=16, chunk=chunk)
    return np.asarray(lg)[len(prompt) - 1:len(prompt) - 1 + len(tokens)]


def _rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


# ---------------------------------------------------------------------------
# the op family
# ---------------------------------------------------------------------------

def _gdn_inputs(rng, b, s, h, dk, dv):
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return {"Q": f(b, s, h * dk), "K": f(b, s, h * dk) + 0.3,
            "V": f(b, s, h * dv), "A": f(b, s, h), "B": f(b, s, h),
            "ALog": jnp.log(jnp.asarray(rng.uniform(1, 16, h), jnp.float32)),
            "DtBias": f(h) - 3.0}


def _gdn(ins, kernels=False):
    op = get_op("gated_delta_rule")
    attrs = {"n_head": 3, "beta_scale": 2.0}
    lctx = LoweringContext(jax.random.PRNGKey(0), is_test=True)
    ins = {k: [v] for k, v in ins.items()}
    if not kernels:
        return op(lctx, ins, attrs)
    pltpu.set_tpu_interpret_mode(INTERPRET)
    try:
        with lowering_target("tpu"):
            return op(lctx, ins, attrs)
    finally:
        pltpu.set_tpu_interpret_mode(None)


def _token_scan(ins, h, dk, dv):
    """The reference's recurrence on one row of the op's inputs."""
    t = ins["Q"].shape[1]
    out = []
    for b in range(ins["Q"].shape[0]):
        q = ins["Q"][b].reshape(t, h, dk)
        k = ins["K"][b].reshape(t, h, dk)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * dk ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        g = -jnp.exp(ins["ALog"]) * jax.nn.softplus(ins["A"][b]
                                                   + ins["DtBias"])
        out.append(ref.delta_rule(
            q, k, ins["V"][b].reshape(t, h, dv), jnp.exp(g),
            2.0 * jax.nn.sigmoid(ins["B"][b]), frozenset(), 1 << 30
        ).reshape(t, h * dv))
    return np.stack(out)


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["jnp", "pallas-interpret"])
def test_chunked_route_equals_recurrent_route_equals_the_token_scan(kernels):
    """150 tokens (neither a multiple of the sub-chunk nor of the first
    launch) in two chunked launches, state carried in a pool slot; the
    same tokens one a launch through the recurrent route; the reference's
    scan.  Padding past each launch's end is an identity update."""
    h, dk, dv, total, first = 3, 16, 24, 150, 100
    rng = np.random.default_rng(0)
    ins = _gdn_inputs(rng, 2, total, h, dk, dv)
    want = _token_scan(ins, h, dk, dv)
    pool = jnp.asarray(rng.normal(size=(5, h, dk, dv)), jnp.float32)
    slot = jnp.array([3, 1], jnp.int32)
    timed = ("Q", "K", "V", "A", "B")

    def launch(lo, hi, width, pool, fresh):
        part = {k: (jnp.pad(v[:, lo:hi], ((0, 0), (0, width - (hi - lo)),
                                          (0, 0))) if k in timed else v)
                for k, v in ins.items()}
        valid = jnp.arange(width)[None, :] < hi - lo
        out = _gdn(dict(part, StatePool=pool, StateSlot=slot,
                        Fresh=jnp.full((2,), fresh, jnp.int32),
                        Valid=jnp.broadcast_to(valid, (2, width))), kernels)
        return np.asarray(out["Out"])[:, :hi - lo], out["StatePoolOut"]

    o1, pool1 = launch(0, first, 128, pool, 1)
    o2, pool2 = launch(first, total, 64, pool1, 0)
    chunked = np.concatenate([o1, o2], axis=1)
    assert _rel(chunked, want) < TOL
    # slots nobody named are untouched, bit for bit
    np.testing.assert_array_equal(np.asarray(pool2)[[0, 2, 4]],
                                  np.asarray(pool)[[0, 2, 4]])
    # the recurrent route, token by token from a zero state
    rpool = pool.at[slot].set(0.0)
    steps = []
    for t in range(total):
        out = _gdn(dict({k: (v[:, t:t + 1] if k in timed else v)
                         for k, v in ins.items()},
                        StatePool=rpool, StateSlot=slot), kernels)
        rpool = out["StatePoolOut"]
        steps.append(np.asarray(out["Out"]))
    assert _rel(np.concatenate(steps, axis=1), want) < TOL
    assert _rel(np.asarray(rpool)[np.asarray(slot)],
                np.asarray(pool2)[np.asarray(slot)]) < TOL


def test_conv_carries_its_tail_between_launches_and_skips_padding():
    op = get_op("causal_conv1d")
    lctx = LoweringContext(jax.random.PRNGKey(0), is_test=True)
    rng = np.random.default_rng(1)
    c, taps, total, first = 12, 4, 21, 13
    xs = jnp.asarray(rng.normal(size=(1, total, c)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, c)), jnp.float32)
    want = np.asarray(ref.causal_conv(xs[0], w, frozenset(), 1 << 30))
    pool = jnp.asarray(rng.normal(size=(3, (taps - 1) * c)), jnp.float32)
    slot = jnp.array([2], jnp.int32)

    def launch(lo, hi, width, pool, fresh):
        part = jnp.pad(xs[:, lo:hi], ((0, 0), (0, width - (hi - lo)),
                                      (0, 0)), constant_values=7.0)
        out = op(lctx, {"X": [part], "W": [w], "TailPool": [pool],
                        "StateSlot": [slot],
                        "Fresh": [jnp.array([fresh], jnp.int32)],
                        "Valid": [jnp.arange(width)[None, :] < hi - lo]},
                 {})
        return np.asarray(out["Out"])[0, :hi - lo], out["TailPoolOut"]

    o1, pool1 = launch(0, first, 16, pool, 1)
    o2, pool2 = launch(first, total, 16, pool1, 0)
    np.testing.assert_allclose(np.concatenate([o1, o2]), want, rtol=1e-5,
                               atol=1e-6)
    # then one token a launch, no Fresh, no Valid: the decode step's form
    o3 = op(lctx, {"X": [xs[:, :1] * 0 + 1.0], "W": [w],
                   "TailPool": [pool2], "StateSlot": [slot]}, {})
    ext = np.concatenate([np.asarray(xs[0, total - 3:]), np.ones((1, c))])
    np.testing.assert_allclose(
        np.asarray(o3["Out"])[0, 0],
        np.asarray(jax.nn.silu((ext * np.asarray(w)).sum(0))), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(pool2)[:2],
                                  np.asarray(pool)[:2])


def test_route_table_picks_the_form_from_the_querys_length():
    from paddle_tpu.ops.registry import pallas_route
    rng = np.random.default_rng(2)
    ins = {k: [v] for k, v in _gdn_inputs(rng, 2, 1, 3, 16, 24).items()}
    pool = {"StatePool": [jnp.zeros((3, 3, 16, 24))],
            "StateSlot": [jnp.zeros((2,), jnp.int32)]}
    attrs = {"n_head": 3}
    assert la.is_recurrent({**ins, **pool})
    assert not la.is_recurrent(ins)
    with lowering_target("tpu"):
        hit = lambda i, k: pallas_route("gated_delta_rule", i, attrs,
                                        count=False, kernel=k)
        assert hit({**ins, **pool}, "gdn_decode")[0].kernels == \
            ("gdn_decode",)
        # no pool, or a launch that may start a sequence: never recurrent
        assert hit(ins, "gdn_decode")[0] is None
        assert hit({**ins, **pool, "Fresh": [jnp.ones((2,), jnp.int32)]},
                   "gdn_decode")[0] is None
        assert hit(ins, "gdn_chunk")[0].kernels == ("gdn_chunk",)
    assert gd.supported(30, 96, 192) == (True, "")
    assert gd.supported(30, 96, 192, "bfloat16") == (
        False, "gdn:state-dtype:bfloat16")
    assert gd.supported(4, 160, 64) == (False, "gdn:key-dim:160")


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------

PROMPTS = (5, 16, 37, 23)       # packed prefill (<= 16) and chunked


@pytest.fixture(scope="module")
def served():
    """Four prompts served together (two by the packed prefill, two in
    chunks; chains of 1 and 4 through both caches), their logits, and the
    engine's weights."""
    engine = _engine()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n) for n in PROMPTS]
    futures = [engine.generate({"src_ids": p}, max_new_tokens=11,
                               return_logits=True) for p in prompts]
    results = [f.result(timeout=600) for f in futures]
    stats = engine.stats()
    return engine.model.cfg, _weights(engine), prompts, results, stats


@pytest.mark.parametrize("i", range(len(PROMPTS)),
                         ids=[f"prompt-{n}" for n in PROMPTS])
def test_prefill_then_decode_through_both_caches_matches_the_reference(
        served, i):
    cfg, weights, prompts, results, _ = served
    want = _reference_rows(weights, cfg, prompts[i], results[i].tokens)
    assert results[i].logits.shape == want.shape
    assert _rel(results[i].logits, want) < TOL
    np.testing.assert_array_equal(results[i].tokens, want.argmax(axis=1))


def test_state_pool_counters_and_spans(served):
    *_, stats = served
    assert stats["state_slots"] == 4 and stats["state_slots_in_use"] == 0
    assert 1 <= stats["state_slots_peak"] <= 4
    cfg = served[0]
    assert stats["state_bytes_per_slot"] == len(cfg.layers_of(LINEAR)) * (
        4 * 8 * 16 * 4 + 3 * (2 * 32 + 64) * 4)
    assert stats["state_rows_live"] == stats["chain_tokens"]
    assert stats["state_rows_launched"] >= stats["state_rows_live"]
    assert stats["admission_waits"] == 0
    assert stats["chunk_tokens"] == 37 + 23


def test_every_program_passes_verify_decode_with_the_state_pools_declared():
    from paddle_tpu.framework.analysis import verify_decode
    model = HybridDecoder(HybridDecoderConfig.tiny(), seed=1)
    progs = model.build(16, 8, 8, 1, chain_lengths=(1, 4), chunk_tokens=16,
                        state_slots=3)
    assert len(progs.cache_vars) == 2 * len(model.cfg.layers_of(FULL)) \
        + 2 * len(model.cfg.layers_of(LINEAR))
    for prog, feeds, fetches in (
            (progs.prefill, progs.prefill_feeds, progs.fetch_names),
            (progs.decode, progs.decode_feeds, progs.fetch_names),
            (progs.chunk, progs.chunk_feeds, progs.fetch_names),
            (progs.chains[4], progs.chain_feeds, progs.chain_fetch_names)):
        assert "state_slot" in feeds
        names = [v for v in prog.global_block().vars]
        verify_decode(prog, feed_names=feeds, fetch_names=fetches,
                      scope_names=names,
                      cache_vars=progs.cache_vars).raise_on_error()
    # a state pool left out of the declaration is a weight write
    res = verify_decode(progs.decode, feed_names=progs.decode_feeds,
                        fetch_names=progs.fetch_names, scope_names=names,
                        cache_vars=[n for n in progs.cache_vars
                                    if "gdn_state" not in n])
    assert not res.ok
    with pytest.raises(ValueError, match="one segment"):
        model.build(16, 8, 8, 4)


def test_prefix_cache_is_refused_for_a_model_with_recurrent_state():
    from paddle_tpu.framework.errors import InvalidArgumentError
    with pytest.raises(InvalidArgumentError, match="recurrent"):
        _engine(prefix_cache=True)


def _serve(engine, prompts, max_new=9):
    futures = [engine.generate({"src_ids": p}, max_new_tokens=max_new,
                               return_logits=True) for p in prompts]
    return [f.result(timeout=600) for f in futures]


def test_a_reused_state_slot_gives_what_a_fresh_engine_gives():
    """One batch row, so one slot: the second and third sequences get the
    slot the first left its state in.  The launch's fresh flag, not a clear, makes it
    zero."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n) for n in (30, 9, 41)]
    lone = _engine(max_batch_size=1, batch_buckets=(1,),
                   prefill_batch_buckets=(1,))
    reused = [_serve(lone, [p])[0] for p in prompts]
    stats = lone.stats()
    assert stats["state_slot_reuses"] == 2 and stats["state_slots"] == 1
    lone.shutdown()
    for p, got in zip(prompts, reused):
        fresh = _engine(max_batch_size=1, batch_buckets=(1,),
                        prefill_batch_buckets=(1,))
        want = _serve(fresh, [p])[0]
        fresh.shutdown()
        np.testing.assert_array_equal(got.tokens, want.tokens)
        np.testing.assert_array_equal(got.logits, want.logits)


def test_rows_cobatched_delayed_or_padded_do_not_perturb_each_other():
    """The same prompts alone (bucket of 1), together in a padded bucket
    of 4 with pad rows on the scratch slot, and behind a K/V pool too
    small for all of them at once."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, n) for n in (12, 33, 20)]
    alone = []
    for p in prompts:
        e = _engine(batch_buckets=(1, 4))
        alone.append(_serve(e, [p])[0])
        e.shutdown()
    together = _engine(batch_buckets=(4,))
    padded = _serve(together, prompts)
    together.shutdown()
    # blocks for two of the three at a time
    tight = _engine(batch_buckets=(4,), pool_blocks=12)
    delayed = _serve(tight, prompts)
    stats = tight.stats()
    tight.shutdown()
    assert stats["admission_waits"] > 0
    assert stats["state_slots_peak"] <= 2
    for a, b, c in zip(alone, padded, delayed):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.tokens, c.tokens)
        assert _rel(b.logits, a.logits) < TOL
        assert _rel(c.logits, a.logits) < TOL


def test_a_free_batch_row_always_finds_a_free_state_slot():
    """The state pool has one slot a batch row, so more requests than rows
    wait for a ROW, never for a slot, and every slot comes back."""
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 256, 10) for _ in range(5)]
    narrow = _engine(max_batch_size=2, batch_buckets=(2,),
                     prefill_batch_buckets=(1, 2))
    served = _serve(narrow, prompts)
    stats = narrow.stats()
    narrow.shutdown()
    assert stats["state_slots"] == stats["state_slots_peak"] == 2
    assert stats["state_slot_reuses"] == 3 and not stats["state_slots_in_use"]
    assert stats["admission_waits"] == 0 and stats["completed"] == 5
    wide = _engine()
    for got, want in zip(served, _serve(wide, prompts)):
        np.testing.assert_array_equal(got.tokens, want.tokens)
        assert _rel(got.logits, want.logits) < TOL
    wide.shutdown()


def test_kernel_routes_inside_a_chain_equal_the_plain_path():
    """The engine with both ``gdn_*`` kernels (interpret mode) in its
    chunk and chain programs against the plain ``jax.numpy`` engine."""
    from paddle_tpu import flags
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, n) for n in (19, 40)]
    plain = _engine()
    want = _serve(plain, prompts, max_new=6)
    plain.shutdown()
    keep = flags.flag("use_flash_attention")
    flags.set_flags({"use_flash_attention": False})
    pltpu.set_tpu_interpret_mode(INTERPRET)
    try:
        with lowering_target("tpu"):
            kernels = _engine()
            got = _serve(kernels, prompts, max_new=6)
            kernels.shutdown()
    finally:
        pltpu.set_tpu_interpret_mode(None)
        flags.set_flags({"use_flash_attention": keep})
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert _rel(a.logits, b.logits) < TOL
