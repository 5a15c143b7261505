"""The window / full attention decoder (models/window_decoder.py: the
Laguna family) behind ``DecodeEngine``, against the plain reference
(benchmark/reference/laguna_jnp.py) at a tiny size in float32.

The engine serves prompts by the packed prefill (fresh keys: the causal
mask and the window) and in chunks (the window layers' reads through the
ring's derived page table, the full layers' through the block table),
then decodes them in chains of 1 and 4, co-batched, with contexts that
cross the window (12 positions, pages of 4) by several pages.  Its
logits are held to ONE full causal pass of the reference on the same
weights at 1e-4 (float32 on both sides, the same products in another
order: the readings are ~2e-6), and the reference computed wrong in
each of ``laguna_jnp.CONTROLS`` is refused by the same limit with room."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna_jnp as ref
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.models.window_decoder import (FULL, SLIDING, WindowDecoder,
                                              WindowDecoderConfig)
from paddle_tpu.serving import DecodeConfig, DecodeEngine

#: float32 throughout: what differs between program and reference is
#: the order of the sums (the readings are ~2e-6)
TOL = 1e-4
CFG = WindowDecoderConfig.tiny(num_experts=8, router_experts=16,
                               held_experts=(4, 12))
#: packed prefill (<= 16) and chunked (chunks of 8); the longest
#: crosses the window by more than four pages of 4
PROMPTS = (5, 13, 30, 47)


def _engine(**kw):
    cfg = dict(block_size=4, max_seq_len=96, max_batch_size=4,
               batch_buckets=(2, 4), prefill_seq_buckets=(16,),
               prefill_batch_buckets=(1,), chain_lengths=(1, 4),
               chunk_tokens=8, prefix_cache=False)
    cfg.update(kw)
    return DecodeEngine(WindowDecoder(CFG, seed=3), DecodeConfig(**cfg))


def _model_dict():
    return dict(dataclasses.asdict(CFG), num_experts=CFG.router_experts)


def _reference_rows(weights, prompt, tokens, wrong=()):
    seq = np.concatenate([prompt, tokens])
    return np.asarray(ref.logits(weights, seq, _model_dict(),
                                 held=CFG.held_experts, wrong=wrong,
                                 q_block=seq.size))[
        prompt.size - 1:seq.size - 1]


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def served():
    """Four prompts served together, their logits, the engine's stats and
    weights (the engine closed)."""
    engine = _engine()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n) for n in PROMPTS]
    futures = [engine.generate({"src_ids": p}, max_new_tokens=20,
                               return_logits=True) for p in prompts]
    results = [f.result(timeout=600) for f in futures]
    stats = engine.stats()
    assert engine.close(timeout=60)
    weights = {n: np.asarray(engine.scope.find_var(n))
               for n in engine.scope.var_names() if not n.startswith("@")}
    return engine, prompts, results, stats, weights


@pytest.mark.parametrize("i", range(len(PROMPTS)),
                         ids=[f"prompt-{n}" for n in PROMPTS])
def test_prefill_chunks_and_chains_match_the_reference(served, i):
    _, prompts, results, _, weights = served
    want = _reference_rows(weights, prompts[i], results[i].tokens)
    assert results[i].logits.shape == want.shape
    assert _rel(results[i].logits, want) < TOL
    np.testing.assert_array_equal(results[i].tokens, want.argmax(axis=1))


def test_the_engine_took_every_path(served):
    engine, _, _, stats, _ = served
    assert stats["prefill_batches"] >= 1 and stats["chunk_steps"] >= 4 + 6
    assert stats["chain_hist"].get(4) and stats["chain_hist"].get(1)
    assert max(int(k) for k in stats["decode_batch_hist"]) >= 2
    # a ring of the window and a chunk: ceil((12 + 16 - 1) / 4) + 1 pages
    assert engine._ring_pages == 8
    assert stats["state_bytes_per_slot"] == 8 * engine.model.\
        window_block_bytes(4)


@pytest.mark.parametrize("wrong", ref.CONTROLS)
def test_the_reference_computed_wrong_is_refused(served, wrong):
    """Each control, ``window_plus_block`` and ``kv_group_mod`` among
    them, reads far above the limit on the longest request."""
    _, prompts, results, _, weights = served
    want = _reference_rows(weights, prompts[-1], results[-1].tokens,
                           wrong=(wrong,))
    assert _rel(results[-1].logits, want) > 100 * TOL


def test_logit_rows_come_to_the_host_beside_the_worker():
    """The worker hands each launch's asked-for rows to the courier; once
    it has caught up, every part is a host array, the parts cover every
    token, and closing the engine stops its thread."""
    engine = _engine()
    prompt = np.random.default_rng(1).integers(0, 256, 5)
    res = engine.generate({"src_ids": prompt}, max_new_tokens=12,
                          return_logits=True).result(timeout=600)
    engine._courier.wait()
    parts = res._logit_parts
    assert len(parts) >= 3
    assert all(isinstance(rows, np.ndarray) for rows, _ in parts)
    assert sum(n for _, n in parts) == res.tokens.size
    assert engine.close(timeout=60)
    assert engine._courier._thread is None
    assert res.logits.shape == (res.tokens.size, CFG.vocab_size)


def test_kv_gauges_and_counters(served):
    """Full blocks and ring pages in use go back to zero; the bytes held
    a live context token lie between the full layers' alone and every
    layer keeping the whole context."""
    engine, _, _, stats, _ = served
    assert stats["full_kv_blocks_in_use"] == 0
    assert stats["window_kv_blocks_in_use"] == 0
    assert stats["ctx_tokens_live"] > 0
    per_token = stats["kv_bytes_held"] / stats["ctx_tokens_live"]
    full = engine.model.cache_block_bytes(4) / 4
    every = full * CFG.num_hidden_layers / len(CFG.layers_of(FULL))
    assert full < per_token, (per_token, full)
    assert len(CFG.layers_of(SLIDING)) == 3
    assert per_token < every * 4, per_token


def test_prefix_cache_is_refused_for_a_model_with_window_rings():
    with pytest.raises(InvalidArgumentError, match="ring"):
        _engine(prefix_cache=True)


def test_a_ring_is_sized_for_the_window_and_a_launch():
    model = WindowDecoder(CFG)
    # the cell: window 512, chunks of 1 024, pages of 16
    assert WindowDecoder(WindowDecoderConfig()).window_ring_pages(
        16, 1024) == 97
    assert model.window_ring_pages(4, 16) == 8
    with pytest.raises(ValueError, match="ring_pages"):
        model.build(16, 4, 8, 1, state_slots=3)


def test_window_ring_slots_and_table():
    """Position p of slot s lives at ring page p // block % R of s's
    ring; padding stays dropped; the table maps logical pages onto it."""
    from paddle_tpu.ops.registry import get_op
    out = get_op("window_ring")(None, {
        "StateSlot": [jnp.asarray([0, 2], jnp.int32)],
        "Pos": [jnp.asarray([[0, 5, 17], [30, 31, 0]])],
        "Slots": [jnp.asarray([[3, 9, 40], [7, 8, -1]], jnp.int32)]},
        {"ring_pages": 3, "block_size": 4, "table_pages": 5})
    assert np.asarray(out["RingSlots"]).tolist() == [
        [0, 5, 1 * 4 + 1], [(6 + 1) * 4 + 2, (6 + 1) * 4 + 3, -1]]
    assert np.asarray(out["Table"]).tolist() == [[0, 1, 2, 0, 1],
                                                 [6, 7, 8, 6, 7]]


def _run_layer(held, w, x, routed_scale=CFG.moe_routed_scaling_factor):
    """The sparse layer as the program builds it, holding ``held`` of
    16 experts (the router's width), with its router's outputs."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.layer_helper import ParamAttr
    from paddle_tpu.parallel import moe_dropless_ffn
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xin = fluid.layers.data("x", shape=list(x.shape),
                                append_batch_size=False)
        out = moe_dropless_ffn(
            xin, 16, CFG.moe_intermediate_size, CFG.num_experts_per_tok,
            held_experts=held, param_attr=ParamAttr(name="L"), name="L_moe",
            scoring="softmax", routed_scale=routed_scale,
            shared_hidden=CFG.shared_expert_intermediate_size,
            counter_tag=False)
    weight = next(op.output("TopkWeight")[0]
                  for op in main.global_block().ops
                  if op.type == "moe_topk_router")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        lo, hi = held
        for name, v in w.items():
            scope.set_var(name, jnp.asarray(
                v[lo:hi] if name.startswith("L_expert_") else v))
        return exe.run(main, feed={"x": x}, fetch_list=[out, weight])


def _layer_weights(rng):
    d, f, e = CFG.hidden_size, CFG.moe_intermediate_size, 16

    def rnd(*shape):
        return (rng.normal(size=shape) * 0.3).astype(np.float32)
    return {"L_router_w": rnd(d, e), "L_expert_gate_w": rnd(e, d, f),
            "L_expert_up_w": rnd(e, d, f), "L_expert_down_w": rnd(e, f, d),
            "L_shared_gate_w": rnd(d, f), "L_shared_up_w": rnd(d, f),
            "L_shared_down_w": rnd(f, d)}, rnd(2, 12, d)


def test_softmax_router_honours_routed_scale():
    """A softmax router's renormalised top-k weights times
    ``routed_scale``; at 1.0 (``s8k``'s router) the op carries no such
    attr, so that program is the one it was."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.parallel import moe_dropless_ffn
    w, x = _layer_weights(np.random.default_rng(1))
    _, scaled = _run_layer((0, 16), w, x)
    _, plain = _run_layer((0, 16), w, x, routed_scale=1.0)
    np.testing.assert_allclose(scaled, 2.5 * plain, rtol=1e-6)
    np.testing.assert_allclose(plain.sum(axis=-1), 1.0, rtol=1e-5)
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        moe_dropless_ffn(fluid.layers.data("x", shape=[2, 4, 8],
                                           append_batch_size=False),
                         8, 16, 2, counter_tag=False)
    router = next(op for op in main.global_block().ops
                  if op.type == "moe_topk_router")
    assert "routed_scale" not in router.attrs


def test_the_shares_routed_parts_and_one_shared_expert_sum_to_the_layer():
    """The four quarters of 16 experts as the program builds the sparse
    layer (softmax top-4, routed scale, a shared expert on every chip):
    each equals the reference's share, and their routed parts with the
    shared expert once add up to the uncut layer."""
    w, x = _layer_weights(np.random.default_rng(0))
    d, e, m = CFG.hidden_size, 16, _model_dict()
    flat = jnp.asarray(x.reshape(-1, d))
    uncut = np.asarray(ref.moe_layer(w, flat, m, "L")).reshape(x.shape)
    shared = np.asarray(ref._swiglu(
        flat, w["L_shared_gate_w"], w["L_shared_up_w"],
        w["L_shared_down_w"])).reshape(x.shape)
    total = np.zeros_like(uncut)
    for i in range(4):
        held = (i * e // 4, (i + 1) * e // 4)
        part = np.asarray(_run_layer(held, w, x)[0])
        wi = dict(w, **{k: v[held[0]:held[1]] for k, v in w.items()
                        if k.startswith("L_expert_")})
        np.testing.assert_allclose(
            part, np.asarray(ref.moe_layer(wi, flat, m, "L", held=held))
            .reshape(x.shape), atol=2e-5)
        total += part - shared          # the chip's ROUTED part
    np.testing.assert_allclose(total + shared, uncut, atol=5e-5)
