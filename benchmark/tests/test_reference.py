"""The plain jnp reference against the program at BertConfig.tiny(), on
the CPU (float32 both sides, so the decoder must agree exactly on the
argmax and the trainer to bf16 rounding)."""

import dataclasses

import numpy as np
import pytest

from benchmark.reference import bert_jnp


@pytest.fixture(scope="module")
def tiny():
    from paddle_tpu.models.bert import BertConfig
    return dataclasses.replace(BertConfig.tiny(), hidden_dropout_prob=0.0,
                               attention_probs_dropout_prob=0.0)


def test_trainer_loss_and_gradients_match(tiny):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.models import bert
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, total, _, _ = bert.build_pretrain_network(tiny)
        decorate(fluid.optimizer.Adam(1e-4),
                 use_pure_bf16=True).minimize(total)
    exe = fluid.Executor(fluid.TPUPlace(0))
    batch = bert.make_fake_batch(np.random.RandomState(0), tiny,
                                 batch_size=4, seq_len=32, num_masks=4)
    names = ["encoder_layer_0_qkv_w", "encoder_layer_1_ffn2_w"]
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        w = {n: np.asarray(scope.find_var(n)) for n in scope.var_names()
             if not n.startswith("@")}
        out = exe.run(main, feed=batch,
                      fetch_list=[total] + [n + "@GRAD" for n in names])
    loss, grads = bert_jnp.pretrain_loss_and_grads(
        w, batch, names, n_layer=2, n_head=2, eps=1e-5)
    assert abs(float(out[0]) - float(loss)) / float(loss) < 5e-3
    for n, g in zip(names, out[1:]):
        ref = np.asarray(grads[n])
        err = np.linalg.norm(np.asarray(g, np.float32) - ref) \
            / np.linalg.norm(ref)
        assert err < 5e-2, (n, err)
    # the tolerance is not slack: a reference that drops the last position
    # of every sequence from attention is far outside it
    broken = dict(batch, input_mask=batch["input_mask"].copy())
    broken["input_mask"][:, -1, :] = 0.0
    loss2, grads2 = bert_jnp.pretrain_loss_and_grads(
        w, broken, names, n_layer=2, n_head=2, eps=1e-5)
    ref = np.asarray(grads2[names[0]])
    err = np.linalg.norm(np.asarray(out[1], np.float32) - ref) \
        / np.linalg.norm(ref)
    assert err > 5e-2


def test_decoder_served_through_the_cache_matches_teacher_forcing(tiny):
    from paddle_tpu.models.decoder import BertDecoder
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    cfg = dataclasses.replace(tiny, initializer_range=0.5)
    engine = DecodeEngine(BertDecoder(cfg, seed=3), DecodeConfig(
        block_size=8, max_seq_len=96, max_batch_size=4,
        prefill_seq_buckets=(16, 32), chain_lengths=(1, 4),
        prefix_cache=True, chunk_tokens=32))
    try:
        import jax.numpy as jnp
        w = {n: jnp.array(engine._scope.find_var(n), copy=True)
             for n in engine._scope.var_names()
             if n not in set(engine.model.cache_var_names())
             and not n.startswith("@")}
        rng = np.random.default_rng(0)
        shared = rng.integers(0, cfg.vocab_size, 40)
        prompts = [rng.integers(0, cfg.vocab_size, 12),      # packed prefill
                   shared,                                   # chunked
                   np.concatenate([shared, rng.integers(
                       0, cfg.vocab_size, 9)])]              # prefix hit
        outs = []
        for p in prompts:       # one after the other: the third one hits
            outs.append(engine.generate({"src_ids": p},
                                        max_new_tokens=10).result(60))
        assert engine.stats()["prefix_hits"] > 0
        for p, res in zip(prompts, outs):
            seq = np.zeros(96, np.int64)
            seq[:p.size] = p
            seq[p.size:p.size + 10] = res.tokens
            logits = np.asarray(bert_jnp.decoder_logits(
                w, seq, n_layer=2, n_head=2, eps=1e-5))
            rows = logits[p.size - 1:p.size + 9]
            gaps = rows.max(axis=1) - rows[np.arange(10), res.tokens]
            assert gaps.max() <= 1e-3, gaps
            # a dropped position is seen: score the same tokens with the
            # prompt's first token removed and the gaps open wide
            shifted = np.zeros(96, np.int64)
            shifted[:p.size + 9] = seq[1:p.size + 10]
            rows = np.asarray(bert_jnp.decoder_logits(
                w, shifted, n_layer=2, n_head=2,
                eps=1e-5))[p.size - 2:p.size + 8]
            gaps = rows.max(axis=1) - rows[np.arange(10), res.tokens]
            assert gaps.max() > 1e-3
    finally:
        engine.shutdown(drain=False)
