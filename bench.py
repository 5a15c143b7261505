"""Headline benchmark: BERT-base pretrain throughput on one TPU chip
(BASELINE config 3, the north-star metric).  Runs on the chip only: with
no TPU it exits non-zero and prints no metric.

Prints ONE JSON line:
  {"metric": ..., "platform": "tpu", "device_kind": ..., "device_count": N,
   "value": N, "unit": ..., "vs_baseline": N, ...}

vs_baseline = measured model FLOP utilisation / 0.35 (the BASELINE.json MFU
target), so 1.0 means the north-star efficiency target is met on-chip; the
peak FLOP/s comes from observability/flops.py's table, keyed by device_kind.
"""

import json
import time

import numpy as np


def bert_flops_per_step(cfg, batch, seq, num_masks):
    """Analytic matmul FLOPs for one fwd+bwd step (2 flops per MAC; bwd
    costs 2x fwd for GEMMs)."""
    d = cfg.hidden_size
    ff = cfg.intermediate_size
    tokens = batch * seq
    per_layer = 2 * tokens * (d * 3 * d          # qkv proj
                              + d * d            # attn out proj
                              + 2 * d * ff)      # ffn
    attn = 2 * batch * cfg.num_attention_heads * seq * seq * \
        (d // cfg.num_attention_heads) * 2       # QK^T and PV
    heads = 2 * (batch * num_masks) * d * cfg.vocab_size \
        + 2 * batch * d * d
    fwd = cfg.num_hidden_layers * (per_layer + attn) + heads
    return 3 * fwd


def main():
    from paddle_tpu.flags import enable_compile_cache
    from paddle_tpu.framework.core import require_tpu
    from paddle_tpu.observability.flops import device_peak_flops
    device = require_tpu()          # no TPU: non-zero exit, no metric
    enable_compile_cache()

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    # BASELINE config 3
    batch, seq, num_masks = 96, 128, 20
    cfg = bert.BertConfig.base()

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(cfg)
        from paddle_tpu.contrib.mixed_precision import decorate
        opt = decorate(fluid.optimizer.Adam(1e-4), use_pure_bf16=True)
        opt.minimize(total)

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    rng = np.random.RandomState(0)
    data = bert.make_fake_batch(rng, cfg, batch_size=batch, seq_len=seq,
                                num_masks=num_masks)
    # Freeze the feed buffers: the executor's feed cache keeps the device
    # copy resident across runs (no per-step H2D re-transfer), exactly how
    # a production loop feeds via the double-buffered DataLoader.
    for v in data.values():
        if hasattr(v, "flags"):
            v.flags.writeable = False
    # warmup (compile) + one steady-state step, fully synced
    l, = exe.run(main_prog, feed=data, fetch_list=[total])
    assert np.isfinite(l).all()
    l, = exe.run(main_prog, feed=data, fetch_list=[total])
    steps = 30
    # Pipelined timing: fetches stay device-resident inside the window
    # (return_numpy=False) so step N+1 dispatches while N computes; the
    # window closes only after the LAST step's loss is materialised on
    # host, which transitively waits for every prior step (the state
    # buffers chain through donation).
    t0 = time.perf_counter()
    for _ in range(steps):
        l, = exe.run(main_prog, feed=data, fetch_list=[total],
                     return_numpy=False)
    l_host = np.asarray(l)
    import jax
    jax.block_until_ready(list(fluid.global_scope().vars.values()))
    dt = (time.perf_counter() - t0) / steps
    assert np.isfinite(l_host).all()

    # pure-step split: the same compiled step driven with device-resident
    # feeds and no executor path — the compute ceiling the executor
    # overhead is measured against
    compiled = exe._compile(main_prog, dict(data), [total.name],
                            fluid.global_scope(), None, (), None)
    feed_dev = {k: jax.device_put(np.ascontiguousarray(v))
                for k, v in data.items()}
    scope = fluid.global_scope()
    state = {n: jax.device_put(np.asarray(scope.find_var(n)))
             for n in compiled.state_in_names}
    key = jax.random.PRNGKey(0)
    fetches, state, key = compiled.fn(feed_dev, state, key)
    jax.block_until_ready(fetches)
    t0 = time.perf_counter()
    for _ in range(steps):
        fetches, state, key = compiled.fn(feed_dev, state, key)
    jax.block_until_ready(fetches)
    dt_pure = (time.perf_counter() - t0) / steps

    # --- streamed: a FRESH batch every step through the DataLoader
    # device double-buffer — the steady-state TRAINING number (the
    # cached number above is the framework ceiling; a real run pays the
    # per-step feed path, overlapped H2D and all, like the reference's
    # buffered_reader.cc:92 side-stream staging).  Batches
    # are pre-generated host arrays (data synthesis excluded, transfer
    # included) and left WRITABLE so the feed device cache cannot elide
    # the H2D copy.
    from paddle_tpu.dataloader import DataLoader
    n_distinct = min(steps, 8)
    batches = [bert.make_fake_batch(rng, cfg, batch_size=batch,
                                    seq_len=seq, num_masks=num_masks)
               for _ in range(n_distinct)]

    def batch_gen():
        for i in range(steps + 1):   # +1 warmup step
            yield batches[i % n_distinct]

    loader = DataLoader.from_generator(capacity=8, use_double_buffer=True)
    loader.set_batch_generator(batch_gen, places=fluid.TPUPlace(0))
    it = iter(loader)
    l, = exe.run(main_prog, feed=next(it), fetch_list=[total])  # warmup
    assert np.isfinite(l).all()
    t0 = time.perf_counter()
    n_done = 0
    for fb in it:
        l, = exe.run(main_prog, feed=fb, fetch_list=[total],
                     return_numpy=False)
        n_done += 1
    l_host = np.asarray(l)
    jax.block_until_ready(list(fluid.global_scope().vars.values()))
    dt_streamed = (time.perf_counter() - t0) / n_done
    assert np.isfinite(l_host).all()

    flops = bert_flops_per_step(cfg, batch, seq, num_masks)
    peak = device_peak_flops()      # table keyed by device_kind
    mfu_streamed = flops / dt_streamed / peak
    print(json.dumps({
        "metric": "bert_base_pretrain_samples_per_sec_per_chip",
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
        # headline = the training case (streamed fresh batches)
        "value": round(batch / dt_streamed, 2),
        "unit": "samples/s",
        "vs_baseline": round(mfu_streamed / 0.35, 4),
        "ms_per_step": round(dt_streamed * 1e3, 2),
        "cached_samples_per_sec": round(batch / dt, 2),
        "cached_ms_per_step": round(dt * 1e3, 2),
        "cached_mfu": round(flops / dt / peak, 4),
        "pure_step_ms": round(dt_pure * 1e3, 2),
        "pure_mfu": round(flops / dt_pure / peak, 4),
    }))


if __name__ == "__main__":
    main()
