"""MoE expert-parallelism probe: prove the planner's expert axis, the
priced (and quantized) expert all_to_all, and the MoE decode serving leg
on the 8-device virtual CPU mesh.

Three sections, each an acceptance contract (tests/test_moe.py runs each
live):

* **planner** — the dp8 → (dp·ep) search on the MoE BERT-tiny pretrain
  step: ``plan_sharding(max_expert=4)`` prices dense AND expert rows,
  the budget (placed between the cheapest expert row's peak and the
  cheapest dense row's peak, measured by a no-budget pass) rejects every
  dense row, the winner is an expert row, and the whole two-pass search
  spends ZERO executor compiles (monitor stat delta);
* **wire census** — the ``c_expert_alltoall`` pair priced by the op_spec
  wire channel at fp32 / bf16 / int8 (``quant_spec`` CompressionSpec
  tiers): int8 must move ≥3.5× fewer wire bytes than fp32, bf16 ≥1.9×;
* **decode** — the MoE BertDecoder through the paged-KV decode engine:
  greedy-reference token parity, then a simulated process restart over
  the persistent AOT cache with 0 fresh compiles and bit-identical
  tokens.

Usage:
    python tools/moe_probe.py
"""

import os
import sys
import tempfile


def _env8():
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _moe_bert(batch_size=8, seq_len=32):
    """MoE BERT-tiny pretrain step (dense build — the planner stamps
    ep) + its feed shapes.  Expert-dominated proportions (one layer,
    fat experts): ZeRO-3 must transiently all-gather the FULL fused
    expert weight per use while ep computes on the resident slice, so
    expert rows beat every dense row on peak HBM and a budget between
    the two families provably forces the planner onto the expert axis."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    cfg = bert.BertConfig(vocab_size=256, hidden_size=64,
                          num_hidden_layers=1, num_attention_heads=2,
                          intermediate_size=2048,
                          max_position_embeddings=64, type_vocab_size=2,
                          moe_experts=4, moe_group_size=64)
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(cfg)
        fluid.optimizer.Adam(1e-4).minimize(total)
    batch = bert.make_fake_batch(np.random.RandomState(0), cfg,
                                 batch_size=batch_size, seq_len=seq_len)
    feed_shapes = {k: (tuple(v.shape), str(v.dtype))
                   for k, v in batch.items()}
    return main_p, startup, total, feed_shapes


def probe_planner(num_devices=8):
    """The dp8 → (dp·ep) search; returns (section dict, winner plan)."""
    from paddle_tpu.framework.compiler import BuildStrategy
    from paddle_tpu.framework.shard_planner import plan_sharding
    from paddle_tpu.monitor import stat

    main_p, _startup, loss, feed_shapes = _moe_bert()
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    bs.overlap_grad_sync = True

    compiles_before = int(stat("executor_compile_count").get())
    probe = plan_sharding(main_p, num_devices, loss_name=loss.name,
                          feed_shapes=feed_shapes,
                          fetch_names=[loss.name], build_strategy=bs,
                          max_expert=4,
                          module="dp8_bert_tiny_moe4_pretrain")
    priced = [c for c in probe.configs
              if c.peak_bytes is not None and not c.error]
    expert_peaks = [c.peak_bytes for c in priced if c.layout.expert > 1]
    dense_peaks = [c.peak_bytes for c in priced if c.layout.expert == 1]
    assert expert_peaks and dense_peaks, \
        "expert search dimension not live"
    assert min(expert_peaks) < min(dense_peaks), \
        "expert rows do not beat dense rows on peak HBM — the budget " \
        "gate cannot separate them"
    budget_gb = round((min(expert_peaks) + min(dense_peaks)) / 2
                      / float(1 << 30), 9)
    plan = plan_sharding(main_p, num_devices, loss_name=loss.name,
                         feed_shapes=feed_shapes, fetch_names=[loss.name],
                         hbm_budget_gb=budget_gb, build_strategy=bs,
                         max_expert=4,
                         module="dp8_bert_tiny_moe4_pretrain")
    compile_delta = int(stat("executor_compile_count").get()) \
        - compiles_before

    d = plan.as_dict()
    priced2 = [c for c in plan.configs
               if c.est is not None and not c.error]
    dense2 = [c for c in priced2 if c.layout.expert == 1]
    assert len(priced2) >= 6, f"only {len(priced2)} configs priced"
    assert {c.layout.expert for c in priced2} >= {1, 2, 4}, \
        "expert degrees {1,2,4} not all priced"
    assert plan.winner is not None and plan.winner.fits
    assert plan.winner.layout.expert > 1, \
        f"winner is a dense row (ep={plan.winner.layout.expert})"
    assert dense2 and all(not c.fits for c in dense2), \
        "a dense row fit the expert-sized budget — gate not exercised"
    assert compile_delta == 0, \
        f"{compile_delta} compiles attempted during the plan search"
    return {
        "module": "dp8_bert_tiny_moe4_pretrain",
        "budget_gb": budget_gb,
        "configs_priced": len(priced2),
        "expert_degrees_priced": sorted({c.layout.expert
                                         for c in priced2}),
        "dense_rows_rejected": len(dense2),
        "winner": {"data": plan.winner.layout.data,
                   "fsdp": plan.winner.layout.fsdp,
                   "tp": plan.winner.layout.tp,
                   "pipe": plan.winner.layout.pipe,
                   "expert": plan.winner.layout.expert},
        "compile_count_delta": compile_delta,
        "plan": d,
    }


def probe_wire_census(ep=4):
    """The expert exchange priced by the op_spec wire channel at the
    fp32 / bf16 / int8 CompressionSpec tiers."""
    from paddle_tpu.framework.memory_analysis import \
        collective_wire_summary
    from paddle_tpu.framework.mesh_layout import MeshLayout
    from paddle_tpu.parallel import apply_expert_sharding

    layout = MeshLayout(data=8 // ep, expert=ep)
    mesh_axes = dict(layout.sizes)
    tiers = {"fp32": None, "bf16": "bfloat16", "int8": "int8"}
    rows = {}
    for label, spec in tiers.items():
        main_p, _startup, _loss, feed_shapes = _moe_bert()
        rep = apply_expert_sharding(main_p, layout, quant_spec=spec)
        assert rep["rewritten"], "expert rewrite inserted no exchanges"
        summary = collective_wire_summary(
            main_p, feed_shapes=feed_shapes, mesh_axes=mesh_axes,
            batch_axis=layout.batch_axes)
        row = summary["by_op"].get("c_expert_alltoall")
        assert row and row["wire_bytes"] > 0, \
            f"{label}: expert all_to_all not priced by the wire channel"
        rows[label] = dict(row)
    for label in ("bf16", "int8"):
        rows[label]["compression_vs_fp32"] = round(
            rows["fp32"]["wire_bytes"] / rows[label]["wire_bytes"], 3)
    assert rows["int8"]["compression_vs_fp32"] >= 3.5, \
        f"int8 expert a2a only {rows['int8']['compression_vs_fp32']}x " \
        f"fewer wire bytes than fp32 (need >=3.5)"
    assert rows["bf16"]["compression_vs_fp32"] >= 1.9, \
        f"bf16 expert a2a only {rows['bf16']['compression_vs_fp32']}x"
    # each routed block carries a dispatch + combine exchange pair (both
    # directions — fwd a2a + transposed bwd a2a — priced inside each
    # op's wire entry)
    assert rows["fp32"]["count"] >= 2, rows["fp32"]["count"]
    return {"expert_degree": ep, "tiers": rows}


def probe_decode():
    """MoE decode serving: greedy parity + AOT warm restart with 0
    fresh compiles (simulated process restart, same cache dir)."""
    import numpy as np
    from paddle_tpu.flags import get_flags, set_flags
    from paddle_tpu.models.bert import BertConfig
    from paddle_tpu.models.decoder import BertDecoder
    from paddle_tpu.monitor import stat
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    cfg = BertConfig(vocab_size=512, hidden_size=64, num_hidden_layers=1,
                     num_attention_heads=2, intermediate_size=128,
                     max_position_embeddings=64, type_vocab_size=2,
                     initializer_range=0.5, moe_experts=4)

    def _model():
        return BertDecoder(cfg, name="moe_decoder", seed=3)

    def _config():
        return DecodeConfig(block_size=4, max_seq_len=32,
                            max_batch_size=2, prefill_seq_buckets=(8,),
                            prefill_batch_buckets=(1,),
                            pack_max_segments=1, max_new_tokens=4)

    rng = np.random.RandomState(17)
    prompts = [rng.randint(0, 512, (n,)).astype(np.int64)
               for n in (5, 7)]

    def run_once():
        eng = DecodeEngine(_model(), _config())
        try:
            c0 = int(stat("executor_compile_count").get())
            combos = eng.warmup()
            fresh_warm = int(stat("executor_compile_count").get()) - c0
            toks = []
            for p in prompts:
                res = eng.generate({"src_ids": p},
                                   max_new_tokens=4).result(timeout=300)
                ref = eng.greedy_reference({"src_ids": p},
                                           max_new_tokens=4)
                assert np.array_equal(res.tokens, ref.tokens), \
                    "MoE decode diverged from the greedy reference"
                toks.append(res.tokens.tolist())
            fresh_total = int(stat("executor_compile_count").get()) - c0
        finally:
            eng.shutdown()
        return combos, fresh_warm, fresh_total, toks

    keep = get_flags(["aot_cache_dir"])
    tmp = tempfile.mkdtemp(prefix="moe_probe_aot_")
    set_flags({"aot_cache_dir": tmp})
    try:
        combos, cold_fresh, _cold_total, cold_toks = run_once()
        assert cold_fresh >= combos, "cold start traced nothing"
        warm_combos, warm_fresh, warm_total, warm_toks = run_once()
    finally:
        set_flags(keep)
    assert warm_combos == combos
    assert warm_fresh == 0, \
        f"MoE decode warm restart paid {warm_fresh} fresh compiles"
    assert warm_total == 0, \
        "live MoE decode traffic after warmup paid a compile"
    assert cold_toks == warm_toks, \
        "warm-restart tokens differ from the cold run"
    return {"model": "moe_decoder(E=4,top_k=2)",
            "executable_grid": combos,
            "cold_fresh_compiles": cold_fresh,
            "warm_fresh_compiles": warm_fresh,
            "greedy_parity": True,
            "tokens": cold_toks}


def main(argv):
    _env8()
    planner = probe_planner()
    census = probe_wire_census()
    decode = probe_decode()
    w = planner["winner"]
    print(f"moe probe OK: {planner['configs_priced']} configs priced, "
          f"winner dp={w['data']} fsdp={w['fsdp']} ep={w['expert']}, "
          f"{planner['dense_rows_rejected']} dense rows rejected, "
          f"int8 a2a {census['tiers']['int8']['compression_vs_fp32']}x "
          f"vs fp32, decode warm restart "
          f"{decode['warm_fresh_compiles']} fresh compiles")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
