"""A/B bench of the Pallas kernel families at bench shapes (VERDICT r3
next-round #2): flash attention and the fused LN/add-LN/bias-GELU
kernels, flag on vs off, same window, same methodology as bench.py
(device-resident feeds, pipelined dispatch, one final sync).

Emits one JSON line per configuration and a JSON artifact
(``KERNEL_AB_r14.json``) carrying every row — the same probe-tool
contract as serve_bench/obs_probe/plan_probe.

``--selftest`` is the CPU-safe preflight leg: BERT-tiny shapes, few
steps, Pallas kernels running through their interpret-mode/jnp
fallbacks — it asserts every flag configuration trains to a finite
loss and the artifact schema holds, without claiming speedups (CPU
relative timings are framework noise; the full run on a real chip is
what measures the kernels).

Run on the real chip: python tools/kernel_ab.py [steps] [--json out]
Preflight:            python tools/kernel_ab.py --selftest
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ARTIFACT = "KERNEL_AB_r14.json"

CONFIGS = (
    ("baseline (no pallas)", False, False),
    ("+flash_attention", True, False),
    ("+fused_ln", False, True),
    ("both (bench default)", True, True),
)


def bench_config(flash, fused, steps, tiny=False):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.framework.core import reset_default_programs
    from paddle_tpu.framework.executor import global_scope

    reset_default_programs()
    global_scope().drop_all()
    fluid.set_flags({"FLAGS_use_flash_attention": flash,
                     "FLAGS_use_pallas_fused": fused})

    if tiny:
        batch, seq, num_masks = 4, 64, 3
        cfg = bert.BertConfig.tiny()
    else:
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
    data = bert.make_fake_batch(np.random.RandomState(0), cfg,
                                batch_size=batch, seq_len=seq,
                                num_masks=num_masks)
    for v in data.values():
        if hasattr(v, "flags"):
            v.flags.writeable = False
    l, = exe.run(main_prog, feed=data, fetch_list=[total])   # compile
    assert np.isfinite(l).all()
    l, = exe.run(main_prog, feed=data, fetch_list=[total],
                 return_numpy=False)
    jax.block_until_ready(l)
    t0 = time.perf_counter()
    for _ in range(steps):
        l, = exe.run(main_prog, feed=data, fetch_list=[total],
                     return_numpy=False)
    loss = float(np.asarray(l).reshape(()))
    jax.block_until_ready(list(global_scope().vars.values()))
    dt = (time.perf_counter() - t0) / steps
    return batch / dt, dt * 1e3, loss


def run(steps, tiny=False, out_path=ARTIFACT):
    import jax
    rows = []
    for name, flash, fused in CONFIGS:
        sps, ms, loss = bench_config(flash, fused, steps, tiny=tiny)
        row = {"config": name, "use_flash_attention": flash,
               "use_pallas_fused": fused,
               "samples_per_sec": round(sps, 2),
               "ms_per_step": round(ms, 2), "final_loss": loss}
        rows.append(row)
        print(json.dumps(row))
    artifact = {
        "artifact": "KERNEL_AB",
        "revision": "r14",
        "mode": "selftest" if tiny else "bench",
        "model": "bert_tiny" if tiny else "bert_base",
        "steps": steps,
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", None),
        "configs": rows,
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {out_path}")
    return artifact


def cross_lower_flag_ladder():
    """Cross-lower the BERT-tiny seq-128 step for TPU per flag config
    (ops.pallas.lowering_target) and census the Pallas kernel names in
    each module — the A/B flags must actually ADD/REMOVE tpu_custom_call
    kernels, not just toggle a python branch.  Returns per-config kernel
    sets (also recorded in the artifact)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.core import reset_default_programs
    from paddle_tpu.framework.executor import global_scope
    from paddle_tpu.framework.export import lower_train_step_for_tpu
    from paddle_tpu.models import bert

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from verify_lowering import kernel_counts

    rows = {}
    for name, flash, fused in CONFIGS:
        reset_default_programs()
        global_scope().drop_all()
        fluid.set_flags({"FLAGS_use_flash_attention": flash,
                         "FLAGS_use_pallas_fused": fused})
        cfg = bert.BertConfig.tiny()
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            feeds, total, mlm, nsp = bert.build_pretrain_network(cfg)
            fluid.optimizer.Adam(1e-4).minimize(total)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            data = bert.make_fake_batch(np.random.RandomState(0), cfg,
                                        batch_size=4, seq_len=128,
                                        num_masks=3)
            exported = lower_train_step_for_tpu(main_prog, data, [total],
                                                scope=scope)
        rows[name] = sorted(kernel_counts(exported.mlir_module()))
    fluid.set_flags({"FLAGS_use_flash_attention": True,
                     "FLAGS_use_pallas_fused": True})
    return rows


def selftest():
    """Preflight gate (CPU-safe): every Pallas flag configuration must
    train BERT-tiny to a finite loss through the interpret/jnp fallback
    paths, the artifact must carry one well-formed row per config, AND
    the TPU cross-lowering of each config must prove the flags gate the
    kernels in/out of the compiled module."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    art = run(steps=2, tiny=True, out_path=None)
    ok = len(art["configs"]) == len(CONFIGS) and all(
        np.isfinite(r["final_loss"]) and r["ms_per_step"] > 0
        for r in art["configs"])
    losses = {r["final_loss"] for r in art["configs"]}
    # the flag ladder changes kernels, not the model: losses agree
    # loosely (flash/fused run different numerics, so not bitwise)
    spread = max(losses) - min(losses)
    ok = ok and spread < 1e-2

    ladder = cross_lower_flag_ladder()
    base = set(ladder["baseline (no pallas)"])
    flash = set(ladder["+flash_attention"])
    fused = set(ladder["+fused_ln"])
    both = set(ladder["both (bench default)"])
    ok = ok and not base                     # flags off → NO pallas calls
    # seq 128 is one tile: fused_attention lowers to the one-tile pair
    # (attn_tile_fwd / attn_tile_bwd), not the blockwise flash_* three
    ok = ok and {"attn_tile_fwd", "attn_tile_bwd"} <= flash
    ok = ok and {"fused_layer_norm_fwd", "fused_layer_norm_bwd"} <= fused
    ok = ok and (flash | fused) <= both
    art["cross_lowered_kernels"] = ladder
    with open(ARTIFACT, "w") as f:
        json.dump(art, f, indent=1)
    print(f"wrote {ARTIFACT}")
    print(f"kernel_ab selftest {'OK' if ok else 'FAILED'} "
          f"(loss spread {spread:.2e}; cross-lowered kernel ladder "
          f"{ {k: len(v) for k, v in ladder.items()} })")
    return 0 if ok else 1


def main():
    argv = sys.argv[1:]
    if "--selftest" in argv:
        sys.exit(selftest())
    out_path = ARTIFACT
    if "--json" in argv:
        i = argv.index("--json")
        out_path = argv[i + 1]
        del argv[i:i + 2]
    steps = int(argv[0]) if argv else 20
    run(steps, tiny=False, out_path=out_path)


if __name__ == "__main__":
    main()
