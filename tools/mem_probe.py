#!/usr/bin/env python
"""mem_probe — validate the static peak-HBM estimator against XLA.

For each leg, builds the training program, runs the static analyzer
(framework/memory_analysis.py — no trace, no device), then compiles the
REAL step and reads XLA's ground truth via
``jit(...).lower().compile().memory_analysis()``; the per-leg relative
error of ``estimate.peak_bytes`` against XLA's
``argument_size_in_bytes + temp_size_in_bytes`` (donated outputs alias
their arguments, so args+temp IS the per-device live peak) must sit
inside the tolerance band (tests/test_memory_analysis.py asserts it
live on the smallest rung and the two mesh legs).

Legs:
  * the transformer-bench ladder (TransformerConfig.tiny at the
    bucketed (seq, batch) rungs the CPU bench runs) — exercises the
    residual-class collapse, the attention/softmax op-internal
    accounting and the 1.5× cotangent factor at five activation scales;
  * dp8        — an MLP under a dp=8 mesh with per-leaf grad all-reduce:
    per-device feed sharding + the collective in/out grad term;
  * dp8_zero1  — the same MLP under ZeRO-1 (strategy.sharded_update):
    1/n flat optimizer-state shards via dist_attr, reduce-scatter
    output-shard accounting.

Usage:
  python tools/mem_probe.py                     # all legs
  MP_LADDER=8x4,16x4 python tools/mem_probe.py  # subset of rungs
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"
                           ).strip()

TOLERANCE = 0.15
DEFAULT_LADDER = ((8, 4), (16, 4), (32, 4), (32, 8), (64, 8))


def _xla_ground_truth(exe, program, feed, fetch_names, scope, mesh=None,
                      axis_names=(), batch_axis=None, feed_specs=None):
    """Compile the real step and read CompiledMemoryStats (per device —
    the compiled module is the per-device SPMD program, so argument
    sizes already reflect sharding)."""
    import jax
    import paddle_tpu.fluid as fluid
    with fluid.scope_guard(scope):
        step = exe._compile(program, feed, fetch_names, scope, mesh,
                            axis_names, batch_axis,
                            feed_specs=feed_specs or {})
        state = {n: np.asarray(scope.find_var(n))
                 for n in step.state_in_names}
        key = jax.random.PRNGKey(0)
        compiled = step.fn.lower({k: feed[k] for k in step.feed_names},
                                 state, key).compile()
        ma = compiled.memory_analysis()
    return {"argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes)}


def _leg_result(name, est, xla):
    gt = xla["argument_bytes"] + xla["temp_bytes"]
    rel = est.peak_bytes / gt - 1.0 if gt else 0.0
    return {
        "leg": name,
        "estimate_bytes": est.peak_bytes,
        "estimate": est.as_dict(),
        "xla": xla,
        "xla_arg_plus_temp_bytes": gt,
        "rel_err": round(rel, 4),
        "within_tolerance": abs(rel) <= TOLERANCE,
    }


def ladder_leg(bucket, batch):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.core import reset_default_programs
    from paddle_tpu.framework.memory_analysis import analyze_memory
    from paddle_tpu.models import transformer

    reset_default_programs()
    cfg = transformer.TransformerConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        feeds, loss, logits = transformer.build_train_network(cfg)
        fluid.optimizer.Adam(1e-4).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    src = [list(rng.randint(3, 100, min(bucket - 2, cfg.max_length - 2)))
           for _ in range(batch)]
    trg = [list(rng.randint(3, 100, min(bucket - 3, cfg.max_length - 3)))
           for _ in range(batch)]
    feed = {k: np.asarray(v) for k, v in transformer.make_batch(
        src, trg, cfg, bucket_ladder=(bucket,)).items()}
    with fluid.scope_guard(scope):
        exe.run(startup)
    est = analyze_memory(main, feed_shapes=feed, fetch_names=[loss.name])
    xla = _xla_ground_truth(exe, main, feed, [loss.name], scope)
    return _leg_result(f"transformer_ladder_{bucket}x{batch}", est, xla)


def _build_mlp_dp8(sharded):
    import jax
    import paddle_tpu.fluid as fluid
    from jax.sharding import Mesh
    from paddle_tpu.distributed.fleet import (DistributedStrategy,
                                              UserDefinedRoleMaker,
                                              distributed_optimizer, fleet)
    from paddle_tpu.framework.core import (Program, program_guard,
                                           reset_default_programs)

    reset_default_programs()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[256])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 512, act="relu", bias_attr=False)
        h2 = fluid.layers.fc(h, 512, act="relu", bias_attr=False)
        pred = fluid.layers.fc(h2, 32, act="softmax", bias_attr=False)
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fleet.init(UserDefinedRoleMaker(0, 1))
        strategy = DistributedStrategy()
        mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
        strategy.mesh = mesh
        strategy.sharded_update = sharded
        opt = distributed_optimizer(fluid.optimizer.Adam(5e-3), strategy)
        opt.minimize(loss)
    return fleet.main_program, startup, loss, mesh


def multichip_leg(sharded):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.memory_analysis import (analyze_memory,
                                                      mesh_axes_of)

    prog, startup, loss, mesh = _build_mlp_dp8(sharded)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(256, 256).astype(np.float32),
            "label": rng.randint(0, 32, (256, 1)).astype(np.int64)}
    with fluid.scope_guard(scope):
        exe.run(startup)
    est = analyze_memory(prog, feed_shapes=feed, fetch_names=[loss.name],
                         mesh_axes=mesh_axes_of(mesh), batch_axis="dp")
    xla = _xla_ground_truth(exe, prog, feed, [loss.name], scope, mesh,
                            ("dp",), "dp")
    return _leg_result("dp8_zero1" if sharded else "dp8", est, xla)


def run_probe(ladder=DEFAULT_LADDER):
    legs = [ladder_leg(b, n) for b, n in ladder]
    legs.append(multichip_leg(sharded=False))
    legs.append(multichip_leg(sharded=True))
    worst = max(abs(l["rel_err"]) for l in legs)
    return {
        "metric": "static_peak_hbm_estimate_vs_xla",
        "definition": "static analyzer peak_bytes vs XLA "
                      "memory_analysis argument+temp bytes per leg "
                      "(per-device, CPU backend ground truth)",
        "tolerance": TOLERANCE,
        "worst_abs_rel_err": round(worst, 4),
        "all_within_tolerance": all(l["within_tolerance"] for l in legs),
        "legs": legs,
    }


def main():
    ladder = DEFAULT_LADDER
    env = os.environ.get("MP_LADDER")
    if env:
        ladder = tuple(tuple(int(p) for p in rung.split("x"))
                       for rung in env.split(","))
    art = run_probe(ladder)
    for leg in art["legs"]:
        mark = "OK " if leg["within_tolerance"] else "FAIL"
        print(f'{mark} {leg["leg"]:32s} est={leg["estimate_bytes"]:>12d} '
              f'xla(arg+temp)={leg["xla_arg_plus_temp_bytes"]:>12d} '
              f'rel={leg["rel_err"]:+.3f}')
    print(f'worst |rel_err| = {art["worst_abs_rel_err"]:.3f} '
          f'(tolerance ±{TOLERANCE})')
    return 0 if art["all_within_tolerance"] else 1


if __name__ == "__main__":
    sys.exit(main())
