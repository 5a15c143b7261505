"""Chip-free lowering verification.

Cross-lowers the ``s128`` cell's BERT training step for ``platforms=("tpu",)``
on the CPU host via jax.export and asserts, from the StableHLO text alone:

  * the kernels of the routes the static report says the program takes
    are present as ``tpu_custom_call``s, and no others,
  * every state buffer is donated (``tf.aliasing_output``),
  * the step is ONE executable and same-shape fresh batches do not
    recompile.

This proves the perf-critical kernels and donation really reach the
lowered TPU program with no TPU attached; that Mosaic compiles them on a
chip is chip_smoke.py's check.
"""

import os
import re

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.framework.export import lower_train_step_for_tpu
from paddle_tpu.models import bert


def _build_pretrain(cfg):
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(cfg)
        from paddle_tpu.contrib.mixed_precision import decorate
        opt = decorate(fluid.optimizer.Adam(1e-4), use_pure_bf16=True)
        opt.minimize(total)
    return main_prog, startup, total


@pytest.fixture(scope="module")
def bench_step():
    """The ``bert_pretrain.s128`` cell's model and optimizer, cross-lowered
    for TPU: ``(exported, program, feed)``.

    Cell shapes (batch 96, seq 128) with a 2-layer config: layers share
    shapes, so kernel presence/donation are identical to the 12-layer
    module while tracing stays fast on the CPU CI host."""
    cfg = bert.BertConfig.base()
    cfg.num_hidden_layers = 2
    main_prog, startup, total = _build_pretrain(cfg)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        data = bert.make_fake_batch(rng, cfg, batch_size=96, seq_len=128,
                                    num_masks=20)
        exported = lower_train_step_for_tpu(main_prog, data, [total],
                                            scope=scope)
    return exported, main_prog, data


@pytest.fixture(scope="module")
def lowered_bench_step(bench_step):
    return bench_step[0]


def test_platform_is_tpu(lowered_bench_step):
    assert tuple(lowered_bench_step.platforms) == ("tpu",)


def test_pallas_kernels_present(bench_step):
    """The module holds exactly the kernels of the routes the static
    report says the program takes (the route table's ``kernels=``): at
    seq 128 the one-tile attention pair and fused LayerNorm, none of the
    blockwise flash kernels, no kernel for the Adam update."""
    from paddle_tpu.framework.analysis import kernel_routing_report
    exported, main_prog, data = bench_step
    txt = exported.mlir_module()
    names = set(re.findall(r'kernel_name = "(\w+)"', txt))
    report = kernel_routing_report(
        main_prog, feed_shapes={k: np.asarray(v) for k, v in data.items()},
        backend="tpu")
    hit = {r["kernel"] for r in report["rows"] if r["route"] == "pallas"}
    assert hit == {"attention_tile", "fused_layer_norm"}
    assert names == {k for r in report["rows"] for k in r["kernels"]}


def test_fluid_op_scopes_and_kernel_names_in_op_metadata(lowered_bench_step):
    """``run_ops`` lowers each Fluid op under ``jax.named_scope(op.type)``
    and every ``pl.pallas_call`` passes ``name=``: the lowered step's
    location metadata (the HLO ``op_name``) says which Fluid op an
    instruction came from, through forward, ``jvp`` and ``transpose``
    wrapping, and attention's forward and backward kernels are told apart."""
    txt = lowered_bench_step.mlir_module()
    op_names = set(re.findall(r'loc\("(jit\(step\)/[^"]*)"', txt))
    assert any("layer_norm" in n for n in op_names)
    assert any(re.search(r"transpose\(jvp\(layer_norm\)\)", n)
               for n in op_names), "backward of layer_norm not attributed"
    # the one-tile kernels are jitted (a model's layers share one traced
    # and lowered body): the call sites carry the Fluid op's scope, forward
    # and backward apart, the shared bodies the kernels' own names
    assert "jit(step)/jvp(fused_attention)/jit(tile_fwd)" in op_names
    assert "jit(step)/transpose(jvp(fused_attention))/jit(tile_bwd)" \
        in op_names
    locs = set(re.findall(r'loc\("([^"]*)"', txt))
    assert {"attn_tile_fwd/pallas_call", "attn_tile_bwd/pallas_call"} <= locs
    # two layers, ONE body each
    assert len(re.findall(r"func\.func private @tile_fwd", txt)) == 1
    assert len(re.findall(r"func\.func private @tile_bwd", txt)) == 1
    # no head split / merge around the kernels any more
    assert not any("fused_attention" in n and n.endswith("/transpose")
                   for n in op_names)
    # matmuls land under the op that asked for them
    assert "jit(step)/jvp(mul)/dot_general" in op_names
    assert "jit(step)/transpose(jvp(matmul))/dot_general" in op_names


def test_all_gemms_pure_bf16(lowered_bench_step):
    """Every dot in the pure-bf16 step must have bf16×bf16 operands —
    jax's native dot transpose used to feed f32 cotangents into the
    backward GEMMs (24 of 37 dots mixed f32×bf16 before the mxu_matmul
    custom vjp), forfeiting bf16 MXU throughput on ~2/3 of the FLOPs."""
    txt = lowered_bench_step.mlir_module()
    pairs = []
    for line in txt.splitlines():
        if "stablehlo.dot_general" not in line:
            continue
        m = re.search(r":\s*\(tensor<([^>]*)>,\s*tensor<([^>]*)>\)", line)
        if m:
            pairs.append(tuple(t.rsplit("x", 1)[-1] for t in m.groups()))
    assert pairs, "no dots found"
    mixed = [p for p in pairs if p != ("bf16", "bf16")]
    assert not mixed, f"non-bf16 GEMM operands: {mixed}"


def test_state_buffers_donated(lowered_bench_step):
    txt = lowered_bench_step.mlir_module()
    sig = re.search(r"func\.func public @main\((.*?)\)\s*->", txt,
                    re.DOTALL).group(1)
    donated = sig.count("tf.aliasing_output")
    # state is arg 1 (a dict pytree); every leaf must be donated.  The
    # signature flattens (feed, state, key): feed leaves + state leaves +
    # key.  Count state leaves from the carry annotations.
    state_args = len(re.findall(r'loc\("state', sig)) or None
    if state_args is not None:
        assert donated >= state_args, \
            f"only {donated} of {state_args} state buffers donated"
    # regardless of loc-name matching, a bf16 BERT step has hundreds of
    # state buffers; all must alias
    assert donated >= 50, f"donation annotations missing ({donated} found)"


def test_donation_ratio_floor(lowered_bench_step):
    """Everything except the feeds and the RNG key must donate — the
    non-donated-arg count stays ≤ 8, i.e. the donation ratio holds the
    791/799-at-799-args floor at any module size (measured here:
    191/199 on the 2-layer bench module)."""
    from tools.verify_multichip_lowering import donation_ratio
    donated, total = donation_ratio(lowered_bench_step.mlir_module())
    assert total - donated <= 8, (donated, total)
    assert donated / total >= (total - 8) / total


def test_single_executable_no_per_step_recompile():
    """Fresh same-shape batches must hit the one cached executable — the
    'no per-step recompile' leg of the perf invariant, at tiny shapes so
    it executes on CPU."""
    from paddle_tpu.monitor import stat
    cfg = bert.BertConfig.tiny()
    main_prog, startup, total = _build_pretrain(cfg)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        before = stat("executor_compile_count").get()
        for _ in range(3):
            data = bert.make_fake_batch(rng, cfg, batch_size=4, seq_len=64,
                                        num_masks=3)
            l, = exe.run(main_prog, feed=data, fetch_list=[total])
            assert np.isfinite(l).all()
        compiles = stat("executor_compile_count").get() - before
    assert compiles == 1, f"expected 1 executable, got {compiles} compiles"


def test_flops_denominator_sane():
    """XLA's counted FLOPs for the compiled step must bracket the
    analytic GEMM count ``train_mfu_pct`` divides by
    (``benchmark/flops.py``) — a wrong denominator would silently
    misreport MFU (tiny config)."""
    import jax
    from benchmark.flops import bert_flops_per_step
    cfg = bert.BertConfig.tiny()
    batch, seq, masks = 8, 64, 4
    main_prog, startup, total = _build_pretrain(cfg)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        data = bert.make_fake_batch(np.random.RandomState(0), cfg,
                                    batch_size=batch, seq_len=seq,
                                    num_masks=masks)
        feed = {k: np.asarray(v) for k, v in data.items()}
        step = exe._compile(main_prog, feed, [total.name], scope, None,
                            (), None)
        state = {n: np.asarray(scope.find_var(n))
                 for n in step.state_in_names}
        compiled = jax.jit(step.raw_fn).lower(
            feed, state, jax.random.PRNGKey(0)).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    xla = float(ca.get("flops", 0.0))
    analytic = float(bert_flops_per_step(vars(cfg), batch, seq, masks))
    ratio = xla / analytic
    # tiny models carry relatively more non-GEMM work, so the band is
    # loose; at bench scale the tool reports ~1.0-1.3
    assert 0.7 < ratio < 3.0, (xla, analytic, ratio)


def test_multichip_step_collectives_in_tpu_module():
    """Cross-lower the dp2×tp2×sp2 TRAINING step for TPU on the virtual
    CPU mesh: the sharded path's collectives (grad all-reduce, Megatron
    g, ring-attention permutes) must appear as real XLA collectives in
    the TPU module — multi-chip perf verifiable without hardware."""
    import jax
    from jax import export as jexp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.parallel import build_mesh

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device virtual mesh conftest")
    mesh = build_mesh({"dp": 2, "tp": 2, "sp": 2}, devs[:8])
    cfg = bert.BertConfig.tiny()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        feeds, loss = bert.build_pretrain_network_parallel(
            cfg, tp_degree=2, seq_axis="sp")
        fluid.optimizer.Adam(1e-4).minimize(loss)
    feed_specs = {f.name: P("dp", "sp") for f in feeds}
    # with_mesh mutates the program: inserts the grad-sync
    # scale+c_allreduce_sum ops over dp×sp (GradAllReduce rewrite)
    fluid.CompiledProgram(main_prog).with_mesh(
        mesh, loss_name=loss.name, batch_axis="dp", seq_axis="sp",
        feed_specs=feed_specs)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        batch = bert.make_fake_parallel_batch(
            np.random.RandomState(0), cfg, batch_size=4, seq_len=64)
        feed = {k: np.asarray(v) for k, v in batch.items()}
        step = exe._compile(main_prog, feed, [loss.name], scope, mesh,
                            tuple(mesh.axis_names), "dp", seq_axis="sp",
                            feed_specs=feed_specs)
        state = {n: np.asarray(scope.find_var(n))
                 for n in step.state_in_names}
        with lowering_target("tpu"):
            exported = jexp.export(step.fn, platforms=("tpu",))(
                feed, state, jax.random.PRNGKey(0))
    txt = exported.mlir_module()
    assert tuple(exported.platforms) == ("tpu",)
    counts = {n: txt.count(f"stablehlo.{n}")
              for n in ("all_reduce", "all_gather", "collective_permute")}
    # grad sync over dp×sp (one per param grad) + the Megatron f/g pair
    assert counts["all_reduce"] >= 30, counts
    # ring attention rotates K/V/mask blocks around the sp axis
    assert counts["collective_permute"] >= 3, counts


# ---------------------------------------------------------------------------
# dp8 gradient-communication census (the grad-comm optimization layer's
# structural proof: bucketing collapses per-leaf grad all-reduces; ZeRO-1
# lowers to reduce_scatter + sharded update + all_gather)
# ---------------------------------------------------------------------------


def _lower_dp8_bert(mode):
    """Cross-lower the dp8 BERT-tiny train step for TPU and return
    (collective census, backward param-leaf count)."""
    import jax
    from jax import export as jexp

    from paddle_tpu.framework.compiler import make_mesh, BuildStrategy
    from paddle_tpu.ops.pallas import lowering_target

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh conftest")
    cfg = bert.BertConfig.tiny()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(cfg)
        if mode == "sharded":
            from paddle_tpu.optimizer import ShardedUpdateOptimizer
            ShardedUpdateOptimizer(fluid.optimizer.AdamOptimizer(1e-4),
                                   nranks=8).minimize(total)
        else:
            fluid.optimizer.Adam(1e-4).minimize(total)
    mesh = make_mesh(8, "dp")
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = mode == "bucketed"
    # ZeRO syncs grads through its own reduce_scatter — no allreduce pass
    ln = None if mode == "sharded" else total.name
    fluid.CompiledProgram(main_prog).with_data_parallel(
        loss_name=ln, mesh=mesh, build_strategy=bs)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        data = bert.make_fake_batch(np.random.RandomState(0), cfg,
                                    batch_size=8, seq_len=64, num_masks=3)
        feed = {k: np.asarray(v) for k, v in data.items()}
        step = exe._compile(main_prog, feed, [total.name], scope, mesh,
                            ("dp",), "dp")
        state = {n: np.asarray(scope.find_var(n))
                 for n in step.state_in_names}
        with lowering_target("tpu"):
            exported = jexp.export(step.fn, platforms=("tpu",))(
                feed, state, jax.random.PRNGKey(0))
    from tools.verify_multichip_lowering import collective_census
    bw = next(op for op in main_prog.global_block().ops
              if op.type == "backward")
    return collective_census(exported.mlir_module()), \
        len(bw.attrs["param_names"])


def test_dp8_bucketed_census_collapses_grad_allreduces():
    """The bucket rewrite's module-level proof: per-leaf dp8 lowers one
    all_reduce per gradient (~38 leaves + the scalar loss merge);
    bucketed lowers ≤ bucket count + the loss merge.  BERT-tiny's fp32
    grads fit one 32 MB bucket, so the census collapses 39 → 2 while the
    reduced payload bytes stay identical."""
    per_leaf, n_leaves = _lower_dp8_bert("perleaf")
    bucketed, _ = _lower_dp8_bert("bucketed")
    assert per_leaf["all_reduce"]["count"] >= n_leaves + 1
    buckets = 1                      # all fp32 grads < fuse_grad_size_in_MB
    assert bucketed["all_reduce"]["count"] <= buckets + 1, bucketed
    # same gradient payload rides 2 collectives instead of 39
    assert bucketed["all_reduce"]["bytes"] == per_leaf["all_reduce"]["bytes"]


def test_dp8_sharded_update_census():
    """ZeRO-1 module proof: no full-gradient all_reduce remains (only
    the 4-byte scalar loss merge); every param leaf syncs through one
    reduce_scatter and rebuilds through one all_gather, and the scatter
    moves 1/8 of the gather payload (the shard)."""
    census, n_leaves = _lower_dp8_bert("sharded")
    assert census["reduce_scatter"]["count"] == n_leaves, census
    assert census["all_gather"]["count"] == n_leaves, census
    ar = census.get("all_reduce", {"count": 0, "bytes": 0})
    assert ar["count"] <= 1 and ar["bytes"] <= 16, census
    assert census["reduce_scatter"]["bytes"] * 8 >= \
        census["all_gather"]["bytes"] - 8 * n_leaves * 8
