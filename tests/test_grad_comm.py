"""Gradient-communication optimization legs (the dp8 parity harness for
the comm layer): bucketed fused all-reduce, bf16-compressed collectives,
blockwise-quantized int8/int4 collectives (the wire-compression layer,
ops/quantize_wire.py), and the ZeRO-1 sharded weight update, each proven
against the plain per-leaf dp8 baseline on the 8-device virtual CPU mesh
and against single-device training (the existing parity-leg bound).

Structural contracts (program-level op census) ride along: buckets
respect the size cap, the sharded program carries reduce_scatter/
all_gather and NO full-gradient all-reduce, and quantized programs carry
NO full-precision grad collective (asserted both at program level and on
the lowered dp8 module census)."""

import functools

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.distributed.fleet import (fleet, DistributedStrategy,
                                          distributed_optimizer,
                                          UserDefinedRoleMaker)

STEPS = 4


def _model():
    x = fluid.layers.data("x", shape=[16])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, 32, act="relu",
                        param_attr=fluid.ParamAttr(
                            name="w1",
                            initializer=fluid.initializer.Constant(0.05)),
                        bias_attr=False)
    h = fluid.layers.fc(h, 32, act="relu",
                        param_attr=fluid.ParamAttr(
                            name="w2",
                            initializer=fluid.initializer.Constant(0.04)),
                        bias_attr=False)
    pred = fluid.layers.fc(h, 4, act="softmax",
                           param_attr=fluid.ParamAttr(
                               name="w3",
                               initializer=fluid.initializer.Constant(0.05)),
                           bias_attr=False)
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    return loss


def _batches(n=STEPS):
    rng = np.random.RandomState(0)
    out = []
    for _ in range(n):
        xs = rng.randn(64, 16).astype(np.float32)
        ys = (xs.sum(1) > 0).astype(np.int64).reshape(-1, 1) * 3
        out.append((xs, ys))
    return out


def _run_leg(mutate_strategy=None, optimizer=None, ndev=8):
    """Train the model via the fleet surface; returns (losses, w1, program)."""
    from paddle_tpu.framework.core import reset_default_programs
    reset_default_programs()
    main, startup = Program(), Program()
    from jax.sharding import Mesh
    with program_guard(main, startup):
        loss = _model()
        fleet.init(UserDefinedRoleMaker(0, 1))
        strategy = DistributedStrategy()
        if ndev > 1:
            strategy.mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))
        else:
            strategy.mesh = None
        if mutate_strategy:
            mutate_strategy(strategy)
        opt = distributed_optimizer(
            optimizer() if optimizer else fluid.optimizer.Adam(5e-3),
            strategy)
        opt.minimize(loss)
    prog = fleet.main_program if ndev > 1 else main
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for xs, ys in _batches():
            l, = exe.run(prog, feed={"x": xs, "label": ys},
                         fetch_list=[loss])
            losses.append(float(np.asarray(l).reshape(())))
        w1 = np.asarray(scope.find_var("w1"))
    return losses, w1, main


def _baseline_dp8():
    def mut(s):
        s.fuse_all_reduce_ops = False
    return _run_leg(mut)


# ---------------------------------------------------------------------------
# dp8 + buckets
# ---------------------------------------------------------------------------


def test_dp8_bucketed_parity():
    """Bucketing only restructures the collectives (concat → one
    all_reduce → split); numerics match the per-leaf dp8 baseline to
    ≤1e-6 rel and single-device training to the standard dp bound."""
    base_l, base_w, _ = _baseline_dp8()

    def mut(s):
        s.fuse_all_reduce_ops = True
    fused_l, fused_w, prog = _run_leg(mut)

    np.testing.assert_allclose(base_l, fused_l, rtol=1e-6)
    np.testing.assert_allclose(base_w, fused_w, rtol=1e-6)

    types = [op.type for op in prog.global_block().ops]
    assert "c_fused_allreduce_sum" in types
    assert "c_allreduce_sum" not in types
    # all 3 fp32 grads share one (dtype, axes) bucket under the default cap
    assert types.count("c_fused_allreduce_sum") == 1
    # the fold-in of the mean-scale removed the per-leaf scale ops too
    bw = types.index("backward")
    assert "scale" not in types[bw + 1:bw + 3]

    single_l, single_w, _ = _run_leg(mutate_strategy=None, ndev=1)
    np.testing.assert_allclose(single_l, fused_l, rtol=2e-3)


def test_bucket_size_cap_partitions():
    """fuse_grad_size_in_MB caps each flat bucket: with a cap smaller
    than one w-matrix the three grads land in three buckets."""
    def mut(s):
        s.fuse_all_reduce_ops = True
        s.fuse_grad_size_in_MB = 1e-4        # ~100 bytes
    _, _, prog = _run_leg(mut)
    types = [op.type for op in prog.global_block().ops]
    assert types.count("c_fused_allreduce_sum") == 3


# ---------------------------------------------------------------------------
# dp8 + bf16-compressed all-reduce
# ---------------------------------------------------------------------------


def test_dp8_bf16_compressed_parity():
    """bf16 grad collectives: same training trajectory within the
    documented looser bound (bf16 has ~3 decimal digits; over 4 Adam
    steps on this model the observed drift is <1e-2 rel — we bound at
    5e-2 to keep the leg robust) and still learning."""
    base_l, _, _ = _baseline_dp8()

    def mut(s):
        s.fuse_all_reduce_ops = True
        s.bf16_allreduce = True
    comp_l, _, prog = _run_leg(mut)

    ops = prog.global_block().ops
    fused = [op for op in ops if op.type == "c_fused_allreduce_sum"]
    assert fused and all(op.attrs.get("compress_dtype") == "bfloat16"
                         for op in fused)
    np.testing.assert_allclose(base_l, comp_l, rtol=5e-2)
    assert comp_l[-1] < comp_l[0]


def test_bf16_compress_composes_with_per_leaf():
    """compress_dtype also rides the un-fused per-leaf c_allreduce_sum."""
    base_l, _, _ = _baseline_dp8()

    def mut(s):
        s.fuse_all_reduce_ops = False
        s.bf16_allreduce = True
    comp_l, _, prog = _run_leg(mut)
    ops = prog.global_block().ops
    leaf = [op for op in ops if op.type == "c_allreduce_sum"]
    assert leaf and all(op.attrs.get("compress_dtype") == "bfloat16"
                        for op in leaf)
    np.testing.assert_allclose(base_l, comp_l, rtol=5e-2)


# ---------------------------------------------------------------------------
# dp8 + blockwise-quantized wire compression (int8/int4 tiers;
# ops/quantize_wire.py CompressionSpec → c_quant_allreduce_sum /
# c_fused_quant_allreduce_sum / quant_reduce_scatter)
# ---------------------------------------------------------------------------


#: dtype-tier parity bounds (loss-trajectory rtol vs fp32 dp8 baseline
#: over 4 Adam steps)
INT8_RTOL = 5e-2
INT4_RTOL = 2.5e-1


def test_dp8_int8_quant_parity():
    """int8 × fused buckets: the bucket rides the two-stage quantized
    collective (all_to_all int8 shards → upcast-accumulate → requantize
    → all_gather), the program carries NO full-precision grad collective,
    and the per-bucket scale var the compiler emits is declared at the
    static block count."""
    base_l, _, _ = _baseline_dp8()

    def mut(s):
        s.fuse_all_reduce_ops = True
        s.quant_allreduce = True
    q_l, _, prog = _run_leg(mut)

    block = prog.global_block()
    types = [op.type for op in block.ops]
    assert types.count("c_fused_quant_allreduce_sum") == 1
    assert "c_fused_allreduce_sum" not in types
    assert "c_allreduce_sum" not in types
    fused = next(op for op in block.ops
                 if op.type == "c_fused_quant_allreduce_sum")
    assert fused.attrs["quant_spec"]["dtype"] == "int8"
    # the per-bucket stage-2 scale tensor is a declared var riding
    # alongside the payload: total numel 16*32+32*32+32*4 = 1664 →
    # padded to 8 ranks × 256-block = 2048 → 8 scales
    (sv_name,) = fused.outputs["QScale"]
    sv = block.vars[sv_name]
    assert tuple(sv.shape) == (8,) and str(sv.dtype) == "float32"

    np.testing.assert_allclose(base_l, q_l, rtol=INT8_RTOL)
    assert q_l[-1] < q_l[0]


def test_int8_quant_composes_with_per_leaf():
    """int8 alone (no buckets): quant_spec rides per-leaf
    c_quant_allreduce_sum ops."""
    base_l, _, _ = _baseline_dp8()

    def mut(s):
        s.fuse_all_reduce_ops = False
        s.quant_allreduce = True
    q_l, _, prog = _run_leg(mut)
    types = [op.type for op in prog.global_block().ops]
    assert types.count("c_quant_allreduce_sum") == 3
    assert "c_allreduce_sum" not in types
    np.testing.assert_allclose(base_l, q_l, rtol=INT8_RTOL)


def test_dp8_int4_quant_parity():
    """int4-packed tier: two nibbles per byte on the wire (≈8× fewer
    bytes than fp32); ~1/7 per-block granularity earns the documented
    looser bound, and training still converges."""
    base_l, _, _ = _baseline_dp8()

    def mut(s):
        s.fuse_all_reduce_ops = True
        s.quant_allreduce = True
        s.quant_configs = {"dtype": "int4", "block_size": 256}
    q_l, _, prog = _run_leg(mut)
    fused = [op for op in prog.global_block().ops
             if op.type == "c_fused_quant_allreduce_sum"]
    assert fused and all(op.attrs["quant_spec"]["dtype"] == "int4"
                         for op in fused)
    np.testing.assert_allclose(base_l, q_l, rtol=INT4_RTOL)
    assert q_l[-1] < q_l[0]


def test_int8_quant_stochastic_rounding_leg():
    """stochastic_rounding stays within the int8 tier bound (unbiased
    rounding trades per-step error for drift-free accumulation)."""
    base_l, _, _ = _baseline_dp8()

    def mut(s):
        s.fuse_all_reduce_ops = True
        s.quant_allreduce = True
        s.quant_configs = {"dtype": "int8", "block_size": 128,
                           "stochastic_rounding": True}
    q_l, _, _ = _run_leg(mut)
    np.testing.assert_allclose(base_l, q_l, rtol=INT8_RTOL)


def test_int8_quant_zero1_reduce_scatter():
    """int8 × ZeRO-1: the grad sync rides quant_reduce_scatter (wire-
    width all_to_all + local upcast-accumulate, no full-precision grad
    collective); the param all_gather half stays full precision."""
    base_l, base_w, _ = _baseline_dp8()

    def mut(s):
        s.sharded_update = True
        s.quant_allreduce = True
    q_l, q_w, prog = _run_leg(mut)

    types = [op.type for op in prog.global_block().ops]
    assert types.count("quant_reduce_scatter") == 3
    assert "zero_reduce_scatter" not in types
    assert "c_allreduce_sum" not in types
    assert "c_fused_allreduce_sum" not in types
    assert types.count("zero_all_gather") == 3
    # the param slice uses the same block alignment as the quantized
    # grad scatter, so param/grad shards cover identical element ranges
    slices = [op for op in prog.global_block().ops
              if op.type == "zero_shard_slice"]
    assert slices and all(op.attrs.get("align") == 256 for op in slices)
    np.testing.assert_allclose(base_l, q_l, rtol=INT8_RTOL)
    np.testing.assert_allclose(base_w, q_w, rtol=INT8_RTOL)


def test_int8_quant_composes_with_amp_and_gradient_merge():
    """int8 × AMP × gradient-merge: the quantized bucket rides the
    composed recipe and training stays finite and learning."""
    def mut(s):
        s.fuse_all_reduce_ops = True
        s.quant_allreduce = True
        s.amp = True
        s.gradient_merge = True
        s.gradient_merge_configs = {"k_steps": 2, "avg": True}
    losses, _, prog = _run_leg(mut)
    types = [op.type for op in prog.global_block().ops]
    assert "c_fused_quant_allreduce_sum" in types
    assert "c_fused_allreduce_sum" not in types
    assert "cast" in types           # amp rewrite ran
    assert all(np.isfinite(losses))


def test_bf16_and_quant_allreduce_reject_composition():
    """Pick-one semantics: bf16_allreduce and quant_allreduce both
    rewrite the grad-collective wire format; the strategy names both
    flags in an InvalidArgumentError instead of silently composing."""
    from paddle_tpu.framework.errors import InvalidArgumentError
    from paddle_tpu.distributed.fleet import CollectiveOptimizer
    s = DistributedStrategy()
    s.bf16_allreduce = True
    s.quant_allreduce = True
    with pytest.raises(InvalidArgumentError) as ei:
        CollectiveOptimizer._validate(s)
    assert "bf16_allreduce" in str(ei.value)
    assert "quant_allreduce" in str(ei.value)


def test_quant_census_zero_full_precision_collectives():
    """Module-level census proof on the lowered dp8 BERT step: with int8
    buckets the only f32 all_reduce left is the scalar loss merge —
    every gradient byte rides int8 all_to_all/all_gather (scale tensors
    are the only float payload there, ≤1/16 of the int8 bytes)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh conftest")
    from tools.verify_multichip_lowering import lower_dp8_bert_census
    census = lower_dp8_bert_census("int8")
    ar = census.get("all_reduce", {"count": 0, "bytes": 0})
    assert ar["bytes"] <= 16, census          # scalar merges only
    moved = {k: census[k] for k in ("all_to_all", "all_gather")}
    for kind, row in moved.items():
        i8 = row["by_dtype"].get("i8", 0)
        f32 = row["by_dtype"].get("f32", 0)
        assert i8 > 0, (kind, row)
        assert f32 <= i8 / 16, (kind, row)    # scales only
        assert row["compression_ratio"] >= 3.5, (kind, row)


@functools.lru_cache(maxsize=None)
def _dp8_wire_bytes(tier):
    from tools.verify_multichip_lowering import lower_dp8_bert_census
    return sum(r["wire_bytes"]
               for r in lower_dp8_bert_census(tier).values())


@pytest.mark.parametrize("tier,against,floor", [
    ("bf16", "fp32", 1.7), ("int8", "fp32", 3.5), ("int8", "bf16", 1.9),
    ("int4", "int8", 1.0)])
def test_dp8_wire_tier_moves_fewer_bytes(tier, against, floor):
    """Ring-model wire bytes of the dp8 BERT bucketed grad sync in the
    TPU-lowered module, tier against tier."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh conftest")
    assert _dp8_wire_bytes(against) / _dp8_wire_bytes(tier) >= floor


# ---------------------------------------------------------------------------
# dp8 + ZeRO-1 sharded update
# ---------------------------------------------------------------------------


def test_dp8_sharded_update_parity():
    """reduce_scatter → sharded Adam → all_gather matches the dense dp8
    baseline to ≤1e-6 rel (same update math, 1/8 of it per replica) and
    the program carries NO full-gradient all-reduce."""
    base_l, base_w, _ = _baseline_dp8()

    def mut(s):
        s.sharded_update = True
    sh_l, sh_w, prog = _run_leg(mut)

    np.testing.assert_allclose(base_l, sh_l, rtol=1e-6)
    np.testing.assert_allclose(base_w, sh_w, rtol=1e-6)

    types = [op.type for op in prog.global_block().ops]
    assert types.count("zero_reduce_scatter") == 3
    assert types.count("zero_shard_slice") == 3
    assert types.count("zero_all_gather") == 3
    assert "c_allreduce_sum" not in types
    assert "c_fused_allreduce_sum" not in types

    single_l, _, _ = _run_leg(mutate_strategy=None, ndev=1)
    np.testing.assert_allclose(single_l, sh_l, rtol=2e-3)


def test_sharded_update_shards_optimizer_state():
    """The ZeRO-1 memory claim: Adam moment accumulators are declared at
    flat padded-numel size with dist_attr over dp, so each replica's
    scope shard holds 1/8 of the state."""
    def mut(s):
        s.sharded_update = True
    _, _, prog = _run_leg(mut)
    accs = [v for n, v in prog.global_block().vars.items()
            if "_zshard" in n and "moment" in n]
    assert len(accs) == 6            # 3 params × 2 Adam moments
    for v in accs:
        assert tuple(getattr(v, "dist_attr", ())) == ("dp",)
        assert len(v.shape) == 1     # flat ZeRO shard layout


def test_sharded_update_sgd_and_momentum():
    """The rewrite is optimizer-generic over elementwise rules."""
    def mut(s):
        s.sharded_update = True
    for make in (lambda: fluid.optimizer.SGD(0.2),
                 lambda: fluid.optimizer.Momentum(0.1, momentum=0.9)):
        base_l, base_w, _ = _run_leg(
            lambda s: setattr(s, "fuse_all_reduce_ops", False),
            optimizer=make)
        sh_l, sh_w, _ = _run_leg(mut, optimizer=make)
        np.testing.assert_allclose(base_l, sh_l, rtol=1e-6)
        np.testing.assert_allclose(base_w, sh_w, rtol=1e-6)


def test_sharded_update_rejects_norm_clip_and_lamb():
    def mut(s):
        s.sharded_update = True
    with pytest.raises(NotImplementedError, match="norm"):
        _run_leg(mut, optimizer=lambda: fluid.optimizer.Adam(
            1e-3, grad_clip=fluid.clip.GradientClipByGlobalNorm(1.0)))

    s = DistributedStrategy()
    s.sharded_update = True
    s.lamb = True
    from paddle_tpu.distributed.fleet import CollectiveOptimizer
    with pytest.raises(ValueError, match="lamb"):
        CollectiveOptimizer._validate(s)


# ---------------------------------------------------------------------------
# composition
# ---------------------------------------------------------------------------


def test_buckets_compose_with_amp_and_gradient_merge():
    """The bucketed sync rides the composed AMP + gradient-merge recipe
    (grads all-reduce every micro-step, apply gated at k=2)."""
    def mut(s):
        s.fuse_all_reduce_ops = True
        s.amp = True
        s.gradient_merge = True
        s.gradient_merge_configs = {"k_steps": 2, "avg": True}
    losses, _, prog = _run_leg(mut)
    types = [op.type for op in prog.global_block().ops]
    assert "c_fused_allreduce_sum" in types
    assert "cast" in types           # amp rewrite ran
    assert all(np.isfinite(losses))


def test_sharded_update_composes_with_amp():
    def mut(s):
        s.sharded_update = True
        s.amp = True
    losses, _, prog = _run_leg(mut)
    types = [op.type for op in prog.global_block().ops]
    assert "zero_reduce_scatter" in types
    assert "cast" in types
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
