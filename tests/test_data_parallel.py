"""Data-parallel equivalence tests — the analog of the reference's
parallel_executor convergence tests (ref: parallel_executor_test_base.py:32,
test_dist_base.py): N-device training must match 1-device training on the
same global batch."""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.framework.compiler import make_mesh
from jax import shard_map


def _build(seed=0):
    main, startup = Program(), Program()
    main.random_seed = seed
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu",
                            param_attr=fluid.ParamAttr(
                                name="w1",
                                initializer=fluid.initializer.Constant(0.05)),
                            bias_attr=False)
        pred = fluid.layers.fc(h, 4, act="softmax",
                               param_attr=fluid.ParamAttr(
                                   name="w2",
                                   initializer=fluid.initializer.Constant(0.05)),
                               bias_attr=False)
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(0.2).minimize(loss)
    return main, startup, loss


def _data(rng, n=64):
    xs = rng.randn(n, 16).astype(np.float32)
    ys = (xs.sum(1) > 0).astype(np.int64).reshape(-1, 1) * 3
    return xs, ys


def test_dp_matches_single_device():
    rng = np.random.RandomState(0)
    batches = [_data(rng) for _ in range(5)]

    # single device
    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    s1 = fluid.Scope()
    single_losses = []
    with fluid.scope_guard(s1):
        exe.run(startup)
        for xs, ys in batches:
            l, = exe.run(main, feed={"x": xs, "label": ys},
                         fetch_list=[loss])
            single_losses.append(float(l))

    # 8-device data parallel on the same global batch
    main2, startup2, loss2 = _build()
    cp = fluid.CompiledProgram(main2).with_data_parallel(
        loss_name=loss2.name, mesh=make_mesh(8, "dp"))
    s2 = fluid.Scope()
    dp_losses = []
    with fluid.scope_guard(s2):
        exe.run(startup2)
        for xs, ys in batches:
            l, = exe.run(cp, feed={"x": xs, "label": ys},
                         fetch_list=[loss2])
            dp_losses.append(float(l))

    # mean-loss fetched under dp is the mean over the local shard of rank 0
    # after identical updates; allow small tolerance for reduction order
    np.testing.assert_allclose(single_losses, dp_losses, rtol=2e-3,
                               err_msg="dp training diverged from single")


def test_collective_transpile_inserts_allreduce():
    main, startup, loss = _build()
    cp = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, mesh=make_mesh(8, "dp"))
    types = [op.type for op in main.global_block().ops]
    assert "c_allreduce_sum" in types
    assert "scale" in types
    bw = types.index("backward")
    assert types.index("c_allreduce_sum") > bw


def test_collective_ops_single_rank_identity():
    """Outside a mesh, c_* ops are identity (single-rank semantics)."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        out = main.global_block().create_var(name="ar_out", shape=(-1, 4),
                                             dtype="float32")
        main.global_block().append_op(
            type="c_allreduce_sum", inputs={"X": [x]},
            outputs={"Out": [out]}, attrs={"ring_id": 0})
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xv = np.ones((2, 4), np.float32)
    r, = exe.run(main, feed={"x": xv}, fetch_list=[out])
    np.testing.assert_array_equal(r, xv)


def test_c_allreduce_prod_zeros_and_negatives():
    """Product-allreduce must be exact on zeros and negative factors
    (ref semantics: ncclProd, collective/c_allreduce_op.h:33)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import get_op
    from paddle_tpu.framework.executor import LoweringContext
    from paddle_tpu.framework.compiler import make_mesh

    mesh = make_mesh(8, "dp")
    vals = np.array([2.0, -3.0, 1.0, -1.0, 0.5, 4.0, -2.0, 1.0],
                    np.float32)

    impl = get_op("c_allreduce_prod")

    def shard_fn(v):
        ctx = LoweringContext(jax.random.PRNGKey(0), mesh, ("dp",), False)
        return impl(ctx, {"X": [v]}, {"ring_id": 0})["Out"]

    out = jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=jax.sharding.PartitionSpec("dp"),
        out_specs=jax.sharding.PartitionSpec("dp")))(vals)
    np.testing.assert_allclose(np.asarray(out), np.full(8, np.prod(vals)),
                               rtol=1e-5)

    # one rank contributes a zero → exact 0, not NaN
    vals0 = vals.copy()
    vals0[3] = 0.0
    out0 = jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=jax.sharding.PartitionSpec("dp"),
        out_specs=jax.sharding.PartitionSpec("dp")))(vals0)
    np.testing.assert_array_equal(np.asarray(out0), np.zeros(8, np.float32))
