"""Tensor/pipeline/sequence parallelism tests over the 8-device virtual CPU
mesh (the reference tests distribution with localhost subprocesses,
ref: test_dist_base.py:506; here a virtual mesh exercises the same
collectives in-process — SURVEY §4.7)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from jax import shard_map
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu import parallel
from paddle_tpu.parallel import build_mesh

layers = fluid.layers


def _train_ref_and_parallel(build_parallel, build_ref, mesh, feed_fn,
                            steps=3, seq_axis=None, feed_specs=None):
    """Run the same model single-device and under the mesh; losses match."""
    # reference (single device)
    from paddle_tpu.framework.core import reset_default_programs
    from paddle_tpu.framework.executor import global_scope
    ref_losses = []
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = build_ref()
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(startup)
        for i in range(steps):
            l, = exe.run(main, feed=feed_fn(i), fetch_list=[loss])
            ref_losses.append(float(np.asarray(l).reshape(())))

    reset_default_programs()
    par_losses = []
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = build_parallel()
        fluid.optimizer.SGD(0.1).minimize(loss)
    compiled = fluid.CompiledProgram(main).with_mesh(
        mesh, loss_name=loss.name, batch_axis="dp", seq_axis=seq_axis,
        feed_specs=feed_specs)
    exe = fluid.Executor(fluid.CPUPlace())
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(startup)
        for i in range(steps):
            l, = exe.run(compiled, feed=feed_fn(i), fetch_list=[loss])
            par_losses.append(float(np.asarray(l).reshape(())))
    return ref_losses, par_losses


def _mlp(x, tp_degree=None):
    if tp_degree:
        h = parallel.column_parallel_fc(
            x, 16, tp_degree, act="relu",
            param_attr=fluid.ParamAttr(
                name="w1", initializer=fluid.initializer.Constant(0.02)),
            bias_attr=False)
        y = parallel.row_parallel_fc(
            h, 4, tp_degree,
            param_attr=fluid.ParamAttr(
                name="w2", initializer=fluid.initializer.Constant(0.01)),
            bias_attr=False)
    else:
        y = fluid.layers.fc(x, 16, act="relu", bias_attr=False,
                            param_attr=fluid.ParamAttr(
                                name="w1",
                                initializer=fluid.initializer.Constant(0.02)))
        y = fluid.layers.fc(y, 4, bias_attr=False,
                            param_attr=fluid.ParamAttr(
                                name="w2",
                                initializer=fluid.initializer.Constant(0.01)))
    return layers.mean(layers.square(y))


def test_tensor_parallel_matches_single_device():
    mesh = build_mesh({"dp": 2, "tp": 4})
    rng = np.random.RandomState(0)
    batches = [rng.rand(8, 6).astype(np.float32) for _ in range(3)]

    def feed(i):
        return {"x": batches[i]}

    def build_tp():
        x = layers.data("x", shape=[6])
        return _mlp(x, tp_degree=4)

    def build_ref():
        x = layers.data("x", shape=[6])
        return _mlp(x)

    ref, par = _train_ref_and_parallel(build_tp, build_ref, mesh, feed)
    np.testing.assert_allclose(ref, par, rtol=2e-4)


def test_vocab_parallel_embedding():
    mesh = build_mesh({"tp": 8})
    ids_np = np.array([[1, 9, 14], [3, 0, 15]], np.int64)

    from paddle_tpu.framework.executor import global_scope
    main, startup = Program(), Program()
    with program_guard(main, startup):
        ids = layers.data("ids", shape=[3], dtype="int64")
        emb = parallel.vocab_parallel_embedding(
            ids, vocab_size=16, embed_dim=4, tp_degree=8,
            param_attr=fluid.ParamAttr(
                name="emb_w", initializer=fluid.initializer.Constant(1.0)))
        out = layers.reduce_sum(emb)
    compiled = fluid.CompiledProgram(main).with_mesh(mesh, batch_axis=None)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    o, = exe.run(compiled, feed={"ids": ids_np}, fetch_list=[out])
    # all-ones embedding: sum = num_ids * embed_dim
    assert np.isclose(float(np.asarray(o).reshape(())), 6 * 4)


def test_ring_attention_matches_full_attention():
    from paddle_tpu.parallel.ring_attention import ring_attention
    from jax.sharding import Mesh, PartitionSpec as P

    B, H, S, D = 2, 2, 32, 8
    rng = np.random.RandomState(0)
    q = rng.randn(B, H, S, D).astype(np.float32)
    k = rng.randn(B, H, S, D).astype(np.float32)
    v = rng.randn(B, H, S, D).astype(np.float32)

    # full attention reference
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    p = jax.nn.softmax(jnp.asarray(s), axis=-1)
    ref = np.einsum("bhqk,bhkd->bhqd", np.asarray(p), v)

    mesh = build_mesh({"sp": 8})

    def f(q, k, v):
        return ring_attention(q, k, v, "sp")

    out = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(None, None, "sp"), P(None, None, "sp"),
                  P(None, None, "sp")),
        out_specs=P(None, None, "sp")))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_ring_attention_causal():
    from paddle_tpu.parallel.ring_attention import ring_attention
    from jax.sharding import PartitionSpec as P

    B, H, S, D = 1, 1, 16, 4
    rng = np.random.RandomState(1)
    q = rng.randn(B, H, S, D).astype(np.float32)
    k = rng.randn(B, H, S, D).astype(np.float32)
    v = rng.randn(B, H, S, D).astype(np.float32)

    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    mask = np.tril(np.ones((S, S), bool))
    s = np.where(mask, s, -1e30)
    p = np.asarray(jax.nn.softmax(jnp.asarray(s), axis=-1))
    ref = np.einsum("bhqk,bhkd->bhqd", p, v)

    mesh = build_mesh({"sp": 4})
    out = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp")))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_gpipe_spmd_matches_sequential():
    from jax.sharding import PartitionSpec as P
    S_stages, M, mb, dim = 4, 4, 2, 8
    rng = np.random.RandomState(0)
    ws = rng.randn(S_stages, dim, dim).astype(np.float32) * 0.3
    xs = rng.randn(M, mb, dim).astype(np.float32)

    # sequential reference
    ref = xs
    for i in range(S_stages):
        ref = np.tanh(ref @ ws[i])

    mesh = build_mesh({"pp": 4})

    def stage(w, x):
        return jnp.tanh(x @ w[0])        # w: [1, dim, dim] local slice

    out = jax.jit(shard_map(
        lambda w, x: parallel.gpipe_spmd(stage, w, x, "pp"),
        mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
        check_vma=False))(ws, xs)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_pipeline_optimizer_program_level():
    """2-stage program pipeline over pp=2 matches single-device training."""
    rng = np.random.RandomState(0)
    batches = [rng.rand(8, 6).astype(np.float32) for _ in range(3)]

    def build(pipelined):
        x = layers.data("x", shape=[6])
        guard0 = fluid.device_guard("tpu:0") if pipelined else _null()
        with guard0:
            h = fluid.layers.fc(x, 8, act="relu", bias_attr=False,
                                param_attr=fluid.ParamAttr(
                                    name="pw1",
                                    initializer=fluid.initializer.Constant(0.05)))
        guard1 = fluid.device_guard("tpu:1") if pipelined else _null()
        with guard1:
            y = fluid.layers.fc(h, 8, bias_attr=False,
                                param_attr=fluid.ParamAttr(
                                    name="pw2",
                                    initializer=fluid.initializer.Constant(0.05)))
            loss = layers.mean(layers.square(y))
        return loss

    import contextlib

    def _null():
        return contextlib.nullcontext()

    # single-device reference
    ref_losses = []
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = build(False)
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for b in batches:
            l, = exe.run(main, feed={"x": b}, fetch_list=[loss])
            ref_losses.append(float(np.asarray(l).reshape(())))

    from paddle_tpu.framework.core import reset_default_programs
    reset_default_programs()

    # pipelined over pp=2, 4 microbatches
    mesh = build_mesh({"pp": 2})
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = build(True)
        opt = parallel.PipelineOptimizer(fluid.optimizer.SGD(0.1),
                                         num_microbatches=4)
        opt.minimize(loss)
        pipe_loss = main.global_block().var(loss.name + "@pipeline")
    compiled = fluid.CompiledProgram(main).with_mesh(
        mesh, loss_name=None, batch_axis=None)
    exe = fluid.Executor(fluid.CPUPlace())
    pipe_losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for b in batches:
            l, = exe.run(compiled, feed={"x": b}, fetch_list=[pipe_loss])
            pipe_losses.append(float(np.asarray(l).reshape(())))

    np.testing.assert_allclose(ref_losses, pipe_losses, rtol=1e-4)
