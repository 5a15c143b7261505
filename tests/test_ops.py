"""Op unit tests vs numpy references + numeric grad checks — the analog of
the reference's ~500 test_*_op.py files (SURVEY §4.1)."""

import numpy as np
import pytest

from op_test import make_op_test


rng = np.random.RandomState(42)


def _f32(*shape):
    return rng.randn(*shape).astype(np.float32)


# the trainers' parameter shapes at CPU size, same divisibility
# (tests/test_tpu_compile.py compiles the real ones for the v5e), and
# the op's first case
ADAM_CASES = [
    pytest.param((3, 3), "float32", id="3x3"),
    pytest.param((3, 16, 2304), "float32",
                 id="stacked-3d-lane-aligned"),     # experts [16,2304,896]
    pytest.param((42, 384), "float32",
                 id="2d-lane-aligned"),     # [30522,768]: rows % 8 == 2
    pytest.param((36, 64), "float32", id="last-dim-64"),    # a router
    pytest.param((8, 12, 128), "float32", id="stacked-rows-not-8"),
    pytest.param((128 * 11,), "float32",
                 id="1d-flat-shard"),               # ZeRO shard, padded
    pytest.param((4, 16, 256), "bfloat16", id="bf16-gradient"),
]


def _adam_operands(shape, g_dtype, seed=3):
    import ml_dtypes
    r = np.random.RandomState(seed)
    p = r.randn(*shape).astype(np.float32)
    g = r.randn(*shape).astype(np.float32)
    if g_dtype == "bfloat16":
        g = g.astype(ml_dtypes.bfloat16)
    m1 = r.randn(*shape).astype(np.float32) * 0.1
    m2 = np.abs(r.randn(*shape)).astype(np.float32) * 0.01
    return p, g, m1, m2


def _adam_case(p, g, m1, m2, steps, lr=0.01, beta1=0.9, beta2=0.999,
               eps=1e-8):
    """(the op's inputs at step ``steps``, (ParamOut, Moment1Out,
    Moment2Out) by the composition in numpy)."""
    b1p, b2p = beta1 ** steps, beta2 ** steps
    ins = {"Param": p, "Grad": g, "Moment1": m1, "Moment2": m2,
           "LearningRate": np.array([lr], np.float32),
           "Beta1Pow": np.array([b1p], np.float32),
           "Beta2Pow": np.array([b2p], np.float32)}
    g = np.asarray(g, np.float32)
    m1o = beta1 * m1 + (1 - beta1) * g
    m2o = beta2 * m2 + (1 - beta2) * g * g
    lr_t = lr * np.sqrt(1 - b2p) / (1 - b1p)
    return ins, (p - lr_t * m1o / (np.sqrt(m2o) + eps), m1o, m2o)


class TestElementwise:
    def test_add(self):
        t = make_op_test("elementwise_add")
        a, b = _f32(3, 4), _f32(3, 4)
        t.check_output({"X": a, "Y": b}, {}, {"Out": a + b})

    def test_add_broadcast_axis(self):
        t = make_op_test("elementwise_add")
        a, b = _f32(2, 3, 4), _f32(3)
        t.check_output({"X": a, "Y": b}, {"axis": 1},
                       {"Out": a + b.reshape(1, 3, 1)})

    def test_sub_mul_div(self):
        a, b = _f32(4, 5), np.abs(_f32(4, 5)) + 0.5
        make_op_test("elementwise_sub").check_output(
            {"X": a, "Y": b}, {}, {"Out": a - b})
        make_op_test("elementwise_mul").check_output(
            {"X": a, "Y": b}, {}, {"Out": a * b})
        make_op_test("elementwise_div").check_output(
            {"X": a, "Y": b}, {}, {"Out": a / b}, rtol=1e-4)

    def test_add_grad(self):
        t = make_op_test("elementwise_add")
        a, b = _f32(3, 4), _f32(3, 4)
        t.check_grad({"X": a, "Y": b}, {}, "Out", ["X", "Y"])

    def test_mul_grad(self):
        t = make_op_test("elementwise_mul")
        a, b = _f32(3, 3), _f32(3, 3)
        t.check_grad({"X": a, "Y": b}, {}, "Out", ["X", "Y"])


class TestMatmul:
    def test_matmul(self):
        t = make_op_test("matmul")
        a, b = _f32(4, 6), _f32(6, 5)
        t.check_output({"X": a, "Y": b}, {}, {"Out": a @ b}, atol=1e-4)

    def test_matmul_transpose(self):
        t = make_op_test("matmul")
        a, b = _f32(6, 4), _f32(6, 5)
        t.check_output({"X": a, "Y": b}, {"transpose_X": True},
                       {"Out": a.T @ b}, atol=1e-4)

    def test_matmul_batched(self):
        t = make_op_test("matmul")
        a, b = _f32(2, 4, 6), _f32(2, 6, 5)
        t.check_output({"X": a, "Y": b}, {}, {"Out": a @ b}, atol=1e-4)

    def test_matmul_grad(self):
        t = make_op_test("matmul")
        a, b = _f32(3, 4), _f32(4, 2)
        t.check_grad({"X": a, "Y": b}, {}, "Out", ["X", "Y"], atol=5e-3)

    def test_mul_flatten(self):
        t = make_op_test("mul")
        a, b = _f32(3, 2, 4), _f32(8, 5)
        t.check_output({"X": a, "Y": b}, {"x_num_col_dims": 1},
                       {"Out": (a.reshape(3, 8) @ b).reshape(3, 5)},
                       atol=1e-4)


class TestActivations:
    def test_relu(self):
        t = make_op_test("relu")
        a = _f32(3, 4)
        t.check_output({"X": a}, {}, {"Out": np.maximum(a, 0)})

    def test_sigmoid(self):
        t = make_op_test("sigmoid")
        a = _f32(3, 4)
        t.check_output({"X": a}, {}, {"Out": 1 / (1 + np.exp(-a))},
                       atol=1e-5)

    def test_tanh_grad(self):
        t = make_op_test("tanh")
        t.check_grad({"X": _f32(3, 3)}, {}, "Out", ["X"])

    def test_gelu(self):
        from scipy.special import erf as scipy_erf  # noqa
        t = make_op_test("gelu")
        a = _f32(4, 4)
        exp = a * 0.5 * (1 + scipy_erf(a / np.sqrt(2)))
        t.check_output({"X": a}, {}, {"Out": exp}, atol=1e-5)

    def test_square_sqrt_exp_log(self):
        a = np.abs(_f32(3, 3)) + 0.1
        make_op_test("square").check_output({"X": a}, {}, {"Out": a * a})
        make_op_test("sqrt").check_output({"X": a}, {}, {"Out": np.sqrt(a)})
        make_op_test("exp").check_output({"X": a}, {}, {"Out": np.exp(a)},
                                         rtol=1e-4)
        make_op_test("log").check_output({"X": a}, {}, {"Out": np.log(a)},
                                         rtol=1e-4)


class TestReduce:
    def test_reduce_sum(self):
        t = make_op_test("reduce_sum")
        a = _f32(3, 4, 5)
        t.check_output({"X": a}, {"dim": [1]}, {"Out": a.sum(1)}, atol=1e-4)

    def test_reduce_mean_keepdim(self):
        t = make_op_test("reduce_mean")
        a = _f32(3, 4)
        t.check_output({"X": a}, {"dim": [0], "keep_dim": True},
                       {"Out": a.mean(0, keepdims=True)})

    def test_reduce_all(self):
        t = make_op_test("reduce_sum")
        a = _f32(3, 4)
        t.check_output({"X": a}, {"reduce_all": True}, {"Out": a.sum()},
                       atol=1e-4)

    def test_reduce_max_min(self):
        a = _f32(3, 4)
        make_op_test("reduce_max").check_output(
            {"X": a}, {"dim": [1]}, {"Out": a.max(1)})
        make_op_test("reduce_min").check_output(
            {"X": a}, {"dim": [0]}, {"Out": a.min(0)})

    def test_mean_grad(self):
        t = make_op_test("mean")
        t.check_grad({"X": _f32(4, 3)}, {}, "Out", ["X"])


class TestSoftmaxLoss:
    def test_softmax(self):
        t = make_op_test("softmax")
        a = _f32(3, 5)
        e = np.exp(a - a.max(-1, keepdims=True))
        t.check_output({"X": a}, {}, {"Out": e / e.sum(-1, keepdims=True)},
                       atol=1e-5)

    def test_cross_entropy(self):
        t = make_op_test("cross_entropy")
        prob = np.abs(_f32(4, 5)) + 0.1
        prob = (prob / prob.sum(-1, keepdims=True)).astype(np.float32)
        label = np.array([[0], [2], [4], [1]], dtype=np.int64)
        exp = -np.log(prob[np.arange(4), label[:, 0]]).reshape(4, 1)
        t.check_output({"X": prob, "Label": label}, {}, {"Y": exp},
                       atol=1e-5)

    def test_softmax_with_cross_entropy(self):
        t = make_op_test("softmax_with_cross_entropy")
        logits = _f32(4, 6)
        label = np.array([[1], [0], [5], [3]], dtype=np.int64)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        sm = e / e.sum(-1, keepdims=True)
        loss = -np.log(sm[np.arange(4), label[:, 0]]).reshape(4, 1)
        t.check_output({"Logits": logits, "Label": label}, {},
                       {"Softmax": sm, "Loss": loss}, atol=1e-5)

    def test_softmax_grad(self):
        t = make_op_test("softmax")
        t.check_grad({"X": _f32(3, 4)}, {}, "Out", ["X"])


class TestConvPool:
    def test_conv2d_identity(self):
        t = make_op_test("conv2d")
        a = _f32(1, 1, 4, 4)
        w = np.ones((1, 1, 1, 1), np.float32)
        t.check_output({"Input": a, "Filter": w},
                       {"strides": [1, 1], "paddings": [0, 0]},
                       {"Output": a}, atol=1e-5)

    def test_conv2d_vs_manual(self):
        t = make_op_test("conv2d")
        a = _f32(2, 3, 5, 5)
        w = _f32(4, 3, 3, 3)
        # manual conv via explicit loops
        out = np.zeros((2, 4, 3, 3), np.float32)
        for n in range(2):
            for o in range(4):
                for i in range(3):
                    for j in range(3):
                        out[n, o, i, j] = np.sum(
                            a[n, :, i:i+3, j:j+3] * w[o])
        t.check_output({"Input": a, "Filter": w},
                       {"strides": [1, 1], "paddings": [0, 0]},
                       {"Output": out}, atol=1e-3)

    def test_conv2d_grad(self):
        t = make_op_test("conv2d")
        a, w = _f32(1, 2, 4, 4), _f32(2, 2, 3, 3)
        t.check_grad({"Input": a, "Filter": w},
                     {"strides": [1, 1], "paddings": [1, 1]},
                     "Output", ["Filter"], atol=2e-2, rtol=2e-2)

    def test_pool2d_max(self):
        t = make_op_test("pool2d")
        a = _f32(1, 2, 4, 4)
        exp = a.reshape(1, 2, 2, 2, 2, 2).max(axis=(3, 5))
        t.check_output({"X": a},
                       {"pooling_type": "max", "ksize": [2, 2],
                        "strides": [2, 2], "paddings": [0, 0]},
                       {"Out": exp})

    def test_pool2d_avg(self):
        t = make_op_test("pool2d")
        a = _f32(1, 2, 4, 4)
        exp = a.reshape(1, 2, 2, 2, 2, 2).mean(axis=(3, 5))
        t.check_output({"X": a},
                       {"pooling_type": "avg", "ksize": [2, 2],
                        "strides": [2, 2], "paddings": [0, 0]},
                       {"Out": exp}, atol=1e-5)

    def test_pool2d_global(self):
        t = make_op_test("pool2d")
        a = _f32(2, 3, 4, 4)
        t.check_output({"X": a}, {"pooling_type": "avg",
                                  "global_pooling": True},
                       {"Out": a.mean(axis=(2, 3), keepdims=True)},
                       atol=1e-5)


class TestNorm:
    def test_layer_norm(self):
        t = make_op_test("layer_norm")
        a = _f32(4, 10)
        scale = _f32(10)
        bias = _f32(10)
        mean = a.mean(-1, keepdims=True)
        var = a.var(-1, keepdims=True)
        exp = (a - mean) / np.sqrt(var + 1e-5) * scale + bias
        t.check_output({"X": a, "Scale": scale, "Bias": bias},
                       {"begin_norm_axis": 1},
                       {"Y": exp}, atol=1e-4)

    def test_batch_norm_infer(self):
        t = make_op_test("batch_norm")
        a = _f32(2, 3, 4, 4)
        scale, bias = _f32(3), _f32(3)
        mean, var = _f32(3), np.abs(_f32(3)) + 0.5
        inv = 1 / np.sqrt(var + 1e-5)
        exp = (a - mean.reshape(1, 3, 1, 1)) * \
            (inv * scale).reshape(1, 3, 1, 1) + bias.reshape(1, 3, 1, 1)
        t.check_output({"X": a, "Scale": scale, "Bias": bias,
                        "Mean": mean, "Variance": var},
                       {"is_test": True, "epsilon": 1e-5},
                       {"Y": exp}, atol=1e-4)

    def test_layer_norm_grad(self):
        t = make_op_test("layer_norm")
        a, s, b = _f32(3, 6), _f32(6), _f32(6)
        t.check_grad({"X": a, "Scale": s, "Bias": b},
                     {"begin_norm_axis": 1}, "Y", ["X", "Scale"],
                     atol=5e-3, rtol=5e-3)


class TestTensorOps:
    def test_reshape(self):
        t = make_op_test("reshape2")
        a = _f32(2, 3, 4)
        t.check_output({"X": a}, {"shape": [6, 4]},
                       {"Out": a.reshape(6, 4)})

    def test_reshape_infer(self):
        t = make_op_test("reshape2")
        a = _f32(2, 3, 4)
        t.check_output({"X": a}, {"shape": [-1, 12]},
                       {"Out": a.reshape(2, 12)})

    def test_transpose(self):
        t = make_op_test("transpose2")
        a = _f32(2, 3, 4)
        t.check_output({"X": a}, {"axis": [1, 0, 2]},
                       {"Out": a.transpose(1, 0, 2)})

    def test_concat_split(self):
        a, b = _f32(2, 3), _f32(2, 5)
        make_op_test("concat").check_output(
            {"X": [a, b]}, {"axis": 1},
            {"Out": np.concatenate([a, b], axis=1)})
        c = _f32(2, 8)
        make_op_test("split").check_output(
            {"X": c}, {"num": 2, "axis": 1},
            {"Out": [c[:, :4], c[:, 4:]]})

    def test_slice(self):
        t = make_op_test("slice")
        a = _f32(4, 5, 6)
        t.check_output({"Input": a},
                       {"axes": [0, 2], "starts": [1, 2], "ends": [3, 5]},
                       {"Out": a[1:3, :, 2:5]})

    def test_cast(self):
        t = make_op_test("cast")
        a = _f32(3, 3)
        t.check_output({"X": a}, {"out_dtype": "int32"},
                       {"Out": a.astype(np.int32)})

    def test_stack_gather(self):
        a, b = _f32(3, 4), _f32(3, 4)
        make_op_test("stack").check_output(
            {"X": [a, b]}, {"axis": 0}, {"Y": np.stack([a, b])})
        c = _f32(5, 3)
        idx = np.array([0, 2, 4], np.int32)
        make_op_test("gather").check_output(
            {"X": c, "Index": idx}, {}, {"Out": c[idx]})

    def test_lookup_table(self):
        t = make_op_test("lookup_table_v2")
        w = _f32(10, 4)
        ids = np.array([[1, 3], [5, 7]], np.int64)
        t.check_output({"W": w, "Ids": ids}, {}, {"Out": w[ids]})

    def test_one_hot(self):
        t = make_op_test("one_hot")
        ids = np.array([[0], [2], [1]], np.int64)
        exp = np.eye(3, dtype=np.float32)[[0, 2, 1]]
        t.check_output({"X": ids}, {"depth": 3}, {"Out": exp})

    def test_dropout_test_mode(self):
        t = make_op_test("dropout")
        a = _f32(4, 4)
        t.check_output({"X": a}, {"dropout_prob": 0.3, "is_test": True,
                                  "dropout_implementation": "upscale_in_train"},
                       {"Out": a})

    def test_scale(self):
        t = make_op_test("scale")
        a = _f32(3, 3)
        t.check_output({"X": a}, {"scale": 2.0, "bias": 1.0},
                       {"Out": a * 2 + 1})

    def test_clip(self):
        t = make_op_test("clip")
        a = _f32(3, 3)
        t.check_output({"X": a}, {"min": -0.5, "max": 0.5},
                       {"Out": np.clip(a, -0.5, 0.5)})

    def test_top_k(self):
        t = make_op_test("top_k")
        a = _f32(3, 6)
        idx = np.argsort(-a, axis=1)[:, :2]
        vals = np.take_along_axis(a, idx, 1)
        t.check_output({"X": a}, {"k": 2},
                       {"Out": vals, "Indices": idx.astype(np.int64)})

    def test_arg_max(self):
        t = make_op_test("arg_max")
        a = _f32(3, 5)
        t.check_output({"X": a}, {"axis": 1},
                       {"Out": a.argmax(1).astype(np.int64)})


class TestOptimOps:
    def test_sgd(self):
        t = make_op_test("sgd")
        p, g = _f32(4, 3), _f32(4, 3)
        lr = np.array([0.1], np.float32)
        t.check_output({"Param": p, "Grad": g, "LearningRate": lr}, {},
                       {"ParamOut": p - 0.1 * g}, atol=1e-6)

    def test_momentum(self):
        t = make_op_test("momentum")
        p, g, v = _f32(3, 3), _f32(3, 3), _f32(3, 3)
        lr = np.array([0.1], np.float32)
        v_out = 0.9 * v + g
        t.check_output({"Param": p, "Grad": g, "Velocity": v,
                        "LearningRate": lr},
                       {"mu": 0.9},
                       {"ParamOut": p - 0.1 * v_out, "VelocityOut": v_out},
                       atol=1e-6)

    @pytest.mark.parametrize("shape,g_dtype", ADAM_CASES)
    def test_adam(self, shape, g_dtype):
        """The update through the executor, third step (moments and
        bias powers not at their start): the composition's to 1e-6, a
        bf16 gradient widened to the moments' f32."""
        p, g, m1, m2 = _adam_operands(shape, g_dtype)
        ins, (p_ref, m1_ref, m2_ref) = _adam_case(p, g, m1, m2, steps=3)
        make_op_test("adam").check_output(
            ins, {}, {"ParamOut": p_ref, "Moment1Out": m1_ref,
                      "Moment2Out": m2_ref}, atol=1e-6, rtol=1e-6)

    @pytest.mark.parametrize("shape,g_dtype", ADAM_CASES)
    def test_adam_keeps_shape_and_dtypes(self, shape, g_dtype):
        """Parameter and moments come back in the input's shape and
        dtype whatever the gradient's, and the bias powers advance."""
        p, g, m1, m2 = _adam_operands(shape, g_dtype, seed=4)
        ins, _ = _adam_case(p, g, m1, m2, steps=1)
        t = make_op_test("adam")
        got = t.check_output(ins, {}, {
            "Beta1PowOut": np.array([0.9 * 0.9], np.float32),
            "Beta2PowOut": np.array([0.999 * 0.999], np.float32)})
        assert all(r.shape == (1,) for r in got)
        import jax.numpy as jnp
        from paddle_tpu.ops.registry import get_op
        outs = get_op("adam")(
            None, {k: [jnp.asarray(v)] for k, v in ins.items()}, {})
        for slot in ("ParamOut", "Moment1Out", "Moment2Out"):
            assert outs[slot].shape == tuple(shape), slot
            assert outs[slot].dtype == np.float32, slot
            assert np.isfinite(np.asarray(outs[slot])).all(), slot

    @pytest.mark.parametrize("shape,g_dtype", ADAM_CASES)
    def test_adam_in_place_steps(self, shape, g_dtype):
        """Three steps of one jitted update over DONATED parameter and
        moments (how a prepared step runs it) equal three steps of the
        composition on the host."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.registry import get_op
        p, g, m1, m2 = _adam_operands(shape, g_dtype, seed=5)

        def step(p, m1, m2, b1p, b2p):
            out = get_op("adam")(None, {
                "Param": [p], "Grad": [jnp.asarray(g)], "Moment1": [m1],
                "Moment2": [m2], "Beta1Pow": [b1p], "Beta2Pow": [b2p],
                "LearningRate": [jnp.full((1,), 0.01, jnp.float32)]}, {})
            return tuple(out[k] for k in (
                "ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
                "Beta2PowOut"))

        state = tuple(jnp.asarray(t) for t in (
            p, m1, m2, np.array([0.9], np.float32),
            np.array([0.999], np.float32)))
        jitted = jax.jit(step, donate_argnums=(0, 1, 2))
        want = (p, m1, m2)
        for k in range(1, 4):
            state = jitted(*state)
            _, want = _adam_case(want[0], g, want[1], want[2], steps=k)
        # three steps of f32 rounding, host against XLA, on values of a
        # few units: some tens of ulps
        for got, ref in zip(state, want):
            np.testing.assert_allclose(np.asarray(got), ref, atol=1e-5,
                                       rtol=1e-5)
