"""Breadth sweep part-3 op tests (sync_batch_norm under a mesh, proximal
optimizers, remaining losses/metrics, pooling variants, utilities)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.registry import OPS, LoweringContext
from jax import shard_map


def _ctx(**kw):
    return LoweringContext(jax.random.PRNGKey(0), **kw)


def _op(name, ins, attrs=None, ctx=None):
    return OPS[name](ctx or _ctx(), {k: [jnp.asarray(q) for q in
                                         (v if isinstance(v, list) else
                                          [v])]
                                     for k, v in ins.items() if v
                                     is not None},
                     attrs or {})


def test_losses_numeric():
    rng = np.random.RandomState(0)
    p = rng.rand(5, 1).astype(np.float32) * 0.8 + 0.1
    y = (rng.rand(5, 1) > 0.5).astype(np.float32)
    out = np.asarray(_op("bce_loss", {"X": p, "Label": y})["Out"])
    np.testing.assert_allclose(
        out, -(y * np.log(p) + (1 - y) * np.log(1 - p)), rtol=1e-5)

    logp = np.log(np.full((4, 3), 1 / 3, np.float32))
    lab = np.array([0, 1, 2, 1])
    nll = _op("nll_loss", {"X": logp, "Label": lab})
    np.testing.assert_allclose(float(nll["Out"]), np.log(3.0), rtol=1e-5)

    a = np.array([2.0, 0.5, -3.0], np.float32)
    yy = np.array([1.0, 0.0, 1.0], np.float32)
    mh = np.asarray(_op("modified_huber_loss",
                        {"X": a, "Y": yy})["Out"]).reshape(-1)
    # z = [2, -0.5, -3]: [0, 2.25, 12]
    np.testing.assert_allclose(mh, [0.0, 2.25, 12.0], rtol=1e-5)

    x2 = rng.rand(3, 4).astype(np.float32)
    y2 = rng.rand(3, 4).astype(np.float32)
    sq = np.asarray(_op("squared_l2_distance",
                        {"X": x2, "Y": y2})["Out"])
    np.testing.assert_allclose(sq.reshape(-1),
                               ((x2 - y2) ** 2).sum(-1), rtol=1e-5)
    assert abs(float(_op("l1_norm", {"X": x2})["Out"])
               - np.abs(x2).sum()) < 1e-4
    np.testing.assert_allclose(
        float(_op("frobenius_norm", {"X": x2})["Out"]),
        np.sqrt((x2 ** 2).sum()), rtol=1e-5)
    assert bool(_op("allclose", {"Input": x2, "Other": x2})["Out"])
    assert not bool(_op("allclose", {"Input": x2,
                                     "Other": x2 + 1})["Out"])


def test_auc_separable():
    """Perfectly separated scores → AUC 1; random-ish → ~0.5."""
    probs = np.stack([1 - np.linspace(0, 1, 100),
                      np.linspace(0, 1, 100)], -1).astype(np.float32)
    label = (np.linspace(0, 1, 100) > 0.5).astype(np.int64)
    out = _op("auc", {"Predict": probs, "Label": label},
              {"num_thresholds": 200})
    assert float(out["AUC"]) > 0.99
    flip = _op("auc", {"Predict": probs, "Label": 1 - label},
               {"num_thresholds": 200})
    assert float(flip["AUC"]) < 0.01


def test_precision_recall_micro():
    pred = np.array([0, 1, 1, 2])
    lab = np.array([0, 1, 2, 2])
    out = _op("precision_recall", {"Indices": pred, "Labels": lab},
              {"class_number": 3})
    batch = np.asarray(out["BatchMetrics"])
    # micro precision = accuracy = 3/4
    np.testing.assert_allclose(batch[3], 0.75, rtol=1e-5)


def test_sync_batch_norm_mesh_statistics():
    """Under shard_map over dp, each shard sees GLOBAL batch stats."""
    from jax.sharding import Mesh, PartitionSpec as P
    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("dp",))
    rng = np.random.RandomState(1)
    xg = rng.randn(8, 3, 2, 2).astype(np.float32) * 3 + 1

    def step(xs):
        ctx = LoweringContext(jax.random.PRNGKey(0), mesh=mesh,
                              axis_names=("dp",))
        out = OPS["sync_batch_norm"](
            ctx, {"X": [xs]}, {"epsilon": 1e-5})
        return out["Y"], out["SavedMean"]

    y, mean = jax.jit(shard_map(
        step, mesh=mesh, in_specs=P("dp"),
        out_specs=(P("dp"), P())))(xg)
    want_mean = xg.mean(axis=(0, 2, 3))
    np.testing.assert_allclose(np.asarray(mean), want_mean, rtol=1e-4,
                               atol=1e-5)
    # normalised output has ~zero mean/unit var per channel GLOBALLY
    yn = np.asarray(y)
    np.testing.assert_allclose(yn.mean(axis=(0, 2, 3)), 0.0, atol=1e-4)
    np.testing.assert_allclose(yn.std(axis=(0, 2, 3)), 1.0, atol=1e-3)


def test_proximal_optimizers():
    p = np.array([1.0, -1.0, 0.01], np.float32)
    g = np.array([0.1, 0.1, 0.1], np.float32)
    lr = np.array([0.5], np.float32)
    out = _op("proximal_gd", {"Param": p, "Grad": g,
                              "LearningRate": lr}, {"l1": 0.1, "l2": 0.0})
    prox = p - 0.5 * g
    want = np.sign(prox) * np.maximum(np.abs(prox) - 0.05, 0)
    np.testing.assert_allclose(np.asarray(out["ParamOut"]), want,
                               rtol=1e-5)
    m = np.ones(3, np.float32)
    out2 = _op("proximal_adagrad",
               {"Param": p, "Grad": g, "Moment": m, "LearningRate": lr},
               {"l1": 0.0, "l2": 0.0})
    np.testing.assert_allclose(np.asarray(out2["MomentOut"]), m + g * g,
                               rtol=1e-6)


def test_pool_with_index_and_unpool_roundtrip():
    rng = np.random.RandomState(2)
    a = rng.rand(1, 2, 4, 4).astype(np.float32)
    out = _op("max_pool2d_with_index", {"X": a},
              {"ksize": [2, 2], "strides": [2, 2]})
    o, mask = np.asarray(out["Out"]), np.asarray(out["Mask"])
    assert o.shape == (1, 2, 2, 2)
    np.testing.assert_allclose(
        o, a.reshape(1, 2, 2, 2, 2, 2).max(axis=(3, 5)), rtol=1e-6)
    # indices point at the argmax in the ORIGINAL map
    flat = a.reshape(1, 2, 16)
    np.testing.assert_allclose(
        np.take_along_axis(flat, mask.reshape(1, 2, 4), -1).reshape(o.shape),
        o, rtol=1e-6)
    # unpool scatters back
    up = _op("unpool", {"X": o, "Indices": mask},
             {"unpooled_size": [4, 4]})
    upn = np.asarray(up["Out"])
    assert upn.shape == a.shape
    np.testing.assert_allclose(upn.sum(), o.sum(), rtol=1e-5)


def test_spp_and_conv_shift():
    rng = np.random.RandomState(3)
    a = rng.rand(2, 3, 4, 4).astype(np.float32)
    out = np.asarray(_op("spp", {"X": a}, {"pyramid_height": 2})["Out"])
    assert out.shape == (2, 3 * (1 + 4))
    np.testing.assert_allclose(out[:, :3], a.max((2, 3)), rtol=1e-6)

    xv = rng.rand(2, 5).astype(np.float32)
    yv = rng.rand(2, 3).astype(np.float32)
    cs = np.asarray(_op("conv_shift", {"X": xv, "Y": yv})["Out"])
    want = np.zeros_like(xv)
    for i in range(5):
        for j in range(3):
            want[:, i] += xv[:, (i + j - 1) % 5] * yv[:, j]
    np.testing.assert_allclose(cs, want, rtol=1e-5)


def test_tensor_utilities():
    out = np.asarray(_op("randperm", {}, {"n": 16})["Out"])
    assert sorted(out.tolist()) == list(range(16))
    rng = np.random.RandomState(4)
    a = rng.rand(6, 4).astype(np.float32)
    b = rng.rand(6, 4).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(_op("minus", {"X": a, "Y": b})["Out"]), a - b)
    pc = np.asarray(_op("partial_concat", {"X": [a, b]},
                        {"start_index": 1, "length": 2})["Out"])
    np.testing.assert_allclose(pc, np.concatenate(
        [a[:, 1:3], b[:, 1:3]], 1))
    ps = np.asarray(_op("partial_sum", {"X": [a, b]},
                        {"start_index": 0, "length": 3})["Out"])
    np.testing.assert_allclose(ps, a[:, :3] + b[:, :3], rtol=1e-6)
    sh = _op("shuffle_batch", {"X": a})
    assert sorted(np.asarray(sh["Out"]).sum(1).tolist()) == \
        pytest.approx(sorted(a.sum(1).tolist()), rel=1e-5)


def test_sequence_erase_and_topk_pool():
    ids = np.array([[3, 0, 5, 0, 7], [1, 1, 2, 0, 0]], np.int64)
    out = _op("sequence_erase", {"X": ids}, {"tokens": [0]})
    o = np.asarray(out["Out"])
    ln = np.asarray(out["Length"])
    np.testing.assert_array_equal(ln, [3, 3])
    np.testing.assert_array_equal(o[0, :3], [3, 5, 7])
    np.testing.assert_array_equal(o[1, :3], [1, 1, 2])

    rng = np.random.RandomState(5)
    seq = rng.rand(2, 6, 3).astype(np.float32)
    tk = np.asarray(_op("sequence_topk_avg_pooling", {"X": seq},
                        {"topks": [2]})["Out"])
    want = np.sort(seq, 1)[:, ::-1][:, :2].mean(1)
    np.testing.assert_allclose(tk, want, rtol=1e-5)
