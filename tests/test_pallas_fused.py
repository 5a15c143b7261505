"""Fused Pallas kernel numerics (interpret mode on CPU; chip_smoke.py leg K
re-validates on hardware).  Reference: the jnp compositions these kernels
replace (ref CUDA analogs: operators/fused/ fused_elemwise kernels,
optimizers/adam_op.cu)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import fused_ops as F


def _ln_ref(x, s, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps) * s + b


def test_layer_norm_fwd_matches_reference():
    rng = np.random.RandomState(0)
    x = rng.randn(40, 256).astype(np.float32)    # 40: exercises edge block
    s = rng.rand(256).astype(np.float32) + 0.5
    b = rng.randn(256).astype(np.float32)
    y = F.layer_norm(jnp.asarray(x), jnp.asarray(s), jnp.asarray(b),
                     1e-5, True)
    np.testing.assert_allclose(np.asarray(y), _ln_ref(x, s, b), rtol=2e-5,
                               atol=2e-5)


def test_layer_norm_grads_match_jnp():
    rng = np.random.RandomState(1)
    x = rng.randn(24, 128).astype(np.float32)
    s = rng.rand(128).astype(np.float32) + 0.5
    b = rng.randn(128).astype(np.float32)

    def f_kernel(x, s, b):
        return jnp.sum(jnp.sin(F.layer_norm(x, s, b, 1e-5, True)))

    def f_ref(x, s, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + 1e-5) * s + b
        return jnp.sum(jnp.sin(y))

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(s), jnp.asarray(b))
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(s), jnp.asarray(b))
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-5)


def test_bias_gelu_fwd_bwd_match_jnp():
    rng = np.random.RandomState(2)
    x = rng.randn(40, 128).astype(np.float32)    # edge block again
    b = rng.randn(128).astype(np.float32)

    def f_kernel(x, b):
        return jnp.sum(F.bias_gelu(x, b, True) ** 2)

    def f_ref(x, b):
        return jnp.sum(jax.nn.gelu(x + b, approximate=False) ** 2)

    yk = F.bias_gelu(jnp.asarray(x), jnp.asarray(b), True)
    yr = jax.nn.gelu(jnp.asarray(x) + jnp.asarray(b), approximate=False)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), rtol=2e-5,
                               atol=2e-5)
    gk = jax.grad(f_kernel, argnums=(0, 1))(jnp.asarray(x), jnp.asarray(b))
    gr = jax.grad(f_ref, argnums=(0, 1))(jnp.asarray(x), jnp.asarray(b))
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-5)


def test_adam_update_matches_composition():
    rng = np.random.RandomState(3)
    n = 8 * 1024
    p = rng.randn(n).astype(np.float32)
    g = rng.randn(n).astype(np.float32)
    m = rng.randn(n).astype(np.float32) * 0.1
    v = np.abs(rng.randn(n)).astype(np.float32) * 0.01
    beta1, beta2, eps, lr_t = 0.9, 0.999, 1e-8, 0.01
    po, mo, vo = F.adam_update(jnp.asarray(p), jnp.asarray(g),
                               jnp.asarray(m), jnp.asarray(v), lr_t,
                               beta1=beta1, beta2=beta2, eps=eps,
                               interpret=True)
    m_ref = beta1 * m + (1 - beta1) * g
    v_ref = beta2 * v + (1 - beta2) * g * g
    p_ref = p - lr_t * m_ref / (np.sqrt(v_ref) + eps)
    np.testing.assert_allclose(np.asarray(mo), m_ref, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(vo), v_ref, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(po), p_ref, rtol=1e-4, atol=1e-6)


def test_adam_update_stacked_3d_param():
    rng = np.random.RandomState(5)
    shape = (3, 16, 2304)               # stacked experts: [E, rows, cols]
    p, g = (rng.randn(*shape).astype(np.float32) for _ in range(2))
    m = rng.randn(*shape).astype(np.float32) * 0.1
    v = np.abs(rng.randn(*shape)).astype(np.float32) * 0.01
    po, mo, vo = F.adam_update(*(jnp.asarray(t) for t in (p, g, m, v)),
                               0.01, beta1=0.9, beta2=0.999, eps=1e-8,
                               interpret=True)
    m_ref = 0.9 * m + 0.1 * g
    v_ref = 0.999 * v + 0.001 * g * g
    np.testing.assert_allclose(np.asarray(mo), m_ref, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(vo), v_ref, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(po), p - 0.01 * m_ref / (np.sqrt(v_ref) + 1e-8),
        rtol=1e-4, atol=1e-6)


def test_adam_update_2d_param_shape_roundtrip():
    rng = np.random.RandomState(4)
    p = rng.randn(16, 128).astype(np.float32)
    g = rng.randn(16, 128).astype(np.float32)
    m = np.zeros_like(p)
    v = np.zeros_like(p)
    po, mo, vo = F.adam_update(jnp.asarray(p), jnp.asarray(g),
                               jnp.asarray(m), jnp.asarray(v), 0.1,
                               beta1=0.9, beta2=0.999, eps=1e-8,
                               interpret=True)
    assert po.shape == p.shape and mo.shape == p.shape
    assert np.isfinite(np.asarray(po)).all()
