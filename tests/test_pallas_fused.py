"""Fused Pallas kernel numerics (interpret mode on CPU; chip_smoke.py leg K
re-validates on hardware).  Reference: the jnp compositions these kernels
replace (ref CUDA analogs: operators/fused/ fused_elemwise kernels)."""

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
