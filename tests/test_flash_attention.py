"""Pallas flash-attention kernel tests (interpret mode on CPU).

Validates the blockwise forward AND backward kernels against the jnp
composition (the numeric spec), mirroring how the reference unit-tests its
fused attention against a python composition
(ref: tests/unittests/test_fused_multihead_matmul_op.py pattern).

Dropout uses the TPU hardware PRNG (pltpu.prng_random_bits), which the
interpreter stubs to zeros — the dropout path is exercised on real TPU by
chip_smoke.py (leg K) and gated off CPU by supported().
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape).astype(np.float32))


@pytest.mark.parametrize("use_bias", [False, True])
def test_forward_matches_reference(use_bias):
    rng = np.random.RandomState(0)
    B, H, S, D = 2, 2, 256, 64
    q, k, v = (_rand(rng, B, H, S, D) for _ in range(3))
    bias = None
    bf = None
    if use_bias:
        mask = (rng.rand(B, 1, 1, S) > 0.2).astype(np.float32)
        bias = jnp.asarray((1 - mask) * -1e9) * jnp.ones((1, 1, S, 1))
        bf = bias.reshape(B, S, S)
    out = fa.flash_attention_bshd(q, k, v, bias, interpret=True)
    ref = fa._reference(q.reshape(B * H, S, D), k.reshape(B * H, S, D),
                        v.reshape(B * H, S, D), bf)
    np.testing.assert_allclose(np.asarray(out).reshape(B * H, S, D),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("use_bias", [False, True])
def test_backward_matches_reference(use_bias):
    """The blockwise dq/dk/dv kernels against jax.grad of the jnp spec."""
    rng = np.random.RandomState(1)
    B, H, S, D = 1, 2, 256, 64
    q, k, v = (_rand(rng, B, H, S, D) for _ in range(3))
    bias = None
    if use_bias:
        mask = (rng.rand(B, 1, 1, S) > 0.2).astype(np.float32)
        bias = jnp.asarray((1 - mask) * -1e9) * jnp.ones((1, 1, S, 1))

    def ref_loss(q, k, v):
        bf = bias.reshape(B, S, S) if bias is not None else None
        o = fa._reference(q.reshape(B * H, S, D), k.reshape(B * H, S, D),
                          v.reshape(B * H, S, D), bf)
        return jnp.sum(jnp.sin(o))

    def ker_loss(q, k, v):
        o = fa.flash_attention_bshd(q, k, v, bias, interpret=True)
        return jnp.sum(jnp.sin(o.reshape(B * H, S, D)))

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_ker = jax.grad(ker_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_ker):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name} mismatch")


def test_head_shared_bias_not_broadcast():
    """A (B,1,S,S) mask stays (B,S,S) on the host side (the kernel's index
    map folds the head dim) and still matches the broadcast reference."""
    rng = np.random.RandomState(2)
    B, H, S, D = 2, 4, 128, 64
    q, k, v = (_rand(rng, B, H, S, D) for _ in range(3))
    bias = _rand(rng, B, 1, S, S) * 0.1
    out = fa.flash_attention_bshd(q, k, v, bias, interpret=True)
    ref = fa._reference(q.reshape(B * H, S, D), k.reshape(B * H, S, D),
                        v.reshape(B * H, S, D), bias.reshape(B, S, S))
    np.testing.assert_allclose(np.asarray(out).reshape(B * H, S, D),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_cross_attention_rectangular():
    """Decoder cross-attention: Sq != Sk (models/transformer.py _mha)."""
    rng = np.random.RandomState(4)
    B, H, SQ, SK, D = 2, 2, 128, 384, 64
    q = _rand(rng, B, H, SQ, D)
    k = _rand(rng, B, H, SK, D)
    v = _rand(rng, B, H, SK, D)

    def ref_loss(q, k, v):
        o = fa._reference(q.reshape(B * H, SQ, D), k.reshape(B * H, SK, D),
                          v.reshape(B * H, SK, D), None)
        return jnp.sum(jnp.sin(o))

    def ker_loss(q, k, v):
        o = fa.flash_attention_bshd(q, k, v, interpret=True)
        return jnp.sum(jnp.sin(o.reshape(B * H, SQ, D)))

    np.testing.assert_allclose(float(ref_loss(q, k, v)),
                               float(ker_loss(q, k, v)), rtol=1e-5)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_ker = jax.grad(ker_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_ker):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name} mismatch")


def test_kv_mask_bias_shape():
    """The dispatch's KVMask-derived bias is (B,1,1,Sk) — must broadcast
    cleanly to all query rows."""
    rng = np.random.RandomState(5)
    B, H, S, D = 2, 2, 128, 64
    q, k, v = (_rand(rng, B, H, S, D) for _ in range(3))
    mask = (rng.rand(B, S) > 0.2).astype(np.float32)
    bias = jnp.asarray((1 - mask)[:, None, None, :] * -1e9)
    out = fa.flash_attention_bshd(q, k, v, bias, interpret=True)
    full = jnp.broadcast_to(bias, (B, 1, S, S)).reshape(B, S, S)
    ref = fa._reference(q.reshape(B * H, S, D), k.reshape(B * H, S, D),
                        v.reshape(B * H, S, D), full)
    np.testing.assert_allclose(np.asarray(out).reshape(B * H, S, D),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_supported_gating():
    # seq not tiling the block → rejected
    assert not fa.supported((1, 2, 100, 64))
    # head dim not 64/128k → rejected
    assert not fa.supported((1, 2, 256, 80))
    # key seq not tiling the block → rejected
    assert not fa.supported((1, 2, 256, 64), k_seq=100, backend="tpu")
    assert fa.supported((1, 2, 256, 64), k_seq=384, backend="tpu")
    # non-TPU backends → rejected (the dispatch falls back to jnp)
    assert not fa.supported((1, 2, 256, 64), backend="cpu")
    assert not fa.supported((1, 2, 256, 64), backend="gpu")
    assert fa.supported((1, 2, 256, 64), backend="tpu")
    # and the entry point raises rather than silently degrading
    q = jnp.zeros((1, 2, 100, 64), jnp.float32)
    with pytest.raises(ValueError):
        fa.flash_attention_bshd(q, q, q)


def test_dropout_requires_seed():
    q = jnp.zeros((1, 2, 256, 64), jnp.float32)
    with pytest.raises(ValueError):
        fa.flash_attention_bshd(q, q, q, dropout_rate=0.1, interpret=True)


def test_bias_grad_is_zero_by_contract():
    """The kernel defines d(bias) = 0 (mask-only contract) — make sure
    nothing leaks through and q/k/v grads are still correct with bias."""
    rng = np.random.RandomState(3)
    B, H, S, D = 1, 1, 128, 64
    q, k, v = (_rand(rng, B, H, S, D) for _ in range(3))
    bias = _rand(rng, B, 1, S, S) * 0.1

    def ker_loss(bias):
        o = fa.flash_attention_bshd(q, k, v, bias, interpret=True)
        return jnp.sum(o)

    g = jax.grad(ker_loss)(bias)
    assert float(jnp.abs(g).max()) == 0.0


def test_causal_fwd_matches_reference():
    rng = np.random.RandomState(10)
    B, H, S, D = 2, 2, 256, 64
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
               for _ in range(3))
    out = fa.flash_attention_bshd(q, k, v, causal=True, interpret=True)
    ref = fa._reference(q.reshape(B * H, S, D), k.reshape(B * H, S, D),
                        v.reshape(B * H, S, D), None, causal=True)
    np.testing.assert_allclose(np.asarray(out.reshape(B * H, S, D)),
                               np.asarray(ref), atol=2e-4)


def test_causal_grads_match_reference():
    rng = np.random.RandomState(11)
    B, H, S, D = 1, 2, 256, 64
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
               for _ in range(3))

    def ker_loss(q, k, v):
        o = fa.flash_attention_bshd(q, k, v, causal=True, interpret=True)
        return jnp.sum(jnp.sin(o))

    def ref_loss(q, k, v):
        o = fa._reference(q.reshape(B * H, S, D), k.reshape(B * H, S, D),
                          v.reshape(B * H, S, D), None, causal=True)
        return jnp.sum(jnp.sin(o))

    gk = jax.grad(ker_loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gk):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-4,
                                   err_msg=f"d{name}")


def test_causal_with_padding_bias():
    """Causal + padding mask combined (decoder with padded batch)."""
    rng = np.random.RandomState(12)
    B, H, S, D = 2, 2, 256, 64
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
               for _ in range(3))
    mask = (rng.rand(B, 1, 1, S) > 0.2).astype(np.float32)
    bias = jnp.asarray((1 - mask) * -1e9) * jnp.ones((1, 1, S, 1))
    out = fa.flash_attention_bshd(q, k, v, bias, causal=True,
                                  interpret=True)
    ref = fa._reference(q.reshape(B * H, S, D), k.reshape(B * H, S, D),
                        v.reshape(B * H, S, D), bias.reshape(B, S, S),
                        causal=True)
    np.testing.assert_allclose(np.asarray(out.reshape(B * H, S, D)),
                               np.asarray(ref), atol=2e-4)


# ---------------------------------------------------------------------------
# the one-tile lowering (ops/pallas/attention_tile.py): Sq == Sk == 128,
# non-causal, operands in the op's own (B, S, H*D) layout
# ---------------------------------------------------------------------------


def _tile_case(dtype, bias_kind, n_head=4, d=64, batch=3):
    from paddle_tpu.ops.pallas import attention_tile as at
    rng = np.random.RandomState(7)
    S = at.TILE
    q, k, v, g = (jnp.asarray(rng.randn(batch, S, n_head * d)
                              .astype(np.float32) * 0.5, dtype)
                  for _ in range(4))
    bias = None
    if bias_kind == "full":         # bert.py's [B, 1, S, S] mask bias
        m = (rng.rand(batch, 1, S, S) > 0.2).astype(np.float32)
        m[0, 0, 3, :] = 0.0         # a fully masked row
        bias = jnp.asarray((m - 1.0) * 1e4)
    elif bias_kind == "keys":       # the KVMask-derived [B, 1, 1, S]
        m = (rng.rand(batch, 1, 1, S) > 0.2).astype(np.float32)
        bias = jnp.asarray((m - 1.0) * 1e9)
    return at, (q, k, v), g, bias


def _rel_l2(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def _tile_matches_reference(dtype, bias_kind, tol, n_head=4, d=64, batch=3):
    """Forward and dq/dk/dv of the one-tile kernels against
    reference_attention in (B, S, H*D) layout."""
    from paddle_tpu.ops.attention_ops import reference_attention
    at, qkv, g, bias = _tile_case(dtype, bias_kind, n_head, d, batch)
    out, vjp = jax.vjp(lambda *a: at.attention_tile_bsd(
        *a, bias, n_head=n_head, interpret=True), *qkv)
    ref, ref_vjp = jax.vjp(lambda *a: reference_attention(
        *a, bias, n_head, 0.0, None, True), *qkv)
    assert out.shape == ref.shape and out.dtype == dtype
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert _rel_l2(out, ref) <= tol
    for got, want in zip(vjp(g), ref_vjp(g)):
        assert got.dtype == dtype
        assert _rel_l2(got, want) <= tol


@pytest.mark.parametrize("bias_kind", [None, "full", "keys"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 8e-3)])
def test_tile_fwd_and_grads_match_reference(dtype, tol, bias_kind):
    """With and without a bias, f32 and bf16; the full bias carries a
    fully masked row (uniform attention, no NaN)."""
    _tile_matches_reference(dtype, bias_kind, tol)


@pytest.mark.parametrize("n_head,d,batch", [(12, 64, 8), (2, 128, 1),
                                            (6, 64, 5)])
def test_tile_head_layouts_and_row_counts(n_head, d, batch):
    """D == 64 (two heads to a 128-lane group), D == 128 (one), and
    batches that give 4, 1 and 1 rows to a grid step."""
    _tile_matches_reference(jnp.float32, "full", 2e-6, n_head, d, batch)


def test_tile_shape_rule():
    from paddle_tpu.ops.pallas import attention_tile as at
    assert at.tiles(128, 128, 12, 64) == (True, "")
    assert at.tiles(128, 128, 12, 64, bias_shape=(96, 1, 128, 128))[0]
    assert at.tiles(128, 128, 12, 64, bias_shape=(96, 1, 1, 128))[0]
    for args, kw, why in (
            ((256, 256, 12, 64), {}, "one-tile:256x256"),
            ((128, 512, 12, 64), {}, "one-tile:128x512"),
            ((128, 128, 12, 64), {"causal": True}, "one-tile:causal"),
            ((128, 128, 3, 64), {}, "one-tile:head-dim:64x3"),
            ((128, 128, 4, 32), {}, "one-tile:head-dim:32x4"),
            ((128, 128, 32, 64), {}, "one-tile:width:2048"),
            ((128, 128, 12, 64), {"bias_shape": (96, 12, 128, 128)},
             "one-tile:bias-shape")):
        assert at.tiles(*args, **kw) == (False, why)
    # the rule and the wrapper agree
    q = jnp.zeros((1, 256, 128), jnp.float32)
    with pytest.raises(ValueError, match="one-tile:256x256"):
        at.attention_tile_bsd(q, q, q, n_head=2, interpret=True)
    q = jnp.zeros((1, 128, 128), jnp.float32)
    with pytest.raises(ValueError, match="requires a seed"):
        at.attention_tile_bsd(q, q, q, n_head=2, dropout_rate=0.1)
    # rows per grid step: the largest divisor that fits VMEM
    assert at._rows_per_step(96, 128 * 768 * 2) == 4
    assert at._rows_per_step(96, 128 * 768 * 4) == 2
    assert at._rows_per_step(7, 128 * 768 * 2) == 1


def test_tile_bias_grad_is_zero_by_contract():
    at, qkv, g, bias = _tile_case(jnp.float32, "full")
    db = jax.grad(lambda b: jnp.sum(at.attention_tile_bsd(
        *qkv, b, n_head=4, interpret=True)))(bias)
    assert float(jnp.max(jnp.abs(db))) == 0.0
