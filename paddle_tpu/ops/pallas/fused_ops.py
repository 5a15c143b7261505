"""Fused elementwise/normalisation Pallas kernels — the TPU analog of the
reference's hand-fused CUDA kernels (ref:
operators/fused/fused_layernorm_residual_dropout_bias.h,
operators/fused/fused_bias_gelu (jit/gen_base.h family)).

XLA already fuses most elementwise chains; these kernels exist for the
cases where owning the schedule still pays on TPU:

- ``layer_norm``: one VMEM pass computes mean/rstd and the normalised
  output per row block (XLA's reduction+broadcast pattern re-reads the
  row); backward recomputes statistics in-kernel so no residual tensor
  but x itself is materialised, and reduces dscale/dbias across row
  blocks inside the same kernel (sequential TPU grid) instead of a
  separate reduction kernel.
- ``bias_gelu``: bias-add + tanh-GELU in one pass; backward recomputes
  the activation input (bandwidth over FLOPs).

The shape gates live in the registry's Pallas channel
(ops/op_specs.py), which reads the bounds below; callers fall back to
the jnp composition off-TPU or at unsupported shapes.  Row counts need
not tile: partial edge blocks mask their reduction contributions
explicitly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

BLOCK_R = 128          # row-block for [R, D] layouts at model widths

#: widest last dim each [R, D] kernel family is admitted at — what the
#: v5e was seen to compile (chip_smoke.py leg K compiles every family at
#: its bound)
LN_MAX_D = 8192
BG_MAX_D = 16384


def _block_rows(d: int) -> int:
    """Rows per (rows, D) block: BLOCK_R at model widths, fewer as D grows
    so that one f32 block stays within 512 KiB.  The backward kernels hold
    three such blocks double-buffered and about as much again in f32
    temporaries, against the 16 MiB default scoped-VMEM limit: at
    (128, 3072) the bias+GELU backward asked the v5e for 17.95 MiB and
    was refused.  A multiple of 16, the bf16 sublane tile."""
    return max(16, min(BLOCK_R, (1 << 17) // d // 16 * 16))


def _row_mask(i, r_total, block_rows):
    rows = i * block_rows + lax.broadcasted_iota(
        jnp.int32, (block_rows, 1), 0)
    return rows < r_total


# ---------------------------------------------------------------------------
# layer_norm
# ---------------------------------------------------------------------------


def _ln_fwd_kernel(x_ref, s_ref, b_ref, y_ref, *, eps):
    xb = x_ref[...].astype(jnp.float32)                      # (BR, D)
    mu = jnp.mean(xb, axis=-1, keepdims=True)
    xc = xb - mu
    rstd = lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    y = xc * rstd * s_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(x_ref, s_ref, dy_ref, dx_ref, ds_ref, db_ref, *,
                   eps, r_total):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    valid = _row_mask(i, r_total, x_ref.shape[0])
    # edge block: interpret/hardware pad rows are undefined (NaN in
    # interpret mode) — zero BOTH operands or 0·NaN poisons the ds sum
    xb = jnp.where(valid, x_ref[...].astype(jnp.float32), 0.0)
    dy = jnp.where(valid, dy_ref[...].astype(jnp.float32), 0.0)
    mu = jnp.mean(xb, axis=-1, keepdims=True)
    xc = xb - mu
    rstd = lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    xhat = xc * rstd
    s = s_ref[...].astype(jnp.float32)
    dys = dy * s
    m1 = jnp.mean(dys, axis=-1, keepdims=True)
    m2 = jnp.mean(dys * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (dys - m1 - xhat * m2)).astype(dx_ref.dtype)
    ds_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True).astype(
        ds_ref.dtype)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True).astype(db_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm(x2, scale, bias, eps=1e-5, interpret=False):
    """Fused LayerNorm over the last dim of x2 [R, D]; scale/bias [D]."""
    y, _ = _ln_fwd(x2, scale, bias, eps, interpret)
    return y


def _ln_fwd(x2, scale, bias, eps, interpret):
    r, d = x2.shape
    br = _block_rows(d)
    grid = (pl.cdiv(r, br),)
    y = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x2.dtype),
        interpret=interpret,
        name="fused_layer_norm_fwd",
    )(x2, scale.reshape(1, d), bias.reshape(1, d))
    return y, (x2, scale)


def _ln_bwd(eps, interpret, res, dy):
    x2, scale = res
    r, d = x2.shape
    br = _block_rows(d)
    grid = (pl.cdiv(r, br),)
    dx, ds, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps, r_total=r),
        grid=grid,
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((br, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                   pl.BlockSpec((1, d), lambda i: (0, 0)),
                   pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, d), x2.dtype),
                   jax.ShapeDtypeStruct((1, d), jnp.float32),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        interpret=interpret,
        name="fused_layer_norm_bwd",
    )(x2, scale.reshape(1, d), dy)
    return dx, ds.reshape(d).astype(scale.dtype), \
        db.reshape(d).astype(scale.dtype)


layer_norm.defvjp(lambda x2, s, b, eps, interp: _ln_fwd(x2, s, b, eps,
                                                        interp),
                  _ln_bwd)


# ---------------------------------------------------------------------------
# residual add + layer_norm (one pass; ref CUDA analog:
# operators/fused/fused_layernorm_residual_dropout_bias.h)
# ---------------------------------------------------------------------------


def _aln_fwd_kernel(a_ref, b_ref, s_ref, bias_ref, y_ref, *, eps):
    u = a_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    mu = jnp.mean(u, axis=-1, keepdims=True)
    uc = u - mu
    rstd = lax.rsqrt(jnp.mean(uc * uc, axis=-1, keepdims=True) + eps)
    y = uc * rstd * s_ref[...].astype(jnp.float32) \
        + bias_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _aln_bwd_kernel(a_ref, b_ref, s_ref, dy_ref, dx_ref, ds_ref, db_ref,
                    *, eps, r_total):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    valid = _row_mask(i, r_total, a_ref.shape[0])
    u = jnp.where(valid, a_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32), 0.0)
    dy = jnp.where(valid, dy_ref[...].astype(jnp.float32), 0.0)
    mu = jnp.mean(u, axis=-1, keepdims=True)
    uc = u - mu
    rstd = lax.rsqrt(jnp.mean(uc * uc, axis=-1, keepdims=True) + eps)
    uhat = uc * rstd
    s = s_ref[...].astype(jnp.float32)
    dys = dy * s
    m1 = jnp.mean(dys, axis=-1, keepdims=True)
    m2 = jnp.mean(dys * uhat, axis=-1, keepdims=True)
    # du is shared by BOTH addends (d/da = d/db)
    dx_ref[...] = (rstd * (dys - m1 - uhat * m2)).astype(dx_ref.dtype)
    ds_ref[...] += jnp.sum(dy * uhat, axis=0, keepdims=True).astype(
        ds_ref.dtype)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True).astype(db_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def add_layer_norm(a2, b2, scale, bias, eps=1e-5, interpret=False):
    """Fused LN(a2 + b2) over the last dim; a2/b2 [R, D], scale/bias [D].
    The residual never materialises in HBM."""
    y, _ = _aln_fwd(a2, b2, scale, bias, eps, interpret)
    return y


def _aln_fwd(a2, b2, scale, bias, eps, interpret):
    r, d = a2.shape
    br = _block_rows(d)
    y = pl.pallas_call(
        functools.partial(_aln_fwd_kernel, eps=eps),
        grid=(pl.cdiv(r, br),),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), a2.dtype),
        interpret=interpret,
        name="fused_add_layer_norm_fwd",
    )(a2, b2, scale.reshape(1, d), bias.reshape(1, d))
    return y, (a2, b2, scale)


def _aln_bwd(eps, interpret, res, dy):
    a2, b2, scale = res
    r, d = a2.shape
    br = _block_rows(d)
    dx, ds, db = pl.pallas_call(
        functools.partial(_aln_bwd_kernel, eps=eps, r_total=r),
        grid=(pl.cdiv(r, br),),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((br, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                   pl.BlockSpec((1, d), lambda i: (0, 0)),
                   pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, d), a2.dtype),
                   jax.ShapeDtypeStruct((1, d), jnp.float32),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        interpret=interpret,
        name="fused_add_layer_norm_bwd",
    )(a2, b2, scale.reshape(1, d), dy)
    return dx, dx, ds.reshape(d).astype(scale.dtype), \
        db.reshape(d).astype(scale.dtype)


add_layer_norm.defvjp(
    lambda a2, b2, s, b, eps, interp: _aln_fwd(a2, b2, s, b, eps, interp),
    _aln_bwd)


# ---------------------------------------------------------------------------
# bias + gelu
# ---------------------------------------------------------------------------


def _erf_f32(x):
    # Mosaic (jax 0.9.0) has no lowering for lax.erf: Abramowitz & Stegun
    # 7.1.26, |error| <= 1.5e-7 — f32 rounding level, so the fused path
    # still matches the stock erf GELU
    ax = jnp.abs(x)
    t = 1.0 / (1.0 + 0.3275911 * ax)
    poly = t * (0.254829592 + t * (-0.284496736 + t * (
        1.421413741 + t * (-1.453152027 + t * 1.061405429))))
    y = 1.0 - poly * jnp.exp(-ax * ax)
    return jnp.where(x < 0, -y, y)


def _gelu_f32(u):
    # EXACT erf GELU — must match the stock gelu op (math_ops.py uses
    # jax.nn.gelu(approximate=False)); a tanh approximation here would
    # silently change numerics between fused/unfused paths
    return 0.5 * u * (1.0 + _erf_f32(u * 0.7071067811865476))


def _dgelu_f32(u):
    cdf = 0.5 * (1.0 + _erf_f32(u * 0.7071067811865476))
    pdf = 0.3989422804014327 * jnp.exp(-0.5 * u * u)   # 1/sqrt(2π)
    return cdf + u * pdf


def _bg_fwd_kernel(x_ref, b_ref, y_ref):
    u = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = _gelu_f32(u).astype(y_ref.dtype)


def _bg_bwd_kernel(x_ref, b_ref, dy_ref, dx_ref, db_ref, *, r_total):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        db_ref[...] = jnp.zeros_like(db_ref)

    valid = _row_mask(i, r_total, x_ref.shape[0])
    xb = jnp.where(valid, x_ref[...].astype(jnp.float32), 0.0)
    u = xb + b_ref[...].astype(jnp.float32)
    dy = jnp.where(valid, dy_ref[...].astype(jnp.float32), 0.0)
    dx = dy * _dgelu_f32(u)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    db_ref[...] += jnp.sum(dx, axis=0, keepdims=True).astype(db_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def bias_gelu(x2, bias, interpret=False):
    """gelu(x2 + bias) fused, x2 [R, D], bias [D]."""
    y, _ = _bg_fwd(x2, bias, interpret)
    return y


def _bg_fwd(x2, bias, interpret):
    r, d = x2.shape
    br = _block_rows(d)
    y = pl.pallas_call(
        _bg_fwd_kernel,
        grid=(pl.cdiv(r, br),),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x2.dtype),
        interpret=interpret,
        name="fused_bias_gelu_fwd",
    )(x2, bias.reshape(1, d))
    return y, (x2, bias)


def _bg_bwd(interpret, res, dy):
    x2, bias = res
    r, d = x2.shape
    br = _block_rows(d)
    dx, db = pl.pallas_call(
        functools.partial(_bg_bwd_kernel, r_total=r),
        grid=(pl.cdiv(r, br),),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((br, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                   pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, d), x2.dtype),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        interpret=interpret,
        name="fused_bias_gelu_bwd",
    )(x2, bias.reshape(1, d), dy)
    return dx, db.reshape(d).astype(bias.dtype)


bias_gelu.defvjp(lambda x2, b, interp: _bg_fwd(x2, b, interp), _bg_bwd)
