"""The gated delta rule's two serving forms as Pallas TPU kernels, over a
pool of per-sequence recurrent states that both read and write IN PLACE.

A state is ``S^T`` of the published recurrence (``S in R^{d_v x d_k}``,
``S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T``,
``o_t = S_t q_t``), kept as ``[d_k, d_v]`` float32 so that a token's key
scales ROWS and the value / output vectors lie along the lanes; the pool
is ``[slots, heads, d_k, d_v]``.  Which slot a row of the launch owns is
a scalar-prefetched ``[B]`` vector: the block specs pick the slot, the
pool is aliased input to output, and only the slots a launch names are
moved.

* :func:`gdn_decode` — the recurrent route, one token a row: a grid step
  holds ``heads_per_step`` heads of one row's state in VMEM, applies the
  update above elementwise (the key and query as COLUMNS, made from
  their rows by an iota select and a lane sum: no transpose, no MXU pass
  that would round the state) and writes the state back.  Bound by the
  state's bytes: read once, written once.
* :func:`gdn_chunk` — the chunked route's sequential part.  The WY / UT
  transform of a sub-chunk (``ops/linear_attn_ops.wy_transform``: the
  unit-lower-triangular solve and the products around it, batched over
  every sub-chunk at once by XLA) leaves, per sub-chunk of ``C`` tokens,
  ``w`` ``[C, d_k]``, ``u`` ``[C, d_v]``, the decayed queries and keys
  and the masked ``q k^T``; what is left is a chain over sub-chunks that
  only the state links: ``v' = u - w S``, ``o = q~ S + (q k^T . L) v'``,
  ``S <- S e^{gamma_C} + k~^T v'``.  The kernel runs that chain with the
  state in VMEM scratch: read from the row's slot at the first sub-chunk
  (or started from zero where the launch's ``fresh`` flag says the
  sequence begins here: a select, not a host-side clear), written back
  after the last.

Both take every matrix product at ``Precision.HIGHEST``: the state is
float32 and the products are a few per cent of a launch's time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: tokens of one sub-chunk of the chunked route
SUB_CHUNK = 64
#: a decode grid step holds at most this many heads of one row's state
MAX_HEADS_PER_STEP = 8

_HI = lax.Precision.HIGHEST


def supported(n_head, key_dim, value_dim, state_dtype="float32"):
    """Static shape rule -> (ok, reason) of both kernels."""
    if jnp.dtype(state_dtype) != jnp.float32:
        return False, f"gdn:state-dtype:{jnp.dtype(state_dtype).name}"
    if key_dim % 8 or key_dim > LANES:
        return False, f"gdn:key-dim:{key_dim}"
    if value_dim % 8:
        return False, f"gdn:value-dim:{value_dim}"
    if n_head < 1:
        return False, f"gdn:heads:{n_head}"
    return True, ""


def heads_per_step(n_head: int) -> int:
    return max(h for h in range(1, min(n_head, MAX_HEADS_PER_STEP) + 1)
               if n_head % h == 0)


# ---------------------------------------------------------------------------
# recurrent route
# ---------------------------------------------------------------------------

def _column(row, n):
    """(1, LANES) -> (n, 1): element ``i`` of the row in sublane ``i``."""
    r = lax.broadcasted_iota(jnp.int32, (n, LANES), 0)
    c = lax.broadcasted_iota(jnp.int32, (n, LANES), 1)
    return jnp.sum(jnp.where(r == c, jnp.broadcast_to(row, (n, LANES)), 0.0),
                   axis=1, keepdims=True)


def _decode_kernel(slot_ref, q_ref, k_ref, v_ref, a_ref, b_ref, s_ref,
                   o_ref, s_out_ref):
    hb, dk, _ = s_ref.shape
    for i in range(hb):
        state = s_ref[i] * a_ref[i]                     # alpha S
        k_col = _column(k_ref[i], dk)
        u = jnp.sum(state * k_col, axis=0, keepdims=True)       # (1, dv)
        delta = b_ref[i] * (v_ref[i] - u)
        state = state + k_col * delta
        o_ref[i] = jnp.sum(state * _column(q_ref[i], dk), axis=0,
                           keepdims=True)
        s_out_ref[i] = state


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_decode(q, k, v, alpha, beta, pool, slot, *, interpret=False):
    """q, k: (B, H, d_k) float32, already normalised and scaled; v: (B, H,
    d_v); alpha, beta: (B, H); pool: (slots, H, d_k, d_v) float32; slot:
    (B,) int32.  Returns (o (B, H, d_v) float32, the pool with the rows'
    slots advanced one token)."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    hb = heads_per_step(h)
    pad = ((0, 0), (0, 0), (0, 0), (0, LANES - dk))

    def row4(t):
        return t.astype(jnp.float32).reshape(b, h, 1, -1)

    def spec(last):
        return pl.BlockSpec((None, hb, 1, last),
                            lambda i, j, *_: (i, j, 0, 0))

    state_spec = pl.BlockSpec((None, hb, dk, dv),
                              lambda i, j, slot_ref: (slot_ref[i], j, 0, 0))
    out, pool = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h // hb),
            in_specs=[spec(LANES), spec(LANES), spec(dv), spec(1), spec(1),
                      state_spec],
            out_specs=[spec(dv), state_spec]),
        out_shape=[jax.ShapeDtypeStruct((b, h, 1, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the scalar-prefetch argument: the pool is the 7th
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=8 * b * h * dk * dv, transcendentals=0,
            bytes_accessed=8 * b * h * dk * dv),
        interpret=interpret,
        name="gdn_decode",
    )(slot.astype(jnp.int32), jnp.pad(row4(q), pad), jnp.pad(row4(k), pad),
      row4(v), row4(alpha), row4(beta), pool)
    return out.reshape(b, h, dv), pool


# ---------------------------------------------------------------------------
# chunked route: the chain over sub-chunks
# ---------------------------------------------------------------------------

def _chunk_kernel(slot_ref, fresh_ref, w_ref, u_ref, qg_ref, attn_ref,
                  kgt_ref, dl_ref, s_ref, o_ref, s_out_ref, state):
    row, n = pl.program_id(0), pl.program_id(2)

    @pl.when(n == 0)
    def _():
        state[...] = jnp.where(fresh_ref[row] != 0, 0.0, s_ref[...])

    s = state[...]
    v_new = u_ref[...] - jnp.dot(w_ref[...], s, precision=_HI,
                                 preferred_element_type=jnp.float32)
    o_ref[...] = jnp.dot(qg_ref[...], s, precision=_HI,
                         preferred_element_type=jnp.float32) \
        + jnp.dot(attn_ref[...], v_new, precision=_HI,
                  preferred_element_type=jnp.float32)
    s = s * dl_ref[...] + jnp.dot(kgt_ref[...], v_new, precision=_HI,
                                  preferred_element_type=jnp.float32)
    state[...] = s

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        s_out_ref[...] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_chunk(w, u, qg, attn, kgt, dlast, pool, slot, fresh, *,
              interpret=False):
    """The chain over the ``N`` sub-chunks of every (row, head): ``w``,
    ``qg`` (B, H, N, C, d_k); ``u`` (B, H, N, C, d_v); ``attn`` (B, H, N,
    C, C); ``kgt`` (B, H, N, d_k, C); ``dlast`` (B, H, N); ``pool``
    (slots, H, d_k, d_v) float32; ``slot``, ``fresh`` (B,) int32.  Returns
    (o (B, H, N, C, d_v) float32, the pool with the rows' slots holding
    the state after the last sub-chunk)."""
    b, h, n, c, dk = w.shape
    dv = u.shape[-1]

    def spec(r, cols):
        return pl.BlockSpec((None, None, None, r, cols),
                            lambda i, j, t, *_: (i, j, t, 0, 0))

    state_spec = pl.BlockSpec(
        (None, None, dk, dv),
        lambda i, j, t, slot_ref, fresh_ref: (slot_ref[i], j, 0, 0))
    out, pool = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, h, n),
            in_specs=[spec(c, dk), spec(c, dv), spec(c, dk), spec(c, c),
                      spec(dk, c), spec(1, 1), state_spec],
            out_specs=[spec(c, dv), state_spec],
            scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, n, c, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * n * c * (3 * dk * dv + c * dv),
            transcendentals=0,
            bytes_accessed=4 * b * h * (n * c * (3 * dk + 2 * dv + c)
                                        + 2 * dk * dv)),
        interpret=interpret,
        name="gdn_chunk",
    )(slot.astype(jnp.int32), fresh.astype(jnp.int32), w, u, qg, attn, kgt,
      dlast.reshape(b, h, n, 1, 1), pool)
    return out, pool
