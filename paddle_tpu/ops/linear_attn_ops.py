"""Linear-attention layers of the gated delta rule (Gated DeltaNet) for
the serving path: the recurrence, the short causal convolution in front
of it and the gated per-head norm behind it (no reference analog;
benchmark/reference/olmo_hybrid_jnp.py writes the equations out token by
token).

Per head, with ``q_t`` L2-normalised and scaled by ``d_k^-1/2``, ``k_t``
L2-normalised, ``alpha_t = exp(g_t)``, ``g_t = -exp(A_log) softplus(a_t +
dt_bias)`` and ``beta_t = beta_scale sigmoid(b_t)``::

    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t

``gated_delta_rule`` keeps ``S^T`` (``[d_k, d_v]`` float32 a head) and
computes the same thing in one of two forms, picked from the query's
length, never from a flag or a model's name:

* **recurrent** (one token a row, a ``StatePool``): the update as
  written.  On a TPU the ``gdn_decode`` kernel, which moves only the
  slots the launch's ``StateSlot`` names, in place;
* **chunked** (a prefill chunk, a packed prefill row, a scored prefix):
  sub-chunks of ``SUB_CHUNK`` tokens by the WY / UT transform
  (:func:`wy_transform`, batched over all sub-chunks), then a chain over
  sub-chunks that only the state links — ``lax.scan`` here, the
  ``gdn_chunk`` kernel on a TPU.  The state comes from the row's slot, or
  from zero where ``Fresh`` says the sequence starts in this launch (a
  select on the device: no host-side clear, leftovers of a slot's last
  owner never reach a sum), and goes back to the slot.  Positions where
  ``Valid`` is 0 (past a prompt's end) are identity updates (``alpha =
  1``, ``beta = 0``).

``causal_conv1d`` is the depthwise convolution over time with SiLU; with
a ``TailPool`` it reads the previous ``kernel - 1`` inputs of the row's
sequence from the row's slot and leaves the last ``kernel - 1`` VALID
inputs there.  ``gated_rms_norm`` is ``rms_h(o) * gain * silu(gate)``
over each head's ``d_v`` values.

State pools are engine state like the KV pools: persistables the served
programs update in place (``analysis.verify_decode`` admits writes to
declared cache vars only), one slot a live sequence instead of a block
table (serving/decode.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .pallas.gated_delta import SUB_CHUNK
from .registry import pallas_route, register, x

L2_EPS = 1e-6
_HI = lax.Precision.HIGHEST


def l2_normalize(t):
    """``t / sqrt(sum t^2 + eps)`` over the last axis, float32."""
    t = t.astype(jnp.float32)
    return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)


def decay_and_beta(a, b, a_log, dt_bias, beta_scale):
    """The layer's two per-head gates, float32: ``g = -exp(A_log)
    softplus(a + dt_bias)`` (a log decay, <= 0) and ``beta = beta_scale
    sigmoid(b)``."""
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return g, beta_scale * jax.nn.sigmoid(b.astype(jnp.float32))


def recurrent_step(state, q, k, v, alpha, beta):
    """One token: state (B, H, d_k, d_v), q / k (B, H, d_k), v (B, H, d_v),
    alpha / beta (B, H) -> (o (B, H, d_v), the new state)."""
    state = state * alpha[..., None, None]
    u = jnp.einsum("bhkv,bhk->bhv", state, k, precision=_HI)
    state = state + k[..., :, None] * (beta[..., None] * (v - u))[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", state, q, precision=_HI), state


def wy_transform(q, k, v, g, beta, sub_chunk=SUB_CHUNK):
    """Everything of the chunked form that no state enters, for all
    sub-chunks at once.  q, k (B, H, S, d_k), v (B, H, S, d_v), g, beta
    (B, H, S), ``S`` a multiple of ``sub_chunk`` -> w, u, qg, attn, kgt,
    dlast as ``pallas.gated_delta.gdn_chunk`` takes them."""
    b, h, s, dk = q.shape
    c = sub_chunk
    n = s // c

    def cut(t):
        return t.reshape(b, h, n, c, *t.shape[3:])

    q, k, v, g, beta = (cut(t) for t in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=-1)                      # within a sub-chunk
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    # exp of masked differences only: gamma_i - gamma_j <= 0 for j <= i
    decay = jnp.exp(jnp.where(col <= row,
                              gamma[..., :, None] - gamma[..., None, :],
                              -jnp.inf))
    kb = k * beta[..., None]
    kk = jnp.einsum("...ik,...jk->...ij", kb, k, precision=_HI)
    a = jnp.where(col < row, kk * decay, 0.0)
    # T = (I + A)^-1, A strictly lower triangular: a forward substitution
    t = lax.linalg.triangular_solve(
        a + jnp.eye(c, dtype=a.dtype),
        jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape),
        left_side=True, lower=True, unit_diagonal=True)
    eg = jnp.exp(gamma)[..., None]
    w = jnp.einsum("...ij,...jk->...ik", t, kb * eg, precision=_HI)
    u = jnp.einsum("...ij,...jv->...iv", t, v * beta[..., None],
                   precision=_HI)
    attn = jnp.einsum("...ik,...jk->...ij", q, k, precision=_HI) * decay
    kg = k * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    return (w, u, q * eg, attn, jnp.swapaxes(kg, -1, -2),
            jnp.exp(gamma[..., -1]))


def chain_sub_chunks(w, u, qg, attn, kgt, dlast, state):
    """The chain over sub-chunks in plain ``jax.numpy`` (the CPU path and
    the spec of the ``gdn_chunk`` kernel): state (B, H, d_k, d_v) ->
    (o (B, H, N, C, d_v), the state after the last sub-chunk)."""
    def step(s, xs):
        w_n, u_n, qg_n, attn_n, kgt_n, dl_n = xs
        v_new = u_n - jnp.einsum("bhck,bhkv->bhcv", w_n, s, precision=_HI)
        o = jnp.einsum("bhck,bhkv->bhcv", qg_n, s, precision=_HI) \
            + jnp.einsum("bhij,bhjv->bhiv", attn_n, v_new, precision=_HI)
        s = s * dl_n[..., None, None] \
            + jnp.einsum("bhkc,bhcv->bhkv", kgt_n, v_new, precision=_HI)
        return s, o

    xs = tuple(jnp.moveaxis(t, 2, 0) for t in (w, u, qg, attn, kgt, dlast))
    state, o = lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 2), state


def _heads(t, h):
    """(B, S, H * d) -> (B, H, S, d)."""
    b, s, hd = t.shape
    return t.reshape(b, s, h, hd // h).transpose(0, 2, 1, 3)


def _prepare(ins, attrs):
    """The op's inputs as the forms take them: q (normalised, scaled), k
    (normalised), v as (B, H, S, d) float32; g, beta (B, H, S) float32 with
    invalid positions made identity updates."""
    h = int(attrs["n_head"])
    q, k, v = (_heads(x(ins, s), h) for s in ("Q", "K", "V"))
    q = l2_normalize(q) * float(q.shape[-1]) ** -0.5
    k = l2_normalize(k)
    g, beta = decay_and_beta(x(ins, "A"), x(ins, "B"), x(ins, "ALog"),
                             x(ins, "DtBias"),
                             float(attrs.get("beta_scale", 1.0)))
    valid = x(ins, "Valid")
    if valid is not None:
        live = valid.astype(bool)[..., None]
        g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    return q, k, v.astype(jnp.float32), g.transpose(0, 2, 1), \
        beta.transpose(0, 2, 1)


def _merge(o, dtype):
    """(B, H, S, d_v) -> (B, S, H * d_v)."""
    b, h, s, dv = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, s, h * dv).astype(dtype)


def _start_state(pool, slot, fresh, b, h, dk, dv):
    if pool is None:
        return jnp.zeros((b, h, dk, dv), jnp.float32)
    state = jnp.take(pool, slot, axis=0)
    if fresh is None:
        return state
    return jnp.where(fresh.astype(bool)[:, None, None, None], 0.0, state)


def _pad_time(t, pad):
    return jnp.pad(t, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 3))


def _chunked(ins, attrs, kernel):
    """The chunked route: ``kernel`` runs the chain over sub-chunks on
    the ``gdn_chunk`` Pallas kernel, else ``lax.scan`` does."""
    q, k, v, g, beta = _prepare(ins, attrs)
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    pad = -s % SUB_CHUNK
    if pad:                     # identity updates: g = 0, beta = 0
        q, k, v, g, beta = (_pad_time(t, pad) for t in (q, k, v, g, beta))
    parts = wy_transform(q, k, v, g, beta)
    pool, slot, fresh = x(ins, "StatePool"), x(ins, "StateSlot"), \
        x(ins, "Fresh")
    if kernel:
        from .pallas.gated_delta import gdn_chunk
        if pool is None:        # a scored prefix: a state nobody keeps
            o, _ = gdn_chunk(*parts, jnp.zeros((b, h, dk, dv), jnp.float32),
                             jnp.arange(b, dtype=jnp.int32),
                             jnp.ones((b,), jnp.int32))
        else:
            o, pool = gdn_chunk(
                *parts, pool, slot.astype(jnp.int32),
                jnp.zeros((b,), jnp.int32) if fresh is None
                else fresh.astype(jnp.int32))
    else:
        slot = None if slot is None else slot.astype(jnp.int32)
        o, state = chain_sub_chunks(
            *parts, _start_state(pool, slot, fresh, b, h, dk, dv))
        if pool is not None:
            pool = pool.at[slot].set(state)
    out = {"Out": _merge(o.reshape(b, h, s + pad, dv)[:, :, :s],
                         x(ins, "Q").dtype)}
    if pool is not None:
        out["StatePoolOut"] = pool
    return out


def _recurrent(ins, attrs, kernel):
    """The recurrent route: one token a row against the row's slot."""
    q, k, v, g, beta = _prepare(ins, attrs)
    pool = x(ins, "StatePool")
    slot = x(ins, "StateSlot").astype(jnp.int32)
    q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
    alpha, beta = jnp.exp(g[:, :, 0]), beta[:, :, 0]
    if kernel:
        from .pallas.gated_delta import gdn_decode
        o, pool = gdn_decode(q, k, v, alpha, beta, pool, slot)
    else:
        o, state = recurrent_step(jnp.take(pool, slot, axis=0), q, k, v,
                                  alpha, beta)
        pool = pool.at[slot].set(state)
    return {"Out": _merge(o[:, :, None], x(ins, "Q").dtype),
            "StatePoolOut": pool}


def lower_gdn_decode(ctx, ins, attrs):
    return _recurrent(ins, attrs, kernel=True)


def lower_gdn_chunk(ctx, ins, attrs):
    return _chunked(ins, attrs, kernel=True)


def is_recurrent(ins) -> bool:
    """One token a row over a state pool: the recurrent form's shape."""
    q = x(ins, "Q")
    return x(ins, "StatePool") is not None and int(q.shape[1]) == 1 \
        and x(ins, "Fresh") is None


@register("gated_delta_rule")
def _gated_delta_rule(ctx, ins, attrs):
    recurrent = is_recurrent(ins)
    route, _ = pallas_route("gated_delta_rule", ins, attrs,
                            kernel="gdn_decode" if recurrent
                            else "gdn_chunk")
    if route is not None:
        return route.lower(ctx, ins, attrs)
    return (_recurrent if recurrent else _chunked)(ins, attrs, kernel=False)


@register("causal_conv1d")
def _causal_conv1d(ctx, ins, attrs):
    """Depthwise causal convolution over time, then SiLU.  ``X`` (B, S,
    C), ``W`` (kernel, C): ``out_t = silu(sum_j W_j x_{t - (kernel-1) +
    j})``, inputs before the sequence's start zero.  With ``TailPool``
    (slots, (kernel-1) * C) and ``StateSlot`` (B,) the row's previous
    ``kernel - 1`` inputs come from its slot (zeros where ``Fresh``), and
    the last ``kernel - 1`` inputs of the sequence so far — counting
    only the first ``sum(Valid)`` positions of this launch — go back."""
    xv, w = x(ins, "X"), x(ins, "W")
    b, s, c = xv.shape
    taps = int(w.shape[0])
    pool = x(ins, "TailPool")
    if pool is None:
        tail = jnp.zeros((b, taps - 1, c), xv.dtype)
    else:
        slot = x(ins, "StateSlot").astype(jnp.int32)
        tail = jnp.take(pool, slot, axis=0).reshape(b, taps - 1, c)
        fresh = x(ins, "Fresh")
        if fresh is not None:
            tail = jnp.where(fresh.astype(bool)[:, None, None], 0, tail)
    ext = jnp.concatenate([tail.astype(xv.dtype), xv], axis=1)
    acc = sum(ext[:, j:j + s].astype(jnp.float32)
              * w[j].astype(jnp.float32) for j in range(taps))
    out = {"Out": jax.nn.silu(acc).astype(xv.dtype)}
    if pool is not None:
        valid = x(ins, "Valid")
        n = jnp.full((b,), s, jnp.int32) if valid is None \
            else jnp.sum(valid.astype(jnp.int32), axis=1)
        idx = n[:, None] + jnp.arange(taps - 1, dtype=jnp.int32)[None, :]
        new_tail = jnp.take_along_axis(ext, idx[:, :, None], axis=1)
        out["TailPoolOut"] = pool.at[slot].set(
            new_tail.reshape(b, -1).astype(pool.dtype))
    return out


@register("gated_rms_norm")
def _gated_rms_norm(ctx, ins, attrs):
    """``rms_h(X) * Scale * silu(Gate)``: RMSNorm over each head's
    ``Scale.size`` values (statistics in float32), gated elementwise."""
    xv, gate, scale = x(ins, "X"), x(ins, "Gate"), x(ins, "Scale")
    d = int(scale.shape[0])
    xf = xv.astype(jnp.float32).reshape(xv.shape[:-1] + (-1, d))
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                       + float(attrs.get("epsilon", 1e-6)))
    y = (y * scale.astype(jnp.float32)).reshape(xv.shape)
    return {"Out": (y * jax.nn.silu(gate.astype(jnp.float32)))
            .astype(xv.dtype)}


__all__ = ["l2_normalize", "decay_and_beta", "recurrent_step",
           "wy_transform", "chain_sub_chunks"]
