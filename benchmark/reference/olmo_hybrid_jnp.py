"""Olmo-Hybrid in plain ``jax.numpy``: the yardstick that decides
``correct`` for the ``serve_hybrid`` cells.

Float32 throughout, ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no chunked form, no batching and no import from
``paddle_tpu``: weights arrive as a plain ``{name: array}`` dict read from
the program's scope (bfloat16 there; every one is widened to float32
where it is used, so the yardstick computes in f32 on the SAME rounded
weights).  Every size is a key of the published ``config.json``
(https://huggingface.co/allenai/Olmo-Hybrid-7B), with ``rms(x) = x /
sqrt(mean(x^2) + rms_norm_eps) * g``:

* block ``l`` (OLMo 2/3's reordered norm, both kinds of layer): ``h = x +
  rms(Mixer_l(x))``, ``y = h + rms(MLP(h))``, ``MLP(h) = (silu(h W_g) * h
  W_u) W_d`` of width ``intermediate_size``; after the last layer ``rms``
  and an untied head.
* ``layer_types[l] == "linear_attention"`` (per head ``h`` of
  ``linear_num_key_heads``; ``d_k = linear_key_head_dim``, ``d_v =
  linear_value_head_dim``):

  1. ``[q' | k' | v'] = x W_qkv``;
  2. a depthwise causal convolution over time of
     ``linear_conv_kernel_dim`` taps on every channel, then SiLU: ``c_t =
     silu(sum_j w_j u_{t-3+j})``, inputs before the sequence's start zero
     — written as a sum over four shifted copies;
  3. ``q_t = q_t / sqrt(|q_t|^2 + 1e-6) * d_k^-1/2``, ``k_t = k_t /
     sqrt(|k_t|^2 + 1e-6)`` per head;
  4. ``beta_t = 2 sigmoid(x_t W_b)`` (the 2 is ``linear_allow_neg_eigval``),
     ``alpha_t = exp(-exp(A_log) softplus(x_t W_a + dt_bias))``;
  5. ``S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T``,
     ``S_0 = 0``, ``o_t = S_t q_t`` — a TOKEN-BY-TOKEN ``lax.scan`` (the
     program's chunked form must stay independent of this);
  6. ``y_t = W_o [rms_h(o_t) * gain (.) silu(x_t W_g)]``, the RMSNorm over
     each head's ``d_v`` values.
* ``"full_attention"``: ``[q | k | v] = x W_qkv``; ``q <- rms(q)``, ``k <-
  rms(k)`` over the whole width with a learned gain; NO position signal
  (``rope_theta: null``); ``num_attention_heads`` heads, ``score_h(i, j) =
  q_h,i . k_h,j * d^-1/2`` for ``j <= i``, softmax in f32 — by blocks of
  ``q_block`` queries so that 16k tokens fit.

``wrong`` (a set of names from :data:`CONTROLS`) computes the forward
pass WRONG in one named way each: the readings a cell's limits must
refuse (benchmark/tests/test_reference_hybrid.py), never the yardstick.
``chunk`` is the program's prefill chunk, which two of the controls break
at.  A name from :data:`ROUNDINGS` is no fault: it keeps the residual
stream, or every activation, of the pass in the configuration's stated
bfloat16, to read how much of the served logits' distance the stated
precision alone accounts for.  ``rows = (lo, hi)`` returns only the logits of those positions: the
head over 100 352 columns is the largest product of the pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"
L2_EPS = 1e-6

#: every way :func:`logits` can be made to compute wrong, by name
CONTROLS = (
    "no_decay",              # alpha = 1
    "beta_sigmoid",          # beta = sigmoid, without the factor 2
    "no_qk_l2norm",          # q and k of the linear layers not normalised
    "no_conv",               # only the current token's tap
    "conv_tail_dropped",     # zeros for the previous chunk's last inputs
                             # at every chunk's first positions
    "state_reset_at_chunk",  # S = 0 at every chunk's first position
    "no_output_gate",        # silu(x W_g) left out
    "pre_norm",              # norm before the mixer and the MLP
    "no_qk_norm",            # the full layers' q / k RMSNorm left out
    "context_minus_block",   # a query does not see the 16 positions
                             # before the newest 16
    "state_bfloat16",        # S rounded to bfloat16 after every token
)

#: quantities kept in the stated precision (readings, not faults)
ROUNDINGS = (
    "residual_bfloat16",     # the residual stream, after each of a
                             # block's two additions
    "activations_bfloat16",  # every tensor the configuration states in
                             # bfloat16: each product's, convolution's,
                             # norm's and mixer's output and the stream
)


def _f32(t):
    return jnp.asarray(t).astype(jnp.float32)


def round_to_bfloat16(x):
    """float32 -> the nearest bfloat16 value (ties to even), as float32,
    in INTEGER arithmetic.  ``x.astype(bfloat16).astype(float32)`` will
    not do on a TPU: XLA may keep the excess precision and drop the pair
    of converts, and the control then computes nothing wrong (found in PR
    36: the state rounded that way read 0.0 off the float32 recurrence)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _kept(x, wrong):
    """An activation as the configuration keeps it between two ops."""
    return round_to_bfloat16(x) if "activations_bfloat16" in wrong else x


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(gain)


def causal_conv(u, w, wrong, chunk):
    """u [T, C], w [taps, C] -> silu of the causal depthwise convolution,
    as an explicit sum over the shifted copies."""
    t, taps = u.shape[0], w.shape[0]
    pos = jnp.arange(t)[:, None]
    acc = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j             # this tap looks `back` tokens back
        if back and "no_conv" in wrong:
            continue
        shifted = jnp.pad(u, ((back, 0), (0, 0)))[:t]
        if back and "conv_tail_dropped" in wrong:
            shifted = jnp.where(pos % chunk < back, 0.0, shifted)
        acc = acc + shifted * w[j]
    return jax.nn.silu(acc)


def delta_rule(q, k, v, alpha, beta, wrong, chunk):
    """The recurrence token by token.  q, k [T, H, d_k], v [T, H, d_v],
    alpha, beta [T, H] -> o [T, H, d_v]."""
    t, h, dk = q.shape
    reset = (jnp.arange(t) % chunk == 0) & (jnp.arange(t) > 0) \
        if "state_reset_at_chunk" in wrong else jnp.zeros((t,), bool)

    def step(s, xs):
        q_t, k_t, v_t, a_t, b_t, r_t = xs
        s = jnp.where(r_t, 0.0, s) * a_t[:, None, None]
        sk = jnp.einsum("hvk,hk->hv", s, k_t)
        s = s + (b_t[:, None] * (v_t - sk))[:, :, None] * k_t[:, None, :]
        o_t = jnp.einsum("hvk,hk->hv", s, q_t)
        if "state_bfloat16" in wrong:
            s = round_to_bfloat16(s)
        return s, o_t

    s0 = jnp.zeros((h, v.shape[-1], dk), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, alpha, beta, reset))[1]


def linear_mixer(x, w, p, m, wrong, chunk):
    h, dk, dv = m["linear_num_key_heads"], m["linear_key_head_dim"], \
        m["linear_value_head_dim"]
    t = x.shape[0]
    mixed = _kept(causal_conv(_kept(x @ _f32(w[p + "_qkv_w"]), wrong),
                              _f32(w[p + "_conv_w"]), wrong, chunk), wrong)
    q = mixed[:, :h * dk].reshape(t, h, dk)
    k = mixed[:, h * dk:2 * h * dk].reshape(t, h, dk)
    v = mixed[:, 2 * h * dk:].reshape(t, h, dv)
    if "no_qk_l2norm" not in wrong:
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    q = q * dk ** -0.5
    beta = jax.nn.sigmoid(_kept(x @ _f32(w[p + "_b_w"]), wrong))
    if m.get("linear_allow_neg_eigval") and "beta_sigmoid" not in wrong:
        beta = 2.0 * beta
    g = -jnp.exp(_f32(w[p + "_a_log"])) * jax.nn.softplus(
        _kept(x @ _f32(w[p + "_a_w"]), wrong) + _f32(w[p + "_dt_bias"]))
    alpha = jnp.ones_like(g) if "no_decay" in wrong else jnp.exp(g)
    o = _kept(delta_rule(q, k, v, alpha, beta, wrong, chunk), wrong)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + m["rms_norm_eps"]) * _f32(w[p + "_o_norm_scale"])
    o = o.reshape(t, h * dv)
    if "no_output_gate" not in wrong:
        o = o * jax.nn.silu(_kept(x @ _f32(w[p + "_g_w"]), wrong))
    return _kept(_kept(o, wrong) @ _f32(w[p + "_o_w"]), wrong)


def full_mixer(x, w, p, m, wrong, q_block):
    h, d = m["num_attention_heads"], m["hidden_size"]
    hd = d // h
    t = x.shape[0]
    q, k, v = jnp.split(_kept(x @ _f32(w[p + "_qkv_w"]), wrong), 3, axis=1)
    if "no_qk_norm" not in wrong:
        q = _kept(rms(q, w[p + "_q_norm_scale"], m["rms_norm_eps"]), wrong)
        k = _kept(rms(k, w[p + "_k_norm_scale"], m["rms_norm_eps"]), wrong)
    qb = min(q_block, t)
    assert t % qb == 0, (t, qb)
    kh = k.reshape(t, h, hd)
    vh = v.reshape(t, h, hd)
    key_pos = jnp.arange(t)[None, None, :]

    def block(args):
        q_blk, start = args
        sc = jnp.einsum("qhd,thd->hqt", q_blk.reshape(qb, h, hd), kh) \
            * hd ** -0.5
        q_pos = (start + jnp.arange(qb))[None, :, None]
        seen = key_pos <= q_pos
        if "context_minus_block" in wrong:
            seen &= (key_pos > q_pos - 16) | (key_pos <= q_pos - 32)
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thd->qhd", pr, vh).reshape(qb, d)

    out = jax.lax.map(block, (q.reshape(t // qb, qb, d),
                              jnp.arange(0, t, qb)))
    return _kept(_kept(out.reshape(t, d), wrong) @ _f32(w[p + "_o_w"]),
                 wrong)


def mlp(x, w, p, wrong):
    h = jax.nn.silu(_kept(x @ _f32(w[p + "_gate_w"]), wrong)) \
        * _kept(x @ _f32(w[p + "_up_w"]), wrong)
    return _kept(_kept(h, wrong) @ _f32(w[p + "_down_w"]), wrong)


@functools.partial(jax.jit, static_argnames=("p", "kind", "model", "wrong",
                                             "q_block", "chunk"))
def _layer(x, w, *, p, kind, model, wrong, q_block, chunk):
    m = dict(model)
    eps = m["rms_norm_eps"]

    def mixer(t):
        if kind == "linear_attention":
            return linear_mixer(t, w, p, m, wrong, chunk)
        return full_mixer(t, w, p, m, wrong, q_block)

    def normed(t, gain):
        return _kept(rms(t, w[p + gain], eps), wrong)

    def add(x, branch):
        y = x + branch
        return round_to_bfloat16(y) if wrong & set(ROUNDINGS) else y

    with jax.default_matmul_precision(PRECISION):
        if "pre_norm" in wrong:
            x = add(x, mixer(normed(x, "_attn_norm_scale")))
            return add(x, mlp(normed(x, "_ffn_norm_scale"), w, p, wrong))
        x = add(x, normed(mixer(x), "_attn_norm_scale"))
        return add(x, normed(mlp(x, w, p, wrong), "_ffn_norm_scale"))


@functools.partial(jax.jit, static_argnames=("eps", "wrong"))
def _head(x, gain, w, *, eps, wrong):
    with jax.default_matmul_precision(PRECISION):
        return _kept(rms(x, gain, eps), wrong) @ _f32(w)


def logits(weights, seq, model, layer_prefix="hybrid_layer_", wrong=(),
           q_block=256, chunk=1024, rows=None):
    """Next-token logits [len(seq) or hi - lo, vocab] float32 of ONE full
    causal pass over the token ids ``seq``.  ``model`` holds the published
    keys (``layer_types`` cut to the depth held); each layer is a compiled
    function of its own weights."""
    wrong = frozenset(wrong)
    unknown = wrong - set(CONTROLS) - set(ROUNDINGS)
    if unknown:
        raise ValueError(f"unknown controls {sorted(unknown)}")
    keys = tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, bool))))
    x = _f32(jnp.asarray(weights["word_embedding"])[jnp.asarray(seq)])
    for i, kind in enumerate(model["layer_types"]):
        p = f"{layer_prefix}{i}"
        own = {n: t for n, t in weights.items() if n.startswith(p + "_")}
        x = _layer(x, own, p=p, kind=kind, model=keys, wrong=wrong,
                   q_block=q_block, chunk=chunk)
    if rows is not None:
        x = x[rows[0]:rows[1]]
    return _head(x, weights["final_norm_scale"], weights["lm_head_w"],
                 eps=model["rms_norm_eps"], wrong=wrong)
