"""DeepSeek-V3 in plain ``jax.numpy``: the yardstick that decides
``correct`` for the ``serve_lm`` cells.

Float32 throughout, ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no absorbed form and no import from ``paddle_tpu``:
weights arrive as a plain ``{name: array}`` dict read from the program's
scope (bfloat16 there; every one is widened to float32 where it is used,
so the yardstick computes in f32 on the SAME rounded weights).  Every
equation is fixed by a key of the published ``config.json``
(https://huggingface.co/deepseek-ai/DeepSeek-V3), with ``d`` =
``hidden_size`` and ``rms(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``:

* block ``l``: ``h = x + MLA_l(rms(x))``, ``y = h + FFN_l(rms(h))``;
  after the last layer ``rms`` and an untied head.
* ``MLA`` (expanded form): ``c_q = rms(x W_qa)`` (``q_lora_rank``);
  ``q = c_q W_qb`` as ``num_attention_heads`` heads of ``[q_nope
  (qk_nope_head_dim), q_rope (qk_rope_head_dim)]``; ``[c_kv
  (kv_lora_rank), k_r (qk_rope_head_dim)] = x W_kva``; ``c_kv <-
  rms(c_kv)``; ``k_rope = RoPE(k_r)``, ONE rotary key a token shared by
  every head; ``q_rope <- RoPE(q_rope)``; ``[k_nope_h, v_h] = c_kv
  W_kvb`` per head (``v_head_dim``); ``score_h(i, j) = (q_nope_h,i .
  k_nope_h,j + q_rope_h,i . k_rope_j) * scale`` for ``j <= i``, softmax
  in f32, ``out = concat_h(softmax(score_h) v_h) W_o``.
  ``scale = (qk_nope_head_dim + qk_rope_head_dim)^-0.5 * m^2``, ``m =
  0.1 * mscale_all_dim * ln(factor) + 1`` (``rope_scaling``).
* ``RoPE``: YaRN as ``transformers`` computes it (``rope_theta``,
  ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
  ``beta_slow``: the blend of ``inv_freq`` and ``inv_freq / factor`` by
  the linear ramp between the two correction dimensions); cos and sin
  times ``mscale / mscale_all_dim`` (1 as published).  ADJACENT pairs
  ``(x_2i, x_2i+1)`` rotate together (the published
  ``apply_rotary_emb``).
* ``FFN_l``, ``l < first_k_dense_replace``: ``(silu(h W_g) * h W_u)
  W_d`` of width ``intermediate_size``.
* ``FFN_l`` otherwise: ``s = sigmoid(h W_r)`` over ``n_routed_experts``
  in f32.  Selection uses ``s' = s + b`` (the ``noaux_tc`` correction
  bias: it chooses, it never weighs).  The experts form ``n_group``
  groups; a group's score is the sum of its two largest ``s'``; the
  ``topk_group`` best groups stay, the rest are masked, and the
  ``num_experts_per_tok`` largest ``s'`` among them are chosen.  Weights
  ``w_k = s_k / sum_chosen s`` (``norm_topk_prob``) ``*
  routed_scaling_factor``.  ``out = sum_k w_k E_k(h) + E_shared(h)``;
  every ``E`` is the SiLU-gated product of width
  ``moe_intermediate_size`` (the shared one of ``n_shared_experts``
  times that).  No capacity: nothing is dropped.

Departures, each because the program under test makes the same choice:

* ``held_experts = (lo, hi)``: the router scores all
  ``n_routed_experts`` and keeps the published groups and top-k, but only
  experts ``lo <= e < hi`` are computed and summed — one chip's share of
  an expert-parallel layer; the expert weights hold ``hi - lo`` experts.
  The shared expert is on every chip, so it is always added.  ``None``
  is the uncut layer;
* the vocabulary is whatever ``word_embedding`` and ``lm_head_w`` hold
  (a slice is a smaller vocabulary);
* the multi-token-prediction module (``num_nextn_predict_layers``) is
  not part of the forward pass.

``wrong`` (a set of names from :data:`CONTROLS`) computes the forward
pass WRONG in one named way each: the readings a cell's limits must
refuse (benchmark/tests/test_reference.py), never the yardstick.

So that 6 144 tokens at the published widths fit on one chip beside the
program's own weights, attention runs by blocks of queries, the held
experts one at a time, and each block is a compiled function of its own
layer's weights.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"

#: every way :func:`logits` can be made to compute wrong, by name
CONTROLS = (
    "no_mscale",            # m^2 left out of the softmax scale
    "no_rope_term",         # q_rope . k_rope dropped from the score
    "no_routed_scale",      # routed_scaling_factor left out
    "bias_in_weights",      # b added to the scores that weigh
    "no_group_limit",       # the top-4 groups ignored: plain top-8
    "no_shared_expert",     # E_shared left out
    "latent_8bit",          # c_kv and k_rope of the context in int8,
                            # one scale a token
    "context_minus_block",  # a query does not see the 16 positions
                            # before the newest 16
    "rotate_half",          # the rotate-half pairing for adjacent pairs
)


def _f32(t):
    return jnp.asarray(t).astype(jnp.float32)


def yarn_inv_freq(dim: int, m: dict):
    """``inv_freq`` [dim / 2] float32 of the rotary table, from the
    model's ``rope_theta`` and ``rope_scaling``; host arithmetic in
    float64: the table is a constant of the model."""
    base = float(m["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = m.get("rope_scaling")
    if not rs:
        return jnp.asarray(1.0 / pos_freqs, jnp.float32)
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return jnp.asarray(ramp / (factor * pos_freqs)
                       + (1.0 - ramp) / pos_freqs, jnp.float32)


def _mscale(factor: float, coeff: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * coeff * math.log(factor) + 1.0


def softmax_scale(m: dict, with_mscale: bool = True) -> float:
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    rs = m.get("rope_scaling")
    if rs and with_mscale:
        scale *= _mscale(float(rs["factor"]),
                         float(rs.get("mscale_all_dim", 0))) ** 2
    return scale


def rope_factor(m: dict) -> float:
    """What cos and sin are multiplied by: ``mscale / mscale_all_dim``
    in ``transformers``' YaRN (1 as published)."""
    rs = m.get("rope_scaling")
    if not rs:
        return 1.0
    f = float(rs["factor"])
    return _mscale(f, float(rs.get("mscale", 1))) \
        / _mscale(f, float(rs.get("mscale_all_dim", 0)))


def _rotary(x, pos, inv_freq, factor, rotate_half=False):
    """x [S, heads, D] at positions ``pos`` [S]; adjacent pairs."""
    freqs = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(freqs) * factor)[:, None, :]
    sin = (jnp.sin(freqs) * factor)[:, None, :]
    if rotate_half:
        half = x.shape[-1] // 2
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * _f32(g)


def _fake_quant8(t):
    """Per-row symmetric 8-bit rounding (what an int8 latent cache with
    one scale a token would keep)."""
    s = jnp.max(jnp.abs(t), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(t / s) * s


def mla(x, w, p, m, wrong=(), q_block=512, block_size=16):
    """The expanded latent attention of one layer; x [S, d]."""
    s, _ = x.shape
    h, dn, dr, dv = m["num_attention_heads"], m["qk_nope_head_dim"], \
        m["qk_rope_head_dim"], m["v_head_dim"]
    dc, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    pos = jnp.arange(s)
    inv_freq, factor = yarn_inv_freq(dr, m), rope_factor(m)
    half = "rotate_half" in wrong
    c_q = _rms(x @ _f32(w[f"{p}_q_a_w"]), w[f"{p}_q_a_norm_scale"], eps)
    q = (c_q @ _f32(w[f"{p}_q_b_w"])).reshape(s, h, dn + dr)
    q_nope = q[..., :dn]
    q_rope = _rotary(q[..., dn:], pos, inv_freq, factor, half)
    kv = x @ _f32(w[f"{p}_kv_a_w"])
    c_kv = _rms(kv[:, :dc], w[f"{p}_kv_a_norm_scale"], eps)
    k_rope = _rotary(kv[:, None, dc:], pos, inv_freq, factor, half)[:, 0]
    if "latent_8bit" in wrong:
        both = _fake_quant8(jnp.concatenate([c_kv, k_rope], axis=-1))
        c_kv, k_rope = both[:, :dc], both[:, dc:]
    kvb = (c_kv @ _f32(w[f"{p}_kv_b_w"])).reshape(s, h, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    scale = softmax_scale(m, "no_mscale" not in wrong)
    qb = min(q_block, s)
    if s % qb:
        raise ValueError(f"query block {qb} does not divide {s}")
    cols = jnp.arange(s)

    def block(args):
        qn, qr, rows = args
        sc = jnp.einsum("qhd,khd->hqk", qn, k_nope)
        if "no_rope_term" not in wrong:
            sc = sc + jnp.einsum("qhd,kd->hqk", qr, k_rope)
        seen = cols[None, :] <= rows[:, None]
        if "context_minus_block" in wrong:
            age = rows[:, None] - cols[None, :]
            seen = seen & ~((age >= block_size) & (age < 2 * block_size))
        sc = jnp.where(seen[None], sc * scale, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, (q_nope.reshape(s // qb, qb, h, dn),
                              q_rope.reshape(s // qb, qb, h, dr),
                              cols.reshape(s // qb, qb)))
    return out.reshape(s, h * dv) @ _f32(w[f"{p}_o_w"])


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def route(scores, bias, m, wrong=()):
    """(weights [N, k] f32, indices [N, k]) from the sigmoid scores
    [N, E] and the selection bias [E]."""
    n, e = scores.shape
    k, ng, kg = m["num_experts_per_tok"], m["n_group"], m["topk_group"]
    pick = scores + _f32(bias)[None, :]
    if "no_group_limit" not in wrong and ng > 1:
        per = pick.reshape(n, ng, e // ng)
        gscore = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)
        _, gidx = jax.lax.top_k(gscore, kg)
        keep = jnp.zeros((n, ng), bool).at[
            jnp.arange(n)[:, None], gidx].set(True)
        pick = jnp.where(jnp.repeat(keep, e // ng, axis=1), pick, -jnp.inf)
    _, idx = jax.lax.top_k(pick, k)
    src = pick if "bias_in_weights" in wrong else scores
    wts = jnp.take_along_axis(src, idx, axis=-1)
    if m.get("norm_topk_prob", True):
        wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-20)
    if "no_routed_scale" not in wrong:
        wts = wts * float(m["routed_scaling_factor"])
    return wts, idx


def moe(x, w, p, m, held=None, wrong=(), shared=True):
    """The sparse FFN of one layer; x [N, d].  ``shared=False`` leaves
    the shared expert out (a chip's ROUTED part alone, for the
    shares-sum test)."""
    e = m["n_routed_experts"]
    lo, hi = held if held is not None else (0, e)
    scores = jax.nn.sigmoid(x @ _f32(w[f"{p}_router_w"]))
    wts, idx = route(scores, w[f"{p}_router_bias"], m, wrong)
    out = jnp.zeros_like(x)
    wg, wu, wd = (w[f"{p}_expert_{n}_w"] for n in ("gate", "up", "down"))
    for j in range(hi - lo):
        # the weight a token gives expert lo + j (0 where not chosen)
        wt = jnp.sum(jnp.where(idx == lo + j, wts, 0.0), axis=-1)
        out = out + wt[:, None] * _swiglu(x, wg[j], wu[j], wd[j])
    if shared and "no_shared_expert" not in wrong:
        out = out + _swiglu(x, *(w[f"{p}_shared_{n}_w"]
                                 for n in ("gate", "up", "down")))
    return out


def _block(x, wl, m, dense, held, wrong, q_block, block_size):
    """One decoder block on ``x`` [S, d]; ``wl`` holds the layer's
    weights under their names without the layer's prefix."""
    with jax.default_matmul_precision(PRECISION):
        eps = m["rms_norm_eps"]
        x = x + mla(_rms(x, wl["_attn_norm_scale"], eps), wl, "", m, wrong,
                    q_block, block_size)
        hn = _rms(x, wl["_ffn_norm_scale"], eps)
        if dense:
            return x + _swiglu(hn, wl["_gate_w"], wl["_up_w"],
                               wl["_down_w"])
        return x + moe(hn, wl, "", m, held, wrong)


def _head(x, g, w_head, eps):
    with jax.default_matmul_precision(PRECISION):
        return _rms(x, g, eps) @ _f32(w_head)


@functools.lru_cache(maxsize=None)
def _jitted(m_json, dense, held, wrong, q_block, block_size):
    """One compiled block per (model, kind of layer, way of being wrong):
    the layers of a kind share it, and a block sees only its own layer's
    weights, so the widest f32 copies alive are one layer's."""
    return jax.jit(functools.partial(
        _block, m=json.loads(m_json), dense=dense, held=held, wrong=wrong,
        q_block=q_block, block_size=block_size))


def logits(w, tokens, m, *, held=None, layer_prefix="latent_layer_",
           wrong=(), q_block=512, block_size=16):
    """Next-token logits [S, V] float32 at EVERY position of ``tokens``
    [S]: one full causal forward pass, no cache."""
    wrong = frozenset(wrong)
    unknown = wrong - set(CONTROLS)
    if unknown:
        raise ValueError(f"unknown controls {sorted(unknown)}")
    m_json = json.dumps({k: v for k, v in m.items()
                         if isinstance(v, (int, float, bool, dict))},
                        sort_keys=True)
    held = None if held is None else tuple(int(e) for e in held)
    x = _f32(jnp.take(w["word_embedding"], jnp.asarray(tokens), axis=0))
    for l in range(m["num_hidden_layers"]):
        p = f"{layer_prefix}{l}"
        wl = {n[len(p):]: v for n, v in w.items() if n.startswith(p + "_")}
        x = _jitted(m_json, l < m["first_k_dense_replace"], held, wrong,
                    int(q_block), int(block_size))(x, wl)
    return jax.jit(_head, static_argnames="eps")(
        x, w["final_norm_scale"], w["lm_head_w"], eps=m["rms_norm_eps"])
