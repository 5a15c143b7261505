"""Laguna-S-2.1 in plain ``jax.numpy``: the yardstick that decides
``correct`` for the ``serve_window`` cells.

Float32 throughout, ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no ring, no batching and no import from ``paddle_tpu``:
weights arrive as a plain ``{name: array}`` dict read from the program's
scope (bfloat16 there; every one is widened to float32 where it is used,
so the yardstick computes in f32 on the SAME rounded weights).  Every
size is a key of the published ``config.json``
(https://huggingface.co/poolside/Laguna-S-2.1), with ``rms(x) = x /
sqrt(mean(x^2) + rms_norm_eps) * g``:

* block ``l``: ``h = x + Attn_l(rms(x))``, ``y = h + MLP_l(rms(h))``;
  after the last layer ``rms`` and an untied head.
* ``Attn_l``: ``H_l = num_attention_heads_per_layer[l]`` query heads of
  ``head_dim`` on ``num_key_value_heads`` K/V heads, query head ``h``
  reading K/V head ``h // (H_l / num_key_value_heads)``; no bias.  Rotary
  by ``rope_parameters[layer_types[l]]`` on the FIRST
  ``partial_rotary_factor * head_dim`` columns of each head, rotate-half
  form; ``yarn`` as ``transformers`` computes it (the blend of
  ``inv_freq`` and ``inv_freq / factor`` by the linear ramp between the
  two correction dimensions of the rotated width; cos and sin times
  ``attention_factor``).  ``score(p, t) = q_p . k_t / sqrt(head_dim)``,
  softmax in f32, over ``t <= p`` and, in a ``sliding_attention`` layer,
  ``p - sliding_window < t``.  The per-head gate (``gating: per-head``):
  head ``h``'s output times ``sigmoid(x W_g)[h]``, then ``W_o``.
* ``MLP_l``: ``(silu(h W_g) * h W_u) W_d`` of ``intermediate_size`` in the
  ``mlp_only_layers``; elsewhere ``p = softmax(h W_r)`` over
  ``num_experts``, the ``num_experts_per_tok`` largest, ``w_k = p_k /
  sum p_k`` (``norm_topk_prob``) ``* moe_routed_scaling_factor``, ``out =
  sum_k w_k E_k(h) + E_shared(h)``, every ``E`` a SiLU-gated product (the
  shared one of ``shared_expert_intermediate_size``).  No capacity.

Departures, each because the program under test makes the same choice:

* Q, K and V come from one ``[hidden, (H_l + 2 H_kv) head_dim]`` matrix
  (the three published matrices side by side);
* ``held = (lo, hi)``: the router scores all of ``num_experts`` (here the
  model dict's ``router_experts``) and keeps the published top-k, but
  only experts ``lo <= e < hi`` are computed and summed — one chip's
  share of an expert-parallel layer; the shared expert is on every chip,
  so it is always added.  ``None`` is the uncut layer;
* the vocabulary is whatever ``word_embedding`` and ``lm_head_w`` hold
  (a slice is a smaller vocabulary).

``wrong`` (a set of names from :data:`CONTROLS`) computes the forward
pass WRONG in one named way each: the readings a cell's limits must
refuse (tests/test_window_decoder.py), never the yardstick.
``activations_bfloat16`` (:data:`ROUNDINGS`) is no fault: it keeps every
activation the configuration states in bfloat16, to read how much of the
served logits' distance the stated precision alone accounts for.

So that 36k tokens at the published widths fit on one chip, attention
runs by blocks of queries (a window layer's block against the keys of
its window alone), the held experts one at a time, and each layer is a
compiled function of its own weights; ``rows = (lo, hi)`` returns only
the logits of those positions.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
FULL, SLIDING = "full_attention", "sliding_attention"

#: every way :func:`logits` can be made to compute wrong, by name
CONTROLS = (
    "no_window",                    # sliding layers see the whole context
    "window_plus_block",            # the window 16 positions wider
    "kv_group_mod",                 # head h reads K/V head h % n_kv
    "no_head_gate",                 # sigmoid(x W_g) left out
    "full_rotary_on_full_layers",   # the whole head rotates in full layers
    "no_yarn",                      # full layers' default rotary at theta
    "no_routed_scale",              # moe_routed_scaling_factor left out
    "no_shared_expert",             # E_shared left out
    "kv_fp8",                       # K and V of the context rounded to
                                    # float8 e4m3, the precision next below
                                    # the stated bfloat16 cache
)

#: quantities kept in the stated precision (readings, not faults)
ROUNDINGS = ("activations_bfloat16",)

#: how much wider ``window_plus_block`` makes the window (a page)
BLOCK = 16


def _f32(t):
    return jnp.asarray(t).astype(jnp.float32)


def round_to_bfloat16(x):
    """float32 -> the nearest bfloat16 value (ties to even), as float32,
    in INTEGER arithmetic: ``x.astype(bfloat16).astype(float32)`` is the
    identity on a TPU (XLA keeps the excess precision)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def round_to_e4m3(x):
    """float32 -> the nearest float8 e4m3 value (3 mantissa bits, ties to
    even, saturating at 448, subnormals in steps of 2^-9), as float32, in
    integer arithmetic and a multiple of a power of two."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x7FFFF) + ((u >> 20) & jnp.uint32(1))) \
        & jnp.uint32(0xFFF00000)
    normal = jnp.clip(jax.lax.bitcast_convert_type(u, jnp.float32),
                      -448.0, 448.0)
    return jnp.where(jnp.abs(x) < 2.0 ** -6, jnp.round(x * 512.0) / 512.0,
                     normal)


def _kept(x, wrong):
    """An activation as the configuration keeps it between two ops."""
    return round_to_bfloat16(x) if "activations_bfloat16" in wrong else x


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(gain)


def rope_tables(head_dim: int, rope: dict, wrong, kind):
    """(inv_freq [rotated / 2], attention_factor, rotated width) of a layer
    kind's ``rope_parameters`` entry; host arithmetic in float64."""
    rope = dict(rope)
    share = float(rope.get("partial_rotary_factor", 1.0))
    if kind == FULL and "full_rotary_on_full_layers" in wrong:
        share = 1.0
    if kind == FULL and "no_yarn" in wrong:
        rope = {"rope_type": "default", "rope_theta": rope["rope_theta"]}
    dim = int(head_dim * share)
    base = float(rope["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") == "default":
        return jnp.asarray(1.0 / pos_freqs, jnp.float32), 1.0, dim
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv_freq = ramp / (factor * pos_freqs) + (1.0 - ramp) / pos_freqs
    att = rope.get("attention_factor")
    if att is None:
        att = 0.1 * math.log(factor) + 1.0
    return jnp.asarray(inv_freq, jnp.float32), float(att), dim


def rotary(x, inv_freq, factor, dim):
    """x [T, heads, D] at positions 0..T-1: the first ``dim`` columns of
    each head rotate (rotate-half), the rest pass."""
    t = x.shape[0]
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    xr, rest = x[..., :dim], x[..., dim:]
    rot = jnp.concatenate([-xr[..., dim // 2:], xr[..., :dim // 2]], -1)
    out = xr * (jnp.cos(emb) * factor) + rot * (jnp.sin(emb) * factor)
    return jnp.concatenate([out, rest], axis=-1)


def attention(x, w, p, m, kind, heads, wrong, q_block):
    """One layer's gated grouped attention on the normed ``x`` [T, d]."""
    t = x.shape[0]
    hkv, d = m["num_key_value_heads"], m["head_dim"]
    group = heads // hkv
    qkv = _kept(x @ _f32(w[p + "_qkv_w"]), wrong)
    q = qkv[:, :heads * d].reshape(t, heads, d)
    k = qkv[:, heads * d:(heads + hkv) * d].reshape(t, hkv, d)
    v = qkv[:, (heads + hkv) * d:].reshape(t, hkv, d)
    inv_freq, factor, dim = rope_tables(d, m["rope_parameters"][kind],
                                        wrong, kind)
    q, k = rotary(q, inv_freq, factor, dim), rotary(k, inv_freq, factor, dim)
    if "kv_fp8" in wrong:
        k, v = round_to_e4m3(k), round_to_e4m3(v)
    else:
        k, v = _kept(k, wrong), _kept(v, wrong)
    if "kv_group_mod" in wrong:
        kv_of = jnp.arange(heads) % hkv
    else:
        kv_of = jnp.arange(heads) // group
    window = 0
    if kind == SLIDING and "no_window" not in wrong:
        window = m["sliding_window"] + (
            BLOCK if "window_plus_block" in wrong else 0)
    qb = min(q_block, t)
    assert t % qb == 0, (t, qb)
    # a window layer's block reads the keys of its window alone: the
    # block's first query sees ``window - 1`` back, padded before 0
    span = t if not window else min(t, qb + window - 1)
    pad = span - qb if window else 0
    kp = jnp.pad(k, ((pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((pad, 0), (0, 0), (0, 0)))

    def block(args):
        q_blk, start = args                             # [qb, H, D]
        if window:
            ks = jax.lax.dynamic_slice_in_dim(kp, start, span)
            vs = jax.lax.dynamic_slice_in_dim(vp, start, span)
            key_pos = start - pad + jnp.arange(span)
        else:
            ks, vs, key_pos = kp, vp, jnp.arange(t)
        ks, vs = ks[:, kv_of], vs[:, kv_of]             # [span, H, D]
        sc = jnp.einsum("qhd,thd->hqt", q_blk, ks) / math.sqrt(d)
        q_pos = (start + jnp.arange(qb))[None, :, None]
        seen = (key_pos[None, None, :] <= q_pos) & (key_pos >= 0)
        if window:
            seen &= key_pos[None, None, :] > q_pos - window
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thd->qhd", pr, vs)

    out = jax.lax.map(block, (q.reshape(t // qb, qb, heads, d),
                              jnp.arange(0, t, qb)))
    out = _kept(out.reshape(t, heads, d), wrong)
    if "no_head_gate" not in wrong:
        gate = jax.nn.sigmoid(_kept(x @ _f32(w[p + "_attn_gate_w"]), wrong))
        out = _kept(out * gate[:, :, None], wrong)
    return _kept(out.reshape(t, heads * d) @ _f32(w[p + "_o_w"]), wrong)


def _swiglu(x, wg, wu, wd, wrong=()):
    h = jax.nn.silu(_kept(x @ _f32(wg), wrong)) * _kept(x @ _f32(wu), wrong)
    return _kept(_kept(h, wrong) @ _f32(wd), wrong)


def route(x, router_w, m, wrong=()):
    """(weights [N, k] f32, expert ids [N, k]) of the tokens ``x``."""
    probs = jax.nn.softmax(x @ _f32(router_w), axis=-1)
    vals, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    if "no_routed_scale" not in wrong:
        vals = vals * float(m["moe_routed_scaling_factor"])
    return vals, idx


def moe(x, w, p, m, held=None, wrong=(), shared=True):
    """The sparse MLP of one layer on ``x`` [N, d]; ``shared=False``
    leaves the shared expert out (a chip's ROUTED part alone, for the
    shares-sum test)."""
    wts, idx = route(x, w[p + "_router_w"], m, wrong)
    wg, wu, wd = (w[f"{p}_expert_{n}_w"] for n in ("gate", "up", "down"))
    lo = held[0] if held is not None else 0

    def expert(acc, ws):
        j, g_, u_, d_ = ws
        # the weight a token gives expert lo + j (0 where not chosen)
        wt = jnp.sum(jnp.where(idx == lo + j, wts, 0.0), axis=-1)
        return acc + wt[:, None] * _swiglu(x, g_, u_, d_, wrong), None

    out = jax.lax.scan(expert, jnp.zeros_like(x),
                       (jnp.arange(wg.shape[0]), _f32(wg), _f32(wu),
                        _f32(wd)))[0]
    if shared and "no_shared_expert" not in wrong:
        out = out + _swiglu(x, *(w[f"{p}_shared_{n}_w"]
                                 for n in ("gate", "up", "down")), wrong)
    return out


@functools.partial(jax.jit, static_argnames=(
    "p", "kind", "heads", "dense", "model", "held", "wrong", "q_block"))
def _layer(x, w, *, p, kind, heads, dense, model, held, wrong, q_block):
    m = dict(model)
    m["rope_parameters"] = {k: dict(v) for k, v in m["rope_parameters"]}
    eps = m["rms_norm_eps"]

    def add(x, branch):
        y = x + branch
        return round_to_bfloat16(y) if "activations_bfloat16" in wrong \
            else y

    with jax.default_matmul_precision(PRECISION):
        x = add(x, attention(_kept(rms(x, w[p + "_attn_norm_scale"], eps),
                                   wrong), w, p, m, kind, heads, wrong,
                             q_block))
        hn = _kept(rms(x, w[p + "_ffn_norm_scale"], eps), wrong)
        if dense:
            return add(x, _swiglu(hn, w[p + "_gate_w"], w[p + "_up_w"],
                                  w[p + "_down_w"], wrong))
        return add(x, moe(hn, w, p, m, held, wrong))


@functools.partial(jax.jit, static_argnames=("eps", "wrong"))
def _head(x, gain, w, *, eps, wrong):
    with jax.default_matmul_precision(PRECISION):
        return _kept(rms(x, gain, eps), wrong) @ _f32(w)


def _frozen(model: dict):
    """The model dict as a hashable, static argument: its scalars and
    the rotary tables by layer kind."""
    keys = {k: v for k, v in model.items()
            if isinstance(v, (int, float, bool, str))}
    keys["rope_parameters"] = tuple(sorted(
        (kind, tuple(sorted(r.items())))
        for kind, r in model["rope_parameters"].items()))
    return tuple(sorted(keys.items()))


def logits(weights, seq, model, *, held=None, layer_prefix="window_layer_",
           wrong=(), q_block=256, rows=None):
    """Next-token logits [len(seq) or hi - lo, vocab] float32 of ONE full
    causal pass over the token ids ``seq``.  ``model`` holds the published
    keys (the per-layer lists cut to the depth held; ``num_experts`` the
    router's width); each layer is a compiled function of its own
    weights."""
    wrong = frozenset(wrong)
    unknown = wrong - set(CONTROLS) - set(ROUNDINGS)
    if unknown:
        raise ValueError(f"unknown controls {sorted(unknown)}")
    frozen = _frozen(model)
    held = None if held is None else tuple(int(e) for e in held)
    x = _f32(jnp.asarray(weights["word_embedding"])[jnp.asarray(seq)])
    for i in range(model["num_hidden_layers"]):
        p = f"{layer_prefix}{i}"
        own = {n: t for n, t in weights.items() if n.startswith(p + "_")}
        x = _layer(x, own, p=p, kind=model["layer_types"][i],
                   heads=int(model["num_attention_heads_per_layer"][i]),
                   dense=i in model["mlp_only_layers"], model=frozen,
                   held=held, wrong=wrong, q_block=q_block)
    if rows is not None:
        x = x[rows[0]:rows[1]]
    return _head(x, weights["final_norm_scale"], weights["lm_head_w"],
                 eps=model["rms_norm_eps"], wrong=wrong)


def moe_layer(w, x, m, prefix, *, held=None, shared=True):
    """One sparse MLP alone on ``x`` [N, d] (the share test)."""
    with jax.default_matmul_precision(PRECISION):
        return moe(_f32(x), w, prefix, m, held, shared=shared)
