"""Mellum2-12B-A2.5B in plain ``jax.numpy``: the yardstick that decides
``correct`` for the ``train_lm`` cells.

Float32 throughout, ``jax.default_matmul_precision("highest")``, no
kernels and no import from ``paddle_tpu``: weights arrive as a plain
``{name: array}`` dict read from the program's scope.  Every equation is
fixed by a key of the published ``config.json``
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct):

* ``rms(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``; layer ``l`` is
  ``h = x + Attn_l(rms(x))``, ``y = h + MoE(rms(h))``; after the last
  layer ``rms`` and an untied head; the loss is the mean next-token
  cross-entropy.
* ``Attn_l``: ``num_attention_heads`` query heads of ``head_dim`` on
  ``num_key_value_heads`` K/V heads (query head ``h`` reads K/V head
  ``h // group``), no bias, rotary over the whole head in the rotate-half
  form.  ``layer_types[l] == "sliding_attention"``: plain rotary
  (``rope_type`` default) and position ``i`` sees ``j <= i`` with
  ``i - j < sliding_window``.  ``"full_attention"``: causal, YaRN as
  ``transformers`` computes it (``_compute_yarn_parameters``: a blend of
  ``inv_freq`` and ``inv_freq / factor`` by the linear ramp between the
  two correction dimensions; cos and sin times ``attention_factor``).
  Softmax of ``q k^T / sqrt(head_dim)`` in f32.
* ``MoE``: ``p = softmax(h W_r)`` over ``num_experts``; the
  ``num_experts_per_tok`` largest; weights ``p_k / sum p_k``
  (``norm_topk_prob``); ``out = sum_k w_k E_k(h)`` with
  ``E(h) = (silu(h W_g) * h W_u) W_d``.

Departures, each because the program under test makes the same choice:

* Q, K and V come from one ``[hidden, (H + 2 Hkv) * head_dim]`` matrix
  (the three published matrices side by side);
* ``held_experts = (lo, hi)``: the router scores all ``num_experts`` and
  keeps the published top-k, but only experts ``lo <= e < hi`` are
  computed and summed — one chip's share of an expert-parallel layer
  (model-configs guide, section 4); the expert weights hold
  ``hi - lo`` experts.  ``None`` is the uncut layer;
* the vocabulary is whatever ``word_embedding`` and ``lm_head_w`` hold
  (a slice is a smaller vocabulary);
* labels are fed (``tokens[1:]`` of a sequence one longer than the
  input), so every position has one.

``softmax_dtype`` / ``residual_dtype`` in the model dict (and
``router_dtype``) lower one f32 part to another precision: the readings a
cell's limits must refuse (tools/lm_reference_probe.py), never the
yardstick.

So that 8 192 tokens at the published widths fit on one chip, attention
runs by blocks of queries, the experts by blocks of tokens and the head by
blocks of rows, each under ``jax.checkpoint``: the arithmetic is the
same, only what is kept for the backward pass is less.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"


# ---------------------------------------------------------------------------
# rotary tables
# ---------------------------------------------------------------------------

def rope_inv_freq(head_dim: int, rope: dict):
    """(inv_freq [head_dim / 2], attention_factor) of one
    ``rope_parameters`` entry: ``rope_type`` ``default`` or ``yarn``."""
    base = float(rope["rope_theta"])
    # host arithmetic in float64: the table is a constant of the model
    pos_freqs = base ** (np.arange(0, head_dim, 2, dtype=np.float64)
                         / head_dim)
    if rope.get("rope_type", "default") == "default":
        return jnp.asarray(1.0 / pos_freqs, jnp.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))),
               head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    inv_freq = jnp.asarray(
        (1.0 / (factor * pos_freqs)) * (1.0 - extrapolation)
        + (1.0 / pos_freqs) * extrapolation, jnp.float32)
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq, float(attention_factor)


def _rotary(x, inv_freq, attention_factor):
    """x [B, S, heads, D] at positions 0..S-1, rotate-half form."""
    s, d = x.shape[1], x.shape[-1]
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)            # [S, D]
    cos = (jnp.cos(emb) * attention_factor)[None, :, None, :]
    sin = (jnp.sin(emb) * attention_factor)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def _attention(x, w, prefix, m, kind, q_block):
    b, s, _ = x.shape
    h, hkv, d = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    qkv = jnp.matmul(x, w[f"{prefix}_qkv_w"])
    q, k, v = jnp.split(qkv, [h * d, (h + hkv) * d], axis=-1)
    inv_freq, factor = rope_inv_freq(d, m["rope_parameters"][kind])
    q = _rotary(q.reshape(b, s, h, d), inv_freq, factor)
    k = _rotary(k.reshape(b, s, hkv, d), inv_freq, factor)
    v = v.reshape(b, s, hkv, d)
    group = h // hkv
    window = m["sliding_window"] if kind == "sliding_attention" else None
    qb = min(q_block, s)
    if s % qb:
        raise ValueError(f"query block {qb} does not divide {s}")
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qi, start = args                                      # [B, qb, H, D]
        qi = qi.reshape(b, qb, hkv, group, d)
        scores = jnp.einsum("bqkgd,btkd->bkgqt", qi, k) / math.sqrt(d)
        rows = start + jnp.arange(qb)
        ok = cols[None, :] <= rows[:, None]
        if window is not None:
            ok = ok & (rows[:, None] - cols[None, :] < window)
        scores = jnp.where(ok[None, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores.astype(m.get("softmax_dtype",
                                                   jnp.float32)),
                               axis=-1).astype(jnp.float32)
        out = jnp.einsum("bkgqt,btkd->bqkgd", probs, v)
        return out.reshape(b, qb, h * d)

    nb = s // qb
    qs = q.reshape(b, nb, qb, h, d).transpose(1, 0, 2, 3, 4)
    outs = jax.lax.map(block, (qs, jnp.arange(nb) * qb))
    ctx = outs.transpose(1, 0, 2, 3).reshape(b, s, h * d)
    return jnp.matmul(ctx, w[f"{prefix}_o_w"])


def route(xf, router_w, m, dtype=None):
    """(weights [N, k] f32, expert ids [N, k]) of flattened tokens.
    ``dtype`` rounds the router's input, weights and softmax to a lower
    precision first: the reading that a tolerance must refuse."""
    if dtype is not None:
        xf, router_w = xf.astype(dtype), router_w.astype(dtype)
    probs = jax.nn.softmax(jnp.matmul(xf, router_w), axis=-1) \
        .astype(jnp.float32)
    vals, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return vals, idx


def _moe(x, w, prefix, m, held, router_dtype=None, token_block=1024):
    b, s, dm = x.shape
    n = b * s
    xf = x.reshape(n, dm)
    vals, idx = route(xf, w[f"{prefix}_router_w"], m, router_dtype)
    lo, hi = held if held is not None else (0, m["num_experts"])
    # combine weight of every (token, held expert): 0 where not routed
    hot = jax.nn.one_hot(idx - lo, hi - lo, dtype=xf.dtype)   # [N, k, E]
    cw = jnp.einsum("nk,nke->ne", vals, hot)                  # [N, E]
    experts = (w[f"{prefix}_expert_gate_w"], w[f"{prefix}_expert_up_w"],
               w[f"{prefix}_expert_down_w"])
    tb = min(token_block, n)
    if n % tb:
        raise ValueError(f"token block {tb} does not divide {n}")

    @jax.checkpoint
    def tokens(args):
        xb, cb = args                       # every held expert, densely

        def expert(acc, ws):
            wg, wu, wd, c = ws
            hid = jax.nn.silu(jnp.matmul(xb, wg)) * jnp.matmul(xb, wu)
            return acc + c[:, None] * jnp.matmul(hid, wd), None

        return jax.lax.scan(expert, jnp.zeros_like(xb),
                            experts + (cb.T,))[0]

    out = jax.lax.map(tokens, (xf.reshape(n // tb, tb, dm),
                               cw.reshape(n // tb, tb, hi - lo)))
    return out.reshape(b, s, dm), idx


def hidden_states(w, src_ids, m, *, held=None, q_block=512,
                  router_dtype=None, layer_prefix="lm_layer_"):
    """(final normed hidden [B, S, d], [expert ids [N, k] per layer])."""
    def stream(x):          # the residual stream's precision
        return x.astype(m.get("residual_dtype", jnp.float32)) \
            .astype(jnp.float32)

    x = stream(w["word_embedding"][src_ids])
    eps = m["rms_norm_eps"]
    routed = []
    for i in range(m["num_hidden_layers"]):
        p = f"{layer_prefix}{i}"
        kind = m["layer_types"][i]
        x = stream(x + _attention(_rms(x, w[f"{p}_attn_norm_scale"], eps),
                                  w, p, m, kind, q_block))
        y, idx = _moe(_rms(x, w[f"{p}_ffn_norm_scale"], eps), w, p, m, held,
                      router_dtype)
        x = stream(x + y)
        routed.append(idx)
    return _rms(x, w["final_norm_scale"], eps), routed


def lm_loss(w, batch, m, *, held=None, q_block=512, row_block=1024,
            router_dtype=None):
    """Mean next-token cross-entropy over every position; ``batch`` holds
    ``src_ids`` and ``labels`` [B, S] (``labels`` = the tokens one step
    on).  Returns (loss, routed expert ids per layer)."""
    x, routed = hidden_states(w, batch["src_ids"], m, held=held,
                              q_block=q_block, router_dtype=router_dtype)
    n = x.shape[0] * x.shape[1]
    xf = x.reshape(n, -1)
    labels = batch["labels"].reshape(n)
    rb = min(row_block, n)
    if n % rb:
        raise ValueError(f"row block {rb} does not divide {n}")

    @jax.checkpoint
    def rows(args):
        xb, lb = args
        logp = jax.nn.log_softmax(jnp.matmul(xb, w["lm_head_w"]), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

    total = jnp.sum(jax.lax.map(
        rows, (xf.reshape(n // rb, rb, -1), labels.reshape(n // rb, rb))))
    return total / n, routed


def logits(w, src_ids, m, *, held=None):
    """[B, S, vocab] next-token logits (small sizes: tests)."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    with jax.default_matmul_precision(PRECISION):
        x, _ = hidden_states(w, jnp.asarray(src_ids), m, held=held,
                             q_block=src_ids.shape[1])
        return jnp.matmul(x, w["lm_head_w"])


def moe_layer(w, x, m, prefix, *, held=None):
    """One MoE block alone on ``x`` [B, S, d] (the share test)."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    with jax.default_matmul_precision(PRECISION):
        return _moe(jnp.asarray(x, jnp.float32), w, prefix, m, held)[0]


def router_only(x, router_w, m, dtype=None):
    """Expert ids [N, k] of the router alone on a GIVEN input ``x``
    [..., d] — the program's own router input, so that nothing before
    the router enters the comparison."""
    with jax.default_matmul_precision(PRECISION):
        return route(jnp.asarray(x, jnp.float32).reshape(-1, x.shape[-1]),
                     jnp.asarray(router_w, jnp.float32), m, dtype)[1]


def adam_first_step(grad, learning_rate, beta1=0.9, beta2=0.999,
                    epsilon=1e-8):
    """What Adam's FIRST step (zero moments) adds to a parameter whose
    gradient is ``grad``, in float64 numpy: the form at the end of
    section 2 of Kingma & Ba, ``lr_t = lr sqrt(1 - beta2^t) / (1 -
    beta1^t)``, ``theta -= lr_t m / (sqrt(v) + epsilon)``, which is the
    one Fluid's ``adam`` documents."""
    g = np.asarray(grad, np.float64)
    m, v = (1 - beta1) * g, (1 - beta2) * g * g
    lr_t = learning_rate * np.sqrt(1 - beta2) / (1 - beta1)
    return -lr_t * m / (np.sqrt(v) + epsilon)


def local_counts(routed, held, num_experts):
    """Assignments each held expert received, per layer: [L, hi - lo]."""
    lo, hi = held if held is not None else (0, num_experts)
    return jnp.stack([
        jnp.sum(jax.nn.one_hot(idx.reshape(-1) - lo, hi - lo,
                               dtype=jnp.int32), axis=0)
        for idx in routed])


def lm_loss_and_grads(w, batch, grad_names, m, *, held=None, q_block=512,
                      row_block=1024, router_dtype=None):
    """(loss, {name: d loss / d w[name]}, routed ids per layer)."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    @jax.jit
    def run(wanted, rest, batch):
        def loss(wanted):
            return lm_loss({**rest, **wanted}, batch, m, held=held,
                           q_block=q_block, row_block=row_block,
                           router_dtype=router_dtype)
        return jax.value_and_grad(loss, has_aux=True)(wanted)

    wanted = {k: w[k] for k in grad_names}
    rest = {k: v for k, v in w.items() if k not in wanted}
    with jax.default_matmul_precision(PRECISION):
        (loss, routed), grads = run(wanted, rest, batch)
    return loss, grads, routed
