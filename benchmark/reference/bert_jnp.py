"""The BERT-base forward pass in plain ``jax.numpy``: the yardstick that
decides ``correct``.

Float32 throughout, ``jax.default_matmul_precision("highest")`` (on a TPU
an f32 matmul otherwise rounds its inputs to bf16), no kernels, no cache,
no batching tricks, and no import from ``paddle_tpu``: weights arrive as a
plain ``{name: array}`` dict read from the program's scope.

Two heads over one stack (Devlin et al. 2018; google-research/bert
``modeling.py``):

* ``pretrain_loss`` — bidirectional encoder, masked-LM + next-sentence
  loss as ``run_pretraining.py`` computes them; ``pretrain_loss_and_grads``
  adds ``jax.grad`` of it for named parameters.
* ``decoder_logits`` — the same stack with a causal mask and a tied LM
  head over the vocabulary, one full forward pass over a whole sequence
  (teacher forcing): row ``p`` holds the next-token logits after tokens
  ``0..p``.

Departures from the published description, each because the program under
test makes the same choice and the comparison is of arithmetic, not of
hyper-parameters:

* LayerNorm epsilon is a parameter (``layer_norm_eps`` of the config
  file; the program uses 1e-5, google-research/bert 1e-12);
* the padding mask enters attention as an additive ``(mask - 1) * 1e4``
  bias exactly as published; a causal mask adds ``-1e9`` above the
  diagonal;
* Q, K and V come from one ``[hidden, 3 * hidden]`` matrix (the three
  published matrices side by side);
* the decoder has no segment embedding, no pooler and no MLM transform:
  its final hidden state goes straight to the tied output embedding plus
  a bias (``models/decoder.py`` defines the architecture; the published
  BERT has no causal form).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def _dense(x, w, b):
    return jnp.matmul(x, w) + b


def _attention(x, w, prefix, n_head, bias):
    """Multi-head self-attention of one layer.  ``x`` is [B, S, H];
    ``bias`` is an additive [B, 1, S, S] (or broadcastable) mask."""
    b, s, h = x.shape
    d = h // n_head
    qkv = _dense(x, w[f"{prefix}_qkv_w"], w[f"{prefix}_qkv_b"])
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)

    scores = jnp.einsum("bhsd,bhtd->bhst", heads(q), heads(k))
    scores = scores / jnp.sqrt(jnp.float32(d)) + bias
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhst,bhtd->bhsd", probs, heads(v))
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    return _dense(ctx, w[f"{prefix}_out_w"], w[f"{prefix}_out_b"])


def _layer(x, w, prefix, n_head, bias, eps):
    """One post-LayerNorm transformer layer."""
    x = _layer_norm(x + _attention(x, w, prefix, n_head, bias),
                    w[f"{prefix}_ln1_scale"], w[f"{prefix}_ln1_bias"], eps)
    ffn = _gelu(_dense(x, w[f"{prefix}_ffn1_w"], w[f"{prefix}_ffn1_b"]))
    ffn = _dense(ffn, w[f"{prefix}_ffn2_w"], w[f"{prefix}_ffn2_b"])
    return _layer_norm(x + ffn, w[f"{prefix}_ln2_scale"],
                       w[f"{prefix}_ln2_bias"], eps)


def _log_softmax_nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


# ---------------------------------------------------------------------------
# bert_base_pretrain: bidirectional, MLM + NSP
# ---------------------------------------------------------------------------

def pretrain_loss(w, batch, *, n_layer, n_head, eps,
                  layer_prefix="encoder_layer_"):
    """Masked-LM loss + next-sentence loss (both batch means), dropout
    off.  ``batch`` holds the program's feeds: ``src_ids``, ``pos_ids``,
    ``sent_ids`` [B, S]; ``input_mask`` [B, S, 1]; ``mask_pos`` [B, M]
    (positions inside each sequence); ``mask_label`` [B * M, 1];
    ``labels`` [B, 1]."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    src = jnp.asarray(batch["src_ids"])
    b, s = src.shape
    x = (w["word_embedding"][src]
         + w["pos_embedding"][jnp.asarray(batch["pos_ids"])]
         + w["sent_embedding"][jnp.asarray(batch["sent_ids"])])
    x = _layer_norm(x, w["pre_encoder_ln_scale"], w["pre_encoder_ln_bias"],
                    eps)
    mask = jnp.asarray(batch["input_mask"], jnp.float32)        # [B, S, 1]
    keep = jnp.matmul(mask, mask.transpose(0, 2, 1))            # [B, S, S]
    bias = ((keep - 1.0) * 1e4)[:, None, :, :]
    for i in range(n_layer):
        x = _layer(x, w, f"{layer_prefix}{i}", n_head, bias, eps)

    # masked-LM head: gather, transform, LayerNorm, tied embedding
    pos = jnp.asarray(batch["mask_pos"])
    flat = (pos + jnp.arange(b)[:, None] * s).reshape(-1)
    feat = x.reshape(b * s, -1)[flat]
    feat = _gelu(_dense(feat, w["mask_lm_trans_fc.w_0"],
                        w["mask_lm_trans_fc.b_0"]))
    feat = _layer_norm(feat, w["mask_lm_trans_ln_scale"],
                       w["mask_lm_trans_ln_bias"], eps)
    logits = jnp.matmul(feat, w["word_embedding"].T) \
        + w["mask_lm_out_fc.b_0"]
    mlm = jnp.mean(_log_softmax_nll(
        logits, jnp.asarray(batch["mask_label"]).reshape(-1)))

    # next-sentence head: first token, tanh pooler, 2-way classifier
    pooled = jnp.tanh(_dense(x[:, 0, :], w["pooled_fc.w_0"],
                             w["pooled_fc.b_0"]))
    ns_logits = _dense(pooled, w["next_sent_fc.w_0"], w["next_sent_fc.b_0"])
    nsp = jnp.mean(_log_softmax_nll(
        ns_logits, jnp.asarray(batch["labels"]).reshape(-1)))
    return mlm + nsp


def pretrain_loss_and_grads(w, batch, grad_names, *, n_layer, n_head, eps):
    """(loss, {name: d loss / d w[name]}) for the named parameters."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    @jax.jit
    def run(wanted, rest, batch):
        def loss(wanted):
            return pretrain_loss({**rest, **wanted}, batch,
                                 n_layer=n_layer, n_head=n_head, eps=eps)
        return jax.value_and_grad(loss)(wanted)

    wanted = {k: w[k] for k in grad_names}
    rest = {k: v for k, v in w.items() if k not in wanted}
    with jax.default_matmul_precision(PRECISION):
        loss, grads = run(wanted, rest, batch)
    return loss, grads


# ---------------------------------------------------------------------------
# bert_base_decoder: causal, LM head
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_layer", "n_head", "eps",
                                             "layer_prefix"))
def _decoder_forward(w, tokens, *, n_layer, n_head, eps, layer_prefix):
    s = tokens.shape[0]
    x = w["word_embedding"][tokens] + w["pos_embedding"][jnp.arange(s)]
    x = _layer_norm(x, w["pre_decoder_ln_scale"], w["pre_decoder_ln_bias"],
                    eps)[None]
    causal = jnp.triu(jnp.full((s, s), -1e9, jnp.float32), k=1)[None, None]
    for i in range(n_layer):
        x = _layer(x, w, f"{layer_prefix}{i}", n_head, causal, eps)
    return jnp.matmul(x[0], w["word_embedding"].T) + w["lm_out_bias"]


def decoder_logits(w, tokens, *, n_layer, n_head, eps,
                   layer_prefix="decoder_layer_"):
    """Next-token logits [S, vocab] of one sequence ``tokens`` [S] under
    teacher forcing: row ``p`` scores the token that follows position
    ``p``.  The causal mask makes padding after the sequence's end
    harmless to the rows before it, so callers may pad ``tokens`` to a
    fixed length to compile once."""
    with jax.default_matmul_precision(PRECISION):
        return _decoder_forward(
            {k: jnp.asarray(v, jnp.float32) for k, v in w.items()},
            jnp.asarray(tokens), n_layer=n_layer, n_head=n_head, eps=eps,
            layer_prefix=layer_prefix)
