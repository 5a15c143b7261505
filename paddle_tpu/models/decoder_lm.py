"""Decoder-only language model with a per-layer attention kind and
dropless sparse experts — the pre-norm RoPE/GQA block of today's open
decoders (no reference analog; the published equations of
JetBrains/Mellum2-12B-A2.5B-Instruct's ``config.json`` fix every one of
them, benchmark/reference/mellum_jnp.py writes them out).

Layer ``l``: ``h = x + Attn_l(rms(x))``, ``y = h + MoE(rms(h))``.
``Attn_l`` has ``num_attention_heads`` query heads on
``num_key_value_heads`` K/V heads, rotary embedding by
``rope_parameters[layer_types[l]]`` (``default`` or ``yarn`` tables) and,
for ``sliding_attention`` layers, a causal window of ``sliding_window``
positions; all of it is ONE ``fused_attention`` op whose attrs
(``window``, ``num_kv_heads``) pick the kernels.  ``MoE`` routes over all
``num_experts`` and computes the experts in ``held_experts`` — the whole
layer, or one chip's share of an expert-parallel one
(parallel.moe_dropless_ffn).  The loss is the mean next-token
cross-entropy over the vocabulary the embedding and the head hold (a
slice is a smaller vocabulary: ids, logits and loss are over it).

Static-graph builder in the style of models/bert.py: feeds ``src_ids``
and ``labels`` [B, S] (``labels`` = the tokens one step on)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

from .. import layers
from ..framework.initializer import TruncatedNormalInitializer
from ..framework.layer_helper import ParamAttr
from .bert import fused_attention

_ROPE_KEYS = ("rope_type", "rope_theta", "factor",
              "original_max_position_embeddings", "beta_fast", "beta_slow",
              "attention_factor")


@dataclass
class DecoderLMConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 896
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    sliding_window: int = 1024
    #: one of ``sliding_attention`` / ``full_attention`` per layer
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 \
        + ("full_attention",)
    #: rotary settings per attention kind, as the published config names
    #: them
    rope_parameters: Optional[dict] = None
    initializer_range: float = 0.02
    #: (lo, hi): the experts this build holds; None holds them all
    held_experts: Optional[Tuple[int, int]] = None
    dtype: str = "float32"

    def __post_init__(self):
        if self.rope_parameters is None:
            self.rope_parameters = {
                k: {"rope_type": "default", "rope_theta": 10000.0}
                for k in ("sliding_attention", "full_attention")}
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")

    @staticmethod
    def tiny():
        """The CPU tests' size: hidden 64, 4 heads on 2, 8 experts top-2,
        window 8, 4 layers (three sliding to one full)."""
        return DecoderLMConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            sliding_window=8, initializer_range=0.2,
            rope_parameters={
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 10000.0},
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 10000.0,
                    "factor": 4.0, "original_max_position_embeddings": 16,
                    "beta_fast": 32, "beta_slow": 1,
                    "attention_factor": 1.1386294361119891}})


def _attr(name, cfg):
    return ParamAttr(name=name, initializer=TruncatedNormalInitializer(
        0.0, cfg.initializer_range))


def decoder_layer(x, cfg: DecoderLMConfig, index: int, is_test=False):
    """One pre-norm layer; ``cfg.layer_types[index]`` picks the window
    and the rotary table."""
    from ..parallel import moe_dropless_ffn
    p = f"lm_layer_{index}"
    kind = cfg.layer_types[index]
    h, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    normed = layers.rms_norm(x, cfg.rms_norm_eps,
                             ParamAttr(name=f"{p}_attn_norm_scale"))
    # one GEMM for the three published matrices side by side
    qkv = layers.fc(normed, (h + 2 * hkv) * d, num_flatten_dims=2,
                    param_attr=_attr(f"{p}_qkv_w", cfg), bias_attr=False)
    q, k, v = layers.split(qkv, [h * d, hkv * d, hkv * d], dim=2)
    rope = {key: val for key, val in cfg.rope_parameters[kind].items()
            if key in _ROPE_KEYS}
    q = layers.rotary_embedding(q, d, **rope)
    k = layers.rotary_embedding(k, d, **rope)
    ctx = fused_attention(
        q, k, v, None, h, 0.0, is_test, name=p, causal=True,
        window=cfg.sliding_window if kind == "sliding_attention" else None,
        num_kv_heads=hkv)
    x = x + layers.fc(ctx, cfg.hidden_size, num_flatten_dims=2,
                      param_attr=_attr(f"{p}_o_w", cfg), bias_attr=False)
    normed = layers.rms_norm(x, cfg.rms_norm_eps,
                             ParamAttr(name=f"{p}_ffn_norm_scale"))
    return x + moe_dropless_ffn(
        normed, cfg.num_experts, cfg.moe_intermediate_size,
        cfg.num_experts_per_tok, held_experts=cfg.held_experts,
        norm_topk_prob=cfg.norm_topk_prob,
        param_attr=_attr(p, cfg), name=f"{p}_moe")


def build_lm_network(cfg: DecoderLMConfig, is_test=False):
    """(feeds, loss, hidden): embedding, ``num_hidden_layers`` layers,
    final RMSNorm (``hidden``; times ``lm_head_w`` it gives the logits),
    untied head and mean next-token cross-entropy in one op."""
    src_ids = layers.data("src_ids", shape=[-1, -1], dtype="int64",
                          append_batch_size=False)
    labels = layers.data("labels", shape=[-1, -1], dtype="int64",
                         append_batch_size=False)
    x = layers.embedding(src_ids, size=[cfg.vocab_size, cfg.hidden_size],
                         dtype=cfg.dtype,
                         param_attr=_attr("word_embedding", cfg))
    for i in range(cfg.num_hidden_layers):
        x = decoder_layer(x, cfg, i, is_test=is_test)
    x = layers.rms_norm(x, cfg.rms_norm_eps,
                        ParamAttr(name="final_norm_scale"))
    loss = layers.mean(layers.lm_head_loss(
        x, labels, cfg.vocab_size, param_attr=_attr("lm_head_w", cfg)))
    return [src_ids, labels], loss, x


def make_fake_batch(rng, cfg: DecoderLMConfig, batch_size=2, seq_len=32):
    """Sequences of ``seq_len + 1`` random ids: the first ``seq_len`` are
    fed, the last ``seq_len`` are the labels."""
    tokens = rng.randint(0, cfg.vocab_size,
                         (batch_size, seq_len + 1)).astype("int64")
    return {"src_ids": tokens[:, :-1].copy(), "labels": tokens[:, 1:].copy()}
