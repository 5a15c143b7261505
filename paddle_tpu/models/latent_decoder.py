"""A served pre-norm decoder with latent attention (MLA) over a paged
LATENT cache, leading dense SwiGLU layers and sparse layers of a shared
expert beside sigmoid-scored, group-limited, dropless routed experts —
the DeepSeek-V3 family's block, for :class:`serving.DecodeEngine`.

Every equation is fixed by a key of the published ``config.json``;
benchmark/reference/deepseek_v3_jnp.py writes them out.  Block ``l``:
``h = x + MLA_l(rms(x))``, ``y = h + FFN_l(rms(h))``; after the last
layer ``rms`` and an untied head with float32 logits.

What the engine gets is the same :class:`models.decoder.DecoderPrograms`
``BertDecoder.build`` returns (prefill, decode, chains, chunk, score,
startup), over one parameter set, built by the scaffold of
models/decoder_programs.py from this file's layer stack, head and cache
description:

* the cache is ONE pool a layer: a block holds ``[block_size, W]``
  bfloat16 rows ``[c_kv after its norm | k_rope after its rotation |
  0]`` (``kv_lora_rank + qk_rope_head_dim`` values padded to whole
  128-lane tiles, 576 -> 640: the tiled HBM layout pads the last
  dimension anyway); ``cache_write`` writes one tensor;
* positions are rotary, computed from the ``pos_ids`` feed (a decode
  step's from its carried position): there is no position table;
* **prefill** and **score** run the EXPANDED attention on fresh latents;
  **chunk** the expanded form over the cache with ``QPos``; **decode /
  chains** the ABSORBED form reading the pool through the block table
  (ops/mla_ops.py picks by shape, the Pallas route
  ``mla_paged_decode`` on a TPU);
* the sparse layers compute the experts in ``held_experts`` — the whole
  layer, or one chip's share of an expert-parallel one — and the shared
  expert, which every chip holds (parallel.moe_dropless_ffn).  Each
  program kind counts its routed assignments and the held experts it hit
  on the device (``.load_stats.<prefill|chunk|chain>`` persistables,
  declared as engine state beside the pools);
* the chain programs also return every step's logits stacked
  (``chain_logits``), which the engine reads — sliced on the device —
  only for requests that asked (``generate(return_logits=True)``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

from .. import layers
from ..framework.initializer import (NormalInitializer,
                                     TruncatedNormalInitializer)
from ..framework.layer_helper import LayerHelper, ParamAttr
from .decoder import DecoderPrograms
from .decoder_programs import CacheFeeds, build_decoder_programs

LANES = 128


@dataclass
class LatentDecoderConfig:
    """The published keys of a DeepSeek-V3-family ``config.json``."""
    vocab_size: int = 129280
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    first_k_dense_replace: int = 3
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = field(default_factory=lambda: {
        "type": "yarn", "factor": 40, "original_max_position_embeddings":
        4096, "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
        "mscale_all_dim": 1.0})
    max_position_embeddings: int = 163840
    initializer_range: float = 0.02
    #: std of the selection-only router bias ``b`` (0 leaves it at 0)
    router_bias_std: float = 0.0
    #: (lo, hi): the routed experts this build holds; None holds them all
    held_experts: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"

    @staticmethod
    def tiny(**kw):
        """The CPU tests' size: hidden 64, 4 heads of 16+8 / v 16, latent
        32, q-latent 48, 16 experts in 4 groups (top-2 groups, top-4),
        3 layers of which 1 dense."""
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=96, moe_intermediate_size=32,
            first_k_dense_replace=1, n_routed_experts=16,
            num_experts_per_tok=4, n_group=4, topk_group=2,
            rope_scaling={"type": "yarn", "factor": 4.0,
                          "original_max_position_embeddings": 16,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
                          "mscale_all_dim": 1.0},
            max_position_embeddings=4096, initializer_range=0.2,
            router_bias_std=0.05, dtype="float32")
        base.update(kw)
        return LatentDecoderConfig(**base)

    # -- derived ----------------------------------------------------------
    @property
    def latent_width(self) -> int:
        """Columns of a cache row: ``[c_kv | k_rope]`` padded to whole
        lane tiles."""
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // LANES) * LANES

    def mscale(self, key: str) -> float:
        rs = self.rope_scaling
        if not rs or float(rs["factor"]) <= 1:
            return 1.0
        return 0.1 * float(rs.get(key, 0)) * math.log(float(rs["factor"])) \
            + 1.0

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * self.mscale("mscale_all_dim") ** 2

    def rope_attrs(self) -> dict:
        rs = self.rope_scaling
        if not rs:
            return {"rope_type": "default", "rope_theta": self.rope_theta}
        return {"rope_type": "yarn", "rope_theta": self.rope_theta,
                "factor": float(rs["factor"]),
                "original_max_position_embeddings":
                    rs["original_max_position_embeddings"],
                "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
                # cos and sin times mscale / mscale_all_dim
                "attention_factor": self.mscale("mscale")
                / self.mscale("mscale_all_dim")}


def _attr(name, cfg):
    return ParamAttr(name=name, initializer=TruncatedNormalInitializer(
        0.0, cfg.initializer_range))


def _fc(x, size, name, cfg):
    return layers.fc(x, size, num_flatten_dims=2,
                     param_attr=_attr(name, cfg), bias_attr=False)


def _swiglu(x, width, p, cfg):
    hid = layers.elementwise_mul(
        layers.swish(_fc(x, width, f"{p}_gate_w", cfg)),
        _fc(x, width, f"{p}_up_w", cfg))
    return _fc(hid, cfg.hidden_size, f"{p}_down_w", cfg)


def _mla(x, pos, cfg: LatentDecoderConfig, p: str,
         cache: Optional[CacheFeeds], layer_idx: int, attn_bias):
    """Latent attention of one layer: the projections, the latent row
    (written to the cache when there is one) and ``mla_attention`` in the
    form the cache wiring selects."""
    h, dn, dr, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim, cfg.v_head_dim
    dc, eps = cfg.kv_lora_rank, cfg.rms_norm_eps
    rope = dict(cfg.rope_attrs(), pos=pos, interleaved=True)
    c_q = layers.rms_norm(_fc(x, cfg.q_lora_rank, f"{p}_q_a_w", cfg), eps,
                          ParamAttr(name=f"{p}_q_a_norm_scale"))
    # heads of [q_nope | q_rope]: only the rotary part rotates
    q = layers.rotary_embedding(_fc(c_q, h * (dn + dr), f"{p}_q_b_w", cfg),
                                dn + dr, rotary_dim=dr, **rope)
    kv_a = _fc(x, dc + dr, f"{p}_kv_a_w", cfg)
    c_kv, k_r = layers.split(kv_a, [dc, dr], dim=2)
    c_kv = layers.rms_norm(c_kv, eps,
                           ParamAttr(name=f"{p}_kv_a_norm_scale"))
    latent = layers.concat(
        [c_kv, layers.rotary_embedding(k_r, dr, **rope)], axis=2)
    pad = cfg.latent_width - dc - dr
    if pad:
        latent = layers.pad(latent, [0, 0, 0, 0, 0, pad])
    helper = LayerHelper("mla_attention", name=f"{p}_attn")
    wkvb = helper.create_parameter(_attr(f"{p}_kv_b_w", cfg),
                                   [dc, h * (dn + dv)], x.dtype)
    inputs = {"Q": [q], "WKVB": [wkvb]}
    attrs = {"n_head": h, "nope_dim": dn, "rope_dim": dr, "v_dim": dv,
             "scale": cfg.softmax_scale}
    if cache is not None:
        pool = cache.pools[layer_idx]
        LayerHelper("cache_write", name=f"{p}_latent").append_op(
            type="cache_write",
            inputs={"KPool": [pool], "K": [latent], "Slots": [cache.slots]},
            outputs={"KPoolOut": [pool]})
    if cache is not None and cache.table is not None:
        inputs.update({"Pool": [cache.pools[layer_idx]],
                       "BlockTable": [cache.table],
                       "CtxLen": [cache.ctx_len]})
        if cache.q_pos is not None:
            inputs["QPos"] = [cache.q_pos]
        attrs["_cached"] = True
    else:
        inputs["Latent"] = [latent]
        if attn_bias is not None:
            inputs["AttnBias"] = [attn_bias]
    out = helper.create_variable_for_type_inference(
        x.dtype, tuple(x.shape[:-1]) + (h * dv,))
    helper.append_op(type="mla_attention", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return _fc(out, cfg.hidden_size, f"{p}_o_w", cfg)


def decoder_layer(x, pos, cfg: LatentDecoderConfig, p: str, index: int,
                  cache: Optional[CacheFeeds], attn_bias, counter_tag):
    eps = cfg.rms_norm_eps
    x = x + _mla(layers.rms_norm(x, eps,
                                 ParamAttr(name=f"{p}_attn_norm_scale")),
                 pos, cfg, p, cache, index, attn_bias)
    normed = layers.rms_norm(x, eps, ParamAttr(name=f"{p}_ffn_norm_scale"))
    if index < cfg.first_k_dense_replace:
        return x + _swiglu(normed, cfg.intermediate_size, p, cfg)
    from ..parallel import moe_dropless_ffn
    return x + moe_dropless_ffn(
        normed, cfg.n_routed_experts, cfg.moe_intermediate_size,
        cfg.num_experts_per_tok, held_experts=cfg.held_experts,
        norm_topk_prob=cfg.norm_topk_prob, param_attr=_attr(p, cfg),
        name=f"{p}_moe", scoring=cfg.scoring_func, n_group=cfg.n_group,
        topk_group=cfg.topk_group,
        routed_scale=cfg.routed_scaling_factor,
        shared_hidden=cfg.n_shared_experts * cfg.moe_intermediate_size,
        bias_attr=ParamAttr(name=p, initializer=NormalInitializer(
            0.0, cfg.router_bias_std)) if cfg.router_bias_std else None,
        counter_tag=counter_tag)


def _lm_head(h2d, cfg: LatentDecoderConfig):
    """Final norm and the untied head on ``[N, d]`` hiddens -> (logits
    [N, V] float32, greedy next tokens [N])."""
    h2d = layers.rms_norm(h2d, cfg.rms_norm_eps,
                          ParamAttr(name="final_norm_scale"))
    helper = LayerHelper("lm_head")
    w = helper.create_parameter(_attr("lm_head_w", cfg),
                                [cfg.hidden_size, cfg.vocab_size], h2d.dtype)
    block = h2d.block
    logits = block.create_var(name="next_logits",
                              shape=(h2d.shape[0], cfg.vocab_size),
                              dtype="float32")
    helper.append_op(type="lm_head_logits", inputs={"X": [h2d], "W": [w]},
                     outputs={"Out": [logits]})
    tokens = layers.argmax(logits, axis=-1)
    out_tokens = block.create_var(name="next_tokens", shape=tokens.shape,
                                  dtype=tokens.dtype)
    helper.append_op(type="assign", inputs={"X": [tokens]},
                     outputs={"Out": [out_tokens]})
    return logits, out_tokens


class LatentDecoder:
    """The latent-attention decoder family for :class:`DecodeEngine`:
    ``build(...)`` as ``models.decoder.BertDecoder.build``."""

    def __init__(self, cfg: Optional[LatentDecoderConfig] = None,
                 name: str = "latent", seed: int = 0):
        self.cfg = cfg or LatentDecoderConfig.tiny()
        self.name = name
        self.seed = seed

    # -- engine state -----------------------------------------------------
    def _sparse_layers(self):
        return range(self.cfg.first_k_dense_replace,
                     self.cfg.num_hidden_layers)

    def pool_var_names(self) -> List[str]:
        return [f"{self.name}_latent_cache_{i}"
                for i in range(self.cfg.num_hidden_layers)]

    def counter_var_names(self, kinds=("prefill", "chunk", "chain")):
        return [f"{self.name}_layer_{i}_moe.load_stats.{k}"
                for k in kinds for i in self._sparse_layers()]

    def cache_var_names(self) -> List[str]:
        """The state the engine owns (zeroed at start, the only
        persistables a served program may write): the latent pools and
        the device counters."""
        return self.pool_var_names() + self.counter_var_names()

    def cache_block_bytes(self, block_size: int) -> int:
        """On-device bytes ONE pool block costs across every layer: a
        row is ``latent_width`` bfloat16 values (the 576 live ones of
        the published sizes are ``block_size * 576 * 2 * layers``)."""
        import numpy as np
        return (self.cfg.num_hidden_layers * block_size
                * self.cfg.latent_width * np.dtype(self.cfg.dtype).itemsize)

    def cache_layout_key(self, block_size: int) -> str:
        cfg = self.cfg
        return (f"{self.name}/mla/seed={self.seed}/L={cfg.num_hidden_layers}"
                f"/H={cfg.hidden_size}/latent={cfg.kv_lora_rank}"
                f"+{cfg.qk_rope_head_dim}/heads={cfg.num_attention_heads}"
                f"/E={cfg.n_routed_experts}k{cfg.num_experts_per_tok}"
                f"held={cfg.held_experts}/V={cfg.vocab_size}"
                f"/dtype={cfg.dtype}/bs={block_size}")

    # -- what models/decoder_programs.py builds from ----------------------
    def declare_cache(self, block, num_blocks, block_size, state_slots=0):
        return [block.create_var(
            name=n, shape=(num_blocks, block_size, self.cfg.latent_width),
            dtype=self.cfg.dtype, persistable=True)
            for n in self.pool_var_names()]

    def cache_vars(self, kinds) -> List[str]:
        return self.pool_var_names() + self.counter_var_names(kinds)

    def body(self, ids, pos2d, cache, attn_bias, tag, lift_1d=False):
        cfg = self.cfg
        x = layers.embedding(ids, size=[cfg.vocab_size, cfg.hidden_size],
                             dtype=cfg.dtype,
                             param_attr=_attr("word_embedding", cfg))
        if lift_1d:
            x = layers.unsqueeze(x, axes=[1])
        for i in range(cfg.num_hidden_layers):
            x = decoder_layer(x, pos2d, cfg, f"{self.name}_layer_{i}", i,
                              cache, attn_bias, tag)
        return x

    def head(self, h2d):
        return _lm_head(h2d, self.cfg)

    def build(self, num_blocks: int, block_size: int,
              max_blocks_per_seq: int, pack_max_segments: int = 1,
              chain_lengths: tuple = (), with_sampling: bool = False,
              chunk_tokens: Optional[int] = None) -> DecoderPrograms:
        return build_decoder_programs(
            self, num_blocks, block_size, max_blocks_per_seq,
            pack_max_segments, chain_lengths, with_sampling, chunk_tokens)


__all__ = ["LatentDecoder", "LatentDecoderConfig"]
