"""A served pre-norm decoder whose attention layers are of two kinds —
sliding-window and full, both over grouped K/V heads with a per-head
output gate — beside a leading dense SwiGLU layer and sparse layers of a
shared expert next to softmax-routed experts: the Laguna family's block,
for :class:`serving.DecodeEngine`.

Every size is a key of the published ``config.json``;
benchmark/reference/laguna_jnp.py writes the equations out.  Block ``l``
(``rms_norm_eps`` throughout): ``h = x + Attn_l(rms(x))``, ``y = h +
MLP_l(rms(h))``; after the last layer ``rms`` and an untied head with
float32 logits.

* ``Attn_l``: ``num_attention_heads_per_layer[l]`` query heads of
  ``head_dim`` on ``num_key_value_heads`` K/V heads (query head ``h``
  reads K/V head ``h // group``); q, k, v from ONE product (the three
  published matrices side by side); rotary by
  ``rope_parameters[layer_types[l]]`` on the first
  ``partial_rotary_factor`` of each head (rotate-half form), applied
  before the cache write; ``sliding_attention`` sees ``p - sliding_window
  < t <= p``, ``full_attention`` the whole causal context; the per-head
  gate ``o_h * sigmoid(x W_g)[h]`` (``gating: per-head``) before ``W_o``.
* ``MLP_l``: a dense SwiGLU of ``intermediate_size`` on ``mlp_only_layers``;
  elsewhere ``parallel.moe_dropless_ffn``: ``softmax(x W_r)`` over
  ``router_experts`` (``num_experts`` as published), the
  ``num_experts_per_tok`` largest renormalised (``norm_topk_prob``) times
  ``moe_routed_scaling_factor``, over the experts in ``held_experts``, plus
  a shared SwiGLU expert of ``shared_expert_intermediate_size``.

The caches, described to models/decoder_programs.py: a full layer keeps
the whole context in paged blocks of ``[num_blocks, block_size, K/V
width]`` addressed by the engine's block table; a window layer keeps a
RING of ``ring_pages`` pages a sequence in a pool of ``[state_slots *
ring_pages, block_size, K/V width]``, one ring a state slot (taken and
freed with the sequence by the engine, as a recurrent state is).  The
``window_ring`` op derives, inside the program, each written token's ring
slot and the window layers' page table from the sequence's slot, so the
same paged reads serve both kinds (``fused_attention``'s cached routes
with ``num_kv_heads`` and ``window``).  A packed prefill row holds ONE
segment, so fresh-key attention needs no segment bias: the causal mask
with the window is all of it (``flash_gqa`` on a TPU).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

from .. import layers
from ..framework.layer_helper import LayerHelper, ParamAttr
from .decoder import DecoderPrograms
from .decoder_programs import CacheFeeds, build_decoder_programs
# the same truncated-normal projections, SwiGLU and normed untied head
from .latent_decoder import _attr, _fc, _lm_head, _swiglu

FULL, SLIDING = "full_attention", "sliding_attention"


def _laguna_rope():
    return {FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                   "original_max_position_embeddings": 8192,
                   "beta_slow": 1, "beta_fast": 32,
                   "attention_factor": 1.4852030263919618,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 10000,
                      "partial_rotary_factor": 1}}


@dataclass
class WindowDecoderConfig:
    """The published keys of a Laguna-family ``config.json``, and the
    experts this build holds."""
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    mlp_only_layers: List[int] = field(default_factory=lambda: [0])
    sliding_window: int = 512
    layer_types: List[str] = field(default_factory=lambda: (
        [FULL] + [SLIDING] * 3) * 12)
    num_attention_heads_per_layer: List[int] = field(
        default_factory=lambda: [48, 72, 72, 72] * 12)
    rope_parameters: dict = field(default_factory=_laguna_rope)
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    #: the router's width when ``num_experts`` counts the experts held
    #: here (one chip's share); None: ``num_experts``
    router_experts: Optional[int] = None
    #: (lo, hi): the routed experts this build holds; None holds them all
    held_experts: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"

    def __post_init__(self):
        n = self.num_hidden_layers
        self.layer_types = list(self.layer_types)[:n]
        self.num_attention_heads_per_layer = [
            int(h) for h in self.num_attention_heads_per_layer][:n]
        if len(self.layer_types) != n or set(self.layer_types) - {FULL,
                                                                  SLIDING}:
            raise ValueError(f"layer_types {self.layer_types} does not name "
                             f"{n} layers of {FULL} / {SLIDING}")
        if len(self.num_attention_heads_per_layer) != n or any(
                h % self.num_key_value_heads
                for h in self.num_attention_heads_per_layer):
            raise ValueError(
                f"num_attention_heads_per_layer "
                f"{self.num_attention_heads_per_layer}: {n} layers of "
                f"multiples of {self.num_key_value_heads} K/V heads")

    @staticmethod
    def tiny(**kw):
        """The CPU tests' size: hidden 64, heads of 16 (4 full / 6 window
        on 2 K/V heads), window 12, one period and a full layer (5
        layers, the first dense), 16 experts top-4 of width 32."""
        rope = {FULL: dict(_laguna_rope()[FULL], rope_theta=10000.0,
                           factor=4.0, original_max_position_embeddings=16),
                SLIDING: dict(_laguna_rope()[SLIDING])}
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_hidden_layers=5, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_experts=16,
            num_experts_per_tok=4, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, sliding_window=12,
            num_attention_heads_per_layer=[4, 6, 6, 6, 4],
            rope_parameters=rope, max_position_embeddings=4096,
            initializer_range=0.2, dtype="float32")
        base.update(kw)
        return WindowDecoderConfig(**base)

    # -- derived ----------------------------------------------------------
    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def routed_experts(self) -> int:
        return int(self.router_experts or self.num_experts)

    def layers_of(self, kind: str) -> List[int]:
        return [i for i, t in enumerate(self.layer_types) if t == kind]

    def sparse_layers(self) -> List[int]:
        return [i for i in range(self.num_hidden_layers)
                if i not in self.mlp_only_layers]

    def rope_attrs(self, kind: str) -> dict:
        """``layers.rotary_embedding``'s keywords for a layer kind."""
        rope = dict(self.rope_parameters[kind])
        share = float(rope.pop("partial_rotary_factor", 1.0))
        out = {k: rope[k] for k in ("rope_type", "rope_theta", "factor",
                                    "original_max_position_embeddings",
                                    "beta_fast", "beta_slow",
                                    "attention_factor") if k in rope}
        out["rope_theta"] = float(out["rope_theta"])
        out["rotary_dim"] = int(self.head_dim * share)
        return out


def _rms(x, name, cfg):
    return layers.rms_norm(x, cfg.rms_norm_eps, ParamAttr(name=name))


def _attention(x, pos, cfg: WindowDecoderConfig, p: str, index: int,
               cache: Optional[CacheFeeds], tables):
    """One layer's gated grouped attention on the normed ``x``; the cache
    is written (and read, where the program has a table) through the
    layer kind's pools."""
    kind = cfg.layer_types[index]
    heads, d = cfg.num_attention_heads_per_layer[index], cfg.head_dim
    kvw = cfg.kv_width
    q, k, v = layers.split(_fc(x, heads * d + 2 * kvw, f"{p}_qkv_w", cfg),
                           [heads * d, kvw, kvw], dim=2)
    rope = dict(cfg.rope_attrs(kind), pos=pos, leading=True)
    q = layers.rotary_embedding(q, d, **rope)
    k = layers.rotary_embedding(k, d, **rope)
    window = cfg.sliding_window if kind == SLIDING else 0
    attrs = {"n_head": heads, "num_kv_heads": cfg.num_key_value_heads,
             "dropout_rate": 0.0, "is_test": True}
    if window:
        attrs["window"] = window
    helper = LayerHelper("fused_attention", name=f"{p}_attn")
    if cache is not None:
        pools, slots, table = tables[kind]
        kpool, vpool = pools[0][index], pools[1][index]
        LayerHelper("cache_write", name=f"{p}_kv").append_op(
            type="cache_write",
            inputs={"KPool": [kpool], "VPool": [vpool], "K": [k], "V": [v],
                    "Slots": [slots]},
            outputs={"KPoolOut": [kpool], "VPoolOut": [vpool]})
    if cache is not None and cache.table is not None:
        inputs = {"Q": [q], "KPool": [kpool], "VPool": [vpool],
                  "BlockTable": [table], "CtxLen": [cache.ctx_len]}
        if cache.q_pos is not None:
            inputs["QPos"] = [cache.q_pos]
        attrs["_cached"] = True
    else:
        # one segment a packed row: the causal mask (and the window) is
        # all the masking fresh keys need
        inputs = {"Q": [q], "K": [k], "V": [v]}
        attrs["causal"] = True
    ctx = helper.create_variable_for_type_inference(q.dtype, q.shape)
    helper.append_op(type="fused_attention", inputs=inputs,
                     outputs={"Out": [ctx]}, attrs=attrs)
    # the per-head output gate, before W_o
    gate = layers.sigmoid(_fc(x, heads, f"{p}_attn_gate_w", cfg))
    gated = layers.elementwise_mul(
        layers.reshape(ctx, [0, 0, heads, d]),
        layers.unsqueeze(gate, axes=[3]))
    return _fc(layers.reshape(gated, [0, 0, heads * d]), cfg.hidden_size,
               f"{p}_o_w", cfg)


def decoder_layer(x, pos, cfg: WindowDecoderConfig, p: str, index: int,
                  cache, tables, counter_tag):
    x = x + _attention(_rms(x, f"{p}_attn_norm_scale", cfg), pos, cfg, p,
                       index, cache, tables)
    normed = _rms(x, f"{p}_ffn_norm_scale", cfg)
    if index in cfg.mlp_only_layers:
        return x + _swiglu(normed, cfg.intermediate_size, p, cfg)
    from ..parallel import moe_dropless_ffn
    return x + moe_dropless_ffn(
        normed, cfg.routed_experts, cfg.moe_intermediate_size,
        cfg.num_experts_per_tok, held_experts=cfg.held_experts,
        norm_topk_prob=cfg.norm_topk_prob, param_attr=_attr(p, cfg),
        name=f"{p}_moe", scoring="softmax",
        routed_scale=cfg.moe_routed_scaling_factor,
        shared_hidden=cfg.shared_expert_intermediate_size,
        counter_tag=counter_tag)


class WindowDecoder:
    """The window / full attention decoder family for
    :class:`DecodeEngine`: ``build(...)`` as
    ``models.decoder.BertDecoder.build``, plus ``state_slots`` (one ring a
    slot) and ``ring_pages``."""

    def __init__(self, cfg: Optional[WindowDecoderConfig] = None,
                 name: str = "window", seed: int = 0):
        self.cfg = cfg or WindowDecoderConfig.tiny()
        self.name = name
        self.seed = seed
        self._ring_pages = 0

    # -- engine state -----------------------------------------------------
    def _names(self, what: str, kind: str) -> dict:
        return {i: f"{self.name}_{what}_{i}" for i in self.cfg.layers_of(kind)}

    def pool_var_names(self) -> List[str]:
        return [n for what, kind in (("k_cache", FULL), ("v_cache", FULL),
                                     ("k_ring", SLIDING), ("v_ring", SLIDING))
                for n in self._names(what, kind).values()]

    def counter_var_names(self, kinds=("prefill", "chunk", "chain")):
        return [f"{self.name}_layer_{i}_moe.load_stats.{k}"
                for k in kinds for i in self.cfg.sparse_layers()]

    def cache_vars(self, kinds) -> List[str]:
        """The state the engine owns (zeroed at start, the only
        persistables a served program may write): the full layers' block
        pools, the window layers' ring pools, the device counters."""
        return self.pool_var_names() + self.counter_var_names(kinds)

    def _page_bytes(self, kind: str, block_size: int) -> int:
        cfg = self.cfg
        return 2 * len(cfg.layers_of(kind)) * block_size * cfg.kv_width \
            * np.dtype(cfg.dtype).itemsize

    def cache_block_bytes(self, block_size: int) -> int:
        """On-device bytes ONE block of the engine's pool costs across the
        full layers (K and V)."""
        return self._page_bytes(FULL, block_size)

    def window_block_bytes(self, block_size: int) -> int:
        """On-device bytes ONE ring page costs across the window layers."""
        return self._page_bytes(SLIDING, block_size)

    def window_ring_pages(self, block_size: int, launch_tokens: int) -> int:
        """Pages of a sequence's ring: every position a launch writing
        ``launch_tokens`` of a row still reads (its first query's window
        reaches ``window - 1`` positions back), in whole pages, plus the
        page a position range may start inside."""
        if not self.cfg.layers_of(SLIDING):
            return 0
        span = self.cfg.sliding_window + int(launch_tokens) - 1
        return -(-span // int(block_size)) + 1

    # -- what models/decoder_programs.py builds from ----------------------
    def declare_cache(self, block, num_blocks, block_size, state_slots):
        cfg = self.cfg

        def declare(names, rows):
            return {i: block.create_var(
                name=n, shape=(rows, block_size, cfg.kv_width),
                dtype=cfg.dtype, persistable=True) for i, n in names.items()}

        ring = state_slots * self._ring_pages
        return {"k": declare(self._names("k_cache", FULL), num_blocks),
                "v": declare(self._names("v_cache", FULL), num_blocks),
                "wk": declare(self._names("k_ring", SLIDING), ring),
                "wv": declare(self._names("v_ring", SLIDING), ring)}

    def _tables(self, cache: CacheFeeds, pos2d):
        """Per layer kind (pools, slot ids of the written tokens, block
        table): the full layers' are the engine's feeds, the window
        layers' come from ``window_ring``."""
        tables = {FULL: ((cache.pools["k"], cache.pools["v"]), cache.slots,
                         cache.table)}
        if self.cfg.layers_of(SLIDING):
            helper = LayerHelper("window_ring", name=f"{self.name}_ring")
            ring_slots = helper.create_variable_for_type_inference(
                "int32", tuple(pos2d.shape))
            outs = {"RingSlots": [ring_slots]}
            attrs = {"ring_pages": self._ring_pages,
                     "block_size": int(cache.pools["wk"][
                         self.cfg.layers_of(SLIDING)[0]].shape[1])}
            table = None
            if cache.table is not None:
                pages = int(cache.table.shape[1])
                table = helper.create_variable_for_type_inference(
                    "int32", (-1, pages))
                outs["Table"] = [table]
                attrs["table_pages"] = pages
            helper.append_op(
                type="window_ring",
                inputs={"StateSlot": [cache.state_slot], "Pos": [pos2d],
                        "Slots": [cache.slots]},
                outputs=outs, attrs=attrs)
            tables[SLIDING] = ((cache.pools["wk"], cache.pools["wv"]),
                               ring_slots, table)
        return tables

    def body(self, ids, pos2d, cache, attn_bias, tag, lift_1d=False):
        cfg = self.cfg
        x = layers.embedding(ids, size=[cfg.vocab_size, cfg.hidden_size],
                             dtype=cfg.dtype,
                             param_attr=_attr("word_embedding", cfg))
        if lift_1d:
            x = layers.unsqueeze(x, axes=[1])
        tables = self._tables(cache, pos2d) if cache is not None else None
        for i in range(cfg.num_hidden_layers):
            x = decoder_layer(x, pos2d, cfg, f"{self.name}_layer_{i}", i,
                              cache, tables, tag)
        return x

    def head(self, h2d):
        return _lm_head(h2d, self.cfg)

    def build(self, num_blocks: int, block_size: int,
              max_blocks_per_seq: int, pack_max_segments: int = 1,
              chain_lengths: tuple = (), with_sampling: bool = False,
              chunk_tokens: Optional[int] = None, state_slots: int = 2,
              ring_pages: int = 0) -> DecoderPrograms:
        if pack_max_segments != 1:
            raise ValueError(
                "a packed prefill row of a model with window rings holds "
                "one segment (one ring a row); got "
                f"pack_max_segments={pack_max_segments}")
        if self.cfg.layers_of(SLIDING) and ring_pages < 1:
            raise ValueError("window layers need ring_pages >= 1 (the "
                             "engine sizes the ring: window_ring_pages)")
        self._ring_pages = int(ring_pages)
        return build_decoder_programs(
            self, num_blocks, block_size, max_blocks_per_seq, 1,
            chain_lengths, with_sampling, chunk_tokens, state_slots)


__all__ = ["WindowDecoder", "WindowDecoderConfig"]
