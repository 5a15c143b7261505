"""A served decoder whose layers are of two kinds — gated delta-rule
linear attention and full softmax attention, in the pattern the
published ``layer_types`` gives — for :class:`serving.DecodeEngine`: the
Olmo-Hybrid family's block.

Every size is a key of the published ``config.json``;
benchmark/reference/olmo_hybrid_jnp.py writes the equations out.  Block
``l`` (OLMo 2/3's reordered norm, for both kinds of layer): ``h = x +
rms(Mixer_l(x))``, ``y = h + rms(MLP(h))``, MLP SwiGLU; after the last
layer ``rms`` and an untied head with float32 logits.

* **linear-attention layer**: ``q' | k' | v' = x W_qkv`` (one product),
  a depthwise causal convolution of ``linear_conv_kernel_dim`` taps over
  time with SiLU on every channel, then the gated delta rule per head
  (ops/linear_attn_ops.py: L2-normalised q and k, ``beta = 2 sigmoid(x
  W_b)`` where ``linear_allow_neg_eigval``, decay ``-exp(A_log)
  softplus(x W_a + dt_bias)``), a per-head RMSNorm gated by ``silu(x
  W_g)`` and the output projection.  Its per-sequence state is one slot
  of two STATE pools a layer: ``S^T`` ``[heads, d_k, d_v]`` float32 and
  the convolution's last ``kernel - 1`` inputs ``[(kernel - 1) *
  channels]`` in the activations' dtype;
* **full-attention layer**: ``q | k | v = x W_qkv``, RMSNorm with a
  learned gain over the whole width of q and of k (OLMo 2/3's QK norm),
  no position signal (the config's ``rope_theta: null``), causal softmax
  attention over the paged K/V pools as ``models.decoder.BertDecoder``
  reads them (``fused_attention``: fresh keys at packed prefill, the
  gathered table with ``QPos`` in a chunk, the paged kernel at decode).

The five programs come from models/decoder_programs.py; this file gives
the layer stack, the head and the cache description (block pools of the
full layers, state pools of the linear ones).  A packed prefill row holds
ONE segment (the engine plans it so): a row is one recurrence.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from .. import layers
from ..framework.initializer import ConstantInitializer, Initializer
from ..framework.layer_helper import LayerHelper, ParamAttr
from .decoder import DecoderPrograms, _attention, _Cache, _cache_write
from .decoder_programs import CacheFeeds, build_decoder_programs
# the same truncated-normal projections, SwiGLU and normed untied head
from .latent_decoder import _attr, _fc, _lm_head, _swiglu

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass
class HybridDecoderConfig:
    """The published keys of an Olmo-Hybrid-family ``config.json``."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    layer_types: List[str] = field(default_factory=lambda: (
        [LINEAR] * 3 + [FULL]) * 8)
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.layer_types = list(self.layer_types)[:self.num_hidden_layers]
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types {self.layer_types} does not name "
                f"{self.num_hidden_layers} layers of {LINEAR} / {FULL}")
        if self.num_key_value_heads != self.num_attention_heads or \
                self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError(
                "grouped K/V heads are not built by this decoder's full "
                "layers (their pools are hidden_size wide; "
                "models/window_decoder.py serves grouped heads), nor "
                "grouped key heads by gated_delta_rule")

    @staticmethod
    def tiny(**kw):
        """The CPU tests' size: hidden 64, one period and a half (6
        layers), 4 heads of 16 / linear 4 heads of 8 x 16."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_hidden_layers=6, num_attention_heads=4,
            num_key_value_heads=4, linear_num_key_heads=4,
            linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=16, max_position_embeddings=4096,
            initializer_range=0.2, dtype="float32")
        base.update(kw)
        return HybridDecoderConfig(**base)

    # -- derived ----------------------------------------------------------
    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_width + self.value_width

    def layers_of(self, kind: str) -> List[int]:
        return [i for i, t in enumerate(self.layer_types) if t == kind]


def _rms(x, name, cfg):
    return layers.rms_norm(x, cfg.rms_norm_eps, ParamAttr(name=name))


class _GateInitializer(Initializer):
    """``u ~ U(low, high)`` drawn ON THE DEVICE from the startup
    program's key, then the unary ``ops`` in turn: the published layer's
    ``A_log = log a``, ``a ~ U(1, 16)`` and ``dt_bias = softplus^-1(dt) =
    log(exp(dt) - 1)``, ``dt ~ logU(1e-3, 1e-1)``.  Values drawn on the
    host and written into the program would make the startup executable
    another for every seed: it then misses the compile cache and compiles
    (a minute at Olmo-Hybrid-7B's widths) in every process."""

    def __init__(self, low, high, ops):
        self.low, self.high, self.ops = low, high, ops

    def __call__(self, var, block):
        block.append_op(type="uniform_random", outputs={"Out": [var]},
                        attrs={"shape": list(var.shape), "dtype": var.dtype,
                               "min": self.low, "max": self.high})
        for op, attrs in self.ops:
            block.append_op(type=op, inputs={"X": [var]},
                            outputs={"Out": [var]}, attrs=attrs)


A_LOG_INIT = _GateInitializer(1.0, 16.0, (("log", {}),))
DT_BIAS_INIT = _GateInitializer(
    float(np.log(1e-3)), float(np.log(1e-1)),
    (("exp", {}), ("exp", {}), ("scale", {"bias": -1.0}), ("log", {})))


def _valid(cache: CacheFeeds):
    """[B, S] bool: the launch's positions that hold a token (a padded
    position writes no cache row: its slot id is -1)."""
    return layers.greater_equal(cache.slots, layers.zeros_like(cache.slots))


def _linear_mixer(x, cfg: HybridDecoderConfig, p: str, index: int,
                  cache: Optional[CacheFeeds]):
    h, dk, dv = cfg.linear_num_key_heads, cfg.linear_key_head_dim, \
        cfg.linear_value_head_dim
    kw, vw = cfg.key_width, cfg.value_width
    taps = cfg.linear_conv_kernel_dim
    stateful = cache is not None
    wiring = {}
    if stateful:
        wiring = {"StateSlot": [cache.state_slot]}
        if cache.fresh is not None:     # prefill, chunk: Sq > 1, padding
            wiring.update(Fresh=[cache.fresh], Valid=[_valid(cache)])
    qkv = _fc(x, cfg.conv_channels, f"{p}_qkv_w", cfg)
    helper = LayerHelper("causal_conv1d", name=f"{p}_conv")
    conv_w = helper.create_parameter(_attr(f"{p}_conv_w", cfg),
                                     [taps, cfg.conv_channels], x.dtype)
    mixed = helper.create_variable_for_type_inference(x.dtype, qkv.shape)
    ins, outs = {"X": [qkv], "W": [conv_w], **wiring}, {"Out": [mixed]}
    if stateful:
        tail = cache.pools["conv"][index]
        ins["TailPool"], outs["TailPoolOut"] = [tail], [tail]
    helper.append_op(type="causal_conv1d", inputs=ins, outputs=outs)
    q, k, v = layers.split(mixed, [kw, kw, vw], dim=2)

    helper = LayerHelper("gated_delta_rule", name=f"{p}_gdn")
    a_log = helper.create_parameter(
        ParamAttr(name=f"{p}_a_log", initializer=A_LOG_INIT), [h], "float32")
    dt_bias = helper.create_parameter(
        ParamAttr(name=f"{p}_dt_bias", initializer=DT_BIAS_INIT), [h],
        "float32")
    ins = {"Q": [q], "K": [k], "V": [v],
           "A": [_fc(x, h, f"{p}_a_w", cfg)],
           "B": [_fc(x, h, f"{p}_b_w", cfg)],
           "ALog": [a_log], "DtBias": [dt_bias], **wiring}
    o = helper.create_variable_for_type_inference(
        x.dtype, tuple(x.shape[:-1]) + (vw,))
    outs = {"Out": [o]}
    if stateful:
        state = cache.pools["state"][index]
        ins["StatePool"], outs["StatePoolOut"] = [state], [state]
    helper.append_op(type="gated_delta_rule", inputs=ins, outputs=outs,
                     attrs={"n_head": h, "beta_scale":
                            2.0 if cfg.linear_allow_neg_eigval else 1.0})

    helper = LayerHelper("gated_rms_norm", name=f"{p}_o_norm")
    gain = helper.create_parameter(
        ParamAttr(name=f"{p}_o_norm_scale"), [dv], x.dtype,
        default_initializer=ConstantInitializer(1.0))
    normed = helper.create_variable_for_type_inference(x.dtype, o.shape)
    helper.append_op(type="gated_rms_norm",
                     inputs={"X": [o], "Scale": [gain],
                             "Gate": [_fc(x, vw, f"{p}_g_w", cfg)]},
                     outputs={"Out": [normed]},
                     attrs={"epsilon": cfg.rms_norm_eps})
    return _fc(normed, cfg.hidden_size, f"{p}_o_w", cfg)


def _full_mixer(x, cfg: HybridDecoderConfig, p: str, index: int,
                cache: Optional[CacheFeeds], attn_bias):
    d = cfg.hidden_size
    q, k, v = layers.split(_fc(x, 3 * d, f"{p}_qkv_w", cfg), 3, dim=2)
    q, k = _rms(q, f"{p}_q_norm_scale", cfg), _rms(k, f"{p}_k_norm_scale",
                                                   cfg)
    kv = None
    if cache is not None:
        # the K/V wiring models.decoder._attention reads
        kv = _Cache(cache.pools["k"], cache.pools["v"], cache.slots,
                    cache.table, cache.ctx_len, cache.q_pos)
        _cache_write(kv.kpools[index], kv.vpools[index], k, v, cache.slots,
                     name=f"{p}_kv")
    ctx = _attention(q, k, v, attn_bias, cfg, p, kv, index)
    return _fc(ctx, d, f"{p}_o_w", cfg)


def decoder_layer(x, cfg, p, index, cache, attn_bias):
    if cfg.layer_types[index] == LINEAR:
        mix = _linear_mixer(x, cfg, p, index, cache)
    else:
        mix = _full_mixer(x, cfg, p, index, cache, attn_bias)
    x = x + _rms(mix, f"{p}_attn_norm_scale", cfg)
    return x + _rms(_swiglu(x, cfg.intermediate_size, p, cfg),
                    f"{p}_ffn_norm_scale", cfg)


class HybridDecoder:
    """The hybrid linear / full attention decoder family for
    :class:`DecodeEngine`: ``build(...)`` as
    ``models.decoder.BertDecoder.build``, plus ``state_slots``."""

    def __init__(self, cfg: Optional[HybridDecoderConfig] = None,
                 name: str = "hybrid", seed: int = 0):
        self.cfg = cfg or HybridDecoderConfig.tiny()
        self.name = name
        self.seed = seed

    # -- engine state -----------------------------------------------------
    def _names(self, what: str, kind: str) -> dict:
        return {i: f"{self.name}_{what}_{i}" for i in self.cfg.layers_of(kind)}

    def cache_vars(self, kinds=()) -> List[str]:
        """The state the engine owns (zeroed at start, the only
        persistables a served program may write): the full layers' K/V
        pools and the linear layers' state and conv-tail pools."""
        return [n for what, kind in (("k_cache", FULL), ("v_cache", FULL),
                                     ("gdn_state", LINEAR),
                                     ("conv_tail", LINEAR))
                for n in self._names(what, kind).values()]

    def cache_block_bytes(self, block_size: int) -> int:
        """On-device bytes ONE K/V pool block costs across the full
        layers (K and V)."""
        cfg = self.cfg
        return 2 * len(cfg.layers_of(FULL)) * block_size * cfg.hidden_size \
            * np.dtype(cfg.dtype).itemsize

    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state one live sequence holds across the
        linear layers: ``S^T`` in float32 and the convolution's tail in
        the activations' dtype (what a decode step reads and writes for
        the row; the device's tiled layout pads a slot further)."""
        cfg = self.cfg
        return len(cfg.layers_of(LINEAR)) * (
            cfg.key_width * cfg.linear_value_head_dim * 4
            + (cfg.linear_conv_kernel_dim - 1) * cfg.conv_channels
            * np.dtype(cfg.dtype).itemsize)

    # -- what models/decoder_programs.py builds from ----------------------
    def declare_cache(self, block, num_blocks, block_size, state_slots):
        cfg = self.cfg

        def declare(names, shape, dtype):
            return {i: block.create_var(name=n, shape=shape, dtype=dtype,
                                        persistable=True)
                    for i, n in names.items()}

        kv_shape = (num_blocks, block_size, cfg.hidden_size)
        return {
            "k": declare(self._names("k_cache", FULL), kv_shape, cfg.dtype),
            "v": declare(self._names("v_cache", FULL), kv_shape, cfg.dtype),
            "state": declare(
                self._names("gdn_state", LINEAR),
                (state_slots, cfg.linear_num_key_heads,
                 cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                "float32"),
            "conv": declare(
                self._names("conv_tail", LINEAR),
                (state_slots,
                 (cfg.linear_conv_kernel_dim - 1) * cfg.conv_channels),
                cfg.dtype)}

    def body(self, ids, pos2d, cache, attn_bias, tag, lift_1d=False):
        cfg = self.cfg
        x = layers.embedding(ids, size=[cfg.vocab_size, cfg.hidden_size],
                             dtype=cfg.dtype,
                             param_attr=_attr("word_embedding", cfg))
        if lift_1d:
            x = layers.unsqueeze(x, axes=[1])
        for i in range(cfg.num_hidden_layers):
            x = decoder_layer(x, cfg, f"{self.name}_layer_{i}", i, cache,
                              attn_bias)
        return x

    def head(self, h2d):
        return _lm_head(h2d, self.cfg)

    def build(self, num_blocks: int, block_size: int,
              max_blocks_per_seq: int, pack_max_segments: int = 1,
              chain_lengths: tuple = (), with_sampling: bool = False,
              chunk_tokens: Optional[int] = None,
              state_slots: int = 2) -> DecoderPrograms:
        if pack_max_segments != 1:
            raise ValueError(
                "a packed prefill row of a model with recurrent layers "
                "holds one segment (one recurrence a row); got "
                f"pack_max_segments={pack_max_segments}")
        return build_decoder_programs(
            self, num_blocks, block_size, max_blocks_per_seq, 1,
            chain_lengths, with_sampling, chunk_tokens, state_slots)


__all__ = ["HybridDecoder", "HybridDecoderConfig"]
