"""Operations and bytes a latent-attention (MLA) decoder with a shared
expert beside routed ones NEEDS for a decode step, computed from the
configuration's shapes.  Kept with the benchmark so that a change to the
program cannot change what a share of a roofline is a share of.  2 per
multiply-add; ``m`` is a configuration file's model keys (the experts
and the vocabulary HELD, the depth as cut)."""

from __future__ import annotations


def mla_params(m: dict) -> int:
    """Parameters of one layer's latent attention: the two query
    projections, the latent projection, the K/V up-projection, the
    output projection and the two latent norms."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], \
        m["v_head_dim"]
    dq, dc = m["q_lora_rank"], m["kv_lora_rank"]
    return d * dq + dq + dq * h * (dn + dr) + d * (dc + dr) + dc \
        + dc * h * (dn + dv) + h * dv * d


def expert_params(m: dict) -> int:
    """One SiLU-gated expert of width ``moe_intermediate_size``."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def step_fixed_weight_bytes(m: dict, router_experts: int,
                            itemsize: int = 2) -> int:
    """Bytes of weights EVERY decode step reads whatever the router
    does: each layer's attention and norms, the dense layers' FFN, each
    sparse layer's router and shared expert, the final norm and the
    head over the vocabulary held (the embedding lookup reads rows
    only)."""
    d = m["hidden_size"]
    dense = m["first_k_dense_replace"]
    sparse = m["num_hidden_layers"] - dense
    per_layer = mla_params(m) + 2 * d
    return itemsize * (
        m["num_hidden_layers"] * per_layer
        + dense * 3 * d * m["intermediate_size"]
        + sparse * (d * router_experts + router_experts
                    + m["n_shared_experts"] * expert_params(m))
        + d + d * m["vocab_size"])


def expert_weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Bytes a step reads for each held expert that got a token."""
    return itemsize * expert_params(m)


def latent_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Bytes of cache one context position holds across the layers: the
    latent after its norm beside the one rotary key (576 values a layer
    at the published sizes) — what a decode step must read for each
    live position, whatever padding a pool's rows carry."""
    return m["num_hidden_layers"] * itemsize \
        * (m["kv_lora_rank"] + m["qk_rope_head_dim"])


def mla_decode_flops_per_position(m: dict) -> int:
    """Score and value FLOPs of one query token against ONE cached
    position, all layers: every head's score over the latent and the
    rotary key, and its weighted sum of the latent (absorbed form: the
    up-projections are per query, not per position)."""
    dc, dr = m["kv_lora_rank"], m["qk_rope_head_dim"]
    return m["num_hidden_layers"] * m["num_attention_heads"] * 2 \
        * ((dc + dr) + dc)


def mla_decode_needed_seconds(m: dict, positions: int, peaks: dict) -> float:
    """The least time the chip could take for the cache reads of decode
    steps that attended ``positions`` (query token, cached position)
    pairs: the larger of bytes over the HBM peak and FLOPs over the bf16
    peak (at the published sizes 242 FLOP a byte: on the v5e's ridge)."""
    return max(
        positions * latent_bytes_per_token(m) / peaks["hbm_bytes_per_s"],
        positions * mla_decode_flops_per_position(m) / peaks["bf16_flops"])
