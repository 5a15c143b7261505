"""Bytes a decode step of a window / full attention decoder with grouped
K/V heads (the Laguna family: ``sliding_attention`` layers see their last
``sliding_window`` positions, ``full_attention`` layers the whole
context) NEEDS to move: the K/V of the positions its tokens attend, and
the weights it reads.  Computed from the configuration's keys alone and
kept with the benchmark, so that a change to the program cannot change
what a share of a roofline is a share of.  ``m`` is a configuration
file's model keys with the per-layer lists cut to the depth held."""

from __future__ import annotations

import numpy as np

from .decode_book import token_index

FULL, SLIDING = "full_attention", "sliding_attention"
#: bytes of a bfloat16 value
BF16 = 2


def layers_of(m: dict, kind: str) -> int:
    return sum(1 for t in m["layer_types"][:m["num_hidden_layers"]]
               if t == kind)


def kv_bytes_per_position(m: dict) -> int:
    """K and V of one position in one layer: ``num_key_value_heads`` heads
    of ``head_dim`` in bfloat16, twice (4 096 B at the published
    sizes)."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * BF16


def decode_kv_bytes(m: dict, ctx) -> int:
    """K/V bytes the decode tokens whose contexts are ``ctx`` (positions
    attended, the token's own included) must read: ``ctx`` positions in
    every full layer, ``min(ctx, sliding_window)`` in every window
    layer."""
    ctx = np.asarray(ctx, np.int64)
    positions = layers_of(m, FULL) * ctx.sum() + layers_of(m, SLIDING) \
        * np.minimum(ctx, m["sliding_window"]).sum()
    return int(positions) * kv_bytes_per_position(m)


def attention_params(m: dict, heads: int) -> int:
    d, hkv, hid = m["head_dim"], m["num_key_value_heads"], m["hidden_size"]
    # q, k, v, o and the per-head gate
    return hid * (heads * d + 2 * hkv * d) + heads * d * hid + hid * heads


def expert_weight_bytes(m: dict) -> int:
    """One routed expert's gate, up and down matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * BF16


def step_fixed_weight_bytes(m: dict, router_experts: int) -> int:
    """Weights EVERY decode step reads whatever the routing: attention,
    the dense MLP of the ``mlp_only_layers``, the routers and shared
    experts of the sparse layers, and the head over the vocabulary held
    (the embedding is a gather of the batch's rows)."""
    hid = m["hidden_size"]
    n = m["num_hidden_layers"]
    total = sum(attention_params(m, m["num_attention_heads_per_layer"][i])
                for i in range(n))
    for i in range(n):
        if i in m["mlp_only_layers"]:
            total += 3 * hid * m["intermediate_size"]
        else:
            total += hid * router_experts \
                + 3 * hid * m["shared_expert_intermediate_size"]
    total += hid * m["vocab_size"]
    return total * BF16


def decode_contexts(events: dict, lo: int, hi: int) -> np.ndarray:
    """The context (positions attended) of every decode token among
    events ``[lo, hi)``: output token ``i`` of a request with a prompt of
    ``p`` came from a step over ``p + i`` positions; token 0 is
    prefill's."""
    req = np.asarray(events["req"])
    idx = token_index(req)
    sel = np.arange(lo, hi)
    dec = sel[idx[sel] >= 1]
    return np.asarray(events["plen"])[req[dec]] + idx[dec]
