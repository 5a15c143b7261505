"""Operations a latent-attention (MLA) decoder NEEDS for the cached read
of its prefill chunks, computed from the configuration's shapes and the
engine's count of the work the chunks' queries had to see.  Kept with
the benchmark so that a change to the program cannot change what a share
of a roofline is a share of.  2 per multiply-add; ``m`` is a
configuration file's model keys (the depth as cut)."""

from __future__ import annotations


def chunk_flops_per_pair(m: dict) -> int:
    """One (query, key) pair, all layers, in the EXPANDED form: every
    head's score over its up-projected key and the shared rotary key,
    and its weighted sum of its up-projected value (81 920 a layer at the
    published widths)."""
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], \
        m["v_head_dim"]
    return m["num_hidden_layers"] * 2 * m["num_attention_heads"] \
        * (dn + dr + dv)


def chunk_flops_per_key(m: dict) -> int:
    """One context position of a chunk, all layers: its latent
    up-projected ONCE to every head's key and value (33 554 432 a layer
    at the published widths)."""
    return m["num_hidden_layers"] * 2 * m["kv_lora_rank"] \
        * m["num_attention_heads"] * (m["qk_nope_head_dim"] + m["v_head_dim"])


def chunk_needed_flops(m: dict, pairs: int, positions: int) -> int:
    """The work any implementation of the chunks' read must do for
    ``pairs`` causal (query, key) pairs over contexts of ``positions``
    positions in all."""
    return pairs * chunk_flops_per_pair(m) \
        + positions * chunk_flops_per_key(m)
