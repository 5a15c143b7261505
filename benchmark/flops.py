"""Operations and bytes the algorithm needs, computed from shapes.  The
functions live with the benchmark so that a change to the program cannot
change what a share of a peak is a share of."""

from __future__ import annotations


def bert_flops_per_step(cfg: dict, batch: int, seq: int, num_masks: int
                        ) -> int:
    """Matrix-multiply FLOPs of one forward + backward BERT pretraining
    step (2 per multiply-add; the backward pass costs twice the
    forward).  Copied from ``bench.py::bert_flops_per_step``.  Counts
    what the mathematics requires: the MLM head scores only the masked
    positions, and nothing recomputed is counted."""
    d = cfg["hidden_size"]
    ff = cfg["intermediate_size"]
    heads = cfg["num_attention_heads"]
    tokens = batch * seq
    per_layer = 2 * tokens * (d * 3 * d + d * d + 2 * d * ff)
    attn = 2 * batch * heads * seq * seq * (d // heads) * 2
    head = 2 * (batch * num_masks) * d * cfg["vocab_size"] \
        + 2 * batch * d * d
    fwd = cfg["num_hidden_layers"] * (per_layer + attn) + head
    return 3 * fwd


def decoder_weight_bytes(cfg: dict, itemsize: int = 4) -> int:
    """Bytes of weights one decode step has to read: every layer's
    matrices and vectors, the position table row aside, plus the tied
    embedding once for the LM head (the input lookup reads rows only)."""
    d = cfg["hidden_size"]
    ff = cfg["intermediate_size"]
    per_layer = (d * 3 * d + 3 * d) + (d * d + d) + (d * ff + ff) \
        + (ff * d + d) + 4 * d
    return itemsize * (cfg["num_hidden_layers"] * per_layer
                       + cfg["vocab_size"] * d + cfg["vocab_size"] + 2 * d)


def kv_bytes_per_token(cfg: dict, itemsize: int = 4) -> int:
    """Bytes of cached keys and values one context position holds across
    all layers — what a decode step must read for each live position."""
    return 2 * cfg["num_hidden_layers"] * cfg["hidden_size"] * itemsize
