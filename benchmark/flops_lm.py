"""Operations a decoder language model's training step needs, computed
from shapes and from the assignments the router really made.  Kept with
the benchmark so that a change to the program cannot change what a share
of a peak is a share of.  2 per multiply-add; the backward pass costs
twice the forward; nothing recomputed is counted."""

from __future__ import annotations


def causal_pairs(seq: int, window=None) -> int:
    """(query, key) pairs one sequence's causal attention scores:
    ``j <= i`` and, under a window, ``i - j < window`` — exactly."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_fwd_flops(cfg: dict, layer_types, batch: int, seq: int
                        ) -> int:
    """QK^T and PV of every layer, over the pairs its kind can see."""
    per_pair = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    total = 0
    for kind in layer_types[:cfg["num_hidden_layers"]]:
        window = cfg["sliding_window"] \
            if kind == "sliding_attention" else None
        total += per_pair * batch * causal_pairs(seq, window)
    return total


def expert_fwd_flops(cfg: dict, assignments: float) -> float:
    """Gate, up and down products of ``assignments`` (token, expert)
    pairs, summed over the layers."""
    return 6.0 * assignments * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]


def dense_fwd_flops(cfg: dict, router_experts: int, batch: int, seq: int
                    ) -> int:
    """Projections, routers and the head over the vocabulary held."""
    tokens = batch * seq
    d = cfg["hidden_size"]
    heads = (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) \
        * cfg["head_dim"]
    per_layer = 2 * tokens * d * (heads + cfg["num_attention_heads"]
                                  * cfg["head_dim"] + router_experts)
    return cfg["num_hidden_layers"] * per_layer \
        + 2 * tokens * d * cfg["vocab_size"]


def lm_flops_per_step(cfg: dict, layer_types, router_experts: int,
                      batch: int, seq: int, assignments: float) -> dict:
    """Forward + backward FLOPs of one step, whole and by part.
    ``assignments``: (token, expert) pairs computed by the experts held
    here in one step, all layers together (the program's
    ``moe_assignments_local`` counter per step)."""
    parts = {
        "attention": 3 * attention_fwd_flops(cfg, layer_types, batch, seq),
        "experts": 3 * expert_fwd_flops(cfg, assignments),
        "dense": 3 * dense_fwd_flops(cfg, router_experts, batch, seq)}
    parts["step"] = parts["attention"] + parts["experts"] + parts["dense"]
    return parts


def kernel_peak_share_pct(run: dict, rows, part: str):
    """Share of the bf16 peak that the trace rows matching ``rows`` (a
    compiled pattern over ``breakdown.device_ops`` names) reach while
    they run: ``lm_flops[part]`` of the traced steps over those rows'
    device seconds.  None where the record has no such row, no trace or
    no FLOP count (another configuration, an untraced run, a program
    without these kernels)."""
    tr, parts = run.get("trace"), run.get("lm_flops")
    if run["kind"] != "train" or not tr or not parts \
            or not run.get("traced_steps") or not run.get("peaks"):
        return None
    seconds = sum(t for name, t in tr["device_ops"] if rows.match(name))
    if not seconds:
        return None
    rate = parts[part] * run["traced_steps"] / seconds
    return 100.0 * rate / run["peaks"]["bf16_flops"]
