"""MoE layer: how uneven the router's load on the experts held here was
over the window — the fullest expert's assignments over the mean
expert's, summed over layers and steps
(``PreparedStep.stats["moe_expert_load_max"]`` and
``["moe_expert_load_mean"]``, device counters folded at the window's two
blocking points).  1.0 is perfect balance; the grouped products' time
follows the total, their tile padding the spread."""


def read(run):
    stats = run.get("prepared_stats") or {}
    mean = stats.get("moe_expert_load_mean")
    if run["kind"] != "train" or not mean:
        return None
    return stats["moe_expert_load_max"] / mean
