"""MoE layer, serving: of the routed experts held here, the share that
got at least one token in a decode step — ``stats()["moe_experts_hit"]
["chain"]`` (a device counter: per step and sparse layer, the held
experts with an assignment) over (held experts x sparse layers x decode
steps) of the window.  A decode step reads the weights of the experts it
hits: at 100 % the step reads every held expert, whatever the batch."""


def read(run):
    st = run.get("engine_stats") or {}
    hit = (st.get("moe_experts_hit") or {}).get("chain")
    slots = run.get("expert_slots_per_step")
    if hit is None or not slots or not st.get("decode_steps"):
        return None
    return 100.0 * hit / (slots * st["decode_steps"])
