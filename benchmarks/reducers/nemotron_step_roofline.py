"""The least time the chip needs for one step of a `nemotron_h` model
(`costs_nemotron_h.serve_step_needed`: the weights outside the routed
experts once, the experts that got a row, every present slot's state read
and written in each Mamba layer, the KV tokens the rows can see, and the
operations of all of it; from the program's counters as deltas over the
window), the larger of its bytes over the memory's peak and its operations
over the bf16 peak, over the median device time of the step program."""
from benchmarks import costs_nemotron_h as costs
from benchmarks.reducers import module_time


def reduce(facts, pattern, steps="pt_serving_device_steps"):
    step_ms = module_time.reduce(facts, pattern)
    c = facts.get("counters") or {}
    if not step_ms or not c.get(steps) or "pt_ssm_state_slots" not in c:
        return None
    cfg = facts["config"]

    def a_step(name):
        return c.get(name, 0.0) / c[steps]
    need_bytes, need_ops = costs.serve_step_needed(
        cfg["model"], cfg["precision"], rows=a_step("pt_ragged_tokens"),
        logit_rows=a_step("pt_logit_rows"),
        experts_touched=a_step("pt_moe_experts_touched"),
        assignments=a_step("pt_moe_assignments"),
        state_slots=a_step("pt_ssm_state_slots"),
        ssm_rows=a_step("pt_ssm_rows"),
        kv_tokens=a_step("pt_ragged_kv_tokens"),
        pairs=a_step("pt_ragged_attn_pairs"))
    least_s = max(need_bytes / facts["peaks"]["hbm_bytes_per_s"],
                  need_ops / facts["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / (step_ms / 1e3)
