"""The least time the chip needs for one step of a `longcat_flash` model
(`costs_longcat_flash.serve_step_needed`: the larger of its bytes over the
HBM's peak and its operations over the MXU's: every weight outside the
real experts once, the held experts that got a row, the latent rows the
step's rows can see in every sublayer; 2 operations a weight and row),
over the median device time of the step program. All from the program's
counters as deltas over the window."""
from benchmarks import costs_longcat_flash as costs
from benchmarks.reducers import module_time


def reduce(facts, pattern, group="latent", steps="pt_serving_device_steps",
           rows="pt_ragged_tokens"):
    step_ms = module_time.reduce(facts, pattern)
    c = facts.get("counters") or {}
    lab = f'{{layer_type="{group}"}}'
    if not step_ms or not c.get(steps) or not c.get(rows) \
            or not c.get("pt_moe_experts_touched"):
        return None
    cfg, n = facts["config"], c[steps]
    need_bytes, need_ops = costs.serve_step_needed(
        cfg["model"], cfg["precision"], c[rows] / n,
        c["pt_moe_experts_touched"] / n, c.get("pt_moe_assignments", 0.0) / n,
        c.get("pt_ragged_attn_pairs" + lab, 0.0) / n,
        c.get("pt_ragged_kv_tokens" + lab, 0.0) / n)
    least_s = max(need_bytes / facts["peaks"]["hbm_bytes_per_s"],
                  need_ops / facts["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / (step_ms / 1e3)
