"""The grouped expert products' share of their roofline for a
`longcat_flash` model that holds a SHARE of each layer's real experts, per
step and layer: the least time the chip needs to read the weights of the
held experts that got a row and to multiply the assignments through them
(`costs_longcat_flash.moe_needed`; identity assignments cost nothing and
are not among them), over the products' self time. Counters as deltas over
the whole window, the time from the traced part of it. None where the
program books no such counter or the trace holds no such operation."""
from benchmarks import costs_longcat_flash as costs, xplane


def reduce(facts, pattern, step_pattern, touched="pt_moe_experts_touched",
           assignments="pt_moe_assignments", steps="pt_serving_device_steps"):
    c = facts.get("counters") or {}
    traced = len(xplane.module_events(facts["trace"], step_pattern))
    kernel_s = xplane.matching_op_seconds(facts["trace"], pattern)
    if not c.get(steps) or not c.get(touched) or not traced or not kernel_s:
        return None
    cfg = facts["config"]
    layers = cfg["model"]["num_layers"]
    calls = layers * c[steps]
    need_bytes, need_ops = costs.moe_needed(
        cfg["model"], cfg["precision"], c[touched] / calls,
        c.get(assignments, 0.0) / calls)
    least_s = max(need_bytes / facts["peaks"]["hbm_bytes_per_s"],
                  need_ops / facts["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / (kernel_s / traced / layers)
