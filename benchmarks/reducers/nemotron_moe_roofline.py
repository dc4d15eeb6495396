"""The grouped expert products' share of their roofline, per step and expert
layer, for a `nemotron_h` model: the least time the chip needs to read the
two matrices of the experts that got a row, at their published 1,856
columns, and to multiply the assignments through them
(`costs_nemotron_h.moe_needed`), over the products' self time. Counters as
deltas over the whole window, the time from the traced part of it, both per
step and expert layer. Bound by BYTES at decode's dozen rows an expert. None
where the program books no such counter or the trace holds no such
operation."""
from benchmarks import costs_nemotron_h as costs, xplane


def reduce(facts, pattern, step_pattern, touched="pt_moe_experts_touched",
           assignments="pt_moe_assignments", steps="pt_serving_device_steps"):
    c = facts.get("counters") or {}
    traced = len(xplane.module_events(facts["trace"], step_pattern))
    kernel_s = xplane.matching_op_seconds(facts["trace"], pattern)
    if not c.get(steps) or not c.get(touched) or not traced or not kernel_s:
        return None
    cfg = facts["config"]
    layers = costs.count(cfg["model"], costs.EXPERTS)
    calls = layers * c[steps]
    need_bytes, need_ops = costs.moe_needed(
        cfg["model"], cfg["precision"], c[touched] / calls,
        c.get(assignments, 0.0) / calls)
    least_s = max(need_bytes / facts["peaks"]["hbm_bytes_per_s"],
                  need_ops / facts["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / (kernel_s / traced / layers)
