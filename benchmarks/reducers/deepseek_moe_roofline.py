"""The routed experts' grouped products' share of their roofline in
training, a step: the least time the chip needs for the forward product and
the two backward products of each of the three matrices over the
assignments that reached a held expert, and to move the touched experts'
weights and the rows (`costs_deepseek_v3.moe_needed`: the larger of
operations over the bf16 peak and bytes over the memory's), over the
products' self time a traced step. Bound by OPERATIONS at thousands of rows
an expert. Assignments and touched experts a step are the registry's
counters over `pt_train_steps`, means over the process
(`train_registry_ratio`). None without them or the operations."""
from benchmarks import costs_deepseek_v3 as costs, xplane
from benchmarks.reducers import train_registry_ratio


def reduce(facts, pattern, step_pattern,
           touched="pt_train_moe_experts_touched",
           assignments="pt_train_moe_assignments", steps="pt_train_steps"):
    c = train_registry_ratio.counters(facts)
    traced = len(xplane.module_events(facts["trace"], step_pattern))
    kernel_s = xplane.matching_op_seconds(facts["trace"], pattern)
    if not c.get(steps) or not c.get(touched) or not traced or not kernel_s:
        return None
    cfg = facts["config"]
    need_bytes, need_ops = costs.moe_needed(
        cfg["model"], cfg["precision"], c[touched] / c[steps],
        c.get(assignments, 0.0) / c[steps])
    least_s = max(need_bytes / facts["peaks"]["hbm_bytes_per_s"],
                  need_ops / facts["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / (kernel_s / traced)
