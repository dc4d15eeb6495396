"""The fullest held expert's rows over the mean touched expert's, an expert
layer and step: `pt_train_moe_rows_max` over the layer-steps, over
`pt_train_moe_assignments / pt_train_moe_experts_touched`. The registry's
counters over the process (`train_registry_ratio`); None without them."""
from benchmarks import costs_deepseek_v3 as costs
from benchmarks.reducers import train_registry_ratio


def reduce(facts, largest="pt_train_moe_rows_max",
           assignments="pt_train_moe_assignments",
           touched="pt_train_moe_experts_touched", steps="pt_train_steps"):
    c = train_registry_ratio.counters(facts)
    if not all(c.get(k) for k in (largest, assignments, touched, steps)):
        return None
    calls = costs.depth(facts["config"]["model"])[1] * c[steps]
    return (c[largest] / calls) / (c[assignments] / c[touched])
