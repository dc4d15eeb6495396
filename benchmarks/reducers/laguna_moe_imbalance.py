"""The fullest expert's rows over the mean expert's, a sparse layer and
step: `pt_moe_rows_max_expert` over the layer-steps, over
`pt_moe_assignments / pt_moe_experts_touched`. Counters as deltas over the
window; None where the program books none."""
from benchmarks import costs_laguna as costs


def reduce(facts, largest="pt_moe_rows_max_expert",
           assignments="pt_moe_assignments", touched="pt_moe_experts_touched",
           steps="pt_serving_device_steps"):
    c = facts.get("counters") or {}
    if not all(c.get(k) for k in (largest, assignments, touched, steps)):
        return None
    calls = costs.sparse_layers(facts["config"]["model"]) * c[steps]
    return (c[largest] / calls) / (c[assignments] / c[touched])
