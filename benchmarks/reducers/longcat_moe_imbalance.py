"""The fullest held expert's rows over the mean touched expert's, a layer
and step, for a `longcat_flash` model: `pt_moe_rows_max_expert` over the
layer-steps, over `pt_moe_assignments / pt_moe_experts_touched`. Counters
as deltas over the window; None where the program books none."""


def reduce(facts, largest="pt_moe_rows_max_expert",
           assignments="pt_moe_assignments", touched="pt_moe_experts_touched",
           steps="pt_serving_device_steps"):
    c = facts.get("counters") or {}
    if not all(c.get(k) for k in (largest, assignments, touched, steps)):
        return None
    calls = facts["config"]["model"]["num_layers"] * c[steps]
    return (c[largest] / calls) / (c[assignments] / c[touched])
