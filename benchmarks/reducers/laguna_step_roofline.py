"""The least time the chip needs to read one step's bytes of a `laguna`
model (`costs_laguna.serve_step_bytes`: the weights outside the routed
experts once, the experts that got a row, and by cache group the KV tokens
the step's rows can see, all from the program's counters as deltas over the
window), over the median device time of the step program. Bound by bytes."""
from benchmarks import costs_laguna as costs
from benchmarks.reducers import module_time


def reduce(facts, pattern, touched="pt_moe_experts_touched",
           steps="pt_serving_device_steps"):
    step_ms = module_time.reduce(facts, pattern)
    c = facts.get("counters") or {}
    if not step_ms or not c.get(steps) or touched not in c:
        return None
    cfg = facts["config"]
    kv = {g: c.get(f'pt_ragged_kv_tokens{{layer_type="{g}"}}', 0.0) / c[steps]
          for g in {g for g, _, _ in costs.layers(cfg["model"])}}
    need = costs.serve_step_bytes(cfg["model"], cfg["precision"],
                                  c[touched] / c[steps], kv)
    return 100.0 * (need / facts["peaks"]["hbm_bytes_per_s"]) / (step_ms / 1e3)
