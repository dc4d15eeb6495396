"""The ragged paged-attention kernel's share of its roofline in the layers
of ONE cache group (`group`: `full`, or `window`), per step and layer: the
least time the chip needs for what the rows can see there
(`costs_laguna.attn_needed`: under a window only the tokens inside it), over
the self time of that group's kernel calls (`pattern` tells them apart by
the query heads in the call's shape). Counters
(`pt_ragged_kv_tokens{layer_type=}`, `pt_ragged_attn_pairs{layer_type=}`) as
deltas over the whole window, the time from the traced part of it."""
from benchmarks import costs_laguna as costs, xplane


def reduce(facts, pattern, step_pattern, group, rows="pt_ragged_tokens",
           steps="pt_serving_device_steps"):
    c = facts.get("counters") or {}
    kv = f'pt_ragged_kv_tokens{{layer_type="{group}"}}'
    pairs = f'pt_ragged_attn_pairs{{layer_type="{group}"}}'
    traced = len(xplane.module_events(facts["trace"], step_pattern))
    kernel_s = xplane.matching_op_seconds(facts["trace"], pattern)
    if not c.get(steps) or kv not in c or not traced or not kernel_s:
        return None
    cfg = facts["config"]
    mine = [nh for g, nh, _ in costs.layers(cfg["model"]) if g == group]
    n = c[steps]
    need_bytes, need_ops = costs.attn_needed(
        cfg["model"], cfg["precision"], mine[0], c[kv] / n,
        c.get(pairs, 0.0) / n, c.get(rows, 0.0) / n)
    least_s = max(need_bytes / facts["peaks"]["hbm_bytes_per_s"],
                  need_ops / facts["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / (kernel_s / traced / len(mine))
