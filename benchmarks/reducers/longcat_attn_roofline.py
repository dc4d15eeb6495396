"""The dense latent attention kernel's share of its roofline, per step and
sublayer: the least time the chip needs to read the latent rows the step's
rows can see once and to put every head through every (row, position) pair
(`costs_longcat_flash.latent_attn_needed`), over the self time of the
kernel's calls. What the kernel reads twice (a context walked by two q
blocks, the lanes a row is padded to) earns nothing here. Counters
(`pt_ragged_attn_pairs` / `pt_ragged_kv_tokens{layer_type=}`, a layer of
the group) as deltas over the whole window, the time from the traced part
of it. None where the program books no such counter or the trace holds no
such operation."""
from benchmarks import costs_longcat_flash as costs, xplane


def reduce(facts, pattern, step_pattern, group="latent",
           steps="pt_serving_device_steps", rows="pt_ragged_tokens"):
    c = facts.get("counters") or {}
    lab = f'{{layer_type="{group}"}}'
    traced = len(xplane.module_events(facts["trace"], step_pattern))
    kernel_s = xplane.matching_op_seconds(facts["trace"], pattern)
    if not c.get(steps) or not c.get("pt_ragged_attn_pairs" + lab) \
            or not traced or not kernel_s:
        return None
    cfg, n = facts["config"], c[steps]
    need_bytes, need_ops = costs.latent_attn_needed(
        cfg["model"], cfg["precision"], c["pt_ragged_attn_pairs" + lab] / n,
        c.get("pt_ragged_kv_tokens" + lab, 0.0) / n, c.get(rows, 0.0) / n)
    least_s = max(need_bytes / facts["peaks"]["hbm_bytes_per_s"],
                  need_ops / facts["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / (kernel_s / traced
                              / costs.sublayers(cfg["model"]))
