"""The sparse latent attention kernel's share of its roofline, per step and
layer: the least time the chip needs to fetch the latent rows the
selections KEEP and to put every head through them
(`costs_glm_dsa.latent_attn_needed`), over the self time of the kernel's
calls. What the kernel reads or multiplies beyond the selected rows (it
walks a run's whole context and masks) earns nothing here. Counters as
deltas over the whole window, the time from the traced part of it."""
from benchmarks import costs_glm_dsa as costs, xplane


def reduce(facts, pattern, step_pattern, group="latent",
           steps="pt_serving_device_steps"):
    c = facts.get("counters") or {}
    lab = f'{{layer_type="{group}"}}'
    traced = len(xplane.module_events(facts["trace"], step_pattern))
    kernel_s = xplane.matching_op_seconds(facts["trace"], pattern)
    if not c.get(steps) or not c.get("pt_dsa_rows" + lab) or not traced \
            or not kernel_s:
        return None
    cfg, n = facts["config"], c[steps]
    need_bytes, need_ops = costs.latent_attn_needed(
        cfg["model"], cfg["precision"],
        c.get("pt_dsa_selected_tokens" + lab, 0.0) / n,
        c["pt_dsa_rows" + lab] / n)
    least_s = max(need_bytes / facts["peaks"]["hbm_bytes_per_s"],
                  need_ops / facts["peaks"]["bf16_flops_per_s"])
    layers = cfg["model"]["num_hidden_layers"]
    return 100.0 * least_s / (kernel_s / traced / layers)
