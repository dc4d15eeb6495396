"""The selective-state scan's share of its roofline, per step and Mamba
layer: the least time the chip needs to read and write the state of every
slot that had a row, to move the rows' operands and to do the recurrence's
operations (`costs_nemotron_h.scan_needed`: the same work whatever
implements it), over the self time of the operations whose name matches.
`pt_ssm_state_slots` and `pt_ssm_rows` are one layer's, as deltas over the
whole window; the time comes from the traced part of it. Bound by BYTES: a
decode row moves 4 MB of state for 2.6 M operations. None where the program
books no such counter or the trace holds no such operation."""
from benchmarks import costs_nemotron_h as costs, xplane


def reduce(facts, pattern, step_pattern, slots="pt_ssm_state_slots",
           rows="pt_ssm_rows", steps="pt_serving_device_steps"):
    c = facts.get("counters") or {}
    traced = len(xplane.module_events(facts["trace"], step_pattern))
    kernel_s = xplane.matching_op_seconds(facts["trace"], pattern)
    if not c.get(steps) or not c.get(slots) or not traced or not kernel_s:
        return None
    cfg = facts["config"]
    need_bytes, need_ops = costs.scan_needed(
        cfg["model"], cfg["precision"], c[slots] / c[steps],
        c.get(rows, 0.0) / c[steps])
    least_s = max(need_bytes / facts["peaks"]["hbm_bytes_per_s"],
                  need_ops / facts["peaks"]["bf16_flops_per_s"])
    layers = costs.count(cfg["model"], costs.MAMBA)
    return 100.0 * least_s / (kernel_s / traced / layers)
