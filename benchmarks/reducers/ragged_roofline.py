"""The ragged paged-attention kernel's share of its roofline, per step and
layer: the least time the chip needs for what the rows attend, over the
kernel's self time.

Needed bytes: the keys and values of every sequence that owns a row, read
ONCE (`pt_ragged_kv_tokens` x 2 x KV heads x head dimension x the cache
type's bytes: the least any tiling must read), plus the rows' queries read
and outputs written. Needed operations: QK^T and PV over the attended
pairs (`pt_ragged_attn_pairs` x heads x head dimension x 4). The larger of
bytes over the HBM rate and operations over the bf16 peak is the bound;
which one binds is a property of the traffic (decode rows are bound by
bytes). Counters are the program's, as deltas over the whole window; the
kernel's time is from the traced part of it: both are per step."""
from benchmarks import costs, xplane


def _per_token(m, heads, dtype):
    """2 x heads x head dimension x the type's bytes, in ONE layer: a
    token's keys and values (KV heads), or a row's query read and output
    written (all heads)."""
    return costs.kv_bytes_per_token(dict(m, num_key_value_heads=heads),
                                    dtype) / m["num_hidden_layers"]


def needed(config, kv_tokens, pairs, rows):
    """-> (bytes, operations) one layer needs for one step."""
    m, precision = config["model"], config["precision"]
    heads = m["num_attention_heads"]
    need_bytes = \
        kv_tokens * _per_token(m, m["num_key_value_heads"],
                               precision["kv_cache"]) + \
        rows * _per_token(m, heads, precision["weights"])
    return need_bytes, pairs * heads * (m["hidden_size"] // heads) * 4


def reduce(facts, pattern, step_pattern, kv="pt_ragged_kv_tokens",
           pairs="pt_ragged_attn_pairs", rows="pt_ragged_tokens",
           steps="pt_serving_device_steps"):
    c = facts.get("counters") or {}
    traced = len(xplane.module_events(facts["trace"], step_pattern))
    kernel_s = xplane.matching_op_seconds(facts["trace"], pattern)
    if not c.get(steps) or kv not in c or not traced or not kernel_s:
        return None
    n = c[steps]
    need_bytes, need_ops = needed(facts["config"], c[kv] / n,
                                  c.get(pairs, 0.0) / n, c.get(rows, 0.0) / n)
    least_s = max(need_bytes / facts["peaks"]["hbm_bytes_per_s"],
                  need_ops / facts["peaks"]["bf16_flops_per_s"])
    layers = facts["config"]["model"]["num_hidden_layers"]
    return 100.0 * least_s / (kernel_s / traced / layers)
