"""Attention kernels' share of the bf16 peak: the operations the traced
steps' attention needs (forward once and backward once per step, from the
run's own batches) over the time of the matching kernel calls. Bound by
operations: at 4,096-token sequences attention does 2 * head_dim = 256
operations a byte of Q, K, V, above the ridge of 240."""
from benchmarks import costs, xplane


def reduce(facts, pattern, step_pattern):
    t = xplane.matching_op_seconds(facts["trace"], pattern)
    steps = len(xplane.module_events(facts["trace"], step_pattern))
    if not t or not steps:
        return None
    m, pairs = facts["config"]["model"], facts["pairs_per_step"]
    need = steps * (costs.attention_flops(m, pairs) +
                    costs.attention_flops(m, pairs, backward=True))
    # op_seconds averages over the chips; each chip does 1/chips of the work
    return 100.0 * need / facts["chips"] / t / facts["peaks"]["bf16_flops_per_s"]
