"""Operations one step needs (6 * matmul parameters * tokens plus causal
attention inside documents, no recompute credit) over the median device
time of the step program and the chips' bf16 peak."""
from benchmarks import costs
from benchmarks.reducers import module_time


def reduce(facts, pattern):
    step_ms = module_time.reduce(facts, pattern)
    if not step_ms:
        return None
    need = costs.train_flops_per_step(facts["config"]["model"],
                                      facts["tokens_per_step"],
                                      facts["pairs_per_step"])
    peak = facts["chips"] * facts["peaks"]["bf16_flops_per_s"]
    return 100.0 * need / (step_ms / 1e3) / peak
