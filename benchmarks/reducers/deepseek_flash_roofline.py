"""The latent attention's flash kernels' share of the bf16 peak: the
products the traced steps' attention needs, forward once and backward once a
step, over the keys' and the values' own widths
(`costs_deepseek_v3.latent_flash_flops`, pairs from the run's own document
lengths), over the time of the matching kernel calls. Bound by OPERATIONS:
at 8,192-token sequences a key block is multiplied by hundreds of query
rows. None where the trace holds no such kernel or step."""
from benchmarks import costs_deepseek_v3 as costs, xplane


def reduce(facts, pattern, step_pattern):
    t = xplane.matching_op_seconds(facts["trace"], pattern)
    steps = len(xplane.module_events(facts["trace"], step_pattern))
    if not t or not steps:
        return None
    need = steps * costs.latent_flash_flops(facts["config"]["model"],
                                            facts["pairs_per_step"])
    return 100.0 * need / facts["chips"] / t / facts["peaks"]["bf16_flops_per_s"]
