"""The whole step's share of the chips' bf16 peak for the `deepseek_v3`
family: the operations the model's equations need for the step's tokens,
its attended pairs and the assignments that reached a held expert
(`costs_deepseek_v3.train_flops_per_step`: no credit for recomputation or
padding), over the median device time of the step program. Assignments a
step are the registry's `pt_train_moe_assignments / pt_train_steps`, a
mean over the process (`train_registry_ratio`). None without the counters
or the step program."""
from benchmarks import costs_deepseek_v3 as costs
from benchmarks.reducers import module_time, train_registry_ratio


def reduce(facts, pattern, assignments="pt_train_moe_assignments",
           steps="pt_train_steps"):
    step_ms = module_time.reduce(facts, pattern)
    per_step = train_registry_ratio.reduce(facts, assignments, steps)
    if not step_ms or per_step is None:
        return None
    need = costs.train_flops_per_step(
        facts["config"]["model"], facts["tokens_per_step"],
        facts["pairs_per_step"], per_step)
    peak = facts["chips"] * facts["peaks"]["bf16_flops_per_s"]
    return 100.0 * need / (step_ms / 1e3) / peak
