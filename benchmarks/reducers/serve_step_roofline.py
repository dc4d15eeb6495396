"""The least time the chip needs to read one step's bytes (the weights once
and the mean live keys and values of the run's own requests), over the
median device time of the step program. Bound by bytes: a step of 32 rows
is far below the ridge (197e12 / 819e9 = 240 operations a byte)."""
from benchmarks import costs
from benchmarks.reducers import module_time


def reduce(facts, pattern):
    step_ms = module_time.reduce(facts, pattern)
    if not step_ms:
        return None
    cfg = facts["config"]
    need = costs.serve_step_bytes(cfg["model"], facts["mean_live_tokens"],
                                  cfg["precision"]["weights"],
                                  cfg["precision"]["kv_cache"])
    return 100.0 * (need / facts["peaks"]["hbm_bytes_per_s"]) / (step_ms / 1e3)
