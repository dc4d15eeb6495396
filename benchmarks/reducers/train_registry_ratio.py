"""One counter of the training program's registry over another, as they
stand when the reducer runs.

`drivers/train.py` hands reducers no counters (`facts["counters"]` is empty
in a training cell), so the family's `models/<family>.py` keeps the step
objects it built (`TRAINERS`) and this reads the newest one's registry
through its `snapshot()`. The reading is over the PROCESS, set-up's three
checked steps included, not over the window: a ratio of two counters of the
same steps bears that (rows a touched expert, assignments a step), a rate
would not, so none is made from it. None where the family keeps no trainer
or the step has no registry (a program from before the counters)."""
from benchmarks import harness


def counters(facts):
    """{name: value} of the newest trainer's registry, {} without one."""
    family = harness.load_module("models", facts["config"]["family"])
    trainers = getattr(family, "TRAINERS", None)
    snapshot = getattr(trainers[-1], "snapshot", None) if trainers else None
    if snapshot is None:
        return {}
    return {k: v["value"] for k, v in snapshot().items() if "value" in v}


def reduce(facts, num, den):
    c = counters(facts)
    if not c.get(den):
        return None
    return c.get(num, 0.0) / c[den]
