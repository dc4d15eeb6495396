"""1 - union of busy intervals over the traced window, in %."""
from benchmarks import xplane


def reduce(facts):
    w = facts.get("trace_window_s")
    if not w:
        return None
    return 100.0 * (1.0 - xplane.busy_seconds(facts["trace"]) / w)
