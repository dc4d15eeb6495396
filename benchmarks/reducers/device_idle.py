"""1 - union of busy intervals over the traced window, in %. The window
(`trace_window_s`) is the trace's own extent, `xplane.window_seconds`, so
the share lies in [0, 100]."""
from benchmarks import xplane


def reduce(facts):
    w = facts.get("trace_window_s")
    if not w:
        return None
    return 100.0 * (1.0 - xplane.busy_seconds(facts["trace"]) / w)
