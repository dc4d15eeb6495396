"""Median gap (ms) on the device between successive step programs."""
import numpy as np

from benchmarks import xplane


def reduce(facts, pattern):
    ev = xplane.module_events(facts["trace"], pattern)
    if len(ev) < 2:
        return None
    gaps = [b[0] - (a[0] + a[1]) for a, b in zip(ev, ev[1:])]
    return float(np.median(gaps)) / 1e6
