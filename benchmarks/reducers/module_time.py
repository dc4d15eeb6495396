"""Median device duration (ms) of the step program whose name matches."""
import numpy as np

from benchmarks import xplane


def reduce(facts, pattern):
    ev = xplane.module_events(facts["trace"], pattern)
    return float(np.median([d for _, d in ev])) / 1e6 if ev else None
