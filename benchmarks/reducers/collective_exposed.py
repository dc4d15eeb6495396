"""Collective time with no other operation running on that device, as a
share (%) of the time in which any operation ran."""
from benchmarks import xplane


def reduce(facts):
    busy = xplane.busy_seconds(facts["trace"])
    if not busy:
        return None
    return 100.0 * xplane.exposed_collective_seconds(facts["trace"]) / busy
