"""Time of the device operations whose label matches, as a share (%) of
the time in which any operation ran."""
from benchmarks import xplane


def reduce(facts, pattern):
    busy = xplane.busy_seconds(facts["trace"])
    if not busy:
        return None
    t = xplane.matching_op_seconds(facts["trace"], pattern)
    return 100.0 * t / busy if t else None
