"""A sum of program counters over another sum of them, all as deltas over
the window, times `scale` (1000: seconds to ms): a share where `nums` is a
part of `dens`, a mean where `dens` counts what `nums` adds up. None where
the program books none of `dens`."""


def reduce(facts, nums, dens, scale=1.0):
    c = facts.get("counters") or {}
    den = sum(c.get(k, 0.0) for k in dens)
    if not den:
        return None
    return scale * sum(c.get(k, 0.0) for k in nums) / den
