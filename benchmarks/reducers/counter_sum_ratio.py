"""A sum of program counters over another counter, all as deltas over the
window, times `scale` (1000: seconds to ms). None where the program books
none of them."""


def reduce(facts, nums, den, scale=1.0):
    c = facts.get("counters") or {}
    if not c.get(den) or not any(k in c for k in nums):
        return None
    return scale * sum(c.get(k, 0.0) for k in nums) / c[den]
