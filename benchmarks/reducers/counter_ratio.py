"""One program counter over another, both as deltas over the window."""


def reduce(facts, num, den):
    c = facts.get("counters") or {}
    if not c.get(den):
        return None
    return c.get(num, 0.0) / c[den]
