"""Assignments to identity (zero-compute) experts over ALL assignments a
router made: `pt_moe_assignments_zero` over itself plus the assignments to
real experts, held here (`pt_moe_assignments`) or elsewhere
(`pt_moe_rows_elsewhere`). Counters as deltas over the window; None where
the program books no identity assignments (it has no such experts)."""


def reduce(facts, zero="pt_moe_assignments_zero", held="pt_moe_assignments",
           elsewhere="pt_moe_rows_elsewhere"):
    c = facts.get("counters") or {}
    if not c.get(zero):
        return None
    return c[zero] / (c[zero] + c.get(held, 0.0) + c.get(elsewhere, 0.0))
