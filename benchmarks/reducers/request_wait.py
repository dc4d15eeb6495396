"""Harrell-Davis median of the wait from a request's due time to the
scheduler's admission stamp, over the requests due in the window."""
from benchmarks import stats


def reduce(facts, q=0.5):
    waits = facts.get("queue_wait_ms") or []
    return stats.harrell_davis(waits, q) if waits else None
