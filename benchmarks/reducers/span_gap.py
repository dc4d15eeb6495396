"""The device's idle time between step programs, split among the program's
own spans on the pump thread (ms a gap).

Every instant of the first device's idle gaps (`xplane.idle_gaps`) between
the start of the trace's first step program and the end of its last goes to
the INNERMOST program span that covers it on the host line that holds
`serving.turn`; a gap is split among the spans under it, never given whole
to one. The sums are divided by the number of gaps between step programs,
so the six parts add up to the mean gap by construction (`unattributed` is
what lies under no span of `SPANS`: outside every turn, or in a turn's own
time between its children). A trace without `serving.turn` has nothing to
read: None."""
from benchmarks import xplane

TURN = "serving.turn"
# program span (its name starts with the key) -> the part its instants go to
SPANS = {
    "serving.sched_feed": "admit", "serving.admit": "admit",
    "serving.plan": "plan",
    "serving.stage": "dispatch", "serving.unified_step": "dispatch",
    "serving.seed_gather": "dispatch",
    "serving.fetch": "consume", "serving.consume": "consume",
    "serving.publish": "consume",
    "serving.telemetry": "telemetry", "pt.track_jit": "telemetry",
}
PARTS = ("admit", "plan", "dispatch", "consume", "telemetry", "unattributed")


def part_of(name):
    for prefix, part in SPANS.items():
        if name.startswith(prefix):
            return part
    return None


def pump_line(trace):
    """The events of the host line that holds `serving.turn`, or None."""
    for events in trace["host"].values():
        if any(name.startswith(TURN) for name, _, _ in events):
            return events
    return None


def innermost(events):
    """[(start, end, part)], disjoint and in time order: each stretch of
    the line's program spans under the part of the innermost span that
    covers it (a turn's own time carries part None)."""
    spans = sorted(((s, s + d, part_of(n)) for n, s, d in events
                    if n.startswith(TURN) or part_of(n) is not None),
                   key=lambda x: (x[0], -x[1]))
    out, stack, at = [], [], 0.0

    def close():
        nonlocal at
        _, end, part = stack.pop()
        if end > at:
            out.append((at, end, part))
            at = end

    for start, end, part in spans:
        while stack and stack[-1][1] <= start:
            close()
        if stack:
            if start > at:
                out.append((at, start, stack[-1][2]))
            end = min(end, stack[-1][1])
        at = start
        stack.append((start, end, part))
    while stack:
        close()
    return out


def split(trace, pattern):
    """-> ({part: idle ns}, gaps between step programs) or None."""
    steps = xplane.module_events(trace, pattern)
    line = pump_line(trace)
    if len(steps) < 2 or line is None:
        return None
    lo, hi = steps[0][0], steps[-1][0] + steps[-1][1]
    gaps = [(max(a, lo), min(b, hi)) for a, b in xplane.idle_gaps(trace)
            if b > lo and a < hi]
    segs = innermost(line)
    acc = dict.fromkeys(PARTS, 0.0)
    i = 0
    for a, b in gaps:                   # both lists are in time order
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j, under = i, 0.0
        while j < len(segs) and segs[j][0] < b:
            s, e, part = segs[j]
            over = min(b, e) - max(a, s)
            if part is not None:
                acc[part] += over
                under += over
            j += 1
        acc["unattributed"] += (b - a) - under
    return acc, len(steps) - 1


def reduce(facts, part, pattern):
    got = split(facts["trace"], pattern)
    if got is None:
        return None
    acc, n_gaps = got
    return acc[part] / n_gaps / 1e6
