"""Reduction from a profiler trace (.xplane.pb) to busy and idle time, the
traced window's length, per-operation time, gap attribution and exposed
collective time.

`load()` turns the profiler's file into a plain dictionary (the form the
recorded trace under tests/benchmarks/data/ is kept in); every reduction
below works on that dictionary, so it is checked without a chip:

    {"devices": {"/device:TPU:0": {"ops": [[label, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "host": {"<thread line>": [[name, start_ns, dur_ns], ...]}}
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|send|recv)")
# host frames that only wait: a gap is attributed to what waited, not to these
WAITING = re.compile(
    r"(threading\.py|queue\.py|selectors\.py|socket\.py|concurrent/futures)|"
    r" (wait|sleep|acquire|join|get|_wait_for_tstate_lock)$")
_SHAPE = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")
_SUFFIX = re.compile(r"([.\-_]\d+)+$")


def newest_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


_HLO = re.compile(r"^%?([\w.\-]+) = (.*)$", re.S)
_OPCODE = re.compile(r"[)}\]] ([a-z][a-z0-9\-]*)\(")


def op_label(text):
    """A stable label for a device operation from the HLO text the trace
    names it by: its name without the compiler's numeric suffix, the type
    and shape of its (first) result, and its opcode where the name does not
    say it: `%copy.146 = bf16[16,8,3072,16,128]{...} copy(...)` ->
    `copy_bf16_16_8_3072_16_128`."""
    m = _HLO.match(text)
    if not m:
        return _SUFFIX.sub("", text.lstrip("%"))
    base, rest = _SUFFIX.sub("", m.group(1)), m.group(2)
    label = base
    shape = _SHAPE.search(rest)
    if shape:
        dims = shape.group(2).replace(",", "_")
        label += f"_{shape.group(1)}" + (f"_{dims}" if dims else "")
    op = _OPCODE.search(rest)
    if op and op.group(1) not in base:
        label += f"_{op.group(1)}"
    return label


def load(path):
    """.xplane.pb -> the plain dictionary described above."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "host": {}}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    label = op_label(e.name) if key == "ops" else e.name
                    dev[key].append([label, float(e.start_ns), float(e.duration_ns)])
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events if e.duration_ns > 0]
                if evs:     # thread names repeat ("python3"): key by index too
                    out["host"][f"{line.name}#{i}"] = evs
    return out


def union(intervals):
    """Merge (start, end) intervals -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _total(intervals):
    return sum(e - s for s, e in intervals)


def _spans(events):
    return [(s, s + d) for _, s, d in events]


def busy_seconds(trace):
    """Seconds in which an operation ran on the device, averaged over the
    devices in the trace."""
    per = [_total(union(_spans(d["ops"]))) for d in trace["devices"].values()]
    return float(np.mean(per)) / 1e9 if per else 0.0


def window_seconds(trace):
    """Seconds the profiler recorded: earliest start to latest end over the
    devices' operations and step programs AND the host's events, on the
    trace's own clock. It covers everything `busy_seconds` sums, so busy
    time cannot pass it; the host's events (the program's own spans run
    through the whole session) keep it from shrinking to the device's
    extent where a device idles at an edge. 0.0 for an empty trace."""
    spans = [sp for d in trace["devices"].values()
             for sp in _spans(d["ops"]) + _spans(d["modules"])]
    spans += [sp for evs in trace["host"].values() for sp in _spans(evs)]
    if not spans:
        return 0.0
    return (max(e for _, e in spans) - min(s for s, _ in spans)) / 1e9


def self_times(events):
    """[(label, self_ns)]: each event's duration less the part its nested
    events cover (a `while` holds a layer scan's operations inside it)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []         # stack of [end_ns, index into out]
    for label, start, dur in order:
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(dur, stack[-1][0] - start)
        out.append([label, dur])
        stack.append([start + dur, len(out) - 1])
    return out


def op_seconds(trace):
    """{label: seconds of self time}, each device's sum, averaged over the
    devices."""
    n = max(len(trace["devices"]), 1)
    acc = {}
    for d in trace["devices"].values():
        for label, self_ns in self_times(d["ops"]):
            acc[label] = acc.get(label, 0.0) + self_ns
    return {k: v / n / 1e9 for k, v in acc.items()}


def matching_op_seconds(trace, pattern):
    rx = re.compile(pattern)
    return sum(v for k, v in op_seconds(trace).items() if rx.search(k))


def module_events(trace, pattern):
    """(start_ns, dur_ns) of the step programs matching `pattern` on the
    first device, in time order."""
    rx = re.compile(pattern)
    dev = next(iter(trace["devices"].values()), {"modules": []})
    return sorted((s, d) for name, s, d in dev["modules"] if rx.search(name))


def idle_gaps(trace):
    """[(start_ns, end_ns)] between busy intervals on the first device."""
    dev = next(iter(trace["devices"].values()), None)
    if dev is None:
        return []
    busy = union(_spans(dev["ops"]))
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def _frame(name):
    """`$llama_serving.py:2297 _ragged_launch` -> `llama_serving.py:_ragged_launch`."""
    m = re.match(r"^\$?([^ :]+\.py):\d+ (.+)$", name)
    return f"{m.group(1)}:{m.group(2)}" if m else name


def working_events(events, share=0.9):
    """Host events of one thread that are not waiting: neither a plain wait
    (`WAITING`) nor covered for `share` of their time by nested waits
    (`scheduler.py:stream` around `queue.get` is the reader waiting)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    waits = [0.0] * len(order)      # time under waiting descendants
    stack, keep = [], []            # stack of indices into order

    def close(i):
        name, start, dur = order[i]
        waiting = bool(WAITING.search(name)) or (dur > 0 and waits[i] >= share * dur)
        if stack:                   # hand the waiting time up to the parent
            waits[stack[-1]] += dur if waiting else waits[i]
        if not waiting:
            keep.append(order[i])

    for i, (name, start, dur) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= start:
            close(stack.pop())
        stack.append(i)
    while stack:
        close(stack.pop())
    return keep


def attribute_gaps(trace, longest=400, top=10):
    """The longest idle gaps by what the host was doing: each gap goes to
    the shortest host event, on any thread, that covers at least half of it
    and is not waiting. -> [[name, seconds], ...] with a last entry for the
    gaps beyond the `longest` longest."""
    gaps = sorted(idle_gaps(trace), key=lambda g: g[0] - g[1])
    host = [(s, s + d, name) for evs in trace["host"].values()
            for name, s, d in working_events(evs)]
    starts = np.array([h[0] for h in host], float)
    ends = np.array([h[1] for h in host], float)
    acc = {}
    for g0, g1 in gaps[:longest]:
        key = "unattributed"
        if host:
            overlap = np.minimum(ends, g1) - np.maximum(starts, g0)
            length = np.where(overlap >= (g1 - g0) / 2, ends - starts, np.inf)
            best = int(np.argmin(length))
            if np.isfinite(length[best]):
                key = _frame(host[best][2])
        acc[key] = acc.get(key, 0.0) + (g1 - g0)
    rest = sum(g1 - g0 for g0, g1 in gaps[longest:])
    if rest:
        acc[f"gaps_beyond_the_{longest}_longest"] = rest
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in ranked]


def exposed_collective_seconds(trace):
    """Collective time during which no other operation ran on that device,
    averaged over the devices."""
    per = []
    for d in trace["devices"].values():
        coll = union(_spans([e for e in d["ops"] if COLLECTIVE.match(e[0])]))
        comp = union(_spans([e for e in d["ops"] if not COLLECTIVE.match(e[0])]))
        hidden = 0.0
        j = 0
        for s, e in coll:
            while j < len(comp) and comp[j][1] <= s:
                j += 1
            k = j
            while k < len(comp) and comp[k][0] < e:
                hidden += min(e, comp[k][1]) - max(s, comp[k][0])
                k += 1
        per.append(_total(coll) - hidden)
    return float(np.mean(per)) / 1e9 if per else 0.0


def breakdown(trace, top=10):
    ops = sorted(op_seconds(trace).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": attribute_gaps(trace, top=top)}
