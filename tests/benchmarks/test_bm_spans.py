"""The three reducers ISSUE 25 adds (`span_gap`, `counter_sum_ratio`,
`ragged_roofline`): on a hand-made trace whose answers are worked out by
hand, and against brute force on `trace_serve_spans_small.json`, four
steps cut from a traced `chat_steady` run of PR 25 on the chip (with the
window's counters beside the events)."""
import json
import os

import numpy as np
import pytest

from benchmarks import harness, xplane
from benchmarks.reducers import (counter_ratio, counter_sum_ratio,
                                 ragged_roofline, span_gap)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# Three step programs; the device idles in (100,140), (150,200), (300,420).
HAND = {
    "devices": {"/device:TPU:0": {
        "ops": [["fusion_bf16_8", 0, 60],
                ["closed_call_bf16_8_custom-call", 60, 40],
                ["convert_s32_8", 140, 10],            # a staging program
                ["closed_call_bf16_8_custom-call", 200, 100],
                ["closed_call_bf16_8_custom-call", 420, 60],
                ["fusion_bf16_8", 480, 20]],
        "modules": [["jit_unified_step(1)", 0, 100],
                    ["jit_convert_element_type(2)", 140, 10],
                    ["jit_unified_step(1)", 200, 100],
                    ["jit_unified_step(1)", 420, 80]]}},
    "host": {
        # a reader thread: no `serving.turn`, never the pump line
        "python3#3": [["bench.stream_read", 100, 300],
                      ["serving.telemetry", 100, 300]],
        "python3#7": [
            ["$scheduler.py:930 _pump", 0, 520],        # a Python frame
            # gap (100,140): half under a child, half under its parent
            ["serving.turn", 90, 50],
            ["serving.unified_step", 100, 40],
            ["pt.track_jit", 120, 20],
            # gap (150,200): under no span at all
            # gap (300,420): two layers of nesting, then siblings, then
            # the turn's own time
            ["serving.turn", 290, 140],
            ["serving.publish", 300, 60],
            ["serving.telemetry", 320, 20],
            ["$scheduler.py:840 _emit_request_spans", 322, 10],
            ["serving.sched_feed", 360, 20],
            ["serving.plan", 380, 30],
            ["PjRtStreamExecutorLoadedExecutable::Execute", 385, 5]]}}

HAND_NS = {"dispatch": 20, "telemetry": 20 + 20, "consume": 40, "admit": 20,
           "plan": 30, "unattributed": 50 + 10}


def test_a_gap_is_split_among_the_spans_under_it():
    acc, n_gaps = span_gap.split(HAND, "unified_step")
    assert n_gaps == 2
    assert acc == pytest.approx(HAND_NS)
    idle = sum(b - a for a, b in xplane.idle_gaps(HAND))
    assert sum(acc.values()) == pytest.approx(idle) == pytest.approx(210)
    for part, ns in HAND_NS.items():
        got = span_gap.reduce({"trace": HAND}, part=part,
                              pattern="unified_step")
        assert got == pytest.approx(ns / 2 / 1e6)


def test_innermost_span_wins_and_the_turn_itself_is_no_part():
    segs = span_gap.innermost(HAND["host"]["python3#7"])
    assert segs == [(90, 100, None), (100, 120, "dispatch"),
                    (120, 140, "telemetry"),
                    (290, 300, None), (300, 320, "consume"),
                    (320, 340, "telemetry"), (340, 360, "consume"),
                    (360, 380, "admit"), (380, 410, "plan"),
                    (410, 430, None)]
    # only idle time INSIDE first..last step program counts: a gap that
    # begins before the first step or ends after the last is clipped
    wide = json.loads(json.dumps(HAND))
    wide["devices"]["/device:TPU:0"]["ops"] += [["fusion_bf16_8", -50, 10],
                                                ["fusion_bf16_8", 600, 10]]
    assert span_gap.split(wide, "unified_step")[0] == pytest.approx(HAND_NS)


def test_nothing_to_read_is_none_not_an_error():
    """The parent commit has neither the spans nor the counters."""
    bare = {"devices": HAND["devices"],
            "host": {"python3#7": [["serving.unified_step", 100, 40]]}}
    facts = {"trace": bare, "counters": {"pt_serving_device_steps": 9.0,
                                         "pt_ragged_tokens": 288.0}}
    assert span_gap.reduce(facts, part="plan", pattern="unified_step") is None
    assert counter_sum_ratio.reduce(
        facts, nums=['pt_serving_turn_seconds{part="plan"}'],
        den="pt_serving_device_steps") is None
    assert ragged_roofline.reduce(facts, pattern="custom-call",
                                  step_pattern="unified_step") is None
    assert counter_ratio.reduce(facts, num="pt_ragged_attn_pairs",
                                den="pt_ragged_kv_tokens") is None
    one_step = {"devices": {"d": {"ops": [], "modules":
                                  [["jit_unified_step(1)", 0, 9]]}},
                "host": HAND["host"]}
    assert span_gap.reduce({"trace": one_step}, part="plan",
                           pattern="unified_step") is None


def test_counter_sum_ratio():
    c = {'pt_serving_turn_seconds{part="admit"}': 0.5,
         'pt_serving_turn_seconds{part="plan"}': 1.5,
         'pt_serving_turn_seconds{part="fetch"}': 190.0,
         "pt_serving_device_steps": 1000.0}
    got = counter_sum_ratio.reduce(
        {"counters": c}, den="pt_serving_device_steps", scale=1000.0,
        nums=['pt_serving_turn_seconds{part="admit"}',
              'pt_serving_turn_seconds{part="plan"}',
              'pt_serving_turn_seconds{part="publish"}'])
    assert got == pytest.approx(2.0)            # ms a step, without fetch


CONFIG = {"model": {"hidden_size": 4096, "num_attention_heads": 32,
                    "num_key_value_heads": 8, "num_hidden_layers": 2,
                    "intermediate_size": 14336, "vocab_size": 32768},
          "precision": {"weights": "bfloat16", "kv_cache": "bfloat16"}}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_ragged_roofline_by_hand():
    # a step: 1,000 tokens of context, 5,000 attended pairs, 32 rows
    c = {"pt_serving_device_steps": 10.0, "pt_ragged_kv_tokens": 10_000.0,
         "pt_ragged_attn_pairs": 50_000.0, "pt_ragged_tokens": 320.0}
    need_bytes = 1000 * 2 * 8 * 128 * 2 + 32 * 2 * 32 * 128 * 2
    need_ops = 5000 * 32 * 128 * 4
    assert ragged_roofline.needed(CONFIG, 1000, 5000, 32) == \
        (need_bytes, need_ops)
    # bytes bind: 4.6 MB at 819 GB/s is 5.6 us, 82 MFLOP at peak 0.4 us
    least = need_bytes / 819e9
    assert least > need_ops / 197e12
    # HAND's kernel ran 40 + 100 + 60 ns in three steps of two layers
    got = ragged_roofline.reduce(
        {"trace": HAND, "counters": c, "config": CONFIG, "peaks": PEAKS},
        pattern="custom-call", step_pattern="unified_step")
    assert got == pytest.approx(100.0 * least / (200e-9 / 3 / 2))
    # int8 pages halve the context's bytes, not the rows'
    int8 = dict(CONFIG, precision={"weights": "bfloat16",
                                   "kv_cache": "int8"})
    assert ragged_roofline.needed(int8, 1000, 5000, 32)[0] == \
        1000 * 2 * 8 * 128 + 32 * 2 * 32 * 128 * 2
    # operations bind once a long chunk re-reads its context often
    busy = dict(c, pt_ragged_attn_pairs=50_000_000.0)
    got = ragged_roofline.reduce(
        {"trace": HAND, "counters": busy, "config": CONFIG, "peaks": PEAKS},
        pattern="custom-call", step_pattern="unified_step")
    assert got == pytest.approx(
        100.0 * (5_000_000 * 32 * 128 * 4 / 197e12) / (200e-9 / 3 / 2))


# ------------------------------------------- the trace cut on the chip
@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_serve_spans_small.json")) as f:
        return json.load(f)


def _grid_share(intervals, lo, hi, n=400_001):
    """Brute force: which points of a grid over [lo, hi] lie inside one of
    `intervals` (possibly overlapping) -> boolean array and the grid."""
    grid = np.linspace(lo, hi, n)
    iv = np.array(sorted(intervals))
    ends = np.maximum.accumulate(iv[:, 1])
    i = np.searchsorted(iv[:, 0], grid, side="right") - 1
    return (i >= 0) & (grid < ends[np.maximum(i, 0)]), grid


def test_recorded_gaps_add_up_to_the_idle_time_between_steps(recorded):
    """The identity ISSUE 25 asks for, and every part against a count on
    a grid of instants."""
    steps = xplane.module_events(recorded, "unified_step")
    assert len(steps) == 4
    lo, hi = steps[0][0], steps[-1][0] + steps[-1][1]
    acc, n_gaps = span_gap.split(recorded, "unified_step")
    assert n_gaps == 3
    dev = next(iter(recorded["devices"].values()))
    busy, grid = _grid_share([(s, s + d) for _, s, d in dev["ops"]], lo, hi)
    idle_ns = (~busy).mean() * (hi - lo)
    assert sum(acc.values()) == pytest.approx(idle_ns, rel=2e-3)
    assert sum(acc.values()) == pytest.approx(
        sum(min(b, hi) - max(a, lo) for a, b in xplane.idle_gaps(recorded)
            if b > lo and a < hi), rel=1e-9)
    line = span_gap.pump_line(recorded)
    names = {n for n, _, _ in line}
    assert {"serving.turn", "serving.sched_feed", "serving.admit",
            "serving.plan", "serving.stage", "serving.unified_step",
            "pt.track_jit", "serving.fetch", "serving.consume",
            "serving.telemetry", "serving.publish"} <= names
    # brute force per part: the innermost covering span of each idle
    # grid point, found by scanning every span
    spans = [(s, s + d, span_gap.part_of(n), n) for n, s, d in line
             if n.startswith("serving.turn") or span_gap.part_of(n)]
    brute = dict.fromkeys(span_gap.PARTS, 0)
    idle_points = grid[~busy]
    for t in idle_points[::20]:
        cover = [sp for sp in spans if sp[0] <= t < sp[1]]
        part = min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover else None
        brute[part or "unattributed"] += 1
    n_pts = len(idle_points[::20])
    for part in span_gap.PARTS:
        assert acc[part] / sum(acc.values()) == \
            pytest.approx(brute[part] / n_pts, abs=0.01), part
    # the mean gap of the six lies near the median gap between programs
    gaps = [b[0] - (a[0] + a[1]) for a, b in zip(steps, steps[1:])]
    assert 0.5 * np.median(gaps) < sum(acc.values()) / n_gaps \
        <= 1.01 * np.mean(gaps)
    assert acc["unattributed"] < 0.25 * sum(acc.values())


def test_recorded_roofline_against_brute_force(recorded):
    cfg = harness.load_json(ROOT, "benchmarks", "configs",
                            "mistral-7b-v0.3.serve1.json")
    peaks = harness.peaks_for("TPU v5 lite")
    c = recorded["counters"]
    facts = {"trace": recorded, "counters": c, "config": cfg, "peaks": peaks}
    got = ragged_roofline.reduce(facts, pattern="custom-call",
                                 step_pattern="unified_step")
    assert 0 < got < 100
    # brute force: the kernel's time on a grid (its calls do not nest),
    # the bytes and operations written out from the published sizes
    dev = next(iter(recorded["devices"].values()))
    calls = [(s, s + d) for n, s, d in dev["ops"] if "custom-call" in n]
    lo, hi = min(a for a, _ in calls), max(b for _, b in calls)
    inside, _ = _grid_share(calls, lo, hi)
    kernel_s = inside.mean() * (hi - lo) / 1e9
    n = c["pt_serving_device_steps"]
    kv_bytes = c["pt_ragged_kv_tokens"] / n * 2 * 8 * 128 * 2
    io_bytes = c["pt_ragged_tokens"] / n * 2 * 32 * 128 * 2
    ops = c["pt_ragged_attn_pairs"] / n * 32 * 128 * 4
    least = max((kv_bytes + io_bytes) / 819e9, ops / 197e12)
    per_call = kernel_s / 4 / cfg["model"]["num_hidden_layers"]
    assert got == pytest.approx(100.0 * least / per_call, rel=5e-3)
    # one kernel call a layer and step; the pattern (ragged_attn_busy_pct's)
    # also takes a 0.5 us `custom-call_u32` of the sampler, a millionth
    kernels = [n for n, _, _ in dev["ops"] if n.startswith("closed_call")
               and "custom-call" in n]
    assert len(kernels) == 4 * cfg["model"]["num_hidden_layers"]
    assert len(calls) - len(kernels) <= 4
    ratio = counter_ratio.reduce(facts, num="pt_ragged_attn_pairs",
                                 den="pt_ragged_kv_tokens")
    assert ratio == pytest.approx(c["pt_ragged_attn_pairs"] /
                                  c["pt_ragged_kv_tokens"]) and ratio >= 1
    turn = ['pt_serving_turn_seconds{part="%s"}' % p for p in
            ("admit", "plan", "dispatch", "consume", "publish", "telemetry")]
    host_ms = counter_sum_ratio.reduce(facts, nums=turn, scale=1000.0,
                                       den="pt_serving_device_steps")
    assert host_ms == pytest.approx(1e3 * sum(c[k] for k in turn) / n)
    assert 0 < host_ms < 1e3 * c['pt_serving_turn_seconds{part="fetch"}'] / n


def test_the_eighteen_metric_files_name_these_reducers():
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    mine = [m for m in manifest["per_layer"]
            if m["name"].split(".")[0] in (
                "gap_admit_ms", "gap_plan_ms", "gap_dispatch_ms",
                "gap_consume_ms", "gap_telemetry_ms", "gap_unattributed_ms",
                "host_turn_ms", "ragged_attn_roofline_pct",
                "ragged_kv_refetch_ratio")]
    assert len(mine) == 18
    parts = set()
    for m in mine:
        spec = harness.load_json(ROOT, "benchmarks", "layer_metrics",
                                 m["name"] + ".json")
        cell = {"steady": "chat_steady", "saturated": "chat_saturated"}[
            m["name"].split(".")[1]]
        assert m["workloads"] == [cell]
        assert m["moves"] == {"chat_steady": "itl_p99_ms",
                              "chat_saturated": "serve_tokens_per_s"}[cell]
        if spec["reducer"] == "span_gap":
            parts.add(spec["args"]["part"])
            assert m["source"] == "device_trace"
        if spec["reducer"] == "counter_sum_ratio":
            assert not any("fetch" in k for k in spec["args"]["nums"])
            assert len(spec["args"]["nums"]) == 6
    assert parts == set(span_gap.PARTS)
