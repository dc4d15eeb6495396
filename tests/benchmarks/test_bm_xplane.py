"""The reduction from a trace to busy and idle time, the traced window's
length (never under the busy time), per-operation time, gap attribution and
exposed collective time; the operation and byte functions
against hand-worked shapes. Nothing here describes a chip topology: the
recorded traces under data/ were cut from chip runs of PR 24."""
import json
import os
import types

import numpy as np
import pytest

from benchmarks import costs, harness, xplane
from benchmarks import run as bench_run
from benchmarks.reducers import device_idle

HERE = os.path.dirname(os.path.abspath(__file__))


def _recorded(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


# a hand-made trace: two devices, nested operations, a collective half hidden
HAND = {
    "devices": {
        "/device:TPU:0": {
            "ops": [["while_u32", 0, 100],            # holds the next two
                    ["fusion_bf16_8", 10, 30],
                    ["closed_call_bf16_8_custom-call", 50, 40],
                    ["all-reduce_f32_8", 120, 40],      # 120..160
                    ["fusion_bf16_8", 140, 10],         # hides 140..150 of it
                    ["copy_bf16_8", 300, 50]],
            "modules": [["jit_step_fn(1)", 0, 160], ["jit_step_fn(1)", 300, 50],
                        ["jit_other(2)", 170, 5]]},
        "/device:TPU:1": {
            "ops": [["fusion_bf16_8", 0, 50], ["all-reduce_f32_8", 50, 20]],
            "modules": [["jit_step_fn(1)", 0, 70]]}},
    "host": {
        "python3#0": [["$scheduler.py:922 _pump", 0, 400],
                      ["$llama_serving.py:2297 _ragged_launch", 155, 150],
                      ["$threading.py:323 wait", 160, 20]],
        "python3#1": [["bench.submit", 100, 30]],
        # a reader: shorter than _ragged_launch, but all of it a nested wait
        "python3#2": [["$scheduler.py:159 stream", 158, 144],
                      ["$queue.py:154 get", 159, 142],
                      ["$threading.py:323 wait", 160, 140]]}}


def test_busy_union_and_idle():
    # device 0: [0,100] + [120,160] + [300,350] = 190; device 1: 70
    assert xplane.busy_seconds(HAND) == pytest.approx((190 + 70) / 2 / 1e9)
    assert xplane.idle_gaps(HAND) == [(100, 120), (160, 300)]
    assert xplane.union([(5, 7), (0, 3), (2, 4), (7, 9)]) == [[0, 4], [5, 9]]


def test_operation_time_is_self_time():
    t = xplane.op_seconds(HAND)
    # the while keeps 100 - 30 - 40 = 30 of its own; fusion: 30+10 on
    # device 0 and 50 on device 1, averaged over the two devices
    assert t["while_u32"] == pytest.approx(30 / 2 / 1e9)
    assert t["fusion_bf16_8"] == pytest.approx((40 + 50) / 2 / 1e9)
    assert xplane.matching_op_seconds(HAND, "custom-call") == \
        pytest.approx(40 / 2 / 1e9)


def test_exposed_collective_time():
    # device 0: 40 of all-reduce, 10 hidden under the fusion; device 1: 20
    assert xplane.exposed_collective_seconds(HAND) == \
        pytest.approx((30 + 20) / 2 / 1e9)


def test_step_programs_and_gaps():
    ev = xplane.module_events(HAND, "step_fn")
    assert ev == [(0, 160), (300, 50)]


def test_gap_attribution_skips_what_only_waits():
    got = dict(xplane.attribute_gaps(HAND))
    # the 140 ns gap lies inside _ragged_launch; the reader's `stream` is
    # shorter but waits all its time, and plain waits never count; the
    # 20 ns gap lies inside bench.submit, shorter than _pump
    assert {n for n, _, _ in xplane.working_events(HAND["host"]["python3#2"])} == set()
    assert got["llama_serving.py:_ragged_launch"] == pytest.approx(140e-9)
    assert got["bench.submit"] == pytest.approx(20e-9)


def test_the_tracer_switches_the_python_tracer_off_where_jax_can():
    """The traced window runs the host code users run: the profiler's Python
    tracer off, the host tracer (the program's own spans) as it was; a jax
    that cannot says so on a line and traces as before."""
    said = []
    ctx = types.SimpleNamespace(cell={"name": "x"}, say=said.append,
                                args=types.SimpleNamespace(seconds=2))
    tracer = harness.Tracer(ctx, {"trace_s": 1})

    class Options:
        python_tracer_level, host_tracer_level = 1, 2
    got = tracer._options(types.SimpleNamespace(ProfileOptions=Options))
    assert got["profiler_options"].python_tracer_level == 0
    assert got["profiler_options"].host_tracer_level == 2
    assert said[-1] == "tracer: python_tracer_level 0"
    assert tracer._options(types.SimpleNamespace()) == {}
    assert "cannot switch" in said[-1]
    import jax
    assert tracer._options(jax.profiler)["profiler_options"] \
        .python_tracer_level == 0


def test_op_label_from_hlo_text():
    text = ("%copy.146 = bf16[16,8,3072,16,128]{4,3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[16,8,3072,16,128]{4,3,2,1,0} %x)")
    assert xplane.op_label(text) == "copy_bf16_16_8_3072_16_128"
    text = ("%closed_call.16 = bf16[32,8,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
            "custom-call(s32[32]{0:T(128)S(1)} %copy-done.5), "
            "custom_call_target=\"tpu_custom_call\"")
    assert xplane.op_label(text) == "closed_call_bf16_32_8_8_128_custom-call"
    text = "%while.8 = (u32[]{:T(128)}, bf16[32,4096]{1,0}) while((u32[]) %t)"
    assert xplane.op_label(text) == "while_u32"


@pytest.mark.parametrize("name", ["trace_serve_small.json",
                                  "trace_train_small.json"])
def test_recorded_trace_against_brute_force(name):
    """The reductions on a trace cut from a chip run, checked against a
    count on a grid of nanoseconds."""
    tr = _recorded(name)
    assert tr["devices"] and tr["host"]
    per = []
    for dev in tr["devices"].values():
        ops = np.array([[s, s + d] for _, s, d in dev["ops"]])
        t0, t1 = ops[:, 0].min(), ops[:, 1].max()
        grid = np.linspace(t0, t1, 200001)
        order = np.argsort(ops[:, 0])
        starts, ends = ops[order, 0], np.maximum.accumulate(ops[order, 1])
        i = np.searchsorted(starts, grid, side="right") - 1
        covered = (i >= 0) & (grid < ends[np.maximum(i, 0)])
        per.append(covered.mean() * (t1 - t0))
    assert xplane.busy_seconds(tr) * 1e9 == pytest.approx(np.mean(per), rel=2e-3)
    # self times add up to the busy time: nothing counted twice
    assert sum(xplane.op_seconds(tr).values()) == \
        pytest.approx(xplane.busy_seconds(tr), rel=1e-6)
    b = xplane.breakdown(tr)
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1] > 0
    gaps = xplane.idle_gaps(tr)
    assert sum(v for _, v in xplane.attribute_gaps(tr, top=10 ** 6)) * 1e9 == \
        pytest.approx(sum(b - a for a, b in gaps), rel=1e-6)
    steps = xplane.module_events(tr, "unified_step|step_fn")
    assert len(steps) >= (2 if "train" in name else 4)
    if "train" in name:
        assert 0 < xplane.exposed_collective_seconds(tr) < xplane.busy_seconds(tr)
    else:
        assert xplane.matching_op_seconds(tr, "custom-call") > \
            0.5 * xplane.busy_seconds(tr)


# ------------------------------------------------- the traced window
S = 1e9                                         # nanoseconds a second
RECORDED = ["trace_serve_small.json", "trace_serve_spans_small.json",
            "trace_train_small.json"]
# what PR 31's chat_saturated printed, made by hand: the host's clock read
# 8.001 s between start_trace's return (2 ms) and stop_trace's call
# (8,003 ms); the device worked from inside the one to inside the other
OVERHANG = {
    "devices": {"/device:TPU:0": {
        "ops": [["fusion_bf16_32_14336", 0.0, 4.0 * S],
                ["fusion_bf16_32_4096", 4.0 * S, 4.005 * S]],
        "modules": [["jit_unified_step(1)", 0.0, 8.005 * S]]}},
    "host": {"python3#0": [["serving.turn", 0.002 * S, 8.001 * S]]}}
# a device with nothing to run at either edge, the pump's spans running on
IDLE_EDGES = {
    "devices": {"/device:TPU:0": {
        "ops": [["fusion_bf16_32_4096", 2.0 * S, 3.0 * S]],
        "modules": [["jit_unified_step(1)", 2.0 * S, 3.0 * S]]}},
    "host": {"python3#0": [["serving.turn", 0.001 * S, 0.004 * S],
                           ["serving.turn", 7.9 * S, 0.1 * S]],
             "pjrt-tpu-tasks/1#1": [["PjRt wait", 0.5 * S, 1.0 * S]]}}
HAND_TRACES = {"hand": HAND, "overhang": OVERHANG, "idle_edges": IDLE_EDGES}


def _trace(name):
    return HAND_TRACES[name] if name in HAND_TRACES else _recorded(name)


def test_window_covers_operations_that_overhang_the_hosts_clock():
    """Two clocks gave busy 8.005 s of 8.001; one gives a share in [0, 1]."""
    host_timed_s = 8.001
    busy, window = xplane.busy_seconds(OVERHANG), xplane.window_seconds(OVERHANG)
    assert busy == pytest.approx(8.005) and busy > host_timed_s
    assert window == pytest.approx(8.005) and window >= busy
    assert 0.0 <= 1.0 - busy / window <= 1.0


def test_window_is_the_host_spans_extent_where_the_device_idles_at_the_edges():
    assert xplane.busy_seconds(IDLE_EDGES) == pytest.approx(3.0)
    assert xplane.window_seconds(IDLE_EDGES) == pytest.approx(8.0 - 0.001)
    # the hand trace's last host event ends at 400 ns, its last operation at 350
    assert xplane.window_seconds(HAND) == pytest.approx(400e-9)
    assert xplane.window_seconds({"devices": {}, "host": {}}) == 0.0


@pytest.mark.parametrize("name", RECORDED + sorted(HAND_TRACES))
def test_busy_time_never_passes_the_window(name):
    tr = _trace(name)
    busy, window = xplane.busy_seconds(tr), xplane.window_seconds(tr)
    assert 0 < busy <= window
    # every device's own extent lies inside it, not only their mean's
    for dev in tr["devices"].values():
        spans = [(s, s + d) for _, s, d in dev["ops"] + dev["modules"]]
        assert (max(e for _, e in spans) - min(s for s, _ in spans)) / 1e9 \
            <= window


@pytest.mark.parametrize("name", RECORDED + sorted(HAND_TRACES))
def test_device_idle_is_never_negative(name):
    tr = _trace(name)
    idle = device_idle.reduce({"trace": tr,
                               "trace_window_s": xplane.window_seconds(tr)})
    assert 0.0 <= idle <= 100.0
    assert device_idle.reduce({"trace": tr}) is None


def _stub_run(trace, host_timed_s=0.777):
    said = []
    ctx = types.SimpleNamespace(
        say=said.append, facts={}, config={}, traffic={},
        cell={"name": "x", "chips": 1}, manifest={"per_layer": []},
        bench_dir=os.path.join(harness.ROOT, "benchmarks"))
    tracer = types.SimpleNamespace(load=lambda: trace, host_timed_s=host_timed_s)
    return ctx, types.SimpleNamespace(tracer=tracer), said


@pytest.mark.parametrize("name", RECORDED + ["overhang"])
def test_the_result_lines_device_block_holds_the_drivers_contract(name):
    tr = _trace(name)
    ctx, outcome, said = _stub_run(tr, host_timed_s=8.001)
    given = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    metrics, device, breakdown = bench_run.layer_metrics(ctx, outcome, given)
    assert set(device) == set(given) | {"busy_s", "window_s"}
    assert 0 < device["busy_s"] <= device["window_s"]
    assert device["busy_s"] == xplane.busy_seconds(tr)
    assert device["window_s"] == xplane.window_seconds(tr)
    assert "busy_s" not in given            # the caller's block is not written to
    # the host's figure is said on a line and is in nothing that is reported
    assert said == [f"tracer: host-timed 8.001000 s, trace extent "
                    f"{device['window_s']:.6f} s, device busy "
                    f"{device['busy_s']:.6f} s"]
    assert metrics == {} and breakdown["device_ops"]


@pytest.mark.parametrize("trace", [
    {"devices": {}, "host": {}},
    {"devices": {}, "host": {"python3#0": [["serving.turn", 0.0, 8.0 * S]]}},
    {"devices": {"/device:TPU:0": {"ops": [], "modules": []}}, "host": {}}],
    ids=["nothing", "host_only", "device_without_operations"])
def test_an_empty_trace_ends_the_run_with_no_result_line(trace, capsys):
    ctx, outcome, _ = _stub_run(trace)
    with pytest.raises(SystemExit) as stop:
        bench_run.layer_metrics(ctx, outcome, {"kind": "TPU v5 lite"})
    assert stop.value.code not in (0, None)
    assert "busy_s has to lie above 0 and at most at window_s" in str(stop.value)
    assert "0.0 s of device operations" in str(stop.value)
    assert capsys.readouterr().out == ""


def test_the_tracer_keeps_the_hosts_figure_apart(monkeypatch):
    """`stop()` times the host's clock for a line to print; the window's
    length is nothing the tracer holds."""
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append("stop"))
    ctx = types.SimpleNamespace(cell={"name": "x"}, say=lambda m: None,
                                args=types.SimpleNamespace(seconds=2))
    tracer = harness.Tracer(ctx, {"trace_s": 1})
    assert not tracer.stopped and tracer.host_timed_s is None
    tracer.start()
    tracer.stop()
    assert calls == ["start", "stop"]
    assert tracer.stopped and tracer.host_timed_s >= 0
    assert not hasattr(tracer, "window_s")


# ------------------------------------------------- operations and bytes
MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "vocab_size": 32768}


def test_parameter_counts():
    full = dict(MISTRAL, num_hidden_layers=32)
    # the model card's 7.25 B
    assert costs.dense_params(full) == 7_248_023_552
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert costs.matmul_params(dict(MISTRAL, num_hidden_layers=4)) == \
        4 * per_layer + 4096 * 32768


def test_attention_pairs_and_flops():
    assert costs.attended_pairs(4096) == 4096 * 4097 // 2
    # two documents of 3 and 2 tokens: 6 + 3 pairs
    assert costs.attended_pairs(5, [0, 0, 0, 1, 1]) == 9
    m = dict(MISTRAL, num_hidden_layers=2)
    assert costs.attention_flops(m, 9) == 4 * 128 * 9 * 32 * 2
    assert costs.attention_flops(m, 9, backward=True) == 10 * 128 * 9 * 32 * 2
    assert costs.train_flops_per_step(m, 100, 9) == \
        6 * costs.matmul_params(m) * 100 + 14 * 128 * 9 * 32 * 2


def test_serving_bytes():
    m = dict(MISTRAL, num_hidden_layers=16)
    assert costs.kv_bytes_per_token(m) == 65536          # 64 KiB a token
    assert costs.kv_bytes_per_token(m, "int8") == 32768
    assert costs.serve_step_bytes(m, 1000) == \
        costs.matmul_params(m) * 2 + 1000 * 65536
