"""`benchmarks/costs_nemotron_h.py` and the three reducers that read it:
the published sizes come to 31.58 B parameters and the nine layers held to
12.15 GB; counts by hand on a small shape; the reducers read the program's
counters and fall silent without them; the eighteen `.hybrid` metric files
name reducers that are there and the one cell."""
import json
import os

import pytest

from benchmarks import costs_nemotron_h as costs, harness
from benchmarks.reducers import (nemotron_moe_roofline,
                                 nemotron_scan_roofline,
                                 nemotron_step_roofline)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "hybrid_reason_saturated"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
HYBRID = ["serve_step_device_ms", "rows_per_step", "device_idle_pct",
          "turn_host_ms", "serve_step_roofline_pct", "ssm_scan_busy_pct",
          "ssm_scan_roofline_pct", "ssm_state_slots_per_step",
          "moe_experts_busy_pct", "moe_experts_roofline_pct",
          "moe_rows_per_expert", "full_attn_busy_pct",
          # the pump's books (PR 43) and the gap between step programs: the
          # layer runs here as in every serving cell
          "decode_period_ms", "prompt_period_ms", "prompt_time_share",
          "step_period_ms", "host_gap_p50_ms",
          # the times a request's tokens were fed: what `pt_ssm_runs_fresh`
          # is for
          "ssm_fresh_runs_per_request"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.serve1.json")) as f:
        return json.load(f)


def test_the_published_sizes_come_to_31_58_billion(cfg):
    pub = cfg["published"]
    assert costs.layer_params(pub, "M") == 38_744_896
    assert costs.layer_params(pub, "*") == 23_399_040
    assert costs.layer_params(pub, "E") == 1_297_468_160
    assert costs.expert_params(pub) == 9_977_856
    assert costs.model_params(pub) == 31_577_940_288          # 31.58 B
    assert (costs.count(pub, "M"), costs.count(pub, "E"),
            costs.count(pub, "*")) == (23, 23, 6)


def test_the_nine_layers_held_are_12_15_gb(cfg):
    m, p = cfg["model"], cfg["precision"]
    assert costs.pattern(m) == "MEMEM*EME"
    assert costs.model_params(m) == 6_072_897_024
    assert round(costs.model_params(m) * 2 / 1e9, 2) == 12.15
    assert costs.state_bytes_per_slot(m, p) == (2_097_152, 36_864)
    assert costs.kv_bytes_per_token(m, p) == 1024
    d = cfg["deployment"]
    state = d["max_seqs"] * 4 * sum(costs.state_bytes_per_slot(m, p))
    assert round(state / 1e9, 2) == 1.09
    assert d["num_pages"] * d["page_size"] * 1024 == 536_870_912
    # the control keeps the state in half the bytes
    assert costs.state_bytes_per_slot(
        m, dict(p, ssm_state="bfloat16"))[0] == 1_048_576


def _small():
    return dict(hidden_size=8, vocab_size=32, num_hidden_layers=3,
                hybrid_override_pattern="ME*M", mamba_num_heads=2,
                mamba_head_dim=4, n_groups=1, ssm_state_size=4,
                conv_kernel=4, num_attention_heads=4, num_key_value_heads=2,
                head_dim=4, n_routed_experts=4, num_experts_per_tok=2,
                moe_intermediate_size=6, n_shared_experts=1,
                moe_shared_expert_intermediate_size=12)


def test_parameters_by_hand_on_a_small_shape():
    m = _small()
    # d_inner 8, conv 8 + 2 x 4 = 16; in-projection 8 x (8 + 16 + 2)
    assert costs.layer_params(m, "M") == 8 + 8 * 26 + 5 * 16 + 3 * 2 + 8 + 64
    assert costs.layer_params(m, "*") == 8 + 2 * 8 * 16 + 2 * 8 * 8
    assert costs.layer_params(m, "E") == 8 + 8 * 4 + 4 + 4 * 96 + 2 * 8 * 12
    assert costs.pattern(m) == "ME*"                # the first three letters
    assert costs.model_params(m) == 2 * 32 * 8 + 8 + sum(
        costs.layer_params(m, x) for x in "ME*")
    assert costs.row_params(m) == (8 * 26 + 64) + (8 * 4 + 2 * 8 * 12) \
        + (2 * 8 * 16 + 2 * 8 * 8)


def test_kernel_costs_by_hand_on_a_small_shape():
    m = _small()
    p = {"weights": "bfloat16", "kv_cache": "bfloat16", "ssm_state": "float32"}
    # 3 experts touched by 10 assignments: their two matrices once, each
    # assignment's row in and out; 2 operations a weight and assignment
    assert costs.moe_needed(m, p, 3, 10) == (3 * 96 * 2 + 10 * 2 * 8 * 2,
                                             10 * 2 * 96)
    # 5 slots' states (8 x 4 float32) read and written; 7 rows of x 8,
    # dt 2, B 4, C 4 in and y 8 out, float32; 5 operations a state value
    assert costs.scan_needed(m, p, 5, 7) == (5 * 2 * 128 + 7 * 26 * 4,
                                             7 * 5 * 32)
    assert costs.attn_needed(m, p, 20, 50, 7) == (
        20 * 2 * 2 * 4 * 2 + 7 * 2 * 4 * 4 * 2, 50 * 4 * 4 * 4)
    b, ops = costs.serve_step_needed(
        m, p, rows=7, logit_rows=5, experts_touched=3, assignments=10,
        state_slots=5, ssm_rows=7, kv_tokens=20, pairs=50)
    head = 8 * 32
    conv = 5 * 2 * 3 * 16 * 2
    assert b == (costs.row_params(m) + head) * 2 + (3 * 96 * 2 + 320) \
        + (5 * 2 * 128 + 7 * 26 * 4 + conv) + (640 + 448)
    assert ops == 2 * 7 * costs.row_params(m) + 2 * 5 * head + 10 * 2 * 96 \
        + 7 * 5 * 32 + 50 * 64


def _facts(cfg, counters, step_ms=20.0, op_ms=None):
    """A synthetic trace: `traced` step programs of `step_ms`, and in each
    the named operations for `op_ms` milliseconds each."""
    traced = 4
    ops = []
    for i in range(traced):
        for name, ms in (op_ms or {}).items():
            ops.append({"name": name, "start": i * 30e6, "dur": ms * 1e6})
    return {"config": cfg, "peaks": PEAKS, "counters": counters,
            "trace": {"modules": [{"name": "jit_nemotron_step(1)",
                                   "start": i * 30e6, "dur": step_ms * 1e6}
                                  for i in range(traced)], "ops": ops}}


@pytest.fixture
def trace(monkeypatch):
    """`xplane`'s three readers over the synthetic trace above."""
    import re
    from benchmarks import xplane
    monkeypatch.setattr(xplane, "module_events", lambda t, pat: [
        (m["start"], m["dur"]) for m in t["modules"]
        if re.search(pat, m["name"])])
    monkeypatch.setattr(xplane, "matching_op_seconds", lambda t, pat: sum(
        o["dur"] for o in t["ops"] if re.search(pat, o["name"])) / 1e9)


def test_the_reducers_read_the_counters_and_fall_silent_without(cfg, trace):
    steps = 100.0
    c = {"pt_serving_device_steps": steps, "pt_ragged_tokens": 161 * steps,
         "pt_logit_rows": 128 * steps, "pt_moe_experts_touched": 512 * steps,
         "pt_moe_assignments": 4 * 161 * 6 * steps,
         "pt_ssm_state_slots": 128 * steps, "pt_ssm_rows": 161 * steps,
         "pt_ragged_kv_tokens": 384_000 * steps,
         "pt_ragged_attn_pairs": 483_000 * steps}
    ops = {"ragged-dot-none_f32_1568_1856": 1.7,        # 8 calls a step
           "ragged_ssm_scan": 3.2}                      # 4 calls a step
    f = _facts(cfg, c, step_ms=20.0, op_ms=ops)
    m, p = cfg["model"], cfg["precision"]
    need = costs.moe_needed(m, p, 128, 161 * 6)[0] / 819e9
    got = nemotron_moe_roofline.reduce(f, "ragged-dot", "nemotron_step")
    assert got == pytest.approx(100 * need / (1.7e-3 / 4)) and 0 < got < 1000
    need = costs.scan_needed(m, p, 128, 161)[0] / 819e9
    got = nemotron_scan_roofline.reduce(f, "ragged_ssm_scan", "nemotron_step")
    assert got == pytest.approx(100 * need / (3.2e-3 / 4))
    b, o = costs.serve_step_needed(
        m, p, rows=161, logit_rows=128, experts_touched=512,
        assignments=4 * 161 * 6, state_slots=128, ssm_rows=161,
        kv_tokens=384_000, pairs=483_000)
    assert 14.0e9 < b < 14.2e9 and o / 197e12 < b / 819e9     # by bytes
    assert nemotron_step_roofline.reduce(f, "nemotron_step") == \
        pytest.approx(100 * (b / 819e9) / 20e-3)
    # a program that books no such counter (the parent of PR 48), a trace
    # with no such operation: nothing to read, and no exception
    for reducer, args in ((nemotron_moe_roofline, ("ragged-dot",
                                                   "nemotron_step")),
                          (nemotron_scan_roofline, ("ragged_ssm_scan",
                                                    "nemotron_step")),
                          (nemotron_step_roofline, ("nemotron_step",))):
        assert reducer.reduce(_facts(cfg, {}, op_ms=ops), *args) is None
        assert reducer.reduce(_facts(cfg, None, op_ms=ops), *args) is None
    assert nemotron_scan_roofline.reduce(
        _facts(cfg, c), "ragged_ssm_scan", "nemotron_step") is None
    assert nemotron_step_roofline.reduce(f, "laguna_step") is None


@pytest.mark.parametrize("stem", HYBRID)
def test_a_hybrid_metric_file_names_a_reducer_and_the_cell(stem):
    name = stem + ".hybrid"
    spec = harness.load_json(ROOT, "benchmarks", "layer_metrics",
                             name + ".json")
    assert spec["name"] == name and spec["workloads"] == [CELL]
    assert spec["moves"] == "serve_tokens_per_s"
    assert callable(harness.load_module("reducers", spec["reducer"]).reduce)
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    entry = {e["name"]: e for e in manifest["per_layer"]}[name]
    assert entry == {k: spec[k] for k in ("name", "unit", "better", "source",
                                          "layer", "moves", "workloads")}


def test_the_eighteen_follow_what_was_there_and_the_cell_is_declared():
    """Held by membership and order among themselves, not by being last:
    a later PR appends after them."""
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    names = [e["name"] for e in manifest["per_layer"]]
    mine = [n for n in names if n.endswith(".hybrid")]
    assert mine == [s + ".hybrid" for s in HYBRID]
    assert names.index(mine[0]) == names.index(
        "moe_expert_imbalance.sparse") + 1
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b-a3b.serve1", "hybrid_reason_backlog", 1)
    e2e = {e["name"]: e for e in manifest["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                "hybrid_reason_backlog.json")
    assert (traffic["backlog_requests"], traffic["block"],
            traffic["order_seed"], traffic["ramp_s"], traffic["trace_s"]) == (
        1024, 8, 48, 51, 8)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.7, "min": 128, "max": 4096}
    assert traffic["output"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.7, "min": 256, "max": 8192}
    assert traffic["eos_id"] is None
    assert max(max(b) for b in traffic["warmup"]["bursts"]) > 256
