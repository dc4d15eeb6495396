"""`benchmarks/costs_longcat_flash.py` by hand on a small shape, the
published sizes' parameter count, and the reducers that read it on a
synthetic counter set and trace: a share of a roofline made from what MUST
be done, and `None` where there is nothing to read."""
import json
import os

import pytest

from benchmarks import costs_longcat_flash as costs, harness
from benchmarks.reducers import (longcat_attn_roofline, longcat_moe_imbalance,
                                 longcat_moe_roofline, longcat_step_roofline,
                                 longcat_zero_share)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BF16 = {"weights": "bfloat16", "kv_cache": "bfloat16"}
SMALL = dict(hidden_size=8, ffn_hidden_size=16, expert_ffn_hidden_size=4,
             num_layers=3, num_attention_heads=2, q_lora_rank=6,
             kv_lora_rank=5, qk_nope_head_dim=3, qk_rope_head_dim=2,
             v_head_dim=4, n_routed_experts=2, router_experts=8,
             zero_expert_num=4, vocab_size=10)
CELL = "agent_rollout_saturated"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "longcat-flash-chat.serve1.json")) as f:
        return json.load(f)


def test_the_published_sizes_come_to_560_66_billion(cfg):
    pub = cfg["published"]
    assert costs.attention_params(pub) == 90_572_800
    assert costs.dense_ffn_params(pub) == 226_492_416
    assert costs.expert_params(pub) == 37_748_736
    assert costs.layer_params(pub, 0) == 638_874_368
    assert costs.params_published(pub) == 560_664_980_480
    held = costs.params_held(cfg["model"])
    assert held == 5_172_749_312 and round(held * 2 / 1e9, 2) == 10.35
    assert costs.cache_bytes_per_token(cfg["model"], cfg["precision"]) == 9216
    assert costs.cache_bytes_per_token(
        cfg["model"], {"kv_cache": "float8_e4m3fn"}) == 4608
    for word in ("5,172,749,312", "560,664,980,480", "638,874,368"):
        assert word in cfg["reckoning"]["parameters"]


def test_parameters_by_hand_on_a_small_shape():
    # attention: 8x6 + 6 + 6x2x5 + 8x7 + 5 + 5x2x7 + 2x4x8 = 309
    attn = 48 + 6 + 60 + 56 + 5 + 70 + 64
    assert costs.attention_params(SMALL) == attn
    assert costs.dense_ffn_params(SMALL) == 3 * 8 * 16
    assert costs.expert_params(SMALL) == 3 * 8 * 4
    assert costs.router_width(SMALL) == 12
    layer = 2 * attn + 2 * 384 + 4 * 8 + 8 * 12 + 12
    assert costs.layer_params(SMALL, 0) == layer
    assert costs.layer_params(SMALL, 2) == layer + 2 * 96
    assert costs.params_held(SMALL) == 3 * (layer + 192) + 2 * 10 * 8 + 8
    assert costs.sublayers(SMALL) == 6
    assert costs.cache_bytes_per_token(SMALL, BF16) == 6 * 2 * (5 + 2)


def test_kernel_costs_by_hand_on_a_small_shape():
    # 100 pairs over 30 latent rows read once, 7 rows of the step
    assert costs.latent_attn_needed(SMALL, BF16, 100, 30, 7) == (
        30 * 7 * 2 + 7 * 2 * (7 + 5) * 2, 100 * 2 * 2 * (7 + 5))
    assert costs.moe_needed(SMALL, BF16, 2, 5) == (
        2 * 96 * 2 + 5 * 2 * 8 * 2, 5 * 2 * 96)
    # products outside the experts: no norm, no correction bias
    outside = 8 * 10 + 3 * (2 * (309 - 11) + 2 * 384 + 8 * 12)
    assert costs.matmul_params_outside_experts(SMALL) == outside
    b, o = costs.serve_step_needed(SMALL, BF16, 7, 2, 5, 100, 30)
    assert b == outside * 2 + 544 + 6 * 756
    assert o == 7 * 2 * outside + 960 + 6 * 4800


def _facts(cfg, counters):
    ms = 1e6
    trace = {"devices": {"/device:TPU:0": {
        "modules": [["jit_longcat_step(1)", 0.0, 40 * ms]],
        "ops": [["ragged_latent_attention_bf16_16384_512_custom-call", 0.0,
                 16 * ms],
                ["ragged-dot_f32_384_2048", 16 * ms, 8 * ms],
                ["convolution_multiply_fusion_bf16_256_12288", 24 * ms,
                 6 * ms]]}}, "host": {}}
    return {"trace": trace, "config": cfg, "counters": counters,
            "trace_window_s": 0.1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}


def test_the_reducers_read_the_counters_and_fall_silent_without(cfg):
    """One traced step program of 40 ms holding the kernel, the grouped
    products and a dense product; counters of a window of 10 steps."""
    lab = '{layer_type="latent"}'
    counters = {"pt_serving_device_steps": 10.0,
                "pt_ragged_tokens": 10 * 250.0,
                "pt_ragged_attn_pairs" + lab: 10 * 450_000.0,
                "pt_ragged_kv_tokens" + lab: 10 * 290_000.0,
                "pt_moe_experts_touched": 10 * 4 * 15.0,
                "pt_moe_assignments": 10 * 4 * 64.0,
                "pt_moe_rows_max_expert": 10 * 4 * 9.0,
                "pt_moe_rows_elsewhere": 10 * 4 * 1940.0,
                "pt_moe_assignments_zero": 10 * 4 * 996.0}
    facts = _facts(cfg, counters)
    kw = dict(step_pattern="longcat_step")
    m, p = cfg["model"], cfg["precision"]

    def share(need, seconds):
        return 100 * max(need[0] / 819e9, need[1] / 197e12) / seconds

    attn = longcat_attn_roofline.reduce(facts, "ragged_latent_attention", **kw)
    assert attn == pytest.approx(share(costs.latent_attn_needed(
        m, p, 450_000, 290_000, 250), 16e-3 / 8))
    moe = longcat_moe_roofline.reduce(facts, "ragged-dot", **kw)
    assert moe == pytest.approx(share(costs.moe_needed(m, p, 15, 64), 2e-3))
    step = longcat_step_roofline.reduce(facts, "longcat_step")
    assert step == pytest.approx(share(costs.serve_step_needed(
        m, p, 250, 60, 256, 450_000, 290_000), 40e-3))
    for value in (attn, moe, step):
        assert 0 < value < 100
    assert longcat_moe_imbalance.reduce(facts) == pytest.approx(
        9.0 / (64 / 15))
    assert longcat_zero_share.reduce(facts) == pytest.approx(
        996 / (996 + 64 + 1940))
    # a program without the counters (the parent), or a trace without the
    # kernels: nothing to read, and no exception
    bare = _facts(cfg, {"pt_serving_device_steps": 10.0})
    assert longcat_attn_roofline.reduce(bare, "ragged_latent_attention",
                                        **kw) is None
    assert longcat_moe_roofline.reduce(bare, "ragged-dot", **kw) is None
    assert longcat_step_roofline.reduce(bare, "longcat_step") is None
    assert longcat_moe_imbalance.reduce(bare) is None
    assert longcat_zero_share.reduce(bare) is None
    assert longcat_zero_share.reduce(_facts(cfg, {})) is None
    assert longcat_attn_roofline.reduce(facts, "no_such_kernel", **kw) is None
    assert longcat_step_roofline.reduce(facts, "glm_step") is None


def test_the_thirteen_metric_files_read_the_synthetic_trace(cfg):
    """Every `*.agent` metric of the manifest names the cell alone, moves
    `serve_tokens_per_s`, and its file's reducer finds a number in a trace
    and counter set that have what this family books."""
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    mine = [e for e in manifest["per_layer"] if e["name"].endswith(".agent")]
    assert len(mine) == 13
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL]["chips"] == 1 and len(cells[CELL]["why"]) <= 200
    rate = next(e for e in manifest["end_to_end"]
                if e["name"] == "serve_tokens_per_s")
    assert rate["workloads"][-1] == CELL and rate["bound"] == 0.025
    lab = '{layer_type="latent"}'
    counters = {"pt_serving_device_steps": 10.0, "pt_ragged_tokens": 2500.0,
                "pt_ragged_attn_pairs" + lab: 4.5e6,
                "pt_ragged_kv_tokens" + lab: 2.9e6,
                "pt_moe_experts_touched": 600.0, "pt_moe_assignments": 2560.0,
                "pt_moe_rows_max_expert": 360.0,
                "pt_moe_rows_elsewhere": 77600.0,
                "pt_moe_assignments_zero": 39840.0}
    facts = _facts(cfg, counters)
    dev = facts["trace"]["devices"]["/device:TPU:0"]   # a second step
    dev["modules"].append(["jit_longcat_step(1)", 41e6, 40e6])
    dev["ops"] += [[n, t + 41e6, d] for n, t, d in dev["ops"]]
    for e in mine:
        assert e["workloads"] == [CELL] and e["moves"] == "serve_tokens_per_s"
        spec = harness.load_json(ROOT, "benchmarks", "layer_metrics",
                                 e["name"] + ".json")
        assert {k: spec[k] for k in e} == e
        value = harness.load_module("reducers", spec["reducer"]).reduce(
            facts, **spec.get("args", {}))
        assert value is not None and value >= 0, e["name"]
        if e["unit"] == "%":
            assert value <= 100, e["name"]
