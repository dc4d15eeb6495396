"""`benchmarks/costs_glm_dsa.py` by hand on a small shape, the published
sizes' parameter count, and the reducers that read it on a recorded
counter set: a share of a roofline made from what MUST be done."""
import json
import os

import pytest

from benchmarks import costs_glm_dsa as costs
from benchmarks.reducers import (glm_attn_roofline, glm_index_roofline,
                                 glm_moe_roofline, glm_step_roofline)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BF16 = {"weights": "bfloat16", "kv_cache": "bfloat16"}
SMALL = dict(hidden_size=8, intermediate_size=16, moe_intermediate_size=4,
             num_hidden_layers=3, first_k_dense_replace=1,
             num_attention_heads=2, q_lora_rank=6, kv_lora_rank=5,
             qk_nope_head_dim=3, qk_rope_head_dim=2, v_head_dim=4,
             index_head_dim=4, index_n_heads=2, n_routed_experts=2,
             router_experts=8, n_shared_experts=1, vocab_size=10)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-5.serve1.json")) as f:
        return json.load(f)


def test_the_published_sizes_come_to_743_9_billion(cfg):
    pub = cfg["published"]
    assert costs.attention_params(pub) == 165_022_208
    assert costs.indexer_params(pub) == 9_371_904
    assert costs.expert_params(pub) == 37_748_736
    assert costs.layer_params(dict(pub, router_experts=256), True, 0) \
        == 213_728_256
    assert costs.layer_params(pub, False, 0) == 400_898_816
    assert costs.params_published(pub) == 743_911_218_432
    assert costs.params_held(cfg["model"]) == 3_909_632_768
    assert costs.cache_bytes_per_token(cfg["model"], cfg["precision"]) == 7040


def test_parameters_by_hand_on_a_small_shape():
    # attention: 8x6 + 6 + 6x2x5 + 8x7 + 5 + 5x2x7 + 2x4x8 = 309
    assert costs.attention_params(SMALL) == 48 + 6 + 60 + 56 + 5 + 70 + 64
    # indexer: 6x2x4 + 8x4 + 2x4 + 8x2 = 104
    assert costs.indexer_params(SMALL) == 48 + 32 + 8 + 16
    assert costs.expert_params(SMALL) == 3 * 8 * 4
    dense = 309 + 104 + 16 + 3 * 8 * 16
    sparse = 309 + 104 + 16 + 8 * 8 + 8 + 96 + 2 * 96
    assert costs.layer_params(SMALL, False, 0) == dense
    assert costs.layer_params(SMALL, True, 2) == sparse
    assert costs.params_held(SMALL) == dense + 2 * sparse + 2 * 10 * 8 + 8
    assert costs.cache_bytes_per_token(SMALL, BF16) == 3 * 2 * (5 + 2 + 4)


def test_kernel_costs_by_hand_on_a_small_shape():
    # 100 scored pairs, 30 columns of keys read once, 7 rows
    assert costs.index_needed(SMALL, BF16, 100, 30, 7) == (
        30 * 4 * 2 + 7 * 2 * (4 * 2 + 4) + 100 * 4, 100 * 2 * 2 * 4)
    # 40 kept pairs: a 7-wide row each, 2 heads x (7 + 5) x 2 operations
    assert costs.latent_attn_needed(SMALL, BF16, 40, 7) == (
        40 * 7 * 2 + 7 * 2 * (7 + 5) * 2, 40 * 2 * 2 * (7 + 5))
    assert costs.moe_needed(SMALL, BF16, 2, 5) == (
        2 * 96 * 2 + 5 * 2 * 8 * 2, 5 * 2 * 96)
    outside = 8 * 10 + 3 * (309 + 104) + 3 * 8 * 16 + 2 * (8 * 8 + 8 + 96)
    assert costs.matmul_params_outside_experts(SMALL) == outside
    b, o = costs.serve_step_needed(SMALL, BF16, 7, 2, 5, 100, 30, 40)
    assert b == outside * 2 + 544 + 3 * (808 + 896)
    assert o == 7 * 2 * outside + 960 + 3 * (1600 + 1920)


def test_the_reducers_read_the_counters_and_fall_silent_without(cfg):
    """One traced step program of 60 ms holding the three kernels and the
    grouped products; counters of a window of 10 steps."""
    ms = 1e6
    trace = {"devices": {"/device:TPU:0": {
        "modules": [["jit_glm_step(1)", 0.0, 60 * ms]],
        "ops": [["ragged_index_scores_f32_88_512_512", 0.0, 5 * ms],
                ["dsa_select_s32_512_128", 5 * ms, 5 * ms],
                ["ragged_sparse_latent_attention_bf16_32768_512", 10 * ms,
                 20 * ms],
                ["ragged-dot_f32_4096_2048", 30 * ms, 8 * ms]]}}, "host": {}}
    lab = '{layer_type="latent"}'
    counters = {"pt_serving_device_steps": 10.0,
                "pt_dsa_rows" + lab: 10 * 300.0,
                "pt_dsa_context_tokens" + lab: 10 * 2_000_000.0,
                "pt_dsa_selected_tokens" + lab: 10 * 500_000.0,
                "pt_ragged_kv_tokens" + lab: 10 * 600_000.0,
                "pt_moe_experts_touched": 10 * 4 * 16.0,
                "pt_moe_assignments": 10 * 4 * 150.0}
    facts = {"trace": trace, "config": cfg, "counters": counters,
             "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    kw = dict(step_pattern="glm_step")
    m, p = cfg["model"], cfg["precision"]

    def share(need, seconds):
        return 100 * max(need[0] / 819e9, need[1] / 197e12) / seconds

    assert glm_index_roofline.reduce(facts, "ragged_index_scores", **kw) == \
        pytest.approx(share(costs.index_needed(m, p, 2e6, 6e5, 300), 1e-3))
    assert glm_attn_roofline.reduce(
        facts, "ragged_sparse_latent_attention", **kw) == pytest.approx(
        share(costs.latent_attn_needed(m, p, 5e5, 300), 4e-3))
    assert glm_moe_roofline.reduce(facts, "ragged-dot", **kw) == \
        pytest.approx(share(costs.moe_needed(m, p, 16, 150), 2e-3))
    assert glm_step_roofline.reduce(facts, "glm_step") == pytest.approx(
        share(costs.serve_step_needed(m, p, 300, 64, 600, 2e6, 6e5, 5e5),
              60e-3))
    for value in (glm_index_roofline.reduce(facts, "ragged_index_scores", **kw),
                  glm_attn_roofline.reduce(
                      facts, "ragged_sparse_latent_attention", **kw),
                  glm_moe_roofline.reduce(facts, "ragged-dot", **kw),
                  glm_step_roofline.reduce(facts, "glm_step")):
        assert 0 < value < 100
    # a program without the counters (the parent), or a trace without the
    # kernels: nothing to read, and no exception
    bare = dict(facts, counters={"pt_serving_device_steps": 10.0})
    assert glm_index_roofline.reduce(bare, "ragged_index_scores", **kw) is None
    assert glm_attn_roofline.reduce(bare, "x", **kw) is None
    assert glm_moe_roofline.reduce(bare, "ragged-dot", **kw) is None
    assert glm_step_roofline.reduce(bare, "glm_step") is None
    assert glm_index_roofline.reduce(facts, "no_such_kernel", **kw) is None
