"""`costs_laguna.py` against the configuration's own reckoning, and the four
`laguna_*` reducers on a trace and counters made by hand: each share is the
needed bytes over the peak over the measured time, counts only what MUST be
read, and reads nothing (None) where the program books no such counter, as
the parent commit does not."""
import json
import os

import pytest

from benchmarks import costs_laguna as costs
from benchmarks.reducers import (laguna_attn_roofline, laguna_moe_imbalance,
                                 laguna_moe_roofline, laguna_step_roofline)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "laguna-xs.2.serve1.json")) as f:
    CFG = json.load(f)
M, PREC = CFG["model"], CFG["precision"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
FULL = "ragged_paged_attention_bf16_8_840_128_custom-call"
WIN = "ragged_paged_attention_bf16_8_1024_128_custom-call"
DOT = "ragged-dot-none_f32_1024_512_custom-call"


def test_the_shapes_are_the_files_reckoning():
    assert costs.layers(M) == [("full", 48, "dense"), ("window", 64, "sparse"),
                               ("window", 64, "sparse"), ("window", 64, "sparse"),
                               ("full", 48, "sparse")]
    assert costs.sparse_layers(M) == 4
    assert costs.expert_params(M) * 2 == 6_291_456          # 6.29 MB
    # all the parameters of the five layers, less the embedding and norms
    held = costs.matmul_params_outside_experts(M) \
        + 4 * 256 * costs.expert_params(M)
    assert held == 3_869_857_792 - 100352 * 2048 - 11 * 2048
    assert costs.kv_bytes_per_token(M, "full") == 2 * 4096
    assert costs.kv_bytes_per_token(M, "window") == 3 * 4096


def _facts(counters, ops, steps=2, step_ms=20.0):
    mods = [["jit_laguna_step(1)", i * 30e6, step_ms * 1e6]
            for i in range(steps)]
    return {"counters": counters, "config": CFG, "peaks": PEAKS,
            "trace": {"devices": {"/device:TPU:0": {
                "ops": [[label, i * 30e6 + j * 5e6, ns]
                        for i in range(steps)
                        for j, (label, ns) in enumerate(ops)],
                "modules": mods}}, "host": {}}}


COUNTERS = {
    "pt_serving_device_steps": 10.0, "pt_ragged_tokens": 1100.0,
    "pt_moe_assignments": 4 * 10 * 880.0, "pt_moe_experts_touched": 4 * 10 * 250.0,
    "pt_moe_rows_max_expert": 4 * 10 * 11.0,
    'pt_ragged_kv_tokens{layer_type="full"}': 10 * 160_000.0,
    'pt_ragged_attn_pairs{layer_type="full"}': 10 * 190_000.0,
    'pt_ragged_kv_tokens{layer_type="window"}': 10 * 50_000.0,
    'pt_ragged_attn_pairs{layer_type="window"}': 10 * 56_000.0,
}


def test_the_expert_products_share_counts_the_experts_touched():
    # 4 sparse layers a step, 1 ms of grouped products each
    facts = _facts(COUNTERS, [(DOT, 1e6)] * 4)
    need = 250 * 6_291_456 + 880 * 2 * 2048 * 2
    want = 100 * (need / 819e9) / 1e-3
    got = laguna_moe_roofline.reduce(facts, "ragged-dot", "laguna_step")
    assert got == pytest.approx(want) and 100 < got < 200   # 1 ms is too fast
    assert laguna_moe_roofline.reduce(
        _facts(COUNTERS, [(DOT, 4e6)] * 4), "ragged-dot",
        "laguna_step") == pytest.approx(want / 4)
    assert laguna_moe_imbalance.reduce(facts) == pytest.approx(11 / (880 / 250))


def test_the_attention_shares_split_by_cache_group():
    facts = _facts(COUNTERS, [(FULL, 1e6)] * 2 + [(WIN, 0.5e6)] * 3)
    full = laguna_attn_roofline.reduce(facts, "_8_840_128", "laguna_step",
                                       "full")
    win = laguna_attn_roofline.reduce(facts, "_8_1024_128", "laguna_step",
                                      "window")
    assert full == pytest.approx(
        100 * ((160_000 * 4096 + 110 * 2 * 48 * 128 * 2) / 819e9) / 1e-3)
    assert win == pytest.approx(
        100 * ((50_000 * 4096 + 110 * 2 * 64 * 128 * 2) / 819e9) / 0.5e-3)


def test_the_steps_share_adds_weights_experts_and_kv():
    facts = _facts(COUNTERS, [(DOT, 1e6)], step_ms=20.0)
    need = (costs.matmul_params_outside_experts(M) + 1000 * 3_145_728) * 2 \
        + 160_000 * 8192 + 50_000 * 12288
    assert laguna_step_roofline.reduce(facts, "laguna_step") == pytest.approx(
        100 * (need / 819e9) / 20e-3)


@pytest.mark.parametrize("reduce, args", [
    (laguna_moe_roofline.reduce, ("ragged-dot", "laguna_step")),
    (laguna_moe_imbalance.reduce, ()),
    (laguna_attn_roofline.reduce, ("_8_840_128", "laguna_step", "full")),
    (laguna_step_roofline.reduce, ("laguna_step",)),
])
def test_nothing_to_read_is_none_and_does_not_raise(reduce, args):
    """A program without these counters or operations (the parent commit)."""
    bare = {"pt_serving_device_steps": 10.0, "pt_ragged_tokens": 320.0}
    assert reduce(_facts(bare, [("fusion_bf16_32_4096", 1e6)]), *args) is None
    assert reduce(_facts({}, []), *args) is None
