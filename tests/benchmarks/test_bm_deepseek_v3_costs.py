"""`costs_deepseek_v3.py` against the configuration's own reckoning and the
published size, and the family's reducers on a trace and a registry made by
hand: each share is the needed operations (or bytes) over the peak over the
measured time, credits neither recomputation nor padding, and reads nothing
(None) where the program keeps no registry or the trace no such operation."""
import json
import os

import pytest

from benchmarks import costs_deepseek_v3 as costs
from benchmarks.reducers import (deepseek_flash_roofline,
                                 deepseek_moe_roofline, train_mfu_sparse,
                                 train_moe_imbalance, train_registry_ratio)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "moonlight-16b-a3b.train1.json")) as f:
    CFG = json.load(f)
M, PUB, PREC = CFG["model"], CFG["published"], CFG["precision"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
STEP = "jit_deepseek_train_step(1)"
FLASH = "train.latent_attention_bf16_64_8192_128_custom-call"
DOT = "ragged-dot-none_f32_8192_1408_custom-call"


def test_the_shapes_are_the_issues_arithmetic():
    assert costs.attention_params(M) == 6_291_456 + 1_179_648 + 2_097_152 \
        + 4_194_304                                             # 13.76 M
    assert costs.expert_params(M) == 8_650_752                 # 8.65 M
    assert costs.layer_params_outside_experts(M) == 13_762_560 \
        + 17_301_504 + 131_072                                  # 31.2 M
    assert costs.dense_layer_params(M) == 13_762_560 + 69_206_016   # 83.0 M
    assert costs.depth(M) == (1, M["num_hidden_layers"] - 1)
    # what this chip holds: the tree `models/deepseek_v3.shapes` lays out
    from benchmarks.models import deepseek_v3 as family
    import numpy as np
    tree = family.shapes(M)
    import jax
    held = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))
    assert costs.held_params(M) == held
    # the whole model from the published counts: the card's 16 B
    whole = dict(PUB, router_experts=PUB["n_routed_experts"])
    assert 15.9e9 < costs.held_params(whole) < 16.0e9


def test_operations_count_once_and_by_assignment():
    tokens, pairs, assignments = 32768, 40e6, 4 * 24576
    flash = costs.latent_flash_flops(M, pairs)
    assert flash == 3 * 2 * (192 + 128) * pairs * 16 * sum(costs.depth(M))
    total = costs.train_flops_per_step(M, tokens, pairs, assignments)
    assert total == 6 * costs.matmul_params_per_token(M) * tokens \
        + 6 * 8_650_752 * assignments + flash
    need_bytes, need_ops = costs.moe_needed(M, PREC, 32, assignments)
    assert need_ops == 6 * 8_650_752 * assignments
    assert need_bytes == 3 * 32 * 8_650_752 * 2 + 4 * assignments * 2048 * 2
    # at thousands of rows an expert the products are bound by operations
    assert need_ops / PEAKS["bf16_flops_per_s"] \
        > need_bytes / PEAKS["hbm_bytes_per_s"]


class _Step:
    def __init__(self, values):
        self.values = values

    def snapshot(self):
        return {k: {"type": "counter", "value": v}
                for k, v in self.values.items()}


@pytest.fixture
def registry(monkeypatch):
    from benchmarks.models import deepseek_v3 as family
    layers = costs.depth(M)[1]
    values = {"pt_train_steps": 10.0,
              "pt_train_moe_assignments": 10.0 * layers * 24_000,
              "pt_train_moe_experts_touched": 10.0 * layers * 8,
              "pt_train_moe_rows_max": 10.0 * layers * 4_200}
    monkeypatch.setattr(family, "TRAINERS", [_Step(values)])
    return values


def _facts(ops, steps=2, step_ms=1000.0):
    mods = [[STEP, i * 2e9, step_ms * 1e6] for i in range(steps)]
    return {"config": CFG, "peaks": PEAKS, "chips": 1,
            "tokens_per_step": 32768, "pairs_per_step": 40e6,
            "trace": {"devices": {"/device:TPU:0": {
                "ops": [[label, i * 2e9 + j * 4e8, ns] for i in range(steps)
                        for j, (label, ns) in enumerate(ops)],
                "modules": mods}}, "host": {}}}


def test_the_registry_is_read_through_the_familys_newest_trainer(registry):
    facts = _facts([])
    assert train_registry_ratio.reduce(
        facts, "pt_train_moe_assignments",
        "pt_train_moe_experts_touched") == 3_000.0
    assert train_moe_imbalance.reduce(facts) == pytest.approx(4_200 / 3_000)
    assert train_registry_ratio.reduce(facts, "pt_train_steps", "nothing") \
        is None


def test_shares_are_needed_over_peak_over_measured(registry):
    layers = costs.depth(M)[1]
    facts = _facts([(FLASH, 300e6), (DOT, 100e6)])
    pat = dict(step_pattern="deepseek_train_step")
    got = deepseek_flash_roofline.reduce(
        facts, pattern=r"latent_attention\S*custom-call", **pat)
    assert got == pytest.approx(
        100 * costs.latent_flash_flops(M, 40e6) / 0.3 / 197e12)
    got = deepseek_moe_roofline.reduce(facts, pattern="ragged-dot", **pat)
    ops = 6 * 8_650_752 * layers * 24_000
    assert got == pytest.approx(100 * (ops / 197e12) / 0.1)
    got = train_mfu_sparse.reduce(facts, pattern="deepseek_train_step")
    assert got == pytest.approx(100 * costs.train_flops_per_step(
        M, 32768, 40e6, layers * 24_000) / 1.0 / 197e12)
    assert 0 < got < 100


def test_nothing_to_read_is_none(monkeypatch, registry):
    from benchmarks.models import deepseek_v3 as family
    pat = dict(step_pattern="deepseek_train_step")
    empty = _facts([])
    assert deepseek_flash_roofline.reduce(
        empty, pattern=r"latent_attention\S*custom-call", **pat) is None
    assert deepseek_moe_roofline.reduce(empty, pattern="ragged-dot",
                                        **pat) is None
    # a program from before the counters: no trainer kept, or no registry
    for kept in ([], [object()]):
        monkeypatch.setattr(family, "TRAINERS", kept)
        facts = _facts([(FLASH, 300e6), (DOT, 100e6)])
        assert train_registry_ratio.counters(facts) == {}
        assert train_moe_imbalance.reduce(facts) is None
        assert train_mfu_sparse.reduce(
            facts, pattern="deepseek_train_step") is None
        assert deepseek_moe_roofline.reduce(facts, pattern="ragged-dot",
                                            **pat) is None


def test_the_metric_files_name_these_reducers_and_the_cell():
    base = os.path.join(ROOT, "benchmarks", "layer_metrics")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = [e for e in manifest["per_layer"] if e["name"].endswith(".sparse")]
    assert len(mine) == 9
    for entry in mine:
        with open(os.path.join(base, entry["name"] + ".json")) as f:
            spec = json.load(f)
        assert entry["workloads"] == spec["workloads"] == ["sparse_pretrain_8k"]
        assert entry["moves"] == "train_tokens_per_s"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "reducers", spec["reducer"] + ".py"))


NINE = [
    "train_step_device_ms.sparse", "train_mfu_pct.sparse",
    "device_idle_pct.sparse", "latent_flash_busy_pct.sparse",
    "latent_flash_roofline_pct.sparse", "moe_experts_busy_pct.sparse",
    "moe_experts_roofline_pct.sparse", "moe_rows_per_expert.sparse",
    "moe_expert_imbalance.sparse"]


def test_the_nine_follow_what_was_there_and_moved_no_entry_of_it():
    """This PR's nine are in `per_layer`, once each, in the order the issue
    lists them and after every entry the parent had, which still stand in
    the parent's order with PR 43's twelve together: by membership and
    relative order, so that the next PR's entries, appended in turn, break
    nothing here. (`test_bm_pump_periods.py` holds the twelve to be the
    list's LAST, which no PR that appends can keep: `tests/conftest.py`,
    PERF.md Open questions.)"""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    names = [m["name"] for m in per_layer]
    assert len(names) == len(set(names))
    assert [n for n in names if n in NINE] == NINE
    was = ["queue_wait_p50_ms.steady", "serve_step_roofline_pct.agent",
           "decode_period_ms.longctx", "pump_parked_share.steady"]
    assert [n for n in names if n in was + NINE[:1]] == was + NINE[:1]
    first = names.index("decode_period_ms.longctx")
    twelve = per_layer[first:first + 12]
    assert {m["layer"] for m in twelve} == {"scheduler"}
    assert twelve[-1]["name"] == "pump_parked_share.steady"
    assert not set(NINE) & {m["name"] for m in twelve}
