"""`agent_rollout_saturated`'s thirteen `*.agent` metric files against the
synthetic trace of `test_bm_longcat_flash_costs.py`, with the cell held under
`serve_tokens_per_s` by MEMBERSHIP: that file's own test of them holds the
cell to be the LAST of the list, which stopped being true with the first
serving cell appended after it (PR 48), and everything behind that line,
the thirteen readings and their <= 100%, went unread. A `benchmark` PR
should make the line there one of membership and take this file out."""
import json
import os

import pytest

from benchmarks import harness

import test_bm_longcat_flash_costs as longcat

ROOT = longcat.ROOT
CELL = longcat.CELL
LAB = '{layer_type="latent"}'
COUNTERS = {"pt_serving_device_steps": 10.0, "pt_ragged_tokens": 2500.0,
            "pt_ragged_attn_pairs" + LAB: 4.5e6,
            "pt_ragged_kv_tokens" + LAB: 2.9e6,
            "pt_moe_experts_touched": 600.0, "pt_moe_assignments": 2560.0,
            "pt_moe_rows_max_expert": 360.0,
            "pt_moe_rows_elsewhere": 77600.0,
            "pt_moe_assignments_zero": 39840.0}
MANIFEST = harness.load_json(ROOT, "BENCHMARK.json")
AGENT = [e for e in MANIFEST["per_layer"] if e["name"].endswith(".agent")]


@pytest.fixture(scope="module")
def facts():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "longcat-flash-chat.serve1.json")) as f:
        facts = longcat._facts(json.load(f), COUNTERS)
    dev = facts["trace"]["devices"]["/device:TPU:0"]   # a second step
    dev["modules"].append(["jit_longcat_step(1)", 41e6, 40e6])
    dev["ops"] += [[n, t + 41e6, d] for n, t, d in dev["ops"]]
    return facts


def test_the_cell_stands_where_it_stood():
    assert len(AGENT) == 13
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[CELL]["chips"] == 1 and len(cells[CELL]["why"]) <= 200
    rate = next(e for e in MANIFEST["end_to_end"]
                if e["name"] == "serve_tokens_per_s")
    # what PR 41 appended it to is still in front of it, in its order
    before = rate["workloads"][:rate["workloads"].index(CELL)]
    assert before == ["chat_saturated", "reason_saturated",
                      "longctx_reason_saturated"]
    assert rate["bound"] == 0.025


@pytest.mark.parametrize("entry", AGENT, ids=lambda e: e["name"])
def test_an_agent_metric_file_reads_the_synthetic_trace(entry, facts):
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    spec = harness.load_json(ROOT, "benchmarks", "layer_metrics",
                             entry["name"] + ".json")
    assert {k: spec[k] for k in entry} == entry
    value = harness.load_module("reducers", spec["reducer"]).reduce(
        facts, **spec.get("args", {}))
    assert value is not None and value >= 0
    if entry["unit"] == "%":
        assert value <= 100
