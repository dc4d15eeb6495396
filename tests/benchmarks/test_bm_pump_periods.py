"""The twelve metrics ISSUE 43 reads from the pump's periods: the reducer
`counter_share` by hand, every counter the twelve files name against the
keys `EngineMetrics` registers (a rename there would otherwise leave a
null in the ledger unnoticed), and each entry's cell, `moves` and reducer
against the issue's table. (The issue's `*.agent` are `*.agent_rollout`
here: `test_bm_longcat_flash_costs.py` counts thirteen names that end in
`.agent` and feeds each a counter set of its own.)"""
import os

import pytest

from benchmarks import harness
from benchmarks.reducers import counter_share, counter_sum_ratio
from paddle_tpu.serving.metrics import EngineMetrics, MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SECONDS = 'pt_serving_period_seconds{carried="%s"}'
PERIODS = 'pt_serving_periods{carried="%s"}'
PARKED = "pt_serving_parked_seconds"
TILE = [SECONDS % "decode", SECONDS % "prompt", PARKED]

# a window of 51 s: 1,000 decode periods of 36 ms, 100 prompt periods of
# 100 ms holding 40,000 prompt rows, 5 s parked, 20 requests started
WINDOW = {SECONDS % "decode": 36.0, SECONDS % "prompt": 10.0, PARKED: 5.0,
          PERIODS % "decode": 1000.0, PERIODS % "prompt": 100.0,
          "pt_serving_prefill_tokens": 40000.0,
          "pt_serving_requests_started": 20.0,
          "pt_serving_device_steps": 1100.0}


def _mean(kind):
    return "counter_sum_ratio", {"nums": [SECONDS % kind],
                                 "den": PERIODS % kind, "scale": 1000.0}


def _share(num):
    return "counter_share", {"nums": [num], "dens": TILE}


ROWS = "counter_sum_ratio", {"nums": ["pt_serving_prefill_tokens"],
                             "den": PERIODS % "prompt"}
# stem -> (reducer, args, its reading of WINDOW)
STEMS = {
    "decode_period_ms": (*_mean("decode"), 36.0),
    "prompt_period_ms": (*_mean("prompt"), 100.0),
    "prompt_time_share": (*_share(SECONDS % "prompt"), 10.0 / 51.0),
    "prompt_rows_per_prompt_step": (*ROWS, 400.0),
    "step_period_ms": ("counter_share", {
        "nums": [SECONDS % "decode", SECONDS % "prompt"],
        "dens": [PERIODS % "decode", PERIODS % "prompt"],
        "scale": 1000.0}, 46000.0 / 1100.0),
    "prompt_periods_per_request": ("counter_sum_ratio", {
        "nums": [PERIODS % "prompt"],
        "den": "pt_serving_requests_started"}, 5.0),
    "pump_parked_share": (*_share(PARKED), 5.0 / 51.0),
}
CELLS = {"longctx": "longctx_reason_saturated",
         "agent_rollout": "agent_rollout_saturated",
         "saturated": "chat_saturated", "steady": "chat_steady"}
# metric -> the end-to-end metric it moves
TABLE = {
    **{f"{stem}.{tag}": "serve_tokens_per_s"
       for tag in ("longctx", "agent_rollout")
       for stem in ("decode_period_ms", "prompt_period_ms",
                    "prompt_time_share", "prompt_rows_per_prompt_step")},
    "step_period_ms.saturated": "serve_tokens_per_s",
    "prompt_period_ms.steady": "itl_p99_ms",
    "prompt_periods_per_request.steady": "ttft_p50_ms",
    "pump_parked_share.steady": "ttft_p50_ms",
}


def test_counter_share_by_hand():
    facts = {"counters": WINDOW}
    assert counter_share.reduce(facts, nums=[PARKED], dens=TILE) == \
        pytest.approx(5.0 / 51.0)
    assert counter_share.reduce(
        facts, nums=TILE[:2], dens=[PERIODS % "decode", PERIODS % "prompt"],
        scale=1000.0) == pytest.approx(46000.0 / 1100.0)
    # a part the window did not book is a share of nothing
    quiet = {k: v for k, v in WINDOW.items() if k != PARKED}
    assert counter_share.reduce({"counters": quiet}, nums=[PARKED],
                                dens=TILE) == 0.0


@pytest.mark.parametrize("counters", [
    None, {}, {"pt_serving_device_steps": 9.0},
    {k: 0.0 for k in TILE}], ids=["no_facts", "empty", "parent", "zeros"])
def test_counter_share_reads_none_where_nothing_is_booked(counters):
    """The parent commit has none of the counters, and a window may book
    none: no number, and no error."""
    facts = {} if counters is None else {"counters": counters}
    assert counter_share.reduce(facts, nums=[PARKED], dens=TILE) is None
    assert counter_share.reduce(facts, nums=TILE[:2], scale=1000.0, dens=[
        PERIODS % "decode", PERIODS % "prompt"]) is None


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def registered():
    """The keys of a window's counter deltas: what `harness.counters`
    reads from the registry `EngineMetrics` fills."""
    registry = MetricsRegistry()
    EngineMetrics(registry, external_queue=True)
    return set(harness.counters(registry))


@pytest.mark.parametrize("name", sorted(TABLE))
def test_the_entry_is_what_the_table_says(manifest, registered, name):
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    spec = harness.load_json(ROOT, "benchmarks", "layer_metrics",
                             name + ".json")
    stem, tag = name.split(".")
    reducer, args, reading = STEMS[stem]
    assert entry["workloads"] == [CELLS[tag]]
    assert entry["moves"] == TABLE[name]
    assert entry["source"] == "program_counter"
    assert (spec["reducer"], spec["args"]) == (reducer, args)
    # every counter it names is one the program registers, letter for
    # letter
    named = set(args["nums"]) | set(args.get("dens", [])) | \
        ({args["den"]} if "den" in args else set())
    assert named <= registered, named - registered
    # and the file's own reading of a window worked out by hand
    reader = {"counter_share": counter_share,
              "counter_sum_ratio": counter_sum_ratio}[reducer]
    assert reader.reduce({"counters": WINDOW}, **spec["args"]) == \
        pytest.approx(reading)
    assert reader.reduce({"counters": {"pt_serving_device_steps": 9.0}},
                         **spec["args"]) is None


def test_the_twelve_are_the_last_entries_and_nothing_else_moved(manifest):
    tail = [m["name"] for m in manifest["per_layer"][-12:]]
    assert sorted(tail) == sorted(TABLE)
    assert all(m["layer"] == "scheduler"
               for m in manifest["per_layer"][-12:])
