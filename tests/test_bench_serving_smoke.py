"""Driver-visible bench artifacts must tell the same story the feature
tests prove (VERDICT r4 weak #1: the published spec-decode entry showed
accept_rate 0.0 because the CPU workload's motif was longer than the
prompt). This smoke test runs bench_models.bench_serving exactly as the
capture chain does and asserts the speculative path actually engages.
"""
import importlib.util
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench_models():
    if _ROOT not in sys.path:
        sys.path.insert(0, _ROOT)
    spec = importlib.util.spec_from_file_location(
        "bench_models", os.path.join(_ROOT, "bench_models.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_spec_bench_workload_engages_speculation(monkeypatch):
    bm = _load_bench_models()
    monkeypatch.setenv("PT_SERVE_SPEC", "4")
    monkeypatch.delenv("PT_SERVE_CACHE", raising=False)
    monkeypatch.delenv("PT_SERVE_PREFIX", raising=False)
    monkeypatch.delenv("PT_SERVE_ROUTER", raising=False)
    monkeypatch.delenv("PT_SERVE_MULTITURN", raising=False)
    monkeypatch.delenv("PT_SERVE_CHAOS", raising=False)
    monkeypatch.delenv("PT_SERVE_DISAGG", raising=False)
    out = bm.bench_serving(on_tpu=False)
    assert out["workload"] == "ngram-repetitive"
    assert out["spec_accept_rate"] > 0, out
    # the whole point: fewer device round-trips than plain decode on
    # the identical workload — and not marginally fewer: the loop
    # regime of long repetitive generations must dominate
    assert out["device_steps"] * 1.5 <= out["plain_device_steps"], out
    # the artifact carries its own comparison point
    assert out["plain_decode_tokens_per_sec"] > 0
    assert "spec_speedup" in out
    _assert_metrics_snapshot(out)


def _assert_metrics_snapshot(out):
    """bench_serving must ship the serving-runtime metrics snapshot —
    the driver-visible artifact carries TTFT/occupancy/preemption
    telemetry, not just tokens/sec."""
    m = out["metrics"]
    assert m["ttft_count"] == out["requests"]
    assert 0 < m["ttft_p50_s"] <= m["ttft_p99_s"]
    assert m["generated_tokens"] == out["new_tokens"]
    assert m["device_steps"] > 0
    assert m["tpot_p50_s"] >= 0
    assert 0 <= m["batch_occupancy"] <= 1
    # ISSUE 8: the step loop's host gap ships with every serving bench
    assert m["host_gap_count"] > 0 and m["host_gap_p50_s"] > 0
    # device telemetry (PR 4): measured MFU from XLA-counted FLOPs over
    # the timed run, per-phase FLOPs attribution, and the HBM high-water
    assert 0 < out["mfu"] <= 1, out
    assert out["xla_flops"] > 0
    assert out["hbm_peak_bytes"] > 0
    phases = out["phase_flops"]
    if "unified_step" in phases:
        # ragged engine (the default): ONE entry point serves prefill
        # chunks, suffix prefills, verify grids and decodes alike
        pass
    else:
        assert "decode_step" in phases or "verify_step" in phases, phases
        assert any(k.startswith("prefill") for k in phases), phases
    assert all(v > 0 for v in phases.values())
    assert sum(phases.values()) <= out["xla_flops"] + 1e-6


def test_serving_load_bench_structure(monkeypatch):
    # scaled-down load sweep: the driver-visible table must carry all
    # four configs with sane latency percentiles
    bm = _load_bench_models()
    monkeypatch.setenv("PT_BENCH_LOAD_REQS", "6")
    out = bm.bench_serving_load(on_tpu=False)
    assert set(out["configs"]) == {"fp", "fp_spec", "int8", "int8_spec"}
    for name, c in out["configs"].items():
        assert c["tokens_per_sec"] > 0, (name, c)
        assert 0 <= c["ttft_p50_ms"] <= c["ttft_p99_ms"], (name, c)
        assert 0 <= c["tpot_p50_ms"] <= c["tpot_p99_ms"], (name, c)
        assert c["new_tokens"] > 0
    assert out["requests"] == 6


def test_prefix_bench_reuses_cached_pages(monkeypatch):
    """PT_SERVE_PREFIX=1: every prompt shares one long header — the
    bench artifact must show the prefix cache actually engaging
    (nonzero hit rate and reused tokens), not just carry the fields."""
    bm = _load_bench_models()
    monkeypatch.delenv("PT_SERVE_SPEC", raising=False)
    monkeypatch.delenv("PT_SERVE_CACHE", raising=False)
    monkeypatch.delenv("PT_SERVE_ROUTER", raising=False)
    monkeypatch.delenv("PT_SERVE_MULTITURN", raising=False)
    monkeypatch.delenv("PT_SERVE_CHAOS", raising=False)
    monkeypatch.delenv("PT_SERVE_DISAGG", raising=False)
    monkeypatch.setenv("PT_SERVE_PREFIX", "1")
    out = bm.bench_serving(on_tpu=False)
    assert out["workload"] == "shared-prefix"
    assert out["prefix_hit_rate"] > 0, out
    assert out["tokens_reused"] > 0, out
    assert out["prefix_evictions"] >= 0
    _assert_metrics_snapshot(out)


def test_multiturn_bench_hits_the_host_tier(monkeypatch):
    """PT_SERVE_MULTITURN=1 (ISSUE 7 acceptance): returning
    conversations must actually hit the host-RAM tier after the burst
    evicted them — nonzero hit rate, spills, reused tokens — and show
    STRICTLY fewer returning-phase prefill tokens than the tier-off
    baseline at token-identical outputs."""
    bm = _load_bench_models()
    monkeypatch.delenv("PT_SERVE_SPEC", raising=False)
    monkeypatch.delenv("PT_SERVE_CACHE", raising=False)
    monkeypatch.delenv("PT_SERVE_PREFIX", raising=False)
    monkeypatch.delenv("PT_SERVE_ROUTER", raising=False)
    monkeypatch.delenv("PT_SERVE_CHAOS", raising=False)
    monkeypatch.delenv("PT_SERVE_DISAGG", raising=False)
    monkeypatch.setenv("PT_SERVE_MULTITURN", "1")
    out = bm.bench_serving(on_tpu=False)
    assert out["workload"] == "multi-turn"
    assert out["outputs_match"] is True, out
    assert out["tier_hit_rate"] > 0, out
    assert out["tier_spills"] > 0 and out["tokens_reused"] > 0, out
    assert out["returning_prefill_tokens"] < \
        out["baseline_prefill_tokens"], out
    assert out["tier_host_bytes"] > 0 and out["tier_pages"] > 0
    assert out["returning_tokens_per_sec"] > 0
    assert out["baseline_returning_tokens_per_sec"] > 0


def test_plain_bench_unaffected(monkeypatch):
    bm = _load_bench_models()
    monkeypatch.delenv("PT_SERVE_SPEC", raising=False)
    monkeypatch.delenv("PT_SERVE_CACHE", raising=False)
    monkeypatch.delenv("PT_SERVE_PREFIX", raising=False)
    monkeypatch.delenv("PT_SERVE_ROUTER", raising=False)
    monkeypatch.delenv("PT_SERVE_MULTITURN", raising=False)
    monkeypatch.delenv("PT_SERVE_CHAOS", raising=False)
    monkeypatch.delenv("PT_SERVE_DISAGG", raising=False)
    out = bm.bench_serving(on_tpu=False)
    assert out["decode_tokens_per_sec"] > 0
    assert "spec_decode" not in out
    assert "prefix_hit_rate" not in out
    _assert_metrics_snapshot(out)


def test_router_bench_snapshot(monkeypatch):
    """PT_SERVE_ROUTER=1: the scale-out artifact must carry the router
    ledger (dispatches / affinity hit rate), the per-replica balance +
    prefix-hit-rate fields, and both topologies' throughput. Group ->
    replica placement is consistent-hash (randomized per process), so
    assertions are distribution-agnostic."""
    bm = _load_bench_models()
    monkeypatch.delenv("PT_SERVE_SPEC", raising=False)
    monkeypatch.delenv("PT_SERVE_CACHE", raising=False)
    monkeypatch.delenv("PT_SERVE_PREFIX", raising=False)
    monkeypatch.delenv("PT_SERVE_MULTITURN", raising=False)
    monkeypatch.delenv("PT_SERVE_CHAOS", raising=False)
    monkeypatch.delenv("PT_SERVE_DISAGG", raising=False)
    monkeypatch.setenv("PT_SERVE_ROUTER", "1")
    out = bm.bench_serving(on_tpu=False)
    assert out["workload"] == "router-shared-prefix"
    assert out["replicas"] == 2
    assert out["router_dispatches"] == out["requests"] > 0
    assert 0 < out["affinity_hit_rate"] <= 1
    assert out["failovers"] == 0 and out["spills"] == 0
    per = out["per_replica"]
    assert set(per) == {"r0", "r1"}
    assert sum(v["dispatches"] for v in per.values()) == \
        out["router_dispatches"]
    assert abs(sum(v["share"] for v in per.values()) - 1.0) < 1e-6
    assert 0 <= out["replica_balance"] <= 1
    # the shared-header workload engaged at least one replica's cache
    assert max(v["prefix_hit_rate"] for v in per.values()) > 0
    for v in per.values():
        lg = v["requests"]
        assert lg["completed"] == lg["submitted"] == v["dispatches"]
        assert lg["failed"] == 0
    assert out["aggregate_tokens_per_sec"] > 0
    assert out["single_engine_tokens_per_sec"] > 0
    assert out["single_engine_prefix_hit_rate"] >= 0


def test_ragged_bench_fewer_compiles_zero_padding(monkeypatch):
    """PT_SERVE_RAGGED=1 (ISSUE 11 acceptance): on the shared-prefix
    workload at token-identical outputs, the unified ragged step must
    show FEWER tracked compiles than the bucketed entry points, zero
    pad tokens (`pt_pad_tokens_total == 0` — unused buffer rows are
    skipped capacity, not padding), and measured MFU no worse than the
    bucketed side."""
    bm = _load_bench_models()
    for env in ("PT_SERVE_SPEC", "PT_SERVE_CACHE", "PT_SERVE_PREFIX",
                "PT_SERVE_ROUTER", "PT_SERVE_MULTITURN",
                "PT_SERVE_CHAOS"):
        monkeypatch.delenv(env, raising=False)
    monkeypatch.setenv("PT_SERVE_RAGGED", "1")
    out = bm.bench_serving(on_tpu=False)
    assert out["workload"] == "ragged-vs-bucketed (shared-prefix)"
    assert out["outputs_match"] is True, out
    assert out["compiles"] < out["bucketed_compiles"], out
    assert out["pad_tokens"] == 0 and out["pt_pad_tokens_total"] == 0, out
    assert out["bucketed_pad_tokens"] > 0, out
    assert out["ragged_tokens"] > 0, out
    # the mfu ORDERING (ragged >= bucketed) only holds on real
    # hardware where the Pallas kernel runs; the CPU smoke exercises
    # the lax.map reference path whose wall-clock is noise, so we only
    # pin that both sides measured something
    assert out["pt_mfu"] > 0 and out["bucketed_pt_mfu"] > 0, out
    assert out["decode_tokens_per_sec"] > 0
    assert out["bucketed_decode_tokens_per_sec"] > 0


def test_chaos_bench_recovers_token_identical(monkeypatch):
    """PT_SERVE_CHAOS=1 (ISSUE 9 acceptance): a seeded fault plan
    kills a device step mid-run under BOTH pumps (the synchronous one
    over a bucketed engine, the deep one over a ragged); warm restart must
    requeue the victims and finish them token-identical to the
    undisturbed baseline with zero failed requests, full goodput, and
    a balanced requeue ledger."""
    bm = _load_bench_models()
    for env in ("PT_SERVE_SPEC", "PT_SERVE_CACHE", "PT_SERVE_PREFIX",
                "PT_SERVE_ROUTER", "PT_SERVE_MULTITURN"):
        monkeypatch.delenv(env, raising=False)
    monkeypatch.delenv("PT_SERVE_DISAGG", raising=False)
    monkeypatch.setenv("PT_SERVE_CHAOS", "1")
    out = bm.bench_serving(on_tpu=False)
    assert out["workload"] == "chaos-recovery"
    assert out["outputs_match"] is True, out
    for pump in ("sync", "pipelined"):
        d = out[pump]
        assert d["outputs_match"] is True, (pump, d)
        assert d["failed_requests"] == 0, (pump, d)
        assert d["restarts"] >= 1 and d["requeued"] >= 1, (pump, d)
        assert d["quarantined"] == 0, (pump, d)
        assert d["goodput_retained"] == 1.0, (pump, d)
        assert d["ledger_balanced"] is True, (pump, d)
        assert d["tokens_per_sec"] > 0
    assert out["baseline_tokens_per_sec"] > 0


def test_slo_bench_accounts_every_request(monkeypatch):
    """PT_SERVE_SLO=1 (ISSUE 14): the goodput artifact must account
    every request exactly once (attained + violated == requests),
    reconcile goodput against total tokens, and ship per-phase latency
    percentiles off the stitched timelines."""
    bm = _load_bench_models()
    for env in ("PT_SERVE_SPEC", "PT_SERVE_CACHE", "PT_SERVE_PREFIX",
                "PT_SERVE_ROUTER", "PT_SERVE_MULTITURN",
                "PT_SERVE_CHAOS",
                "PT_SERVE_DISAGG", "PT_SERVE_RAGGED"):
        monkeypatch.delenv(env, raising=False)
    monkeypatch.setenv("PT_SERVE_SLO", "1")
    out = bm.bench_serving(on_tpu=False)
    assert out["workload"] == "slo-goodput"
    assert out["requests"] == out["interactive"] + out["batch"] > 0
    n_att = sum(out["slo_attained"].values())
    assert n_att + out["slo_violated"] == out["requests"], out
    assert sum(out["violations_by_phase"].values()) == \
        out["slo_violated"], out
    assert 0 < out["goodput_tokens"] <= out["total_tokens"] \
        or out["slo_violated"] == out["requests"], out
    assert out["goodput_ratio"] == (
        0.0 if not out["total_tokens"] else
        round(out["goodput_tokens"] / out["total_tokens"], 6))
    pl = out["phase_latency"]
    assert set(pl) == {"queued", "prefill", "decode", "preempted",
                       "handoff"}
    # every request spent measurable time queued and decoding
    assert pl["decode"]["count"] == out["requests"]
    assert pl["decode"]["p50_s"] <= pl["decode"]["p99_s"]
    assert out["tokens_per_sec"] > 0


def test_pulse_bench_bounds_overhead_and_lands_one_bundle(monkeypatch):
    """PT_SERVE_PULSE=1 (ISSUE 15): the pulse-plane smoke must show
    the forced stall as a step-time spike in the rings, fire the
    step_stall trigger, land EXACTLY ONE capture bundle (the
    min-interval rate limit, not a bundle storm), tag it with the
    in-flight trace ids, and keep the sampler's per-tick self-cost
    bounded (the artifact's own assert backs the number shipped)."""
    bm = _load_bench_models()
    for env in ("PT_SERVE_SPEC", "PT_SERVE_CACHE", "PT_SERVE_PREFIX",
                "PT_SERVE_ROUTER", "PT_SERVE_MULTITURN",
                "PT_SERVE_CHAOS",
                "PT_SERVE_DISAGG", "PT_SERVE_RAGGED",
                "PT_SERVE_SLO"):
        monkeypatch.delenv(env, raising=False)
    monkeypatch.setenv("PT_SERVE_PULSE", "1")
    out = bm.bench_serving(on_tpu=False)
    assert out["workload"] == "pulse-plane"
    assert out["signals"] > 20, out          # the rings actually fill
    assert out["step_p99_spike_x"] > 3, out  # the stall is visible
    assert out["stall_triggers"] >= 1, out
    assert out["bundles_written"] == 1, out
    assert out["bundle_trigger"] == "step_stall"
    assert out["bundle_trace_ids"] > 0, out
    assert out["tick_mean_ms"] < 25, out
    assert out["tokens_per_sec"] > 0


def test_disagg_bench_migrates_and_matches(monkeypatch):
    """PT_SERVE_DISAGG=1 (ISSUE 13 acceptance): the 1 prefill + 1
    decode topology must actually migrate every eligible request
    (exports > 0, router handoffs counted), produce token-identical
    outputs vs the 2x "both" baseline, degrade nothing
    (handoff_failures == 0, ledgers balanced including the "handoff"
    terminal state), and ship decode-TPOT percentiles for both
    topologies so the capture chain can gate the tail on chip."""
    bm = _load_bench_models()
    for env in ("PT_SERVE_SPEC", "PT_SERVE_CACHE", "PT_SERVE_PREFIX",
                "PT_SERVE_ROUTER", "PT_SERVE_MULTITURN",
                "PT_SERVE_CHAOS"):
        monkeypatch.delenv(env, raising=False)
    monkeypatch.setenv("PT_SERVE_DISAGG", "1")
    out = bm.bench_serving(on_tpu=False)
    assert out["workload"] == "disagg-mixed"
    assert out["outputs_match"] is True, out
    assert out["handoff_exports"] > 0, out
    assert out["handoff_imports"] == out["handoff_exports"], out
    assert out["handoff_bytes"] > 0, out
    assert out["handoff_failures"] == 0, out
    assert out["router_handoffs"] == out["handoff_exports"], out
    # prefill side closes its requests as "handoff", decode completes
    led = out["ledgers"]
    pre = next(v for k, v in led.items() if k.startswith("prefill:"))
    dec = next(v for k, v in led.items() if k.startswith("decode:"))
    assert pre["handoff"] == out["handoff_exports"], led
    assert pre["failed"] == 0 and dec["failed"] == 0, led
    assert dec["completed"] == dec["submitted"], led
    # decode-TPOT ships for both sides (the on-chip gate's input)
    assert out["decode_tpot"]["count"] > 0
    assert out["baseline_decode_tpot"]["count"] > 0
    assert out["decode_tpot"]["p99_s"] > 0
    assert set(out["per_role_mfu"]) == {"prefill", "decode"}
    assert out["disagg_tokens_per_sec"] > 0
    assert out["baseline_tokens_per_sec"] > 0


@pytest.mark.slow
def test_fleet_bench_crosses_the_socket_and_matches(monkeypatch):
    """PT_SERVE_FLEET=1 (ISSUE 16 acceptance): the 1 prefill + 1
    decode SUBPROCESS topology must produce token-identical outputs vs
    the in-process router, count real handoff payload bytes on the
    bulk socket (not estimates), balance every worker's ledger across
    the wire, and shut the workers down with exit code 0. Slow-marked:
    the in-tier-1 subprocess drill lives in tests/test_fleet.py; this
    guards the driver-visible artifact shape."""
    bm = _load_bench_models()
    for env in ("PT_SERVE_SPEC", "PT_SERVE_CACHE", "PT_SERVE_PREFIX",
                "PT_SERVE_ROUTER", "PT_SERVE_MULTITURN",
                "PT_SERVE_CHAOS",
                "PT_SERVE_DISAGG"):
        monkeypatch.delenv(env, raising=False)
    monkeypatch.setenv("PT_SERVE_FLEET", "1")
    out = bm.bench_serving(on_tpu=False)
    assert out["workload"] == "fleet-mixed"
    assert out["outputs_match"] is True, out
    assert out["handoff_serves"] >= out["requests"], out
    assert out["handoff_wire_bytes"] > 0, out
    assert out["handoff_wire_bytes_per_sec"] > 0, out
    assert out["router_handoffs"] > 0, out
    assert out["clean_shutdown"] is True, out
    assert out["worker_exit_codes"] == [0, 0], out
    led = out["ledgers"]
    pre = next(v for k, v in led.items() if k.startswith("prefill:"))
    dec = next(v for k, v in led.items() if k.startswith("decode:"))
    assert pre["failed"] == 0 and dec["failed"] == 0, led
    assert pre["handoff"] > 0, led
    assert out["fleet_tokens_per_sec"] > 0
    assert out["baseline_tokens_per_sec"] > 0
