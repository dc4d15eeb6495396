"""Perf-regression guard (SURVEY §4 'perf guard').

bench.py appends every run to BENCH_HISTORY.jsonl (a run-time record,
not committed); this test compares the two most recent entries with the
same backend + config and fails on a >25% throughput drop. Skips until two comparable datapoints exist
(e.g. first round on a machine, or CPU-only CI where only smoke entries
accumulate — CPU smoke numbers on shared machines are too noisy, so
only TPU entries are guarded).
"""
import importlib.util
import json
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "_tuning_defaults",
    os.path.join(_ROOT, "paddle_tpu", "_tuning_defaults.py"))
_TD = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_TD)

HIST = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_HISTORY.jsonl")


def _entries():
    if not os.path.exists(HIST):
        return []
    out = []
    with open(HIST) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
    return out


def test_no_tpu_throughput_regression():
    tpu = [e for e in _entries()
           if e.get("extra", {}).get("backend") not in (None, "cpu")
           # entries annotated invalid after the fact must not serve
           # as the regression baseline
           and not e.get("extra", {}).get("invalid")]
    # group by (model, batch, seq, remat) so config changes don't
    # false-alarm and bench_models.py entries (keyed by "model") never
    # cross-compare with each other or the llama headline. Pre-format
    # entries lacking the remat key ran the default remat=True, and the
    # metric string is a label (it once hard-coded the config), so
    # neither joins the grouping key in a way that would orphan history.
    # block_q/block_k/n_micro joined the key in r3, fused_ce in r4
    # (autotune sweeps write same-batch entries differing only in
    # those knobs).
    # effective_knobs (shared with autotune + the kernel defaults)
    # normalizes absent/None to the kernel defaults so pre-r3 entries
    # still compare against new same-config runs.
    by_cfg = {}
    for e in tpu:
        x = e.get("extra", {})
        by_cfg.setdefault((e.get("model", "llama"), e.get("batch"),
                           e.get("seq"), e.get("remat", "True"),
                           e.get("docs"), bool(e.get("fused_ce")))
                          + _TD.effective_knobs(e)
                          # serving entries: workload regime joins the
                          # key (r5 raised spec new_tokens 2048→4096
                          # total; cross-regime steps/s must not
                          # regression-compare)
                          + (x.get("cache_dtype"), x.get("spec_decode"),
                             x.get("new_tokens"), x.get("requests")),
                          []).append(e)
    comparable = [v for v in by_cfg.values() if len(v) >= 2]
    if not comparable:
        pytest.skip("need two same-config TPU bench entries to compare")
    for runs in comparable:
        prev, cur = runs[-2], runs[-1]
        assert cur["value"] > 0.75 * prev["value"], (
            f"TPU throughput regressed >25%: {prev['value']} -> "
            f"{cur['value']} tokens/s for {prev['metric']}")
