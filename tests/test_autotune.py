"""End-to-end tests for tools/autotune.py in smoke mode.

The tuner runs unattended on a chip; every guard in run_trial() — JSON
parsing, CPU-result rejection, crash, garbage output, timeout — must be
proven here so a parsing bug can't silently waste a chip run.

Parity: the reference auto_tuner is a searched-config harness with its
own recorder/pruner tests (/root/reference/python/paddle/distributed/
auto_tuner/tuner.py); this is our equivalent confidence layer.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TUNER = os.path.join(ROOT, "tools", "autotune.py")
SMOKE_CHILD = os.path.join(ROOT, "tools", "_tune_smoke_child.py")


def run_tuner(tmp_path, fault=None, fault_block_q=None, timeout_s="30",
              dead_trip=None, stages=None):
    out = str(tmp_path / "TUNED.json")
    env = dict(os.environ, PT_TUNE_SMOKE="1", PT_TUNE_OUT=out,
               PT_TUNE_TRIAL_TIMEOUT=timeout_s)
    env.pop("PT_TUNE_DEAD_TRIP", None)
    if dead_trip is not None:
        env["PT_TUNE_DEAD_TRIP"] = str(dead_trip)
    env.pop("PT_SMOKE_FAULT", None)
    env.pop("PT_SMOKE_FAULT_BLOCK_Q", None)
    env.pop("PT_TUNE_CHILD", None)
    env.pop("PT_TUNE_STAGES", None)
    if stages is not None:
        env["PT_TUNE_STAGES"] = stages
    if fault:
        env["PT_SMOKE_FAULT"] = fault
    if fault_block_q is not None:
        env["PT_SMOKE_FAULT_BLOCK_Q"] = str(fault_block_q)
    r = subprocess.run([sys.executable, TUNER], env=env,
                       capture_output=True, text=True, timeout=300)
    data = None
    if os.path.exists(out):
        with open(out) as f:
            data = json.load(f)
    return r, data


def test_full_search_finds_planted_peak(tmp_path):
    r, data = run_tuner(tmp_path)
    assert r.returncode == 0, r.stderr
    assert data["stages_done"] == ["A", "B", "C"]
    assert data["smoke"] is True
    best = data["best"]
    # the smoke child's landscape peaks exactly here
    assert (best["batch"], best["remat"]) == (64, "true")
    assert best["fused_ce"] is True
    assert (best["block_q"], best["block_k"]) == (256, 512)
    assert best["n_micro"] == 2
    assert best["tok_s"] == 15350.0


def test_dedup_skips_equivalent_configs(tmp_path):
    r, data = run_tuner(tmp_path)
    assert r.returncode == 0
    # stage A: every STAGE_A entry measured once; stage B: 5 configs
    # but (128,128) == the stage-A winner's effective knobs ->
    # 4 measured; stage C: n_micro=2 dedups against the stage-A peak
    # (which carries n_micro=2 itself) -> 1 measured (n_micro=4).
    n_stage_a = len(_load_tuner().STAGE_A)
    assert data["n_trials"] == n_stage_a + 4 + 1
    cfgs = [json.dumps(t["cfg"], sort_keys=True) for t in data["trials"]]
    assert len(set(cfgs)) == len(cfgs), "a config was measured twice"


def test_consecutive_cpu_results_abort_search(tmp_path):
    # every child answers backend:"cpu" -> the consecutive-failure stop
    # must abort the search after DEAD_TRIP (3) trials instead of
    # walking the whole STAGE_A list, with a non-zero exit and no
    # winner written
    r, data = run_tuner(tmp_path, fault="cpu")
    assert r.returncode != 0
    assert "aborting search" in r.stderr and "consecutive" in r.stderr
    assert data is None
    assert r.stdout.count("INVALID: child ran on the CPU") == 3


def test_breaker_mid_search_keeps_best_so_far(tmp_path):
    # cpu-fault only block_q=512 trials with DEAD_TRIP=2: stage B's two
    # consecutive 512 trials trip the breaker AFTER stage A found a
    # winner — the tuner must exit 0 with the best-so-far persisted,
    # not lose the search
    r, data = run_tuner(tmp_path, fault="cpu", fault_block_q=512,
                        dead_trip=2)
    assert r.returncode == 0, r.stderr
    assert "aborting search" in r.stderr
    assert data is not None and "best" in data
    assert data["best"]["batch"] == 64  # stage-A peak survived
    assert "C" not in data["stages_done"]


def test_crashing_child_is_survived(tmp_path):
    r, data = run_tuner(tmp_path, fault="crash")
    assert r.returncode != 0  # nothing succeeded, abort is correct
    assert "FAILED rc=7" in r.stdout
    assert "Traceback" not in r.stderr  # tuner itself must not crash


def test_garbage_output_is_survived(tmp_path):
    r, data = run_tuner(tmp_path, fault="garbage")
    assert r.returncode != 0
    assert "FAILED rc=0" in r.stdout  # exit 0 but no JSON -> trial fails
    assert "Traceback" not in r.stderr


def test_hanging_child_times_out(tmp_path):
    # only block_q=512 hangs; the trial timeout reaps it and the search
    # completes on the remaining configs. 15s, not 5: a loaded machine
    # can push an honest child's python startup past 5s and the reaped
    # honest trial flips the search result (observed flake 2026-08-01
    # with two suites running)
    r, data = run_tuner(tmp_path, fault="hang", fault_block_q=512,
                        timeout_s="15")
    assert r.returncode == 0, r.stderr
    assert "TIMED OUT" in r.stdout
    assert data["stages_done"] == ["A", "B", "C"]
    assert (data["best"]["block_q"], data["best"]["block_k"]) == (256, 512)


def test_smoke_never_touches_real_tuned_json(tmp_path):
    """Without PT_TUNE_OUT, smoke mode must write TUNED.smoke.json,
    not the TUNED.json bench.py reads as its on-chip defaults."""
    real = os.path.join(ROOT, "TUNED.json")
    before = os.path.getmtime(real) if os.path.exists(real) else None
    env = dict(os.environ, PT_TUNE_SMOKE="1", PT_TUNE_TRIAL_TIMEOUT="30")
    env.pop("PT_TUNE_OUT", None)
    env.pop("PT_SMOKE_FAULT", None)
    r = subprocess.run([sys.executable, TUNER], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    smoke = os.path.join(ROOT, "TUNED.smoke.json")
    assert os.path.exists(smoke)
    with open(smoke) as f:
        assert json.load(f)["smoke"] is True
    after = os.path.getmtime(real) if os.path.exists(real) else None
    assert before == after


# ---------------------------------------------------------------------------
# stage D: parallel placement search (VERDICT r4 item 6; reference
# parity: auto_tuner/{search,prune,cost_model}.py)
# ---------------------------------------------------------------------------
def _load_tuner():
    import importlib.util
    spec = importlib.util.spec_from_file_location("autotune", TUNER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestParallelEnumeration:
    def test_all_candidates_valid(self):
        at = _load_tuner()
        cands = at.enumerate_parallel_configs(8, n_layers=8, batch=8,
                                              n_heads=8)
        assert cands, "no candidates enumerated"
        seen = set()
        for c in cands:
            key = json.dumps(c, sort_keys=True)
            assert key not in seen, f"duplicate candidate {c}"
            seen.add(key)
            assert c["dp"] * c["tp"] * c["pp"] == 8
            assert 8 % c["pp"] == 0 and 8 % c["dp"] == 0
            assert c["tp"] <= 8
            if c.get("zero"):
                assert c["tp"] == 1 and c["pp"] == 1
            if c["pp"] > 1:
                assert c["n_micro"] in (2, 4)
                assert c["schedule"] in ("1f1b", "interleave")
                if c["schedule"] == "interleave":
                    assert 8 % (c["pp"] * 2) == 0
        # the classic placements must be present
        flat = [(c["dp"], c["tp"], c["pp"]) for c in cands]
        for want in [(8, 1, 1), (4, 2, 1), (2, 2, 2), (1, 1, 8)]:
            assert want in flat, want

    def test_pruning_respects_divisibility(self):
        at = _load_tuner()
        # 6 layers: pp=4/8 impossible; interleave needs layers % 2pp
        cands = at.enumerate_parallel_configs(8, n_layers=6, batch=8,
                                              n_heads=8)
        assert all(c["pp"] in (1, 2) for c in cands)
        # heads=2 caps tp
        cands = at.enumerate_parallel_configs(8, n_layers=8, batch=8,
                                              n_heads=2)
        assert all(c["tp"] <= 2 for c in cands)


class TestCommCostModel:
    def test_orderings(self):
        at = _load_tuner()
        cost = at.parallel_comm_cost
        # more tp -> more activation all-reduce traffic
        assert cost({"dp": 1, "tp": 8, "pp": 1}) > \
            cost({"dp": 4, "tp": 2, "pp": 1})
        # zero-3 pays param all-gathers on top of dp grads
        assert cost({"dp": 8, "tp": 1, "pp": 1, "zero": True}) > \
            cost({"dp": 8, "tp": 1, "pp": 1})
        # interleave shrinks the pp bubble term at same n_micro
        c1 = cost({"dp": 2, "tp": 1, "pp": 4, "n_micro": 4,
                   "schedule": "1f1b"})
        ci = cost({"dp": 2, "tp": 1, "pp": 4, "n_micro": 4,
                   "schedule": "interleave", "vpp": 2})
        assert ci < c1
        # pure dp=1 single placement has zero comm
        assert cost({"dp": 1, "tp": 1, "pp": 1}) == 0.0


class TestParallelSearch:
    def test_search_with_injected_runner(self, tmp_path, monkeypatch):
        at = _load_tuner()
        out = str(tmp_path / "TUNED.json")
        # pre-seed a single-chip best: the merge must keep it
        with open(out, "w") as f:
            json.dump({"best": {"batch": 24}, "stages_done": ["A"]}, f)
        monkeypatch.setattr(at, "TUNED", out)

        def fake_runner(cfg):
            if cfg.get("zero"):
                return None  # injected failure
            # make (4,2,1) the measured winner
            return 0.1 if (cfg["dp"], cfg["tp"], cfg["pp"]) == (4, 2, 1) \
                else 0.5
        block = at.run_parallel_search(runner=fake_runner)
        assert block is not None
        with open(out) as f:
            data = json.load(f)
        assert data["best"] == {"batch": 24}, "stage A-C result clobbered"
        par = data["parallel"]
        assert (par["best"]["dp"], par["best"]["tp"],
                par["best"]["pp"]) == (4, 2, 1)
        assert any(c.get("zero") for c in par["failed"])
        ranking = par["ranking"]
        assert ranking == sorted(ranking, key=lambda r: r["score"])
        # domination marking: the winner is never dominated
        assert ranking[0]["dominated"] is False

    @pytest.mark.slow
    def test_search_real_child_tiny(self, tmp_path):
        """Two REAL child trials on the 8-device CPU mesh — proves the
        subprocess plumbing end-to-end before any unattended run."""
        out = str(tmp_path / "TUNED.json")
        env = dict(os.environ, PT_TUNE_OUT=out, PT_TUNE_PAR_SIZE="tiny",
                   PT_TUNE_PAR_MAX="2", PT_TUNE_TRIAL_TIMEOUT="300")
        env.pop("JAX_PLATFORMS", None)
        r = subprocess.run([sys.executable, TUNER, "--parallel"], env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr + r.stdout
        with open(out) as f:
            par = json.load(f)["parallel"]
        assert par["best"]["dp"] * par["best"]["tp"] * par["best"]["pp"] == 8
        assert all(row["step_time_s"] > 0 for row in par["ranking"])


def test_staged_split_a_then_bc(tmp_path):
    """The capture chain runs PT_TUNE_STAGES=A early and =BC later: the
    BC pass must refine the recorded stage-A winner (not restart A) and
    keep 'A' on the stages_done record."""
    r, data = run_tuner(tmp_path, stages="A")
    assert r.returncode == 0, r.stderr
    assert data["stages_done"] == ["A"]
    assert (data["best"]["batch"], data["best"]["remat"]) == (64, "true")
    assert "block_q" not in data["best"]

    # the refine guard refuses smoke results as defaults; flip the flag
    # to simulate the prior pass having been a real on-chip search
    out = tmp_path / "TUNED.json"
    d = json.loads(out.read_text())
    d["smoke"] = False
    out.write_text(json.dumps(d))

    r, data = run_tuner(tmp_path, stages="BC")
    assert r.returncode == 0, r.stderr
    assert data["stages_done"] == ["A", "B", "C"]
    best = data["best"]
    assert (best["batch"], best["remat"]) == (64, "true")
    assert (best["block_q"], best["block_k"]) == (256, 512)
    assert best["n_micro"] == 2
    assert best["tok_s"] == 15350.0
    # stage A's full trial record is carried over (marked prior, so the
    # OOM/fail evidence survives the staged split) and was NOT re-run:
    # only the winner was re-measured, + 4 stage-B + 1 stage-C trials
    # (n_micro=2 dedups against the carried stage-A peak)
    n_stage_a = len(_load_tuner().STAGE_A)
    prior = [t for t in data["trials"] if t.get("prior")]
    live = [t for t in data["trials"] if not t.get("prior")]
    assert len(prior) == n_stage_a and len(live) == 6
    assert data["n_trials"] == n_stage_a + 6


def test_staged_bc_without_prior_a_refuses(tmp_path):
    r, data = run_tuner(tmp_path, stages="BC")
    assert r.returncode == 1
    assert "needs a prior" in r.stderr
