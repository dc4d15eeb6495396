"""TPU-native auto-tuner (parity: reference auto_tuner subsystem,
/root/reference/python/paddle/distributed/auto_tuner/tuner.py — a
parallel-config/batch search harness; ours searches the knobs that
matter on one TPU chip and persists the winner).

Staged search over (batch, remat policy, fused linear+CE head, flash
block_q/block_k, n_micro) for the headline Llama pretrain step:

  stage A: batch x remat x fused_ce coarse grid
  stage B: flash block sizes at the stage-A winner
  stage C: grad-accum microbatching at the stage-B winner

Every trial is a `bench.py` child process (so a Mosaic rejection or OOM
kills the trial, not the tuner) and appends to BENCH_HISTORY.jsonl via
bench.py's own history hook.  The tuner itself never imports jax: a
chip belongs to one process, and each trial child takes it in turn.
The winner is written to TUNED.json after every stage (partial progress
survives an interrupted search), and bench.py reads TUNED.json as its
defaults.

Run on a machine with a chip:  python tools/autotune.py

Smoke mode (no hardware): PT_TUNE_SMOKE=1 skips the TPU probe and
runs the full stage-A/B/C search against a stub child
(tools/_tune_smoke_child.py by default) that answers with deterministic
fake numbers — so the tuner's parsing, guards, dedup, and persistence
are all proven before its first unattended run on a chip.
Smoke results are written to TUNED.smoke.json (or $PT_TUNE_OUT), never
to the TUNED.json that bench.py reads as defaults.

Env knobs:
  PT_TUNE_SMOKE=1   — smoke mode (see above)
  PT_TUNE_CHILD     — path to the per-trial child script
  PT_TUNE_OUT       — output path override for the winner JSON
  PT_TUNE_TRIAL_TIMEOUT — per-trial wall clock (seconds)
  PT_TUNE_STAGES    — subset of "ABC" to run (default all): a
                      stage-A-only pass sweeps the big levers (batch x
                      remat x fused_ce) first; a later BC pass refines
                      its recorded winner
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SMOKE = os.environ.get("PT_TUNE_SMOKE") == "1"
# Smoke output must NEVER land on the TUNED.json bench.py reads as
# defaults — fake numbers as real defaults would poison the next
# on-chip bench.
TUNED = os.environ.get("PT_TUNE_OUT") or os.path.join(
    ROOT, "TUNED.smoke.json" if SMOKE else "TUNED.json")
_DEFAULT_CHILD = os.path.join(HERE, "_tune_smoke_child.py") if SMOKE \
    else os.path.join(ROOT, "bench.py")
CHILD = os.environ.get("PT_TUNE_CHILD") or _DEFAULT_CHILD

TRIAL_TIMEOUT = int(os.environ.get("PT_TUNE_TRIAL_TIMEOUT", "600"))

# consecutive-failure stop: N trials in a row that timed out or ran on
# the CPU abort the search instead of burning TRIAL_TIMEOUT on every
# remaining trial. Best-so-far is already persisted on every improvement.
DEAD_TRIP = int(os.environ.get("PT_TUNE_DEAD_TRIP", "3"))
_consec_dead = 0


class SearchStalled(RuntimeError):
    pass


def _mark_trial(kind):
    """kind: 'ok' | 'dead' (timeout / ran on the CPU) | 'bad' (config)."""
    global _consec_dead
    _consec_dead = _consec_dead + 1 if kind == "dead" else 0
    if _consec_dead >= DEAD_TRIP:
        raise SearchStalled(
            f"{_consec_dead} consecutive timed-out/CPU trials")


def _load_defaults():
    import importlib.util
    p = os.path.join(ROOT, "paddle_tpu", "_tuning_defaults.py")
    spec = importlib.util.spec_from_file_location("_tuning_defaults", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_TD = _load_defaults()

# Stage A: batch x remat x fused_ce, ordered by expected win so an
# interrupted search has measured the promising region first. 2026-08-01
# on-chip evidence (first honest pass): full-remat MFU CLIMBS with
# batch — 16→0.33, 24→0.43, 32→0.60 strict — while dots at batch 8
# disappointed (0.22). So the big-batch full-remat ladder leads, pushed
# to the OOM wall (48/64), with dots as the secondary branch. fused_ce
# avoids the (B,S,V) logits materialization (speeds the head AND frees
# HBM); fused-off rungs ride along at every leading batch so the lever
# is quantified at whatever batch wins. The n_micro=2 corners exist
# because grad accumulation halves peak activation memory and may fit
# configs that OOM above — stage C only refines the winner, so those
# corners are never reached unless tried here. Module-level so the
# smoke tests derive trial counts instead of hardcoding them.
STAGE_A = [
    {"batch": 32, "remat": "true", "fused_ce": True},  # evidence leader
    {"batch": 48, "remat": "true", "fused_ce": True},
    {"batch": 64, "remat": "true", "fused_ce": True},
    {"batch": 32, "remat": "true", "fused_ce": False},
    {"batch": 48, "remat": "true", "fused_ce": False},
    {"batch": 64, "remat": "true", "fused_ce": False},
    {"batch": 24, "remat": "true", "fused_ce": True},
    {"batch": 40, "remat": "true", "fused_ce": True},
    {"batch": 16, "remat": "true", "fused_ce": True},
    {"batch": 32, "remat": "dots", "fused_ce": True},
    {"batch": 48, "remat": "dots", "fused_ce": True},
    {"batch": 16, "remat": "dots", "fused_ce": True},
    {"batch": 8, "remat": "dots", "fused_ce": True},
    {"batch": 16, "remat": "true", "fused_ce": False},
    {"batch": 64, "remat": "true", "fused_ce": True, "n_micro": 2},
    {"batch": 48, "remat": "dots", "fused_ce": True, "n_micro": 2},
    {"batch": 8, "remat": "false", "fused_ce": True},
]


def _resolved(cfg):
    """Dedup key over EFFECTIVE knobs: {batch,seq,remat} and the same
    cfg with explicit default block/n_micro values build identical
    child environments and must not be measured twice."""
    return (cfg["batch"], cfg["seq"], str(cfg["remat"]).lower(),
            bool(cfg.get("fused_ce"))) + _TD.effective_knobs(cfg)


def run_trial(cfg, trials):
    """One bench.py child at `cfg`; returns the parsed JSON line or None."""
    for t in trials:
        if t.get("prior"):
            # record carried over from an earlier staged pass for the
            # persisted trials log — not a full result (no extra),
            # never serve it as a measurement
            continue
        if _resolved(t["cfg"]) == _resolved(cfg):
            return t["result"]  # already measured this round
    # pin EVERY knob explicitly: an unset env var would fall back to a
    # stale TUNED.json inside the bench child, mislabeling the trial
    env = dict(os.environ,
               PT_BENCH_BATCH=str(cfg["batch"]),
               PT_BENCH_SEQ=str(cfg["seq"]),
               PT_BENCH_REMAT=str(cfg["remat"]).lower(),
               PT_FLASH_BLOCK_Q=str(cfg.get("block_q")
                                    or _TD.DEFAULT_FLASH_BLOCK_Q),
               PT_FLASH_BLOCK_K=str(cfg.get("block_k")
                                    or _TD.DEFAULT_FLASH_BLOCK_K),
               PT_BENCH_NMICRO=str(cfg.get("n_micro", 0)),
               PT_FUSED_CE="1" if cfg.get("fused_ce") else "0")
    t0 = time.perf_counter()
    try:
        r = subprocess.run([sys.executable, CHILD],
                           env=env, capture_output=True, text=True,
                           timeout=TRIAL_TIMEOUT)
    except subprocess.TimeoutExpired:
        print(f"  trial {cfg} TIMED OUT after {TRIAL_TIMEOUT}s", flush=True)
        trials.append({"cfg": cfg, "result": None, "error": "timeout"})
        _mark_trial("dead")
        return None
    out = None
    for line in reversed(r.stdout.strip().splitlines()):
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):  # bare numbers/strings are valid JSON
            out = parsed
            break
    if r.returncode != 0 or out is None:
        tail = "\n".join(r.stderr.strip().splitlines()[-4:])
        print(f"  trial {cfg} FAILED rc={r.returncode}: {tail}", flush=True)
        trials.append({"cfg": cfg, "result": None,
                       "error": f"rc={r.returncode}"})
        _mark_trial("bad")
        return None
    if out.get("extra", {}).get("backend") == "cpu":
        # the child ran bench.py's CPU smoke (PT_BENCH_CPU=1 in the
        # environment) — a number that must never reach TUNED.json
        print(f"  trial {cfg} INVALID: child ran on the CPU", flush=True)
        trials.append({"cfg": cfg, "result": None, "error": "cpu_backend"})
        _mark_trial("dead")
        return None
    dt = time.perf_counter() - t0
    print(f"  trial {cfg}: {out['value']} tok/s "
          f"(mfu={out['extra']['mfu']}, {dt:.0f}s wall)", flush=True)
    trials.append({"cfg": cfg, "result": out})
    _mark_trial("ok")
    return out


def score(res):
    return res["value"] if res else -1.0


def _tuned_defaults_for_refine():
    """(cfg, stages_done, prior_trials) recorded by a prior non-smoke
    search in this output file — lets PT_TUNE_STAGES=BC refine an
    earlier stage-A pass without re-running it. Requires stage A to
    have actually COMPLETED: a best persisted mid-stage-A (timeout kill
    between consider() and done.append) must not let the refine pass
    mark the search finished with most of the grid unsearched."""
    try:
        with open(TUNED) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None, [], []
    if data.get("smoke") or "best" not in data \
            or "A" not in data.get("stages_done", []):
        return None, [], []
    # PT_TUNE_MIN_TS rejects a stale winner from an earlier session:
    # if this session's stage-A pass recorded nothing, refining an old
    # best would stamp the search complete without the grid being swept
    min_ts = float(os.environ.get("PT_TUNE_MIN_TS", "0") or 0)
    if data.get("ts", 0) < min_ts:
        print(f"autotune: recorded best is older than PT_TUNE_MIN_TS "
              f"({data.get('ts')} < {min_ts}); not refining it",
              file=sys.stderr)
        return None, [], []
    cfg = {k: v for k, v in data["best"].items()
           if k not in ("tok_s", "mfu", "mfu_legacy")}
    prior = [{"cfg": t["cfg"], "prior": True,
              "result": ({"value": t["tok_s"]} if t.get("tok_s") is not None
                         else None),
              "error": t.get("error")}
             for t in data.get("trials", [])]
    return cfg, list(data.get("stages_done", [])), prior


def _merge_tuned(updates):
    """Atomically merge top-level keys into TUNED.json, preserving
    whatever other stages wrote there."""
    data = {}
    try:
        with open(TUNED) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        pass
    data.update(updates)
    tmp = TUNED + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1)
    os.replace(tmp, TUNED)
    return data


def persist(best_cfg, best_res, trials, done):
    data = _merge_tuned(dict(
        best=dict(best_cfg, tok_s=best_res["value"],
                  mfu=best_res["extra"]["mfu"],
                  mfu_legacy=best_res["extra"].get("mfu_legacy")),
        stages_done=done, n_trials=len(trials), smoke=SMOKE,
        # refresh provenance: _merge_tuned preserves unknown keys, so
        # a hand-seeded "source" note from an earlier search would
        # otherwise survive and describe the WRONG measurement
        source=(f"autotune search on this host (stages "
                f"{','.join(done) or 'in-progress'}, "
                f"{len(trials)} trials); best re-measured fresh, "
                "not hand-seeded"),
        trials=[dict({"cfg": t["cfg"],
                      "tok_s": t["result"]["value"] if t["result"] else None,
                      "error": t.get("error")},
                     **({"prior": True} if t.get("prior") else {}))
                for t in trials],
        ts=time.time()))
    print(f"{os.path.basename(TUNED)} <- {data['best']}", flush=True)


# ---------------------------------------------------------------------------
# stage D: parallel-config search on the virtual CPU mesh (reference
# parity: the auto_tuner's dp/tp/pp/sharding search with cost-model
# pruning, /root/reference/python/paddle/distributed/auto_tuner/
# {search,prune,cost_model}.py). Needs NO hardware: each candidate is
# timed on the 8-device CPU mesh (captures partition imbalance and
# schedule bubbles) and scored with an analytic ICI comm model
# (captures what CPU timing cannot — the collectives' on-chip cost).
# ---------------------------------------------------------------------------
# stage-D child model dims per PT_TUNE_PAR_SIZE — enumeration, the comm
# cost model, and the compute estimate must all use the dims the child
# actually runs, or the ranking scores a model that was never measured
PAR_MODELS = {
    "small": {"hidden": 256, "layers": 8, "ffn": 704, "vocab": 1024,
              "batch": 8, "seq": 128, "heads": 8},
    "tiny": {"hidden": 64, "layers": 8, "ffn": 128, "vocab": 128,
             "batch": 8, "seq": 32, "heads": 4},
}
PAR_MODEL = PAR_MODELS["small"]
V5E_ICI_BPS = 1.6e11   # ~per-chip ICI bandwidth, bytes/s (order-of-mag)
V5E_FLOPS = 197e12 * 0.4  # assume 40% MFU for the compute-time estimate


def model_flops(model):
    """fwd+bwd matmul FLOPs per step of the stage-D child model (6N
    convention, lm_head kept) — single source for the bubble term and
    the score's compute estimate."""
    H, L, F_, V = (model["hidden"], model["layers"], model["ffn"],
                   model["vocab"])
    return 6 * (L * (4 * H * H + 3 * H * F_) + V * H) \
        * model["batch"] * model["seq"]


def enumerate_parallel_configs(n_devices, n_layers, batch, n_heads):
    """Candidate placements with reference-style pruning
    (auto_tuner/prune.py parity): device/layer/batch/head divisibility,
    tp capped at head count; pp adds n_micro x {1f1b, interleave}
    (interleave only when layers admit 2 chunks per stage); ZeRO-3 only
    for the pure-dp placement."""
    out = []
    for pp in (1, 2, 4, 8):
        for tp in (1, 2, 4, 8):
            if pp * tp > n_devices or n_devices % (pp * tp):
                continue
            dp = n_devices // (pp * tp)
            if n_layers % pp or batch % dp or n_heads % tp:
                continue
            base = {"dp": dp, "tp": tp, "pp": pp, "fused_ce": True}
            if pp == 1:
                out.append(dict(base))
                if tp == 1 and dp > 1:
                    out.append(dict(base, zero=True))
                continue
            for nm in (2, 4):
                if batch % nm:
                    continue
                out.append(dict(base, n_micro=nm, schedule="1f1b"))
                if n_layers % (pp * 2) == 0:
                    out.append(dict(base, n_micro=nm,
                                    schedule="interleave", vpp=2))
    return out


def parallel_comm_cost(cfg, model=PAR_MODEL):
    """Analytic per-step ICI seconds for a placement (bf16 wire bytes).

    tp: 4 activation all-reduces per layer (2 fwd + 2 bwd, megatron);
    dp: one grad all-reduce (2x param bytes ring cost);
    zero: + param all-gather fwd+bwd and reduce-scatter grads;
    pp: p2p activations per microbatch boundary, plus the schedule
    bubble inflating COMPUTE time (modeled on the compute estimate).
    A ranking heuristic to combine with measured CPU step time — not a
    simulator; not yet calibrated against a chip.
    """
    H, L, F_, V = (model["hidden"], model["layers"], model["ffn"],
                   model["vocab"])
    B, S = model["batch"], model["seq"]
    dp, tp, pp = cfg.get("dp", 1), cfg.get("tp", 1), cfg.get("pp", 1)
    act = B * S * H * 2 / dp          # bf16 activation bytes per shard
    params = (L * (4 * H * H + 3 * H * F_) + 2 * V * H) * 2
    comm = 0.0
    if tp > 1:
        comm += 4 * L * act * (tp - 1) / tp / V5E_ICI_BPS
    if cfg.get("zero"):
        # ZeRO-3 REPLACES the grad all-reduce: param all-gather fwd +
        # bwd and grad reduce-scatter, ~3x param wire bytes total —
        # over the dp shard of THIS rank's tp/pp param slice, same
        # sharding the dp branch below charges
        comm += 3 * (params / (tp * pp)) * (dp - 1) / dp / V5E_ICI_BPS
    elif dp > 1:
        comm += 2 * (params / (tp * pp)) * (dp - 1) / dp / V5E_ICI_BPS
    if pp > 1:
        nm = cfg.get("n_micro", pp)
        comm += 2 * act * (pp - 1) / V5E_ICI_BPS  # p2p fwd+bwd
        compute = model_flops(model) / V5E_FLOPS
        fill = (pp - 1) / cfg.get("vpp", 1) if \
            cfg.get("schedule") == "interleave" else (pp - 1)
        comm += compute * fill / (nm + fill)      # bubble as lost time
    return comm


def run_parallel_trial(cfg, ndev=8, size="small", timeout=None):
    """One _tune_parallel_child.py run; returns step_time_s or None."""
    env = dict(os.environ, PT_TUNE_PAR_CFG=json.dumps(cfg),
               PT_TUNE_PAR_NDEV=str(ndev), PT_TUNE_PAR_SIZE=size)
    env.pop("JAX_PLATFORMS", None)  # child pins cpu via jax.config
    try:
        r = subprocess.run(
            [sys.executable, os.path.join(HERE, "_tune_parallel_child.py")],
            env=env, capture_output=True, text=True,
            timeout=timeout or TRIAL_TIMEOUT)
    except subprocess.TimeoutExpired:
        print(f"  parallel trial {cfg} TIMED OUT", flush=True)
        return None
    out = None
    for line in reversed(r.stdout.strip().splitlines()):
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):
            out = parsed
            break
    if r.returncode != 0 or out is None:
        tail = "\n".join(r.stderr.strip().splitlines()[-3:])
        print(f"  parallel trial {cfg} FAILED rc={r.returncode}: {tail}",
              flush=True)
        return None
    return float(out["step_time_s"])


def run_parallel_search(ndev=8, size="small", runner=None, max_trials=None):
    """Measure every candidate, score = cpu_step_time x (1 + modeled
    ICI comm / modeled compute), prune dominated configs, and merge the
    ranking into TUNED.json under "parallel"."""
    model = PAR_MODELS[size]
    cands = enumerate_parallel_configs(ndev, model["layers"],
                                       model["batch"], model["heads"])
    if max_trials:
        cands = cands[:max_trials]
    runner = runner or (lambda cfg: run_parallel_trial(cfg, ndev, size))
    compute_s = model_flops(model) / V5E_FLOPS
    rows = []
    print(f"stage D: parallel placement search ({len(cands)} candidates, "
          f"{ndev} virtual devices)", flush=True)
    for cfg in cands:
        t = runner(cfg)
        if t is None:
            rows.append({"cfg": cfg, "step_time_s": None, "score": None})
            continue
        comm = parallel_comm_cost(cfg, model)
        score = t * (1.0 + comm / compute_s)
        rows.append({"cfg": cfg, "step_time_s": t,
                     "comm_model_s": round(comm, 6),
                     "score": round(score, 5)})
        print(f"  {cfg}: cpu {t:.3f}s, comm-model {comm * 1e3:.2f}ms, "
              f"score {score:.4f}", flush=True)
    ok = [r_ for r_ in rows if r_["score"] is not None]
    if not ok:
        print("stage D: every parallel trial failed", file=sys.stderr)
        return None
    ok.sort(key=lambda r_: r_["score"])
    # dominated = strictly worse on BOTH measured time and modeled comm
    for r_ in ok:
        r_["dominated"] = any(
            o is not r_ and o["step_time_s"] <= r_["step_time_s"]
            and o["comm_model_s"] <= r_["comm_model_s"]
            and (o["step_time_s"] < r_["step_time_s"]
                 or o["comm_model_s"] < r_["comm_model_s"])
            for o in ok)
    block = {"best": ok[0]["cfg"], "n_devices": ndev, "size": size,
             "model": model, "ranking": ok,
             "failed": [r_["cfg"] for r_ in rows if r_["score"] is None],
             "note": "cpu-mesh measured step time x analytic ICI comm "
                     "model; calibrate on chip", "ts": time.time()}
    _merge_tuned({"parallel": block})
    print(f"{os.path.basename(TUNED)} parallel <- {block['best']}",
          flush=True)
    return block


def main():
    if "--parallel" in sys.argv:
        # stage D runs WITHOUT hardware (virtual CPU mesh)
        ok = run_parallel_search(
            ndev=int(os.environ.get("PT_TUNE_PAR_NDEV", "8")),
            size=os.environ.get("PT_TUNE_PAR_SIZE", "small"),
            max_trials=int(os.environ.get("PT_TUNE_PAR_MAX", "0")) or None)
        sys.exit(0 if ok else 1)
    if SMOKE:
        print(f"autotune: SMOKE mode (child={os.path.basename(CHILD)}, "
              f"out={os.path.basename(TUNED)})", flush=True)
    else:
        # refuse to tune on CPU — numbers would be meaningless as defaults
        try:
            probe = subprocess.run(
                [sys.executable, "-c",
                 "import jax; print(jax.devices()[0].platform)"],
                capture_output=True, text=True, timeout=180)
            alive = probe.returncode == 0 and probe.stdout.strip() == "tpu"
        except subprocess.TimeoutExpired:
            alive = False  # device init hung
        if not alive:
            print("autotune: TPU unreachable; not tuning", file=sys.stderr)
            sys.exit(1)

    seq = int(os.environ.get("PT_TUNE_SEQ", "2048"))
    trials = []
    best_cfg, best_res = None, None
    done = []

    def consider(cfg):
        nonlocal best_cfg, best_res
        res = run_trial(cfg, trials)
        if score(res) > score(best_res):
            best_cfg, best_res = cfg, res
            # persist on every improvement, not just stage boundaries —
            # an interrupted stage must not lose the search
            persist(best_cfg, best_res, trials, list(done))

    stages = os.environ.get("PT_TUNE_STAGES", "ABC").upper()
    if not stages or not set(stages) <= set("ABC"):
        print(f"autotune: invalid PT_TUNE_STAGES={stages!r} "
              "(want a non-empty subset of 'ABC')", file=sys.stderr)
        sys.exit(2)
    try:
        if "A" in stages:
            print("stage A: batch x remat x fused_ce", flush=True)
            for cfg in STAGE_A:
                consider(dict(cfg, seq=seq))
            if best_res is None:
                print("autotune: every stage-A trial failed; aborting",
                      file=sys.stderr)
                sys.exit(1)
            done.append("A")
            persist(best_cfg, best_res, trials, done)
        else:
            # B/C refine the recorded stage-A winner
            prev, prev_done, prior = _tuned_defaults_for_refine()
            if not prev:
                print("autotune: PT_TUNE_STAGES without A needs a prior "
                      "non-smoke TUNED.json with stage A completed",
                      file=sys.stderr)
                sys.exit(1)
            # keep earlier stages on the record, minus the ones this
            # pass re-runs (a BC refine over a full ABC file must not
            # persist ['A','B','C','B','C'])
            done.extend(s for s in prev_done if s not in stages)
            trials.extend(prior)     # and their trial log (marked prior)
            best_cfg = prev
            best_res = run_trial(dict(prev), trials)
            if best_res is None:
                print("autotune: could not re-measure the recorded best",
                      file=sys.stderr)
                sys.exit(1)

        if "B" in stages:
            # stage B: flash block sizes at the winner (must divide seq)
            print("stage B: flash block_q/block_k", flush=True)
            a_win = dict(best_cfg)
            for bq, bk in ((128, 128), (256, 256), (256, 512), (512, 256),
                           (512, 512)):
                consider(dict(a_win, block_q=bq, block_k=bk))
            done.append("B")
            persist(best_cfg, best_res, trials, done)

        if "C" in stages:
            # stage C: gradient accumulation (true grad-accum scan in
            # make_train_step — trades peak activation memory for a
            # serial loop; can unlock bigger batch or lighter remat)
            print("stage C: n_micro grad accumulation", flush=True)
            b_win = dict(best_cfg)
            for nm in (2, 4):
                if b_win["batch"] % nm == 0:
                    consider(dict(b_win, n_micro=nm))
            done.append("C")
            persist(best_cfg, best_res, trials, done)
    except SearchStalled as e:
        print(f"autotune: aborting search — {e}; "
              f"stages completed: {done or 'none'}", file=sys.stderr)
        if best_res is None:
            sys.exit(3)
        # re-persist so the trials record includes the dead trials that
        # tripped the breaker — TUNED.json must explain why the search
        # stopped, not just stderr
        persist(best_cfg, best_res, trials, list(done))
    print(json.dumps({"best": best_cfg, "tok_s": best_res["value"],
                      "mfu": best_res["extra"]["mfu"]}))


if __name__ == "__main__":
    main()
