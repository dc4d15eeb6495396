#!/usr/bin/env python3
"""Run a cell several times, one process a run (a chip belongs to one process
at a time; this parent never touches jax), and print each metric's median
and quartile spread: how the bounds in BENCHMARK.json were measured.

    python3 benchmarks/measure.py --workload chat_steady --seeds 11,12,13 \
        [--seconds 51] [--trace 0] [--control int8_kv] [--tag sweep]

Every result line is appended to chiprun_out/measure_<tag>.jsonl.
"""
import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks import stats  # noqa: E402 - numpy only, never jax


def variant(manifest, args):
    """A copy of the benchmark's data files with the cell's traffic file
    changed as `--set` says -> the path of the copy's manifest."""
    import shutil
    base = os.path.join(ROOT, ".bench_out", f"variant_{args.tag}")
    shutil.rmtree(base, ignore_errors=True)
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", sub),
                        os.path.join(base, "b", sub))
    cell = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    path = os.path.join(base, "b", "traffic", cell["traffic"] + ".json")
    spec = json.load(open(path))
    for kv in args.set:
        key, value = kv.split("=", 1)
        spec[key] = json.loads(value)
    json.dump(spec, open(path, "w"))
    copy = dict(manifest, configs=[dict(c, file="b/configs/" + os.path.basename(
        c["file"])) for c in manifest["configs"]])
    json.dump(copy, open(os.path.join(base, "BENCHMARK.json"), "w"))
    return os.path.join(base, "BENCHMARK.json")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--tag", default="runs")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="run a variant of the cell's traffic file with this "
                         "parameter changed (a rate sweep); the variant's "
                         "files go under .bench_out/")
    args = ap.parse_args()
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    seconds = args.seconds or manifest["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = os.path.join(out_dir, f"measure_{args.tag}.jsonl")
    extra = ["--manifest", variant(manifest, args)] if args.set else []
    rows = []
    for seed in args.seeds.split(","):
        cmd = manifest["command"] + ["--workload", args.workload, "--seed", seed,
                                     "--seconds", str(seconds),
                                     "--trace", str(args.trace)]
        if args.control:
            cmd += ["--control", args.control]
        cmd += extra
        t = time.monotonic()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.monotonic() - t
        lines = p.stdout.strip().splitlines()
        for line in lines[:-1]:
            print("   |", line[:400])
        try:
            res = json.loads(lines[-1])
        except (IndexError, ValueError):
            print(f"seed {seed}: rc={p.returncode}, no result line\n"
                  f"{p.stderr[-3000:]}", flush=True)
            continue
        row = {"workload": args.workload, "seed": int(seed), "seconds": seconds,
               "trace": args.trace, "control": args.control, "rc": p.returncode,
               "wall_s": wall, **res}
        rows.append(row)
        with open(log, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(f"seed {seed}: rc={p.returncode} wall={wall:.1f}s correct="
              f"{res.get('correct')} failed={res.get('failed')} " +
              " ".join(f"{k}={v['value']:.6g}" for k, v in res["metrics"].items()),
              flush=True)
    names = sorted({k for r in rows for k in r["metrics"]})
    for k in names:
        v = [r["metrics"][k]["value"] for r in rows if k in r["metrics"]]
        if len(v) >= 2:
            print(f"{k}: n={len(v)} median={stats.percentile(v, 0.5):.6g} "
                  f"iqr_share={stats.quartile_spread(v):.4%} min={min(v):.6g} "
                  f"max={max(v):.6g}")
    return 0 if rows and all(r["rc"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
