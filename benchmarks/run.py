#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. Fails (non-zero, no result line) unless jax reports a
TPU with at least the cell's chips. Everything about the cell is data found
by name: its configuration, traffic, per-layer metrics and their reducers.
The last line of standard output is the result object.
"""
import time
T_START = time.monotonic()          # set-up is counted from here

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another table of cells (the tests' tiny one)")
    ap.add_argument("--control", default=None,
                    help="run one of the configuration's `controls` in the "
                         "program's place: `correct` has to come out false")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug the command where there is no chip: prints "
                         "counts, never a time or a rate, and no result line")
    return ap.parse_args(argv)


class Context:
    def __init__(self, args, manifest, cell, config, traffic):
        self.args, self.manifest, self.cell = args, manifest, cell
        self.config, self.traffic = config, traffic
        self.control = args.control
        self.facts = {}
        self.t_open = None

    def say(self, msg):
        print(msg, flush=True)

    def say_time(self, msg):
        """A line that holds a time or a rate: never from a CPU rehearsal."""
        if not self.args.rehearse_cpu:
            print(msg, flush=True)

    def window_opened(self, t):
        self.t_open = t


def resolve(args):
    from benchmarks import harness
    manifest = harness.load_json(args.manifest)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        sys.exit(f"run.py: no workload {args.workload!r} in {args.manifest}; "
                 f"it has {sorted(cells)}")
    cell = cells[args.workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    base = os.path.dirname(os.path.abspath(args.manifest))
    config = harness.load_json(base, cfg_entry["file"])
    bench_dir = os.path.join(base, os.path.dirname(os.path.dirname(
        cfg_entry["file"])))
    traffic = harness.load_json(bench_dir, "traffic", cell["traffic"] + ".json")
    if args.control and args.control not in config.get("controls", {}):
        sys.exit(f"run.py: configuration {cell['config']!r} names no control "
                 f"{args.control!r}")
    ctx = Context(args, manifest, cell, config, traffic)
    ctx.bench_dir = bench_dir
    return ctx


def find_chips(ctx):
    """The accelerator, or exit. Never a fallback to the CPU."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if ctx.args.rehearse_cpu:
        return {"platform": platform, "kind": devs[0].device_kind,
                "count": len(devs)}
    if platform != "tpu":
        sys.exit(f"run.py: jax found platform {platform!r}, not 'tpu'. The "
                 "benchmark measures on the accelerator and has no CPU "
                 "fallback.")
    if len(devs) < ctx.cell["chips"]:
        sys.exit(f"run.py: cell {ctx.cell['name']!r} needs "
                 f"{ctx.cell['chips']} chips, jax found {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def reports(entry, cell):
    """Whether a metric's entry lists the cell (no list: every cell)."""
    return cell["name"] in entry.get("workloads", [cell["name"]])


def traced_device(ctx, device, trace, host_timed_s):
    """`device` with `busy_s` and `window_s`, both read from the trace on
    its own clock, so that 0 < busy_s <= window_s; where that does not hold
    the trace is empty and the run ends here, with no result line."""
    from benchmarks import xplane
    busy_s, window_s = xplane.busy_seconds(trace), xplane.window_seconds(trace)
    ctx.say(f"tracer: host-timed {host_timed_s:.6f} s, trace extent "
            f"{window_s:.6f} s, device busy {busy_s:.6f} s")
    if not 0 < busy_s <= window_s:
        sys.exit(f"run.py: the trace holds {busy_s!r} s of device operations "
                 f"in a window of {window_s!r} s: busy_s has to lie above 0 "
                 "and at most at window_s, so the profiler recorded nothing "
                 "on the device and no result is printed")
    return dict(device, busy_s=busy_s, window_s=window_s)


def layer_metrics(ctx, outcome, device):
    """The cell's per-layer metrics: each from the small reader its file
    names. A reader that finds nothing to read returns None and the metric
    is left out of the line."""
    from benchmarks import harness, xplane
    trace = outcome.tracer.load()
    device = traced_device(ctx, device, trace, outcome.tracer.host_timed_s)
    facts = dict(ctx.facts, trace=trace, config=ctx.config,
                 traffic=ctx.traffic, chips=ctx.cell["chips"],
                 peaks=harness.peaks_for(device["kind"]),
                 trace_window_s=device["window_s"])
    base = os.path.join(ctx.bench_dir, "layer_metrics")
    out = {}
    for entry in ctx.manifest["per_layer"]:
        if not reports(entry, ctx.cell):
            continue
        spec = harness.load_json(base, entry["name"] + ".json")
        reader = harness.load_module("reducers", spec["reducer"])
        value = reader.reduce(facts, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out, device, xplane.breakdown(trace)


def main(argv=None):
    args = parse(sys.argv[1:] if argv is None else argv)
    # the program keeps its compile cache where this says, at one fixed
    # path inside the checkout, unless the caller has already said where
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    ctx = resolve(args)
    device = find_chips(ctx)
    import paddle_tpu  # noqa: F401 - as a user does; turns x64 on
    from benchmarks import harness
    from paddle_tpu.observability import compile_telemetry
    cache_dir = compile_telemetry.ensure_compile_cache()
    ctx.say(f"device: platform={device['platform']} kind={device['kind']!r} "
            f"count={device['count']}; cell {ctx.cell['name']} = "
            f"{ctx.cell['config']} x {ctx.cell['traffic']}, seed {args.seed}, "
            f"{args.seconds} s, trace {args.trace}; compile cache {cache_dir}")
    driver = harness.load_module("drivers", ctx.traffic["driver"])
    outcome = driver.run(ctx)

    correct, lines = True, []
    for name, value, limit in outcome.checks:
        ok = limit is not None and value <= limit
        correct = correct and ok
        lines.append(f"compare: {name} = {value!r} limit {limit!r} "
                     f"{'ok' if ok else 'FAILED'}")
        ctx.say(lines[-1])
    totals = compile_telemetry.REGISTRY.totals()
    ctx.say(f"compiles: {totals['compiles']} in the process "
            f"({totals['cache_hits']} from the cache)")
    result = {"correct": bool(correct), "attempted": outcome.attempted,
              "failed": outcome.failed}
    if args.rehearse_cpu:
        result = dict(
            rehearsal=True, platform=device["platform"], **result,
            counts={k: v for k, v in ctx.facts.items()
                    if isinstance(v, (int, dict))},
            note="counts only: a time or a rate comes from a chip run")
    else:
        metrics = dict(outcome.end_to_end)
        metrics["setup_s"] = ctx.t_open - T_START
        units = {m["name"]: m["unit"] for m in ctx.manifest["end_to_end"]
                 if reports(m, ctx.cell)}
        device["memory_peak_bytes"] = outcome.memory_peak_bytes
        if args.trace:
            per_layer, device, breakdown = layer_metrics(ctx, outcome, device)
            ctx.say("end_to_end in this traced run (not reported): " +
                    json.dumps(metrics))
            result["metrics"] = per_layer
            result["breakdown"] = breakdown
        else:
            result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                                 for k, v in metrics.items() if k in units}
        result["device"] = device
    # each number compared beside its limit: last in the result's line, and
    # the last lines on standard error (what the driver's record keeps)
    result["compared"] = {name: {"value": float(value), "limit": limit}
                          for name, value, limit in outcome.checks}
    print(json.dumps(result), flush=True)
    print("\n".join(lines), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
