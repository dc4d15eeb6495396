"""Device telemetry (aux subsystem: observability).

PR 3 made the host observable (who compiled, who waited); this module
makes the HARDWARE observable. Two halves:

  * **CostRegistry** — whenever a tracked jit entry point compiles
    (`compile_telemetry` detects the fresh arg-shape signature), the
    just-built executable is re-resolved through jax's AOT path and its
    `cost_analysis()` / `memory_analysis()` are captured: FLOPs, bytes
    accessed, argument/output/temp/generated-code HBM sizes. Every
    subsequent *call* of that signature adds its known FLOPs/bytes to
    issued counters, so any step loop that knows its wall time can ask
    "what fraction of peak did the chip just do" — `note_step()` turns
    (issued Δ, step seconds) into an MFU gauge against the per-device
    peak table below, plus roofline arithmetic intensity against peak
    HBM bandwidth. The serving pump calls it every engine step
    (`pt_mfu` on `/metrics`); bench and `hapi.Model.fit` read the same
    counters over their own windows.

  * **MemoryAccountant** — polls `device.memory_stats()` on every
    local device (gracefully absent on CPU) and walks
    `jax.live_arrays()` into a by-shape/dtype breakdown, keeping a
    live-bytes high-water mark. Exposed as `pt_device_*` gauges, in
    the bench snapshot (`hbm_peak_bytes`), and as `device.memory`
    flight-recorder records.

The capture runs one extra shape-only `lower()` + HLO cost analysis
per *new* signature (no second XLA backend compile — measured ~8x
cheaper than the compiled-executable route; set
PADDLE_TPU_DEVICE_COST=full for the executable-level
`memory_analysis()` with temp/generated-code HBM) and is never
allowed to break the wrapped call (every capture is best-effort).
Disable with PADDLE_TPU_DEVICE_COST=0.

Import cost: stdlib only (jax is imported inside functions), matching
the rest of `paddle_tpu.observability`.
"""
from __future__ import annotations

import threading
import time
from typing import NamedTuple

from .._env import env_float, env_str

__all__ = [
    "PEAK_SPECS", "device_generation", "device_peaks",
    "CostRegistry", "COSTS", "MemoryAccountant", "ACCOUNTANT",
    "note_step", "snapshot", "render_prometheus", "reset",
]

class PeakSpec(NamedTuple):
    flops: float        # peak dense bf16 FLOP/s of one device
    hbm_bw: float       # peak HBM bytes/s of one device
    kinds: tuple        # substrings of jax's lower-cased `device_kind`
    source: str


# The ONE table of per-device peaks — the roofline denominators, keyed
# by generation and matched against `jax.Device.device_kind`. A TPU
# whose kind matches no row is an error, never a default. The cpu row
# is deliberately generous (no laptop hits 1 TFLOP/s dense) so CPU-run
# MFU gauges stay honest fractions in (0, 1] while still being nonzero
# and testable; it is a counter's denominator, never a device metric.
_TPU_DOCS = "Google Cloud documentation, cloud.google.com/tpu/docs/"
PEAK_SPECS = {
    "v4": PeakSpec(275e12, 1.2288e12, ("v4",),
                   _TPU_DOCS + "v4: 275 TFLOP/s bf16, 1228 GB/s"),
    "v5e": PeakSpec(197e12, 8.19e11, ("v5 lite", "v5e", "v5litepod"),
                    _TPU_DOCS + "v5e: 197 TFLOP/s bf16, 819 GB/s"),
    "v5p": PeakSpec(459e12, 2.765e12, ("v5p",),
                    _TPU_DOCS + "v5p: 459 TFLOP/s bf16, 2765 GB/s"),
    "v6e": PeakSpec(918e12, 1.640e12, ("v6 lite", "v6e"),
                    _TPU_DOCS + "v6e: 918 TFLOP/s bf16, 1640 GB/s"),
    "cpu": PeakSpec(1e12, 1e11, (), "placeholder, not a measurement"),
}

_COST_ENABLED = env_str("PADDLE_TPU_DEVICE_COST") != "0"


def device_generation():
    """The PEAK_SPECS key of the first local device. Off-TPU this is
    always "cpu" regardless of env hints (a CPU run must never be
    scored against a chip's peak). On TPU, PADDLE_TPU_GEN overrides,
    else `device_kind` is matched against the table; a kind no row
    matches raises — and so does a backend that fails to initialise —
    rather than being scored against another chip's peak."""
    import jax
    dev = jax.local_devices()[0]
    if dev.platform != "tpu":
        return "cpu"
    gen = env_str("PADDLE_TPU_GEN")
    if gen in PEAK_SPECS:
        return gen
    kind = dev.device_kind.lower()
    for key, spec in PEAK_SPECS.items():
        if any(p in kind for p in spec.kinds):
            return key
    raise RuntimeError(
        f"unknown TPU device_kind {dev.device_kind!r}: add its published "
        "peaks to observability.device_telemetry.PEAK_SPECS (or set "
        "PADDLE_TPU_GEN to the row that applies)")


def device_peaks():
    """(peak_flops_per_s, peak_hbm_bytes_per_s) for ONE local device —
    MFU here is the single-chip convention, same as bench.py.
    PADDLE_TPU_PEAK_FLOPS / PADDLE_TPU_PEAK_BW override numerically
    (e.g. a future generation missing from the table)."""
    spec = PEAK_SPECS[device_generation()]
    flops = env_float("PADDLE_TPU_PEAK_FLOPS", spec.flops)
    bw = env_float("PADDLE_TPU_PEAK_BW", spec.hbm_bw)
    return flops, bw


def _aval_bytes(tree):
    import math

    import numpy as np

    total = 0
    for leaf in _flat_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            total += math.prod(shape) * np.dtype(dtype).itemsize
    return int(total)


def _flat_leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def _analysis_of(fn, args, kwargs):
    """Best-effort (cost, memory) analysis of `fn`'s executable for
    `args`/`kwargs`. Arrays are replaced by ShapeDtypeStructs so
    donated-then-deleted buffers (the trainer's params) never need
    their data.

    Default mode stops at `lower()`: `Lowered.cost_analysis()` gives
    the same FLOPs/bytes-accessed numbers WITHOUT a second XLA backend
    compile (measured ~8x cheaper), and argument/output HBM comes from
    the in/out avals. PADDLE_TPU_DEVICE_COST=full additionally runs
    `lower().compile()` for the executable-level `memory_analysis()`
    (temp + generated-code HBM — the numbers only the compiled
    allocation plan knows)."""
    import jax

    def spec(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return jax.ShapeDtypeStruct(tuple(shape), dtype)
        return x
    sargs = jax.tree_util.tree_map(spec, args)
    skwargs = jax.tree_util.tree_map(spec, kwargs or {})
    lowered = fn.lower(*sargs, **skwargs)
    mem = {"argument_bytes": _aval_bytes((sargs, skwargs)),
           "output_bytes": 0, "temp_bytes": 0, "generated_code_bytes": 0}
    if env_str("PADDLE_TPU_DEVICE_COST") == "full":
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        m = compiled.memory_analysis()
        mem = {"argument_bytes": int(getattr(
                   m, "argument_size_in_bytes", 0) or 0),
               "output_bytes": int(getattr(
                   m, "output_size_in_bytes", 0) or 0),
               "temp_bytes": int(getattr(
                   m, "temp_size_in_bytes", 0) or 0),
               "generated_code_bytes": int(getattr(
                   m, "generated_code_size_in_bytes", 0) or 0)}
    else:
        cost = lowered.cost_analysis()
        try:
            mem["output_bytes"] = _aval_bytes(lowered.out_info)
        except Exception:
            pass
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}, mem


class _FnCost:
    __slots__ = ("name", "flops", "bytes_accessed", "argument_bytes",
                 "output_bytes", "temp_bytes", "code_bytes",
                 "flops_issued", "bytes_issued", "calls", "captures",
                 "capture_failures")

    def __init__(self, name):
        self.name = name
        # latest-signature static analysis (what one call costs)
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.argument_bytes = 0
        self.output_bytes = 0
        self.temp_bytes = 0
        self.code_bytes = 0
        # issued counters (what all calls cost so far)
        self.flops_issued = 0.0
        self.bytes_issued = 0.0
        self.calls = 0
        self.captures = 0
        self.capture_failures = 0

    def snap(self):
        hbm = self.argument_bytes + self.output_bytes + self.temp_bytes
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "arithmetic_intensity": (self.flops / self.bytes_accessed
                                     if self.bytes_accessed else None),
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.code_bytes,
            "hbm_bytes": hbm,
            "flops_issued": self.flops_issued,
            "bytes_issued": self.bytes_issued,
            "calls": self.calls,
            "captures": self.captures,
            "capture_failures": self.capture_failures,
        }


class CostRegistry:
    """Per-entry-point XLA cost/memory analysis + issued-FLOPs window
    accounting. `capture()` is called by compile_telemetry's tracked
    wrapper on every observed compile; `note_executed()` on every call;
    `note_step()` by whoever owns a step clock (the serving pump)."""

    def __init__(self, enabled=None):
        self._lock = threading.Lock()
        self._by_sig = {}          # (name, signature) -> (flops, bytes)
        self._fns = {}             # name -> _FnCost
        self.enabled = _COST_ENABLED if enabled is None else enabled
        # step-window state (note_step deltas) + MFU gauges
        self._win_flops = 0.0
        self._win_bytes = 0.0
        self.last_mfu = 0.0
        self.peak_mfu = 0.0
        self.last_step_flops = 0.0
        self.last_step_bytes = 0.0
        self.last_intensity = 0.0
        self.steps_measured = 0

    # -- capture (compile time) ---------------------------------------
    def capture(self, name, signature, fn, args, kwargs=None):
        """Record the cost/memory analysis of `fn`'s fresh executable.
        Never raises: telemetry must not break the wrapped call."""
        if not self.enabled:
            return None
        key = (name, signature)
        with self._lock:
            st = self._fns.get(name)
            if st is None:
                st = self._fns[name] = _FnCost(name)
            if key in self._by_sig:
                return None        # e.g. two registries sharing a fn
        if not hasattr(fn, "lower"):
            return None
        try:
            cost, mem = _analysis_of(fn, args, kwargs)
            flops = float(cost.get("flops", 0.0) or 0.0)
            byts = float(cost.get("bytes accessed", 0.0) or 0.0)
            entry = dict(mem, flops=flops, bytes_accessed=byts)
        except Exception:          # noqa: BLE001 — best-effort probe
            with self._lock:
                self._by_sig[key] = (0.0, 0.0)
                st.capture_failures += 1
            return None
        with self._lock:
            self._by_sig[key] = (flops, byts)
            st.captures += 1
            st.flops = flops
            st.bytes_accessed = byts
            st.argument_bytes = entry["argument_bytes"]
            st.output_bytes = entry["output_bytes"]
            st.temp_bytes = entry["temp_bytes"]
            st.code_bytes = entry["generated_code_bytes"]
        from . import flight_recorder as _fr
        _fr.record("device.cost", fn=name, **entry)
        return entry

    # -- accounting (every call) --------------------------------------
    def note_executed(self, name, signature):
        """Add one call's known FLOPs/bytes to the issued counters."""
        with self._lock:
            ent = self._by_sig.get((name, signature))
            st = self._fns.get(name)
            if st is not None:
                st.calls += 1
            if ent is None:
                return
            flops, byts = ent
            if st is not None:
                st.flops_issued += flops
                st.bytes_issued += byts
            self._win_flops += flops
            self._win_bytes += byts

    def issued_totals(self):
        """Cumulative issued FLOPs/bytes, total + per function — the
        raw counters bench/hapi compute their own windows from."""
        with self._lock:
            per_fn = {n: {"flops": s.flops_issued,
                          "bytes": s.bytes_issued}
                      for n, s in self._fns.items()}
            return {
                "flops": sum(v["flops"] for v in per_fn.values()),
                "bytes": sum(v["bytes"] for v in per_fn.values()),
                "per_fn": per_fn,
            }

    # -- MFU / roofline (per step) ------------------------------------
    def note_step(self, elapsed_s):
        """Close one step window: everything issued since the previous
        call ran in `elapsed_s` wall seconds (the caller's clock must
        bracket a synced device step — the serving pump's does). Sets
        the pt_mfu / intensity gauges; returns the step's numbers."""
        with self._lock:
            flops, byts = self._win_flops, self._win_bytes
            self._win_flops = 0.0
            self._win_bytes = 0.0
        if elapsed_s <= 0 or flops <= 0:
            return None
        peak_flops, peak_bw = device_peaks()
        mfu = flops / (elapsed_s * peak_flops)
        with self._lock:
            self.last_mfu = mfu
            self.peak_mfu = max(self.peak_mfu, mfu)
            self.last_step_flops = flops
            self.last_step_bytes = byts
            self.last_intensity = flops / byts if byts else 0.0
            self.steps_measured += 1
        return {"mfu": mfu, "flops": flops, "bytes": byts,
                "step_s": elapsed_s,
                "arithmetic_intensity": self.last_intensity}

    def mfu_over(self, flops, elapsed_s):
        """MFU of an arbitrary (flops, seconds) window — bench/hapi."""
        if elapsed_s <= 0:
            return 0.0
        return flops / (elapsed_s * device_peaks()[0])

    # -- exposition ----------------------------------------------------
    def snapshot(self):
        peak_flops, peak_bw = device_peaks()
        with self._lock:
            fns = {n: s.snap() for n, s in self._fns.items()}
            out = {
                "device_generation": device_generation(),
                "peak_flops_per_s": peak_flops,
                "peak_hbm_bytes_per_s": peak_bw,
                "roofline_ridge_flops_per_byte": peak_flops / peak_bw,
                "mfu": self.last_mfu,
                "mfu_peak": self.peak_mfu,
                "step_flops": self.last_step_flops,
                "step_bytes": self.last_step_bytes,
                "step_arithmetic_intensity": self.last_intensity,
                "steps_measured": self.steps_measured,
                "functions": fns,
            }
        return out

    def render_prometheus(self):
        peak_flops, peak_bw = device_peaks()
        with self._lock:
            rows = sorted(self._fns.values(), key=lambda s: s.name)
            fn_rows = [(s.name, s.flops, s.bytes_accessed,
                        s.argument_bytes + s.output_bytes + s.temp_bytes,
                        s.flops_issued) for s in rows]
            mfu, mfu_peak = self.last_mfu, self.peak_mfu
            sflops, sbytes = self.last_step_flops, self.last_step_bytes
            inten = self.last_intensity
        out = [
            "# HELP pt_mfu Model FLOPs utilization of the last measured "
            "step (XLA-counted FLOPs / step seconds / device peak).",
            "# TYPE pt_mfu gauge",
            f"pt_mfu {mfu:.6g}",
            "# TYPE pt_mfu_peak gauge",
            f"pt_mfu_peak {mfu_peak:.6g}",
            "# HELP pt_step_flops XLA-counted FLOPs issued in the last "
            "measured step.",
            "# TYPE pt_step_flops gauge",
            f"pt_step_flops {sflops:.6g}",
            "# TYPE pt_step_bytes gauge",
            f"pt_step_bytes {sbytes:.6g}",
            "# HELP pt_roofline_intensity FLOPs per HBM byte of the "
            "last measured step (compare against pt_roofline_ridge).",
            "# TYPE pt_roofline_intensity gauge",
            f"pt_roofline_intensity {inten:.6g}",
            "# HELP pt_roofline_ridge Device ridge point: peak FLOPs / "
            "peak HBM bandwidth; intensity below this is memory-bound.",
            "# TYPE pt_roofline_ridge gauge",
            f"pt_roofline_ridge {peak_flops / peak_bw:.6g}",
            "# TYPE pt_peak_flops_per_s gauge",
            f"pt_peak_flops_per_s {peak_flops:.6g}",
            "# TYPE pt_peak_hbm_bytes_per_s gauge",
            f"pt_peak_hbm_bytes_per_s {peak_bw:.6g}",
        ]
        out.append("# HELP pt_fn_flops XLA-counted FLOPs of one call "
                   "of this entry point (latest compiled signature).")
        out.append("# TYPE pt_fn_flops gauge")
        for name, flops, byts, hbm, issued in fn_rows:
            out.append(f'pt_fn_flops{{fn="{name}"}} {flops:.6g}')
        out.append("# TYPE pt_fn_bytes_accessed gauge")
        for name, flops, byts, hbm, issued in fn_rows:
            out.append(f'pt_fn_bytes_accessed{{fn="{name}"}} {byts:.6g}')
        out.append("# HELP pt_fn_hbm_bytes argument+output+temp HBM of "
                   "this entry point's executable.")
        out.append("# TYPE pt_fn_hbm_bytes gauge")
        for name, flops, byts, hbm, issued in fn_rows:
            out.append(f'pt_fn_hbm_bytes{{fn="{name}"}} {hbm}')
        out.append("# TYPE pt_fn_flops_issued_total counter")
        for name, flops, byts, hbm, issued in fn_rows:
            out.append(
                f'pt_fn_flops_issued_total{{fn="{name}"}} {issued:.6g}')
        return "\n".join(out) + "\n"

    def reset(self):
        with self._lock:
            self._by_sig.clear()
            self._fns.clear()
            self._win_flops = self._win_bytes = 0.0
            self.last_mfu = self.peak_mfu = 0.0
            self.last_step_flops = self.last_step_bytes = 0.0
            self.last_intensity = 0.0
            self.steps_measured = 0


class MemoryAccountant:
    """Device-memory snapshots: allocator stats where the backend has
    them (`memory_stats()` — None on CPU), plus a `jax.live_arrays()`
    walk bucketed by dtype/shape. The walk touches every undeleted
    buffer's metadata, so polls are rate-limited (`min_interval_s`)
    unless forced — scrapes, bench ends, and log_freq records force."""

    def __init__(self, min_interval_s=1.0, top_buckets=8):
        self._lock = threading.Lock()
        self.min_interval_s = float(min_interval_s)
        self.top_buckets = int(top_buckets)
        self._last = None
        self._last_t = 0.0
        self.live_peak_bytes = 0
        self.in_use_peak_bytes = 0

    def poll(self, force=False, record=True):
        """Take (or reuse) a snapshot; returns the snapshot dict."""
        now = time.monotonic()
        with self._lock:
            if (not force and self._last is not None
                    and now - self._last_t < self.min_interval_s):
                return self._last
        snap = self._take()
        with self._lock:
            self._last = snap
            self._last_t = now
            self.live_peak_bytes = max(self.live_peak_bytes,
                                       snap["live_bytes"])
            self.in_use_peak_bytes = max(self.in_use_peak_bytes,
                                         snap.get("bytes_in_use") or 0)
            snap["live_peak_bytes"] = self.live_peak_bytes
            if snap.get("bytes_in_use") is not None:
                snap["peak_bytes_in_use"] = max(
                    snap.get("peak_bytes_in_use") or 0,
                    self.in_use_peak_bytes)
        if record:
            from . import flight_recorder as _fr
            _fr.record("device.memory",
                       live_bytes=snap["live_bytes"],
                       live_arrays=snap["live_arrays"],
                       live_peak_bytes=snap["live_peak_bytes"],
                       bytes_in_use=snap.get("bytes_in_use"),
                       bytes_limit=snap.get("bytes_limit"))
        return snap

    def _take(self):
        snap = {"ts": time.time(), "live_bytes": 0, "live_arrays": 0,
                "by_bucket": [], "devices": [], "bytes_in_use": None,
                "peak_bytes_in_use": None, "bytes_limit": None}
        try:
            import jax
        except Exception:
            return snap
        # allocator stats (TPU/GPU backends; None on CPU — graceful)
        in_use = peak = limit = 0
        have_stats = False
        try:
            for d in jax.local_devices():
                stats = d.memory_stats()
                if not stats:
                    snap["devices"].append(
                        {"id": d.id, "platform": d.platform,
                         "memory_stats": None})
                    continue
                have_stats = True
                in_use += int(stats.get("bytes_in_use", 0))
                peak += int(stats.get("peak_bytes_in_use", 0))
                limit += int(stats.get("bytes_limit", 0))
                snap["devices"].append(
                    {"id": d.id, "platform": d.platform,
                     "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                     "peak_bytes_in_use":
                         int(stats.get("peak_bytes_in_use", 0)),
                     "bytes_limit": int(stats.get("bytes_limit", 0))})
        except Exception:
            pass
        if have_stats:
            snap["bytes_in_use"] = in_use
            snap["peak_bytes_in_use"] = peak
            snap["bytes_limit"] = limit
        # live-array walk: who holds the bytes, by dtype/shape bucket
        buckets = {}
        total = count = 0
        try:
            for a in jax.live_arrays():
                try:
                    n = int(a.nbytes)
                    key = f"{a.dtype}{tuple(a.shape)}"
                except Exception:
                    continue
                total += n
                count += 1
                b = buckets.get(key)
                buckets[key] = (b[0] + n, b[1] + 1) if b else (n, 1)
        except Exception:
            pass
        snap["live_bytes"] = total
        snap["live_arrays"] = count
        snap["by_bucket"] = [
            {"bucket": k, "bytes": v[0], "count": v[1]}
            for k, v in sorted(buckets.items(),
                               key=lambda kv: -kv[1][0])[:self.top_buckets]]
        return snap

    def snapshot(self):
        """Last poll (taking one if none exists yet)."""
        with self._lock:
            last = self._last
        return last if last is not None else self.poll(force=True)

    def render_prometheus(self, force_poll=True):
        snap = self.poll(force=force_poll) if force_poll \
            else self.snapshot()
        out = [
            "# HELP pt_device_live_bytes Bytes held by live (undeleted) "
            "device arrays.",
            "# TYPE pt_device_live_bytes gauge",
            f"pt_device_live_bytes {snap['live_bytes']}",
            "# TYPE pt_device_live_arrays gauge",
            f"pt_device_live_arrays {snap['live_arrays']}",
            "# HELP pt_device_live_peak_bytes High-water mark of "
            "pt_device_live_bytes across polls.",
            "# TYPE pt_device_live_peak_bytes gauge",
            f"pt_device_live_peak_bytes {snap['live_peak_bytes']}",
        ]
        if snap.get("bytes_in_use") is not None:
            out += [
                "# HELP pt_device_bytes_in_use Allocator bytes in use "
                "(sum over local devices; absent on CPU).",
                "# TYPE pt_device_bytes_in_use gauge",
                f"pt_device_bytes_in_use {snap['bytes_in_use']}",
                "# TYPE pt_device_peak_bytes_in_use gauge",
                f"pt_device_peak_bytes_in_use {snap['peak_bytes_in_use']}",
                "# TYPE pt_device_bytes_limit gauge",
                f"pt_device_bytes_limit {snap['bytes_limit']}",
            ]
        for b in snap["by_bucket"]:
            out.append(
                f'pt_device_live_bucket_bytes{{bucket="{b["bucket"]}"}} '
                f'{b["bytes"]}')
        return "\n".join(out) + "\n"

    def reset(self):
        with self._lock:
            self._last = None
            self._last_t = 0.0
            self.live_peak_bytes = 0
            self.in_use_peak_bytes = 0


COSTS = CostRegistry()
ACCOUNTANT = MemoryAccountant()


def note_step(elapsed_s):
    """Module-level shorthand: the serving pump's per-step MFU hook."""
    return COSTS.note_step(elapsed_s)


def snapshot():
    return {"cost": COSTS.snapshot(), "memory": ACCOUNTANT.snapshot()}


def render_prometheus():
    """Everything this module knows, Prometheus text — appended to the
    serving `/metrics` next to the compile exposition."""
    return COSTS.render_prometheus() + ACCOUNTANT.render_prometheus()


def reset():
    COSTS.reset()
    ACCOUNTANT.reset()
