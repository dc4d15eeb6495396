"""What every driver shares: finding files by name, the compile counter,
the program's counters, the profiler window, and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
OUT_DIR = os.path.join(ROOT, ".bench_out")          # listed in .gitignore
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmarks/<kind>/<name>.py, found by name: one file each."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def peaks_for(device_kind):
    table = load_json(HERE, "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmarks/peaks.json ({sorted(table)}): add it with "
                       "its source, there is no default")
    return table[device_kind]


def sleep_until(t):
    while True:
        wait = t - time.monotonic()
        if wait <= 0:
            return
        time.sleep(min(wait, 0.05))


def annotator(trace_on):
    """`jax.profiler.TraceAnnotation` in a traced run, nothing otherwise."""
    if not trace_on:
        return lambda name, **kw: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class CompileCounter:
    """Programs built in this process, counted from jax's own event for a
    lowering (it fires for a persistent-cache hit too), beside the
    program's `CompileRegistry`. Inside the window both must stay at 0."""
    _count = 0
    _listening = False
    names = []          # what was lowered since the last mark()

    def __init__(self):
        import jax
        cls = CompileCounter
        if not cls._listening:
            def on(event, duration, fun_name=None, **_):
                if event == LOWERING_EVENT:
                    cls._count += 1
                    cls.names.append(fun_name)
            jax.monitoring.register_event_duration_secs_listener(on)
            cls._listening = True
        self._mark = self._now()

    @staticmethod
    def _now():
        from paddle_tpu.observability import compile_telemetry
        return (CompileCounter._count,
                compile_telemetry.REGISTRY.totals()["compiles"])

    def mark(self):
        self._mark = self._now()
        del CompileCounter.names[:]

    def since_mark(self):
        a, b = self._now(), self._mark
        return max(a[0] - b[0], a[1] - b[1])


def counters(registry):
    """{name: value} of the program's counters and gauges."""
    return {k: v["value"] for k, v in registry.snapshot().items()
            if "value" in v}


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


class Tracer:
    """A short profiler window inside the measured one. The reduction runs
    after the system under test is gone. How long the window was is read
    from the trace (`xplane.window_seconds`), on the clock the busy time is
    on: the host's clock here starts after the profiler has and stops before
    it does, so `host_timed_s` is printed and divides nothing."""

    def __init__(self, ctx, spec):
        self.ctx = ctx
        self.dir = os.path.join(OUT_DIR, "trace", ctx.cell["name"])
        self.seconds = float(min(spec.get("trace_s", 8), ctx.args.seconds))
        self.host_timed_s = None        # None until stop()
        self._t = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir, **self._options(jax.profiler))
        self._t = time.monotonic()

    def _options(self, profiler):
        """The profiler's Python tracer off, so that the traced window runs
        the host code users run: with it on, a serve turn's host work read
        double (PR 25). The program's own `TraceAnnotation` spans are the
        host tracer's and stay; `span_gap` reads them."""
        options = getattr(profiler, "ProfileOptions", lambda: None)()
        if not hasattr(options, "python_tracer_level"):
            self.ctx.say("tracer: this jax cannot switch the profiler's Python "
                         "tracer off; host times in the traced window read high")
            return {}
        options.python_tracer_level = 0
        self.ctx.say("tracer: python_tracer_level 0")
        return {"profiler_options": options}

    @property
    def stopped(self):
        return self.host_timed_s is not None

    def stop(self):
        import jax
        self.host_timed_s = time.monotonic() - self._t
        jax.profiler.stop_trace()

    def load(self):
        from benchmarks import xplane
        return xplane.load(xplane.newest_xplane(self.dir))


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to run.py."""
    checks: list                 # (name, value, limit); limit None = failed
    attempted: int
    failed: int
    end_to_end: dict
    memory_peak_bytes: int
    tracer: Tracer | None = None
