"""Profiler (reference: python/paddle/profiler/profiler.py).

Wraps jax.profiler: traces are Perfetto/XPlane (TensorBoard-compatible),
replacing the reference's CUPTI/nvprof collection. summary() reports
host-side op timings from our dispatch-layer TraceEvent ring.

Scheduled capture: `Profiler(scheduler=make_scheduler(...))` drives
CLOSED → READY → RECORD windows from `step()` — warmup (READY) events
are excluded from the exported session, each RECORD window ends by
firing `on_trace_ready` (and, with an `export_chrome_tracing` handler,
writing this session's chrome-tracing JSON), and `repeat` cycles each
produce their own export.

Spans (`record_span` / `RecordEvent`) carry the observability layer's
trace context: the current request's trace id plus parent/child span
ids, and every finished span also lands in the crash flight recorder
(`paddle_tpu.observability.flight_recorder`).
"""
from __future__ import annotations

import contextlib
import enum
import os
import threading
import time

import jax


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SortedKeys(enum.Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        total = closed + ready + record
        if repeat and s >= total * repeat:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready handler: export each finished RECORD window's
    host-side trace as chrome-tracing JSON under `dir_name` (one file
    per window: <worker>.pt_trace.<n>.json)."""
    def handler(prof):
        prof._export_dir = dir_name
        prof._export_worker = worker_name
        prof._export_session()
    return handler


export_protobuf = export_chrome_tracing

_RECORDING = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)


class Profiler:
    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False):
        self._dir = os.environ.get("PADDLE_TPU_PROFILE_DIR", "/tmp/pt_profile")
        self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        # profile_memory: poll the device-memory accountant on every
        # recorded step — snapshots land as `device.memory` flight
        # events next to the window's spans (reference: the profiler's
        # MemoryView, rebuilt on memory_stats + live_arrays)
        self._profile_memory = bool(profile_memory)
        self._active = False        # a jax.profiler device trace is live
        self._recording = False     # a host RECORD window is open
        self._state = ProfilerState.CLOSED
        self._step = 0
        self._step_times = []
        self._last = None
        self._export_dir = None
        self._export_worker = None
        self._export_seq = 0

    # -- capture windows ----------------------------------------------
    def _open_window(self):
        # host event ring: windows export only events recorded after
        # this timestamp — earlier sessions' spans must not leak in
        self._t_session = time.time()
        self._recording = True
        if not self._timer_only:
            try:
                jax.profiler.start_trace(self._dir)
                self._active = True
            except Exception:
                self._active = False

    def _close_window(self, ready=True):
        if self._active:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._active = False
        self._recording = False
        if ready and self._on_trace_ready:
            self._on_trace_ready(self)

    def _export_session(self):
        """Write the current window's chrome trace into the handler's
        dir (wired by export_chrome_tracing); returns the path."""
        if not self._export_dir:
            return None
        os.makedirs(self._export_dir, exist_ok=True)
        worker = self._export_worker or f"host_{os.getpid()}"
        self._export_seq += 1
        path = os.path.join(self._export_dir,
                            f"{worker}.pt_trace.{self._export_seq}.json")
        self.export(path)
        return path

    # -- lifecycle -----------------------------------------------------
    def start(self):
        from ..utils import trace as _trace
        self._prev_trace_enabled = _trace.enabled()
        _trace.enable()
        self._t_session = time.time()
        if self._scheduler is not None:
            self._state = self._scheduler(0)
        else:
            self._state = ProfilerState.RECORD
        if self._state in _RECORDING:
            self._open_window()
        self._last = time.perf_counter()

    def stop(self):
        if self._recording:
            self._close_window(ready=True)
        elif self._scheduler is None and self._on_trace_ready:
            self._on_trace_ready(self)   # legacy: handler always fires
        self._state = ProfilerState.CLOSED
        if not getattr(self, "_prev_trace_enabled", True):
            from ..utils import trace as _trace
            _trace.disable()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last is not None:
            self._step_times.append(now - self._last)
        self._last = now
        self._step += 1
        if self._profile_memory and self._recording:
            from ..observability.device_telemetry import ACCOUNTANT
            ACCOUNTANT.poll()   # rate-limited live-array walk
        if self._scheduler is None:
            return
        old = self._state
        new = self._scheduler(self._step)
        self._state = new
        if self._recording and (old is ProfilerState.RECORD_AND_RETURN
                                or new not in _RECORDING):
            # the window just finished (AND_RETURN marks the last
            # recorded step of a cycle): hand the trace over now, so a
            # `repeat` schedule exports one file per cycle
            self._close_window(ready=True)
        if new in _RECORDING and not self._recording:
            self._open_window()

    @property
    def current_state(self):
        return self._state

    def step_info(self, unit=None):
        if not self._step_times:
            return "no steps recorded"
        import numpy as np
        ts = np.asarray(self._step_times[-10:])
        return (f"avg step {ts.mean()*1000:.2f} ms, ips "
                f"{1.0/ts.mean():.2f} steps/s")

    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail=True,
                thread_sep=False, time_unit="ms", views=None):
        from ..utils.trace import summary as trace_summary
        print(trace_summary())

    def export(self, path, format="json"):
        """Write THIS session's host-side events (RecordEvent spans +
        dispatch-layer op spans fed by _core.apply when tracing is on)
        as chrome://tracing JSON. On-chip XLA traces captured by
        start_trace live under self._dir for TensorBoard/XProf."""
        if format not in ("json", "chrome"):
            raise ValueError(
                f"unsupported export format {format!r}: only chrome-"
                "tracing 'json' is implemented (XLA device traces are "
                "XPlane dumps under the profiler dir)")
        import json as _json

        from ..observability.chrome_trace import chrome_trace_doc
        from ..utils import trace as _trace
        t0 = getattr(self, "_t_session", 0.0)
        spans = []
        for ev in _trace.events():
            if ev.ts_end < t0:
                continue  # a previous session's span
            args = dict(ev.args or {})
            if ev.shape is not None:
                args["shape"] = str(ev.shape)
            spans.append({"name": ev.name, "t_start": ev.ts_end - ev.dur,
                          "dur_s": ev.dur, "trace_id": ev.trace_id,
                          "span_id": ev.span_id,
                          "parent_id": ev.parent_id,
                          "args": args or None})
        with open(path, "w") as f:
            _json.dump(chrome_trace_doc(spans), f)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


TURN = "turn"       # `part` of the root span of one pump turn

_open = threading.local()    # .spans: this thread's open part spans


def record_span(name, args=None, part=None, ring=None):
    """A RecordEvent as a with-block: annotates the device trace (when
    one is being captured), feeds the host event ring (when tracing
    is enabled), and drops a span — stamped with the current trace
    context — into the crash flight recorder. The serving engine wraps
    its prefill/decode/verify device calls in these, so a Profiler
    session over a serving workload attributes wall-clock to engine
    phases. Near-free when no profiler is active.

        with profiler.record_span("serving.decode_step"):
            ...

    `part` makes the span one part of a serving pump turn
    (docs/observability.md § A turn of the pump): it is timed on
    `time.monotonic()` and, when it closes, its SELF seconds — its
    duration less its part children's — are added under `part` to the
    enclosing `part=TURN` span's `parts`. The annotation still lies in
    the profiler's trace on the thread that opened it; the flight
    recorder and the trace context get nothing unless `ring=True` (a
    turn has nine parts, the crash ring 4,096 events). `t_end` is the
    stamp a part span closed at, for whoever bounds a stretch by it (the
    pump's periods end where `serving.fetch` does)."""
    return RecordEvent(name, args=args, part=part, ring=ring)


class RecordEvent:
    def __init__(self, name, event_type=None, args=None, part=None,
                 ring=None):
        self.name = name
        self.args = args
        self.part = part
        self.ring = part is None if ring is None else ring
        self.parts = {} if part == TURN else None
        self.dur_s = None
        self.t_end = None
        self._ctx = None
        self._span = None

    def set_args(self, **kw):
        """Arguments known only once the span is under way (a turn's
        row mix): they ride the annotation into the profiler's trace."""
        if self._ctx is not None:
            self._ctx.set_metadata(**kw)

    def begin(self):
        if self.ring:
            from ..observability import trace_context as _tc
            self._span = _tc.span(self.name, args=self.args)
            self._span.__enter__()
        try:
            self._ctx = jax.profiler.TraceAnnotation(self.name)
            self._ctx.__enter__()
        except Exception:
            self._ctx = None
        if self.part is not None:
            spans = _open.__dict__.setdefault("spans", [])
            spans.append(self)
            self._children_s = 0.0
            self._t0 = time.monotonic()

    def end(self):
        if self.part is not None:
            self.t_end = time.monotonic()
            self.dur_s = self.t_end - self._t0
            spans = _open.spans
            spans.pop()
            if spans:
                spans[-1]._children_s += self.dur_s
                parts = spans[0].parts
                if parts is not None:
                    parts[self.part] = parts.get(self.part, 0.0) + \
                        self.dur_s - self._children_s
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None
        if self._span is not None:
            # feeds the host ring (gated: Profiler.start enables tracing
            # for its session; PADDLE_TPU_TRACE=1 enables it globally)
            # and the flight recorder (always; bounded ring)
            self._span.__exit__(None, None, None)
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def load_profiler_result(filename):
    raise NotImplementedError("load XPlane dumps with TensorBoard")


class SummaryView:
    """reference: profiler.SummaryView enum (table selection)."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8
