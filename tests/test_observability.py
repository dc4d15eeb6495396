"""Runtime observability layer: compile/retrace telemetry, trace
context propagation, structured logging with rate limits, the crash
flight recorder (incl. SIGTERM dump), the serving /debug endpoints,
and the ptdump CLI — end-to-end on CPU over a real ServingEngine."""
import json
import os
import signal
import subprocess
import sys
import threading
import time
from http.client import HTTPConnection

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import (compile_telemetry, flight_recorder,
                                      trace_context)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# compile telemetry
# ---------------------------------------------------------------------------
class TestCompileTelemetry:
    def test_counts_compiles_retraces_and_signatures(self):
        reg = compile_telemetry.CompileRegistry(warn_after=100)
        f = reg.tracked("unit.f")(jax.jit(lambda x: x * 2))
        for n in (2, 3, 4, 2, 3):
            f(jnp.zeros((n,), jnp.float32))
        st = reg.snapshot()["unit.f"]
        assert st["calls"] == 5
        assert st["compiles"] == 3          # shapes 2, 3, 4
        assert st["retraces"] == 2
        assert st["distinct_signatures"] == 3
        assert st["compile_seconds"] > 0

    def test_static_args_are_part_of_the_signature(self):
        reg = compile_telemetry.CompileRegistry(warn_after=100)
        f = reg.tracked("unit.static")(lambda x, flag=False: x)
        x = jnp.zeros((4,))
        f(x, flag=False)
        f(x, flag=True)                     # static churn == retrace
        f(x, flag=True)
        st = reg.snapshot()["unit.static"]
        assert st["compiles"] == 2 and st["calls"] == 3

    def test_retrace_storm_warning_fires_once(self):
        warned = []
        reg = compile_telemetry.CompileRegistry(
            warn_after=3, warn_hook=lambda name, snap: warned.append(snap))
        f = reg.tracked("unit.storm")(lambda x: x)
        for n in range(6):                  # 6 distinct shapes
            f(jnp.zeros((n + 1,)))
        assert len(warned) == 1
        assert warned[0]["compiles"] == 3

    def test_prometheus_exposition(self):
        reg = compile_telemetry.CompileRegistry(warn_after=100)
        f = reg.tracked("unit.prom")(lambda x: x)
        f(jnp.zeros((1,)))
        f(jnp.zeros((2,)))
        text = reg.render_prometheus()
        assert "pt_compile_total 2" in text
        assert "pt_compile_retraces_total 1" in text
        assert 'pt_compile_fn_total{fn="unit.prom"} 2' in text
        assert "pt_compile_seconds_total" in text

    def test_compile_events_reach_flight_recorder(self):
        flight_recorder.RECORDER.clear()
        reg = compile_telemetry.CompileRegistry(warn_after=100)
        f = reg.tracked("unit.flight")(lambda x: x)
        f(jnp.zeros((1,)))
        f(jnp.zeros((2,)))
        evs = [e for e in flight_recorder.RECORDER.events(kind="compile")
               if e["fn"] == "unit.flight"]
        assert len(evs) == 2
        assert evs[0]["retrace"] is False and evs[1]["retrace"] is True

    def test_persistent_cache_hit_tagging(self):
        """A compile during which jax loaded an executable from the
        persistent cache is tagged on the flight record; hits (jax's own
        monitoring event, not a wall-time guess) are counted in
        pt_compile_cache_hits_total."""
        flight_recorder.RECORDER.clear()
        reg = compile_telemetry.CompileRegistry(warn_after=100)
        reg.note_call("unit.cc", ("a",), elapsed_s=0.001)
        reg.note_cache_hit()
        reg.note_call("unit.cc", ("b",), elapsed_s=0.001, cache_hit=True)
        # a non-compile repeat call never tags
        reg.note_call("unit.cc", ("b",), elapsed_s=0.001, cache_hit=True)
        assert reg.totals()["cache_hits"] == 1
        assert "pt_compile_cache_hits_total 1" in reg.render_prometheus()
        evs = [e for e in flight_recorder.RECORDER.events(kind="compile")
               if e["fn"] == "unit.cc"]
        assert [e["cache_hit"] for e in evs] == [False, True]
        reg.reset()
        assert reg.totals()["cache_hits"] == 0

    def _fresh_helper(self, monkeypatch):
        """ensure_compile_cache with its once-per-process latch open and
        jax.config.update recorded instead of applied."""
        writes = []
        monkeypatch.setattr(compile_telemetry.REGISTRY,
                            "persistent_cache_dir", None)
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: writes.append((k, v)))
        return writes

    def test_cache_dir_from_env_is_left_to_jax(self, tmp_path, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: jax reads it itself — the
        helper reports that directory and writes no config."""
        writes = self._fresh_helper(monkeypatch)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_telemetry.ensure_compile_cache() == str(tmp_path)
        assert writes == []
        # once per process: a later call (env gone) answers the same
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_telemetry.ensure_compile_cache() == str(tmp_path)
        assert writes == []

    def test_cache_dir_defaults_to_checkout(self, monkeypatch):
        writes = self._fresh_helper(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert compile_telemetry.ensure_compile_cache() == want
        assert writes == [("jax_compilation_cache_dir", want)]

    def test_cache_dir_is_assigned_only_in_the_helper(self):
        """Source scan: nothing but ensure_compile_cache places the
        cache (no second directory, no path built from a temp name)."""
        hits = []
        for top in ("paddle_tpu", "tools", "examples", "."):
            walk = os.walk(os.path.join(REPO, top)) if top != "." else \
                [(REPO, [], os.listdir(REPO))]
            for d, _, files in walk:
                for f in files:
                    if f.endswith((".py", ".sh")):
                        path = os.path.join(d, f)
                        with open(path, errors="replace") as fh:
                            if "jax_compilation_cache_dir" in fh.read():
                                hits.append(os.path.relpath(path, REPO))
        assert hits == [os.path.join("paddle_tpu", "observability",
                                     "compile_telemetry.py")]


# ---------------------------------------------------------------------------
# trace context
# ---------------------------------------------------------------------------
class TestTraceContext:
    def test_bind_and_nested_spans(self):
        flight_recorder.RECORDER.clear()
        assert trace_context.current_trace_id() is None
        with trace_context.bind("req-42"):
            assert trace_context.current_trace_id() == "req-42"
            with trace_context.span("outer"):
                with trace_context.span("inner", args={"k": 1}):
                    pass
        assert trace_context.current_trace_id() is None
        spans = flight_recorder.RECORDER.events(kind="span")
        by_name = {s["name"]: s for s in spans}
        assert by_name["inner"]["trace_id"] == "req-42"
        assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
        assert by_name["outer"]["parent_id"] is None
        assert by_name["inner"]["args"]["k"] == 1

    def test_span_error_annotation(self):
        flight_recorder.RECORDER.clear()
        with pytest.raises(ValueError):
            with trace_context.span("boom"):
                raise ValueError("x")
        sp = flight_recorder.RECORDER.events(kind="span")[0]
        assert sp["args"]["error"] == "ValueError"

    def test_record_span_event_feeds_trace_ring_when_enabled(self):
        from paddle_tpu.utils import trace
        was = trace.enabled()
        trace.enable()
        trace.clear()
        try:
            trace_context.record_span_event(
                "phase-span", 0.25, trace_id="req-7", t_end=1000.0)
            evs = [e for e in trace.events() if e.name == "phase-span"]
            assert len(evs) == 1
            assert evs[0].trace_id == "req-7"
            assert evs[0].ts_end == 1000.0 and evs[0].dur == 0.25
        finally:
            trace.clear()
            if not was:
                trace.disable()


# ---------------------------------------------------------------------------
# structured logging
# ---------------------------------------------------------------------------
class TestStructuredLogging:
    def test_json_lines_and_rate_limit(self):
        import io
        buf = io.StringIO()
        lg = obs.StructuredLogger("t", stream=buf, rate_per_s=50,
                                  burst=2)
        results = [lg.event("tick", i=i) for i in range(4)]
        assert results[:2] == [True, True] and results[2:] == [False, False]
        time.sleep(0.1)                      # ~5 tokens refill
        assert lg.event("tick", i=99)
        lines = [json.loads(l) for l in buf.getvalue().splitlines()]
        assert len(lines) == 3
        assert lines[0]["logger"] == "t" and lines[0]["event"] == "tick"
        # the post-limit line reports what was suppressed
        assert lines[2]["rate_limited_dropped"] == 2

    def test_events_always_reach_flight_recorder(self):
        rec = flight_recorder.FlightRecorder(capacity=16, enabled=True)
        lg = obs.StructuredLogger("quiet", stream=None, recorder=rec)
        assert lg.event("hidden", x=1) is False   # no stream
        evs = rec.events(kind="log")
        assert len(evs) == 1 and evs[0]["event"] == "hidden"

    def test_get_logger_caches(self):
        assert obs.get_logger("same-name") is obs.get_logger("same-name")


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_bounded_ring_and_snapshot(self):
        rec = flight_recorder.FlightRecorder(capacity=4, enabled=True)
        for i in range(10):
            rec.record("tick", i=i)
        snap = rec.snapshot()
        assert len(snap["events"]) == 4
        assert snap["dropped"] == 6
        assert [e["i"] for e in snap["events"]] == [6, 7, 8, 9]
        seqs = [e["seq"] for e in snap["events"]]
        assert seqs == sorted(seqs)

    def test_disabled_records_nothing(self):
        rec = flight_recorder.FlightRecorder(capacity=4, enabled=False)
        rec.record("tick")
        assert rec.events() == []

    def test_dump_writes_valid_json(self, tmp_path):
        rec = flight_recorder.FlightRecorder(capacity=8, enabled=True)
        rec.record("err", msg="boom")
        path = rec.dump(str(tmp_path / "fr.json"), reason="unit")
        doc = json.loads(open(path).read())
        assert doc["reason"] == "unit" and doc["pid"] == os.getpid()
        assert doc["events"][0]["kind"] == "err"
        assert "compile" in doc

    def test_sigterm_dumps_then_chains(self, tmp_path):
        """SIGTERM must flush the ring to disk, then hand off to the
        previous handler (here: a no-op, so the test survives)."""
        seen = []
        prev = signal.signal(signal.SIGTERM, lambda *a: seen.append(1))
        try:
            rec = flight_recorder.FlightRecorder(capacity=8, enabled=True)
            rec.record("before-term", n=1)
            path = str(tmp_path / "term.json")
            assert rec.install(dump_path=path, fault=False)
            os.kill(os.getpid(), signal.SIGTERM)
            for _ in range(100):             # handler runs async-signal
                if seen and os.path.exists(path):
                    break
                time.sleep(0.01)
            doc = json.loads(open(path).read())
            assert doc["reason"] == "SIGTERM"
            kinds = [e["kind"] for e in doc["events"]]
            assert "before-term" in str(doc["events"]) and "signal" in kinds
            assert seen, "previous handler was not chained"
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_thread_stacks_lists_every_thread(self):
        ev = threading.Event()
        t = threading.Thread(target=ev.wait, name="stacks-probe",
                             daemon=True)
        t.start()
        try:
            out = flight_recorder.thread_stacks()
            assert "stacks-probe" in out
            assert "MainThread" in out
        finally:
            ev.set()


# ---------------------------------------------------------------------------
# ptdump CLI
# ---------------------------------------------------------------------------
class TestPtdump:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "ptdump.py"),
             *args], capture_output=True, text=True, timeout=60)

    def test_pretty_prints_flight_dump(self, tmp_path):
        rec = flight_recorder.FlightRecorder(capacity=8, enabled=True)
        rec.record("sched.admit", rid="r1", queued_s=0.01)
        rec.record("compile", fn="serving.prefill", retrace=True)
        path = rec.dump(str(tmp_path / "fr.json"))
        proc = self._run(path)
        assert proc.returncode == 0, proc.stderr
        assert "flight recorder dump" in proc.stdout
        assert "sched.admit" in proc.stdout
        assert "serving.prefill" in proc.stdout
        proc = self._run(path, "--kind", "compile")
        assert "sched.admit" not in proc.stdout.split("---")[-1]

    def test_pretty_prints_chrome_trace(self, tmp_path):
        doc = obs.chrome_trace_doc([
            {"name": "request.queued", "t_start": 10.0, "dur_s": 0.002,
             "trace_id": "req-1", "span_id": "s1", "parent_id": None},
            {"name": "request.decode", "t_start": 10.002, "dur_s": 0.01,
             "trace_id": "req-1", "span_id": "s2", "parent_id": None},
        ])
        path = str(tmp_path / "trace.json")
        with open(path, "w") as f:
            json.dump(doc, f)
        proc = self._run(path)
        assert proc.returncode == 0, proc.stderr
        assert "chrome trace" in proc.stdout
        assert "request.decode" in proc.stdout
        assert "req-1" in proc.stdout

    def test_rejects_garbage(self, tmp_path):
        path = str(tmp_path / "junk.json")
        with open(path, "w") as f:
            json.dump({"nope": 1}, f)
        assert self._run(path).returncode == 2


# ---------------------------------------------------------------------------
# serving end-to-end (the acceptance criteria)
# ---------------------------------------------------------------------------
from paddle_tpu.models.llama import LlamaConfig          # noqa: E402
from paddle_tpu.models import llama_spmd as M            # noqa: E402
from paddle_tpu.models.llama_serving import ServingEngine  # noqa: E402
from paddle_tpu.serving import ServingServer             # noqa: E402

CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


def _post(conn, prompt, trace_id=None, max_tokens=4):
    headers = {"Content-Type": "application/json"}
    if trace_id:
        headers["X-Request-Id"] = trace_id
    conn.request("POST", "/v1/completions",
                 body=json.dumps({"prompt": prompt,
                                  "max_tokens": max_tokens}),
                 headers=headers)
    resp = conn.getresponse()
    return resp, json.loads(resp.read())


def _get(conn, path):
    conn.request("GET", path)
    resp = conn.getresponse()
    return resp, resp.read()


class TestServingObservability:
    def test_request_tracing_compile_metrics_and_flightrecorder(
            self, params):
        compile_telemetry.reset()
        flight_recorder.RECORDER.clear()
        # bucketed machinery under test: the forced bucket-change
        # retrace below is what lets this test observe the retrace
        # telemetry plumbing — the ragged engine retraces nothing
        # (asserted in test_ragged_step.py)
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, ragged=False)
        with ServingServer(eng, port=0) as srv:
            conn = HTTPConnection(srv.host, srv.port, timeout=60)
            resp, out = _post(conn, [1, 5, 9, 3], trace_id="req-obs-1")
            assert resp.status == 200
            assert out["state"] == "done" and len(out["tokens"]) == 4
            # the client's X-Request-Id is the trace id, echoed back
            assert out["trace_id"] == "req-obs-1"
            assert resp.getheader("X-Request-Id") == "req-obs-1"

            # chrome export: this request's phase spans share its id
            _, raw = _get(conn, "/debug/trace")
            doc = json.loads(raw)
            mine = [e for e in doc["traceEvents"] if e.get("ph") == "X"
                    and (e.get("args") or {}).get("trace_id")
                    == "req-obs-1"]
            names = {e["name"] for e in mine}
            assert {"request.queued", "request.prefill",
                    "request.decode"} <= names, names
            # all three phases render on ONE named row
            assert len({e["tid"] for e in mine}) == 1

            # /metrics exposes nonzero compile counts (prefill + decode
            # compiled for this request) next to the serving registry
            _, raw = _get(conn, "/metrics")
            text = raw.decode()
            assert "pt_serving_ttft_seconds" in text
            total = [l for l in text.splitlines()
                     if l.startswith("pt_compile_total ")]
            assert total and float(total[0].split()[1]) > 0, total
            assert "pt_serving_step_seconds" in text

            # forced re-shape retrace: a much longer prompt lands in a
            # different prefill bucket → new signature → retrace
            before = compile_telemetry.snapshot().get(
                "serving.prefill", {"retraces": 0})["retraces"]
            resp, out2 = _post(conn, list(range(1, 21)),
                               trace_id="req-obs-2")
            assert resp.status == 200
            after = compile_telemetry.snapshot()["serving.prefill"]
            assert after["retraces"] >= before + 1

            # ... and the retrace is in the flight recorder dump
            _, raw = _get(conn, "/debug/flightrecorder")
            snap = json.loads(raw)
            retraces = [e for e in snap["events"]
                        if e["kind"] == "compile"
                        and e["fn"] == "serving.prefill"
                        and e["retrace"]]
            assert retraces, "prefill retrace not in flight recorder"
            assert snap["compile"]["retraces"] >= 1
            # scheduler decisions are in the ring too
            kinds = {e["kind"] for e in snap["events"]}
            assert {"sched.submit", "sched.admit",
                    "request.done"} <= kinds
            conn.close()

    def test_debug_stacks_and_dump_endpoints(self, params, tmp_path,
                                             monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        with ServingServer(eng, port=0) as srv:
            conn = HTTPConnection(srv.host, srv.port, timeout=30)
            resp, raw = _get(conn, "/debug/stacks")
            assert resp.status == 200
            out = raw.decode()
            assert "pt-serving-pump" in out      # the engine's thread
            assert "pt-serving-http" in out

            resp, raw = _get(conn, "/debug/flightrecorder?dump=1")
            snap = json.loads(raw)
            assert os.path.exists(snap["path"])
            on_disk = json.loads(open(snap["path"]).read())
            assert on_disk["reason"] == "/debug/flightrecorder"
            conn.close()

    def test_batch_spans_carry_no_request_id_but_exist(self, params):
        """Engine-level spans (decode covers the whole batch) are
        recorded too — without a single request's id."""
        flight_recorder.RECORDER.clear()
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        from paddle_tpu.models.llama_serving import Request
        eng.submit(Request("a", [1, 2, 3], max_new_tokens=3))
        eng.run()
        spans = flight_recorder.RECORDER.events(kind="span")
        names = {s["name"] for s in spans}
        if eng.ragged:
            # the ragged engine's one entry point covers prefill AND
            # decode waves — one span name for the whole batch
            assert "serving.unified_step" in names
        else:
            assert "serving.prefill" in names
            assert "serving.decode_step" in names
