"""Telemetry pulse plane (ISSUE 15): ring time-series over the metrics
registry, /debug/pulse JSON + SSE exposure, anomaly-triggered capture
bundles, and the satellite hardening that rode along — Prometheus
label-value escaping, /debug query-parsing 400s, process-start-time /
scrape-self-cost gauges, and the ptop / ptdump-bundle renderers.

The acceptance scenario runs over REAL HTTP with the pipelined pump: a
PT_FAULTS-style injected stall must appear as a spike in the pulse
step-time series and land EXACTLY ONE capture bundle whose flight dump
and pulse window both carry the triggering request's trace id — and
PT_SERVE_PULSE=0 must produce token-identical outputs with zero extra
threads.
"""
import importlib.util
import io
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import jax.numpy as jnp
import pytest

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama_serving import ServingEngine
from paddle_tpu.serving import (FaultPlan, MetricsRegistry,
                                RequestScheduler, Router, ServingClient,
                                ServingHTTPError, ServingServer,
                                build_replicas)
from paddle_tpu.serving.metrics import EngineMetrics
from paddle_tpu.observability.pulse import (PulsePlane, PulseRing,
                                            PulseSampler,
                                            _windowed_percentile)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PTDUMP = os.path.join(_ROOT, "tools", "ptdump.py")

CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)
PAGE = 8


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


def _engine(params, faults=None, **kw):
    kw.setdefault("max_seqs", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("use_pallas", False)
    kw.setdefault("prefix_cache", True)
    return ServingEngine(params, CFG, faults=faults, **kw)


def _load_ptop():
    spec = importlib.util.spec_from_file_location(
        "ptop", os.path.join(_ROOT, "tools", "ptop.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# sampler unit: snapshots in, ring series out
# ---------------------------------------------------------------------------
class TestSamplerUnit:
    def test_ring_bounded_and_windowed(self):
        r = PulseRing(4)
        for i in range(10):
            r.append(float(i), i * 10)
        assert len(r) == 4
        assert r.window() == [[6.0, 60], [7.0, 70], [8.0, 80],
                              [9.0, 90]]
        assert r.window(since=8.0) == [[8.0, 80], [9.0, 90]]
        assert r.last() == (9.0, 90)

    def test_gauge_samples_and_counter_rates(self):
        s = PulseSampler(depth=8)
        snap1 = {"g": {"type": "gauge", "value": 2.0},
                 "c": {"type": "counter", "value": 10.0}}
        snap2 = {"g": {"type": "gauge", "value": 3.0},
                 "c": {"type": "counter", "value": 30.0}}
        s.sample(snap1, t=100.0)
        s.sample(snap2, t=102.0)
        out = s.series()
        assert [v for _, v in out["g"]] == [2.0, 3.0]
        # first sample has no delta; the second books (30-10)/2s
        assert [v for _, v in out["c:rate"]] == [10.0]

    def test_counter_reset_clamps_to_zero(self):
        s = PulseSampler(depth=8)
        s.sample({"c": {"type": "counter", "value": 50.0}}, t=0.0)
        s.sample({"c": {"type": "counter", "value": 5.0}}, t=1.0)
        assert [v for _, v in s.series()["c:rate"]] == [0.0]

    def test_histogram_windowed_percentiles_and_carry(self):
        s = PulseSampler(depth=8)
        h1 = {"type": "histogram", "count": 0, "sum": 0.0,
              "buckets": {"0.1": 0, "1": 0, "+Inf": 0}}
        # 10 observations land in (0.1, 1] between t0 and t1
        h2 = {"type": "histogram", "count": 10, "sum": 5.0,
              "buckets": {"0.1": 0, "1": 10, "+Inf": 10}}
        s.sample({"h": h1}, t=0.0)   # first sample: no window yet
        s.sample({"h": h2}, t=1.0)
        s.sample({"h": h2}, t=2.0)   # idle interval: carries forward
        p50 = [v for _, v in s.series()["h:p50"]]
        assert p50[0] == pytest.approx(0.1 + 0.9 * 0.5)
        assert p50[1] == p50[0]      # carried, not zeroed
        assert len(p50) == 2
        assert "h:p99" in s.series()

    def test_windowed_percentile_inf_is_lower_bound(self):
        prev = {"1": 0, "+Inf": 0}
        cur = {"1": 0, "+Inf": 4}    # everything past the last edge
        v, n = _windowed_percentile(prev, cur, 50)
        assert (v, n) == (1.0, 4)
        assert _windowed_percentile(cur, cur, 50) == (None, 0)

    def test_goodput_composite(self):
        s = PulseSampler(depth=8)

        def snap(total, good):
            return {"pt_tokens": {"type": "counter", "value": total},
                    "pt_goodput_tokens": {"type": "counter",
                                          "value": good}}
        s.sample(snap(0, 0), t=0.0)       # idle: no evidence -> 1.0
        s.sample(snap(10, 5), t=1.0)      # half the window was badput
        s.sample(snap(10, 5), t=2.0)      # idle again: carries 0.5
        assert [v for _, v in s.series()["goodput_ratio"]] == \
            [1.0, 0.5, 0.5]

    def test_series_prefix_filter_and_window(self):
        s = PulseSampler(depth=8)
        s.sample({"pt_a": {"type": "gauge", "value": 1.0},
                  "pt_b": {"type": "gauge", "value": 2.0}}, t=100.0)
        s.sample({"pt_a": {"type": "gauge", "value": 3.0},
                  "pt_b": {"type": "gauge", "value": 4.0}}, t=200.0)
        only_a = s.series(signals=["pt_a"], now=200.0)
        assert set(only_a) == {"pt_a"}
        recent = s.series(window=50, now=200.0)
        assert [v for _, v in recent["pt_b"]] == [4.0]


# ---------------------------------------------------------------------------
# satellite: Prometheus label-value escaping + new process gauges
# ---------------------------------------------------------------------------
class TestMetricsSatellites:
    def test_label_values_escaped(self):
        reg = MetricsRegistry()
        c = reg.counter("pt_esc", "escaping regression",
                        labels={"path": 'a"b\\c\nd'})
        c.inc()
        text = reg.render_prometheus()
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("pt_esc_total{"))
        # spec: backslash -> \\, quote -> \", newline -> literal \n —
        # and the raw newline must NOT split the exposition line
        assert line == 'pt_esc_total{path="a\\"b\\\\c\\nd"} 1'
        assert "\n".join(text.splitlines()) == text.rstrip("\n")

    def test_escaping_roundtrip_keeps_snapshot_key_stable(self):
        reg = MetricsRegistry()
        reg.counter("pt_esc", "", labels={"k": 'v"1'}).inc(2)
        snap = reg.snapshot()
        key = 'pt_esc{k="v\\"1"}'
        assert key in snap and snap[key]["value"] == 2

    def test_process_start_time_and_scrape_self_gauges(self):
        m = EngineMetrics(MetricsRegistry())
        snap = m.registry.snapshot()
        start = snap["pt_process_start_time_seconds"]
        assert start["type"] == "gauge"
        # a plausible wall-clock stamp: after 2020, not in the future
        assert 1577836800 < start["value"] <= time.time() + 1
        assert snap["pt_scrape_self_seconds"]["type"] == "gauge"
        m.observe_scrape_self(0.25)
        snap = m.registry.snapshot()
        assert snap["pt_scrape_self_seconds"]["value"] == \
            pytest.approx(0.25)


# ---------------------------------------------------------------------------
# plane unit: triggers + capture bundles, no engine, no threads
# ---------------------------------------------------------------------------
def _mk_plane(tmp_path, snaps, info=None, **kw):
    """A thread-less plane over a scripted snapshot sequence."""
    it = iter(snaps)
    kw.setdefault("capture_dir", str(tmp_path))
    kw.setdefault("capture_min_s", 600.0)
    kw.setdefault("interval_s", 0.01)
    return PulsePlane(
        lambda: next(it),
        info_fn=lambda: dict(info or {}),
        recent_fn=lambda n: [{"rid": "r1", "trace_id": "req-t1",
                              "state": "done"}],
        start_thread=False, **kw)


def _ctr(v):
    return {"type": "counter", "value": float(v)}


class TestPlaneTriggersAndBundles:
    def test_stall_trigger_writes_one_tagged_bundle(self, tmp_path):
        snaps = [{"pt_step_anomalies": _ctr(0)},
                 {"pt_step_anomalies": _ctr(1)},
                 {"pt_step_anomalies": _ctr(2)}]
        plane = _mk_plane(tmp_path, snaps,
                          info={"trace_ids": ["req-t1"],
                                "breaker_open": False})
        plane.tick()                 # baseline only, never triggers
        assert plane.triggers["step_stall"] == 0
        plane.tick()                 # delta -> trigger -> bundle
        plane.tick()                 # second delta: rate-limited out
        assert plane.triggers["step_stall"] == 2
        assert len(plane.bundles) == 1
        bdir = plane.bundles[0]
        files = sorted(os.listdir(bdir))
        assert files == ["config.json", "flight.json", "meta.json",
                         "metrics.json", "pulse.json", "requests.json"]
        meta = json.load(open(os.path.join(bdir, "meta.json")))
        assert meta["trigger"] == "step_stall"
        assert meta["trace_ids"] == ["req-t1"]
        # the pulse window is self-describing: it embeds the trigger
        pulse = json.load(open(os.path.join(bdir, "pulse.json")))
        assert pulse["trigger"]["trigger"] == "step_stall"
        assert pulse["trigger"]["trace_ids"] == ["req-t1"]
        reqs = json.load(open(os.path.join(bdir, "requests.json")))
        assert reqs["requests"][0]["trace_id"] == "req-t1"
        cfgdoc = json.load(open(os.path.join(bdir, "config.json")))
        assert cfgdoc["pid"] == os.getpid() and "env" in cfgdoc

    def test_slo_burst_needs_threshold(self, tmp_path):
        snaps = [{"pt_slo_violated{a=\"b\"}": _ctr(0)},
                 {"pt_slo_violated{a=\"b\"}": _ctr(2)},   # < burst
                 {"pt_slo_violated{a=\"b\"}": _ctr(5)}]   # >= burst
        plane = _mk_plane(tmp_path, snaps, slo_burst=3)
        plane.tick()
        plane.tick()
        assert plane.triggers["slo_burst"] == 0
        plane.tick()
        assert plane.triggers["slo_burst"] == 1

    def test_breaker_open_edge_triggers_once(self, tmp_path):
        info = {"breaker_open": False}
        plane = PulsePlane(lambda: {}, info_fn=lambda: dict(info),
                           capture_dir=str(tmp_path),
                           interval_s=0.01, start_thread=False)
        plane.tick()
        info["breaker_open"] = True
        plane.tick()                 # False -> True edge
        plane.tick()                 # still True: no re-trigger
        assert plane.triggers["breaker_open"] == 1

    def test_trigger_accounting_runs_under_the_plane_lock(self):
        """Regression (found by tpuracer's TPL008 pass): the counter-
        delta pass and the `triggers[trig] += 1` read-modify-write used
        to run OUTSIDE self._lock, so the pulse daemon racing an
        opportunistic scrape tick could lose fires. Pin the fix: every
        trigger-dict write happens with the lock held."""
        plane = PulsePlane(lambda: {}, interval_s=3600.0,
                           start_thread=False)
        locked_writes = []

        class Guarded(dict):
            def __setitem__(self, key, value):
                locked_writes.append(plane._lock.locked())
                super().__setitem__(key, value)

        plane.triggers = Guarded(plane.triggers)
        plane._check_triggers({"pt_step_anomalies": _ctr(0)})  # baseline
        plane._check_triggers({"pt_step_anomalies": _ctr(2)})
        assert plane.triggers["step_stall"] == 1
        assert locked_writes == [True]

    def test_payload_triggers_are_a_snapshot(self, tmp_path):
        plane = _mk_plane(tmp_path, [{}])
        doc = plane.payload()
        doc["triggers"]["step_stall"] = 99
        doc["bundles"].append("bogus")
        assert plane.triggers["step_stall"] == 0
        assert plane.bundles == []

    def test_no_capture_dir_means_no_bundles(self, tmp_path):
        snaps = [{"pt_engine_restarts": _ctr(0)},
                 {"pt_engine_restarts": _ctr(1)}]
        plane = _mk_plane(tmp_path, snaps, capture_dir=None)
        plane.capture_dir = None
        plane.tick()
        plane.tick()
        assert plane.triggers["engine_restart"] == 1
        assert plane.bundles == []

    def test_capture_max_bounds_bundle_count(self, tmp_path):
        n = 5
        snaps = [{"pt_step_anomalies": _ctr(i)} for i in range(n + 1)]
        plane = _mk_plane(tmp_path, snaps, capture_max=2,
                          capture_min_s=0.0)
        for _ in range(n + 1):
            plane.tick()
        assert plane.triggers["step_stall"] == n
        assert len(plane.bundles) == 2

    def test_ptdump_renders_bundle_narrative(self, tmp_path):
        snaps = [{"pt_step_anomalies": _ctr(0),
                  "pt_serving_step_seconds": {
                      "type": "histogram", "count": 0, "sum": 0.0,
                      "buckets": {"0.1": 0, "+Inf": 0}}},
                 {"pt_step_anomalies": _ctr(1),
                  "pt_serving_step_seconds": {
                      "type": "histogram", "count": 3, "sum": 0.9,
                      "buckets": {"0.1": 0, "+Inf": 3}}}]
        plane = _mk_plane(tmp_path, snaps,
                          info={"trace_ids": ["req-t1"]})
        plane.tick()
        plane.tick()
        [bdir] = plane.bundles
        for argv in ([PTDUMP, "bundle", bdir], [PTDUMP, bdir]):
            proc = subprocess.run([sys.executable, *argv],
                                  capture_output=True, text=True,
                                  timeout=60)
            assert proc.returncode == 0, proc.stderr
            assert "capture bundle" in proc.stdout
            assert "trigger: step_stall" in proc.stdout
            assert "req-t1" in proc.stdout
            assert "flight recorder dump" in proc.stdout

    def test_ptop_renders_recorded_payload(self, tmp_path):
        snaps = [{"pt_q": {"type": "gauge", "value": float(i)},
                  "pt_step_anomalies": _ctr(0)} for i in range(6)]
        plane = _mk_plane(tmp_path, snaps)
        for _ in range(6):
            plane.tick()
        f = tmp_path / "pulse.json"
        f.write_text(json.dumps(plane.payload()))
        ptop = _load_ptop()
        out = io.StringIO()
        rc = ptop.main(["--file", str(f), "--once", "--no-color"],
                       out=out)
        text = out.getvalue()
        assert rc == 0
        assert "pt_q" in text and "pt_step_anomalies:rate" in text
        assert any(ch in text for ch in ptop.BARS)

    def test_ptop_renders_router_columns_and_highlights(self):
        ptop = _load_ptop()
        mk = lambda anom: {
            "enabled": True, "interval_s": 1.0,
            "signals": {"pt_serving_queue_depth": [[1.0, 2], [2.0, 3]],
                        "pt_step_anomalies:rate": [[2.0, anom]]},
            "triggers": {"step_stall": int(anom)}, "bundles": []}
        out = io.StringIO()
        ptop.render({"enabled": True,
                     "replicas": {"r0": mk(0), "r1": mk(1)}}, out=out)
        text = out.getvalue()
        assert "r0" in text and "r1" in text
        assert "pt_serving_queue_depth" in text
        assert "triggers step_stall=1" in text
        out = io.StringIO()
        ptop.render({"enabled": False}, out=out)
        assert "disabled" in out.getvalue()


# ---------------------------------------------------------------------------
# HTTP: /debug hardening (400s, never 500s) + pulse exposure
# ---------------------------------------------------------------------------
class TestDebugEndpoints:
    @pytest.fixture()
    def served(self, params, monkeypatch, tmp_path):
        monkeypatch.setenv("PT_PULSE_INTERVAL_S", "0.05")
        monkeypatch.setenv("PT_CAPTURE_DIR", str(tmp_path / "caps"))
        monkeypatch.delenv("PT_SERVE_PULSE", raising=False)
        sched = RequestScheduler(_engine(params), max_queue=8,
                                 metrics=MetricsRegistry())
        srv = ServingServer(sched, port=0).start()
        yield srv, sched, ServingClient(port=srv.port)
        srv.stop(drain=False, timeout=30)

    def _get(self, srv, path):
        conn = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=30)
        return conn.status, json.loads(conn.read().decode())

    def test_bad_query_values_are_400_not_500(self, served):
        srv, _, cl = served
        for path in ("/debug/requests?last=abc",
                     "/debug/requests?last=1.5",
                     "/debug/flightrecorder?dump=yes",
                     "/debug/pulse?window=abc",
                     "/debug/pulse?count=x&stream=1"):
            with pytest.raises(ServingHTTPError) as ei:
                cl._json_call("GET", path)
            assert ei.value.status == 400, path
            assert "bad request" in str(ei.value), path

    def test_good_queries_still_work(self, served):
        srv, _, cl = served
        cl.complete([1, 2, 3], max_tokens=2)
        assert cl.debug_requests(last=5)["requests"]
        st, doc = self._get(srv, "/debug/flightrecorder?dump=0")
        assert st == 200 and "events" in doc

    def test_debug_pulse_json_and_filter(self, served):
        srv, sched, cl = served
        cl.complete([1, 2, 3], max_tokens=4)
        sched._pulse.tick()
        doc = cl.debug_pulse()
        assert doc["enabled"] is True
        assert doc["interval_s"] == pytest.approx(0.05)
        assert any(k.startswith("pt_serving_queue_depth")
                   for k in doc["signals"])
        only = cl.debug_pulse(signals=["goodput_ratio"])
        assert set(only["signals"]) == {"goodput_ratio"}

    def test_pulse_sse_stream_bounded_by_count(self, served):
        srv, _, cl = served
        cl.complete([1, 2, 3], max_tokens=2)
        events = []
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/debug/pulse?stream=1&count=2",
            timeout=30)
        assert resp.headers["Content-Type"] == "text/event-stream"
        for raw in resp:
            line = raw.decode().strip()
            if line.startswith("data: "):
                events.append(json.loads(line[len("data: "):]))
        assert len(events) == 2
        assert all(e["enabled"] for e in events)

    def test_metrics_scrape_rides_sampling(self, served):
        srv, sched, cl = served
        cl.complete([1, 2, 3], max_tokens=2)
        time.sleep(0.06)            # let the dedup interval lapse
        text = cl.metrics_text()
        assert "pt_process_start_time_seconds" in text
        assert "pt_scrape_self_seconds" in text
        assert 'pt_serving_slots{kind="decode"}' in text
        assert 'pt_serving_queue_depth_priority{priority="normal"}' \
            in text
        assert len(sched._pulse.sampler.series()) > 0


# ---------------------------------------------------------------------------
# the acceptance: stall over real HTTP -> spike + one tagged bundle
# ---------------------------------------------------------------------------
class TestStallCaptureE2E:
    def test_injected_stall_spikes_and_bundles(self, params,
                                               monkeypatch, tmp_path):
        cap = tmp_path / "caps"
        monkeypatch.setenv("PT_SERVE_PULSE", "1")
        monkeypatch.setenv("PT_PULSE_INTERVAL_S", "0.05")
        monkeypatch.setenv("PT_CAPTURE_DIR", str(cap))
        monkeypatch.setenv("PT_CAPTURE_MIN_S", "600")
        # the drill: one device-step launch delayed 0.5s, well past
        # the sentinel's band, after its 20-step warmup has settled
        sched = RequestScheduler(
            _engine(params, faults=FaultPlan(
                "step_launch:delay@30:delay=0.5")),
            max_queue=8, metrics=MetricsRegistry())
        srv = ServingServer(sched, port=0).start()
        try:
            cl = ServingClient(port=srv.port, timeout=300)
            r = cl.complete([1, 5, 9], max_tokens=60)
            trace_id = r["trace_id"]
            assert trace_id and len(r["tokens"]) == 60
            # deterministic close: drain the sentinel + judge triggers
            sched._pulse.tick()
            payload = cl.debug_pulse()
        finally:
            srv.stop(drain=False, timeout=60)

        # the stall is visible in the ring: p99 spikes over the median
        series = payload["signals"]["pt_serving_step_seconds:p99"]
        vals = [v for _, v in series if v]
        assert max(vals) >= 0.5, series
        assert max(vals) > 3 * sorted(vals)[len(vals) // 2]
        assert payload["triggers"]["step_stall"] >= 1

        # exactly one bundle (rate limit), tagged with the trace id
        bundles = sorted(cap.iterdir())
        assert len(bundles) == 1, bundles
        bdir = str(bundles[0])
        assert "step_stall" in os.path.basename(bdir)
        pulse = json.load(open(os.path.join(bdir, "pulse.json")))
        assert trace_id in pulse["trigger"]["trace_ids"]
        flight_text = open(os.path.join(bdir, "flight.json")).read()
        assert trace_id in flight_text
        assert "anomaly.step_stall" in flight_text

        # both tools render the drill's artifacts
        ptop = _load_ptop()
        f = tmp_path / "pulse.json"
        f.write_text(json.dumps(payload))
        out = io.StringIO()
        assert ptop.main(["--file", str(f), "--once", "--no-color"],
                         out=out) == 0
        assert "pt_serving_step_seconds:p99" in out.getvalue()
        assert "triggers step_stall=" in out.getvalue()
        proc = subprocess.run(
            [sys.executable, PTDUMP, "bundle", bdir],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        assert "trigger: step_stall" in proc.stdout
        assert trace_id in proc.stdout

    def test_pulse_off_is_token_identical_and_threadless(
            self, params, monkeypatch):
        prompt, kw = [2, 7, 11], {"max_new_tokens": 12}

        def run():
            sched = RequestScheduler(_engine(params), max_queue=4,
                                     metrics=MetricsRegistry())
            plane = sched._pulse
            try:
                return sched.submit(prompt, **kw).result(timeout=600), \
                    plane
            finally:
                sched.shutdown(drain=True, timeout=60)

        monkeypatch.setenv("PT_SERVE_PULSE", "1")
        on_tokens, on_plane = run()
        assert on_plane is not None and not on_plane.thread_alive

        monkeypatch.setenv("PT_SERVE_PULSE", "0")
        before = {t.name for t in threading.enumerate()}
        off_tokens, off_plane = run()
        after = {t.name for t in threading.enumerate()}
        assert off_plane is None
        assert not any(n.startswith("pt-pulse") for n in after - before)
        assert off_tokens == on_tokens      # token-identical

    def test_pulse_off_debug_endpoint_says_disabled(self, params,
                                                    monkeypatch):
        monkeypatch.setenv("PT_SERVE_PULSE", "0")
        sched = RequestScheduler(_engine(params), max_queue=4,
                                 metrics=MetricsRegistry())
        srv = ServingServer(sched, port=0).start()
        try:
            cl = ServingClient(port=srv.port)
            assert cl.debug_pulse() == {"enabled": False}
        finally:
            srv.stop(drain=False, timeout=30)


# ---------------------------------------------------------------------------
# router aggregation: one payload per replica, TPL004-clean
# ---------------------------------------------------------------------------
class TestRouterPulse:
    def test_router_aggregates_per_replica(self, params, monkeypatch):
        monkeypatch.setenv("PT_SERVE_PULSE", "1")
        monkeypatch.setenv("PT_PULSE_INTERVAL_S", "0.05")
        monkeypatch.delenv("PT_CAPTURE_DIR", raising=False)
        reps = build_replicas(lambda i: _engine(params), 2,
                              max_queue=8)
        router = Router(reps)
        srv = ServingServer(router, port=0).start()
        try:
            cl = ServingClient(port=srv.port)
            cl.complete([1, 2, 3], max_tokens=2)
            for rep in reps:
                rep.scheduler._pulse.tick()
            doc = cl.debug_pulse()
            assert doc["enabled"] is True
            assert set(doc["replicas"]) == \
                {r.replica_id for r in reps}
            for rid, p in doc["replicas"].items():
                assert p["enabled"] and p["signals"], rid
        finally:
            srv.stop(drain=False, timeout=30)
