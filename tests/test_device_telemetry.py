"""Device telemetry + training health (PR 4): XLA cost/memory analysis
captured at compile time, MFU/roofline gauges, the device-memory
accountant, the jit-safe TrainingHealthMonitor (NaN injection through a
real Trainer step, GradScaler overflow recovery, NaN blame), and the
serving `/metrics` exposure — all on the CPU backend."""
import json
import os
import subprocess
import sys
import time
from http.client import HTTPConnection

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
from paddle_tpu.observability import (compile_telemetry, device_telemetry,
                                      flight_recorder, health)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# cost analysis capture
# ---------------------------------------------------------------------------
class TestCostRegistry:
    def test_tracked_matmul_captures_flops_and_memory(self):
        reg = compile_telemetry.CompileRegistry(warn_after=100)
        costs = device_telemetry.COSTS
        costs.reset()
        f = reg.tracked("unit.matmul")(jax.jit(lambda a, b: a @ b))
        x = jnp.ones((64, 64), jnp.float32)
        f(x, x)
        f(x, x)
        snap = costs.snapshot()["functions"]["unit.matmul"]
        # a 64x64x64 matmul is 2*64^3 = 524288 FLOPs (XLA counts MACs*2)
        assert snap["flops"] >= 2 * 64 ** 3
        assert snap["bytes_accessed"] > 0
        assert snap["argument_bytes"] == 2 * 64 * 64 * 4
        assert snap["output_bytes"] == 64 * 64 * 4
        assert snap["arithmetic_intensity"] > 0
        # issued counters accumulate per CALL, not per compile
        assert snap["calls"] == 2
        assert snap["flops_issued"] == pytest.approx(2 * snap["flops"])
        # the capture landed in the flight recorder
        evs = [e for e in flight_recorder.RECORDER.events(
            kind="device.cost") if e["fn"] == "unit.matmul"]
        assert evs and evs[-1]["flops"] == snap["flops"]

    def test_mfu_gauge_finite_and_in_unit_interval(self):
        costs = device_telemetry.COSTS
        costs.reset()
        reg = compile_telemetry.CompileRegistry(warn_after=100)
        f = reg.tracked("unit.mfu")(jax.jit(lambda a, b: a @ b))
        x = jnp.ones((128, 128), jnp.float32)
        t0 = time.perf_counter()
        jax.block_until_ready(f(x, x))
        step = costs.note_step(time.perf_counter() - t0)
        assert step is not None
        assert np.isfinite(step["mfu"]) and 0 < step["mfu"] <= 1, step
        assert costs.last_mfu == step["mfu"]
        assert costs.peak_mfu >= step["mfu"]
        text = costs.render_prometheus()
        assert "pt_mfu " in text and "pt_roofline_ridge " in text
        assert 'pt_fn_flops{fn="unit.mfu"}' in text

    def test_untracked_window_is_empty(self):
        costs = device_telemetry.COSTS
        costs.reset()
        assert costs.note_step(0.01) is None   # nothing issued

    def test_capture_survives_unjittable_fn(self):
        reg = compile_telemetry.CompileRegistry(warn_after=100)
        f = reg.tracked("unit.plain")(lambda x: x)   # no .lower
        f(jnp.zeros((2,)))
        # no entry exploded; issued accounting simply has no cost
        snap = device_telemetry.COSTS.snapshot()["functions"]
        assert snap.get("unit.plain", {}).get("flops", 0) == 0

    def test_device_generation_cpu_ignores_tpu_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_GEN", "v5p")
        assert device_telemetry.device_generation() == "cpu"
        flops, bw = device_telemetry.device_peaks()
        assert flops == device_telemetry.PEAK_SPECS["cpu"][0]
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "5e11")
        assert device_telemetry.device_peaks()[0] == 5e11


    def test_unknown_tpu_kind_is_an_error_not_a_default(self, monkeypatch):
        class Dev:
            platform = "tpu"
            device_kind = "TPU v9 mega"

        import jax
        monkeypatch.delenv("PADDLE_TPU_GEN", raising=False)
        monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [Dev()])
        with pytest.raises(RuntimeError, match="unknown TPU device_kind"):
            device_telemetry.device_generation()
        Dev.device_kind = "TPU v5 lite"       # what a v5e reports
        assert device_telemetry.device_generation() == "v5e"
        spec = device_telemetry.PEAK_SPECS["v5e"]
        assert (spec.flops, spec.hbm_bw) == (197e12, 8.19e11)
        assert "819 GB/s" in spec.source

    def test_backend_failure_is_not_answered_cpu(self, monkeypatch):
        import jax

        def boom(*a, **k):
            raise RuntimeError("backend init failed")

        monkeypatch.setattr(jax, "local_devices", boom)
        with pytest.raises(RuntimeError, match="backend init failed"):
            device_telemetry.device_generation()
        monkeypatch.setattr(jax, "default_backend", boom)
        import importlib
        from paddle_tpu import device
        with pytest.raises(RuntimeError, match="backend init failed"):
            device.get_device()
        with pytest.raises(RuntimeError, match="backend init failed"):
            importlib.import_module(
                "paddle_tpu.ops.paged_attention")._on_tpu()

    def test_set_device_refuses_a_missing_accelerator(self):
        from paddle_tpu import device
        assert device.set_device("cpu") == "cpu"
        with pytest.raises(ValueError, match="no accelerator"):
            device.set_device("tpu")


# ---------------------------------------------------------------------------
# memory accountant
# ---------------------------------------------------------------------------
class TestMemoryAccountant:
    def test_poll_counts_live_arrays_and_keeps_peak(self):
        acct = device_telemetry.MemoryAccountant(min_interval_s=0.0)
        big = jnp.ones((256, 256), jnp.float32)    # 256 KiB live
        snap = acct.poll(force=True)
        assert snap["live_bytes"] >= big.nbytes
        assert snap["live_arrays"] >= 1
        assert snap["live_peak_bytes"] >= snap["live_bytes"]
        # CPU backend: allocator stats gracefully absent
        assert snap["bytes_in_use"] is None
        buckets = {b["bucket"]: b for b in snap["by_bucket"]}
        assert any("(256, 256)" in k for k in buckets)
        peak_before = snap["live_peak_bytes"]
        del big
        snap2 = acct.poll(force=True)
        assert snap2["live_peak_bytes"] >= peak_before  # high-water holds
        assert snap2["live_bytes"] <= peak_before

    def test_rate_limit_reuses_snapshot(self):
        acct = device_telemetry.MemoryAccountant(min_interval_s=60.0)
        s1 = acct.poll(force=True)
        s2 = acct.poll()               # inside the interval: cached
        assert s2 is s1
        assert acct.poll(force=True) is not s1

    def test_prometheus_has_live_but_not_allocator_gauges_on_cpu(self):
        acct = device_telemetry.MemoryAccountant(min_interval_s=0.0)
        text = acct.render_prometheus()
        assert "pt_device_live_bytes " in text
        assert "pt_device_live_peak_bytes " in text
        assert "pt_device_bytes_in_use" not in text   # None on CPU

    def test_poll_records_flight_event(self):
        flight_recorder.RECORDER.clear()
        pinned = jnp.ones((16, 16))       # keep at least one live array
        device_telemetry.MemoryAccountant(min_interval_s=0.0).poll(
            force=True)
        evs = flight_recorder.RECORDER.events(kind="device.memory")
        assert evs and evs[-1]["live_bytes"] >= pinned.nbytes


# ---------------------------------------------------------------------------
# training health: monitor + NaN injection through a real Trainer step
# ---------------------------------------------------------------------------
def _tiny_trainer(monitor=None, poison=False):
    from paddle_tpu.parallel.trainer import Trainer
    net = nn.Linear(8, 8)
    if poison:
        net.weight._value = net.weight._value.at[0, 0].set(jnp.nan)
    opt = pt.optimizer.SGD(learning_rate=0.01, parameters=net.parameters())

    def loss_fn(model, batch):
        x, y = batch
        d = model(x) - y
        return (d * d).mean()
    tr = Trainer(net, opt, loss_fn, mesh=None, health_monitor=monitor,
                 donate=False)
    batch = (np.ones((4, 8), np.float32), np.zeros((4, 8), np.float32))
    return tr, batch


class TestTrainingHealth:
    def test_clean_step_reports_finite_health(self):
        health.reset()
        mon = health.TrainingHealthMonitor(name="unit")
        tr, batch = _tiny_trainer(mon)
        tr.step(batch)
        rec = mon.last
        assert rec["nonfinite"] == 0
        assert np.isfinite(rec["loss"])
        assert rec["grad_norm"] > 0
        assert 0 < rec["update_ratio"] < 1
        assert health.HEALTH.nonfinite_steps == 0

    def test_nan_injection_increments_counter_and_aborts(self):
        health.reset()
        mon = health.TrainingHealthMonitor(name="unit", abort=True)
        tr, batch = _tiny_trainer(mon, poison=True)
        with pytest.raises(FloatingPointError, match="non-finite"):
            tr.step(batch)
        assert health.HEALTH.nonfinite_steps == 1
        assert "pt_train_nonfinite_total 1" in health.render_prometheus()
        evs = flight_recorder.RECORDER.events(kind="health")
        assert any(e["event"] == "nonfinite" for e in evs)

    def test_non_abort_monitor_counts_without_raising(self):
        health.reset()
        mon = health.TrainingHealthMonitor(name="unit", abort=False)
        tr, batch = _tiny_trainer(mon, poison=True)
        tr.step(batch)
        tr.step(batch)
        assert health.HEALTH.nonfinite_steps == 2

    def test_nan_blame_names_the_poisoned_layer(self):
        health.reset()

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(4, 4)
                self.fc2 = nn.Linear(4, 4)
                self.fc3 = nn.Linear(4, 4)

            def forward(self, x):
                return self.fc3(self.fc2(self.fc1(x)))

        net = Net()
        assert health.nan_blame(net, pt.ones([2, 4])) is None  # clean
        net.fc2.weight._value = \
            net.fc2.weight._value.at[0, 0].set(jnp.nan)
        hit = health.nan_blame(net, pt.ones([2, 4]))
        assert hit == {"layer": "fc2", "class": "Linear",
                       "inputs_finite": True}
        assert health.HEALTH.last_blame == "fc2"
        evs = flight_recorder.RECORDER.events(kind="health")
        assert any(e.get("event") == "nan_blame" and e["layer"] == "fc2"
                   for e in evs)

    def test_nan_blame_flags_poisoned_network_input(self):
        net = nn.Linear(4, 4)
        bad = pt.to_tensor(np.array([[np.nan, 1, 1, 1]], np.float32))
        hit = health.nan_blame(net, bad)
        assert hit is not None and hit["inputs_finite"] is False

    def test_grad_scaler_overflow_recovers_and_reports(self):
        health.reset()
        from paddle_tpu.amp.grad_scaler import GradScaler
        lin = nn.Linear(4, 4)
        opt = pt.optimizer.SGD(learning_rate=0.1,
                               parameters=lin.parameters())
        sc = GradScaler(init_loss_scaling=2.0 ** 15,
                        decr_every_n_nan_or_inf=1)
        x = pt.ones([2, 4])
        w0 = np.asarray(lin.weight._value).copy()
        # scaled loss overflows fp32 → grads inf → step skipped
        sc.scale((lin(x) * 1e36).sum()).backward()
        sc.step(opt)
        sc.update()
        assert sc.found_inf_steps == 1
        assert sc._scale == 2.0 ** 14          # backed off
        assert np.allclose(np.asarray(lin.weight._value), w0)
        assert health.HEALTH.found_inf_steps == 1
        assert "pt_amp_found_inf_total 1" in health.render_prometheus()
        # next clean step applies: the scaler recovered
        opt.clear_grad()
        sc.scale(lin(x).sum()).backward()
        sc.step(opt)
        sc.update()
        assert not np.allclose(np.asarray(lin.weight._value), w0)
        assert sc.found_inf_steps == 1         # no new skip

    def test_check_numerics_is_traced_safe(self):
        """Inside jit the old implementation raised
        TracerArrayConversionError (np.asarray on a tracer); it must
        now trace cleanly and report the count asynchronously."""
        from paddle_tpu._core.tensor import Tensor
        from paddle_tpu.amp import debugging as D
        health.reset()

        @jax.jit
        def f(x):
            D.check_numerics(Tensor(x), var_name="probe")
            return x * 2
        jax.block_until_ready(f(jnp.array([1.0, jnp.nan])))
        deadline = time.time() + 5
        while health.HEALTH.nonfinite_steps == 0 and time.time() < deadline:
            time.sleep(0.01)       # debug.callback is async
        assert health.HEALTH.nonfinite_steps == 1
        # eager semantics unchanged: raises with counts
        with pytest.raises(FloatingPointError, match="nan=1"):
            D.check_numerics(pt.to_tensor(np.array([1.0, np.nan])))

    def test_watchdog_check_finite_single_transfer(self):
        from paddle_tpu.utils.watchdog import check_finite
        assert check_finite({"a": pt.ones([2]), "b": pt.ones([3])})
        with pytest.raises(FloatingPointError, match="leaf indices"):
            check_finite([pt.ones([2]), pt.to_tensor([np.inf])])

    def test_watchdog_hang_dumps_flight_recorder(self, tmp_path,
                                                 monkeypatch, capsys):
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        from paddle_tpu.utils.watchdog import HangWatchdog
        wd = HangWatchdog(timeout_s=0.01, name="unit-hang")
        wd._default_on_hang()
        out = capsys.readouterr().out
        assert "flight recorder dumped to" in out
        assert "MainThread" in out             # thread stacks printed
        dumps = list(tmp_path.glob("pt_flightrecorder-*.json"))
        assert dumps
        doc = json.loads(dumps[0].read_text())
        assert doc["reason"] == "watchdog:unit-hang"
        assert any(e["kind"] == "watchdog.hang" for e in doc["events"])


# ---------------------------------------------------------------------------
# hapi fit record: accountant bytes + MFU gauge
# ---------------------------------------------------------------------------
class TestHapiStepRecord:
    def test_fit_record_carries_memory_and_mfu(self):
        from paddle_tpu.hapi.model import Model
        recorded = []
        logger = __import__(
            "paddle_tpu.observability.logging",
            fromlist=["get_logger"]).get_logger("hapi")
        orig = logger.event

        def spy(event, **fields):
            if event == "train.step":
                recorded.append(fields)
            return orig(event, **fields)
        logger.event = spy
        try:
            net = nn.Linear(4, 2)
            model = Model(net)
            model.prepare(
                optimizer=pt.optimizer.SGD(learning_rate=0.01,
                                           parameters=net.parameters()),
                loss=lambda out, y: ((out - y) ** 2).mean())
            xs = np.ones((8, 4), np.float32)
            ys = np.zeros((8, 2), np.float32)
            data = [(xs[i], ys[i]) for i in range(8)]
            model.fit(data, batch_size=2, epochs=1, log_freq=2, verbose=0)
        finally:
            logger.event = orig
        assert recorded, "no train.step records emitted"
        rec = recorded[-1]
        assert rec["live_device_bytes"] > 0
        assert rec["hbm_peak_bytes"] >= rec["live_device_bytes"]
        assert "mfu" in rec and np.isfinite(rec["mfu"])
        assert rec["mfu"] >= 0


# ---------------------------------------------------------------------------
# serving /metrics exposure (acceptance e2e)
# ---------------------------------------------------------------------------
from paddle_tpu.models.llama import LlamaConfig          # noqa: E402
from paddle_tpu.models import llama_spmd as M            # noqa: E402
from paddle_tpu.models.llama_serving import ServingEngine  # noqa: E402
from paddle_tpu.serving import ServingServer             # noqa: E402

# hidden=48/ffn=96 is deliberately UNIQUE among the test suite's tiny
# configs: the compile registry is process-global, and a config shape
# another test already compiled would make this test's reset() orphan
# the signature (no compile observed → no cost captured → pt_mfu 0)
CFG = LlamaConfig.tiny(vocab=64, hidden=48, layers=2, heads=4, kv_heads=2,
                       ffn=96, seq=128)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


def _metric_value(text, name):
    rows = [l for l in text.splitlines() if l.startswith(name + " ")]
    assert rows, f"{name} not exposed"
    return float(rows[0].split()[1])


class TestServingDeviceTelemetry:
    def test_request_yields_mfu_and_device_gauges(self, params):
        device_telemetry.reset()
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        with ServingServer(eng, port=0) as srv:
            conn = HTTPConnection(srv.host, srv.port, timeout=60)
            conn.request(
                "POST", "/v1/completions",
                body=json.dumps({"prompt": [1, 5, 9, 3],
                                 "max_tokens": 4}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            resp.read()
            conn.request("GET", "/metrics")
            text = conn.getresponse().read().decode()
            mfu = _metric_value(text, "pt_mfu")
            assert np.isfinite(mfu) and 0 < mfu <= 1
            assert _metric_value(text, "pt_mfu_peak") >= mfu
            assert _metric_value(text, "pt_step_flops") > 0
            assert _metric_value(text, "pt_roofline_intensity") > 0
            assert _metric_value(text, "pt_device_live_bytes") > 0
            assert _metric_value(text, "pt_device_live_peak_bytes") > 0
            assert _metric_value(text, "pt_train_nonfinite_total") >= 0
            # per-entry-point cost rows for the engine's jit fns —
            # ragged engines (the default) run everything through
            # unified_step, bucketed ones through decode_step
            fn = "serving.unified_step" if eng.ragged \
                else "serving.decode_step"
            assert f'pt_fn_flops{{fn="{fn}"}}' in text
            assert f'pt_fn_hbm_bytes{{fn="{fn}"}}' in text
            # JSON snapshot carries both halves
            conn.request("GET", "/metrics?format=json")
            snap = json.loads(conn.getresponse().read())
            # text exposition renders %.6g — compare at that precision
            assert snap["pt_device"]["cost"]["mfu"] == pytest.approx(
                mfu, rel=1e-4)
            assert snap["pt_device"]["memory"]["live_bytes"] > 0
            assert "nonfinite_steps" in snap["pt_health"]
            fns = snap["pt_device"]["cost"]["functions"]
            assert fns[fn]["flops"] > 0
            conn.close()


# ---------------------------------------------------------------------------
# ptdump renders the new record kinds
# ---------------------------------------------------------------------------
class TestPtdumpDeviceRecords:
    def test_pretty_prints_cost_memory_and_health(self, tmp_path):
        rec = flight_recorder.FlightRecorder(capacity=32, enabled=True)
        rec.record("device.cost", fn="serving.decode_step",
                   flops=1.23e9, bytes_accessed=4.5e8,
                   argument_bytes=1 << 20, output_bytes=1 << 18,
                   temp_bytes=1 << 16, generated_code_bytes=0)
        rec.record("device.memory", live_bytes=300 << 20,
                   live_arrays=42, live_peak_bytes=512 << 20,
                   bytes_in_use=None, bytes_limit=None)
        rec.record("health", event="nonfinite", where="train",
                   source="monitor", count=3)
        rec.record("health", event="nan_blame", layer="blocks.3.mlp",
                   **{"class": "Linear", "inputs_finite": True})
        path = rec.dump(str(tmp_path / "fr.json"))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "ptdump.py"),
             path], capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        out = proc.stdout
        assert "cost serving.decode_step: 1.23GFLOP" in out
        assert "device memory" in out and "300.0MiB" in out
        assert "health: 2 incidents" in out
        assert "last blame: blocks.3.mlp" in out
