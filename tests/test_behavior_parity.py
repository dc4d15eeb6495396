"""Value-level tests for the r2 parity tail (VERDICT r3 item 9):
symbols previously covered only by hasattr/import checks now get
behavioral assertions — EMA decay math, static program serialization
round-trips executed through the Executor, exact AUC, hapi callback
semantics, profiler trace export."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt


class TestEMA:
    def test_incubate_ema_decay_math(self):
        """Shadow values follow s = d*s + (1-d)*p exactly; apply/restore
        swap and restore the live parameters."""
        from paddle_tpu.incubate.optimizer import ExponentialMovingAverage
        net = pt.nn.Linear(3, 2)
        d = 0.9
        ema = ExponentialMovingAverage(net.parameters(), decay=d)
        w0 = net.weight.numpy().copy()

        shadow = w0.copy()
        for step in range(3):
            with pt.no_grad() if hasattr(pt, "no_grad") else _noop():
                net.weight.set_value(net.weight.numpy() + 1.0)
            ema.update()
            shadow = d * shadow + (1 - d) * net.weight.numpy()
        live = net.weight.numpy().copy()
        assert not np.allclose(shadow, live)

        with ema.apply(net):
            assert np.allclose(net.weight.numpy(), shadow, atol=1e-6), \
                "apply() must install the decayed shadow weights"
        assert np.allclose(net.weight.numpy(), live, atol=1e-6), \
            "restore must put the live weights back"

    def test_static_ema_parity_surface(self):
        from paddle_tpu.static import ExponentialMovingAverage as SEMA
        assert callable(SEMA)


def _noop():
    import contextlib
    return contextlib.nullcontext()


class TestStaticProgramSerialization:
    def test_serialize_deserialize_roundtrip_runs(self):
        """serialize_program -> bytes -> deserialize_program preserves
        every variable's VALUES (not just names)."""
        import paddle_tpu.static as static
        with static.program_guard(static.Program(), static.Program()):
            x = static.data("x", [4], "float32")
            w = pt.to_tensor(np.arange(4, dtype=np.float32))
            prog = static.default_main_program()
            prog._register("w", w, trainable=True)
            data = static.serialize_program([x], [w], prog)
            prog2 = static.deserialize_program(data)
            assert "w" in prog2._vars
            assert np.allclose(prog2._vars["w"].numpy(),
                               np.arange(4, dtype=np.float32))

    def test_save_load_inference_model_file_roundtrip(self, tmp_path):
        import paddle_tpu.static as static
        with static.program_guard(static.Program(), static.Program()):
            x = static.data("x", [4], "float32")
            w = pt.to_tensor(np.array([1.0, 2.0, 3.0, 4.0], np.float32))
            prog = static.default_main_program()
            prog._register("w", w, trainable=True)
            prefix = str(tmp_path / "model")
            static.save_inference_model(prefix, [x], [w], program=prog)
            assert os.path.exists(prefix + ".pdmodel")
            assert os.path.exists(prefix + ".pdiparams")
            prog2, feeds, fetches = static.load_inference_model(prefix)
            assert np.allclose(prog2._vars["w"].numpy(),
                               [1.0, 2.0, 3.0, 4.0])


class TestAucExact:
    def test_auc_matches_manual_roc(self):
        """Auc must equal the exact pairwise ROC-AUC statistic, not just
        land in [0, 1]."""
        rng = np.random.RandomState(0)
        scores = rng.rand(64)
        labels = (rng.rand(64) < 0.4).astype(np.int64)
        auc = pt.metric.Auc(num_thresholds=4095)
        auc.update(np.stack([1 - scores, scores], 1), labels)
        got = auc.accumulate()
        pos = scores[labels == 1]
        neg = scores[labels == 0]
        cmp = (pos[:, None] > neg[None, :]).sum() + \
            0.5 * (pos[:, None] == neg[None, :]).sum()
        exact = cmp / (len(pos) * len(neg))
        assert abs(got - exact) < 2e-3, (got, exact)


class TestHapiCallbacks:
    def _fit(self, cbs, epochs=6):
        from paddle_tpu.io import DataLoader, TensorDataset
        rng = np.random.RandomState(0)
        x = rng.randn(32, 4).astype(np.float32)
        y = rng.randint(0, 2, (32, 1))
        net = pt.nn.Sequential(pt.nn.Linear(4, 8), pt.nn.ReLU(),
                               pt.nn.Linear(8, 2))
        model = pt.Model(net)
        model.prepare(pt.optimizer.SGD(0.0, parameters=net.parameters()),
                      pt.nn.CrossEntropyLoss(), pt.metric.Accuracy())
        loader = DataLoader(TensorDataset([x, y]), batch_size=16)
        model.fit(loader, loader, epochs=epochs, callbacks=cbs, verbose=0)
        return model

    def test_early_stopping_stops(self):
        """lr=0 -> eval loss is constant -> patience=1 must stop long
        before the epoch budget."""
        es = pt.callbacks.EarlyStopping(monitor="loss", patience=1,
                                        mode="min")
        self._fit([es], epochs=10)
        assert getattr(es, "stopped_epoch", 0) < 9, \
            "EarlyStopping never fired on a flat loss"

    def test_model_checkpoint_writes(self, tmp_path):
        mc = pt.callbacks.ModelCheckpoint(save_dir=str(tmp_path),
                                          save_freq=1)
        self._fit([mc], epochs=2)
        written = [f for f in os.listdir(tmp_path)]
        assert written, "ModelCheckpoint wrote nothing"


class TestProfilerTrace:
    def test_profiler_records_and_exports_json(self, tmp_path):
        """Profiler must capture RecordEvent spans and export a JSON
        trace containing them."""
        import paddle_tpu.profiler as profiler
        with profiler.Profiler() as prof:
            with profiler.RecordEvent("unit-test-span"):
                _ = (pt.ones([64, 64]) @ pt.ones([64, 64])).numpy()
            prof.step()
        path = str(tmp_path / "trace.json")
        prof.export(path, format="json")
        raw = open(path).read()
        assert "unit-test-span" in raw
        json.loads(raw)  # must be valid JSON, not just a text dump


class TestOrbaxInterop:
    def test_roundtrip_and_cross_compat(self, tmp_path):
        """save_orbax/load_orbax speak real orbax: raw orbax reads our
        checkpoints and we read raw-orbax checkpoints."""
        from paddle_tpu.utils.checkpoint import save_orbax, load_orbax
        net = pt.nn.Linear(4, 3)
        sd = dict(net.state_dict())
        p = str(tmp_path / "ckpt")
        save_orbax(p, sd)
        back = load_orbax(p, like=sd)
        for k in sd:
            assert np.allclose(np.asarray(back[k]), sd[k].numpy()), k

        ocp = pytest.importorskip("orbax.checkpoint")
        with ocp.StandardCheckpointer() as c:
            raw = c.restore(os.path.abspath(p))
        assert np.allclose(np.asarray(raw["weight"]), sd["weight"].numpy())
        with ocp.StandardCheckpointer() as c:
            c.save(os.path.abspath(str(tmp_path / "foreign")),
                   {"a": np.arange(6.0).reshape(2, 3)})
        ours = load_orbax(str(tmp_path / "foreign"))
        assert np.allclose(ours["a"], np.arange(6.0).reshape(2, 3))

    def test_crash_window_recovery(self, tmp_path):
        """save_orbax's two-rename swap has a window where nothing
        exists at `path`; load_orbax must recover from the .old-orbax /
        .tmp-orbax survivors (ADVICE r3)."""
        import shutil
        from paddle_tpu.utils.checkpoint import save_orbax, load_orbax
        old_v, new_v = np.arange(3.0), np.arange(3.0) + 1
        save_orbax(str(tmp_path / "prev"), {"v": old_v})
        save_orbax(str(tmp_path / "next"), {"v": new_v})
        # simulate the crash window: nothing at `path`, both survivors
        p = str(tmp_path / "ckpt")
        shutil.copytree(str(tmp_path / "prev"), p + ".old-orbax")
        shutil.copytree(str(tmp_path / "next"), p + ".tmp-orbax")
        # .tmp-orbax is the fully-written NEW checkpoint — preferred
        assert np.allclose(load_orbax(p)["v"], new_v)
        shutil.rmtree(p + ".tmp-orbax")
        # only the previous live checkpoint survived
        assert np.allclose(load_orbax(p)["v"], old_v)

    def test_save_after_crash_window_keeps_a_loadable_ckpt(self,
                                                          tmp_path,
                                                          monkeypatch):
        """A save issued right after a crash-window crash must promote
        the survivor to `path` before clearing scratch names — even if
        that save dies too, a loadable checkpoint remains."""
        import shutil
        import orbax.checkpoint as ocp
        from paddle_tpu.utils.checkpoint import save_orbax, load_orbax
        v = np.arange(4.0)
        save_orbax(str(tmp_path / "prev"), {"v": v})
        p = str(tmp_path / "ckpt")
        shutil.copytree(str(tmp_path / "prev"), p + ".old-orbax")
        # the retry save itself dies before writing anything
        monkeypatch.setattr(
            ocp.StandardCheckpointer, "save",
            lambda self, *a, **k: (_ for _ in ()).throw(
                RuntimeError("disk died")))
        with pytest.raises(RuntimeError):
            save_orbax(p, {"v": v + 1})
        assert np.allclose(load_orbax(p)["v"], v)


class TestQuantValues:
    def test_weight_quantize_dequantize_roundtrip(self):
        """int8 weight-only quantization: per-out-channel absmax scale,
        dequantized error bounded by scale/2 elementwise."""
        rng = np.random.RandomState(0)
        w = rng.randn(16, 8).astype(np.float32)
        q, scale = pt.quantization.weight_quantize(pt.to_tensor(w))
        qn = q.numpy()
        sn = scale.numpy()
        assert qn.dtype == np.int8 and sn.shape == (8,)
        assert np.abs(qn).max() <= 127
        exp_scale = np.abs(w).max(0) / 127.0
        assert np.allclose(sn, exp_scale, atol=1e-7)
        back = pt.quantization.weight_dequantize(q, scale).numpy()
        assert np.abs(back - w).max() <= sn.max() / 2 + 1e-7

    def test_weight_only_linear_matches_fp(self):
        rng = np.random.RandomState(1)
        x = rng.randn(4, 16).astype(np.float32)
        w = rng.randn(16, 8).astype(np.float32)
        b = rng.randn(8).astype(np.float32)
        q, scale = pt.quantization.weight_quantize(pt.to_tensor(w))
        out = pt.quantization.weight_only_linear(
            pt.to_tensor(x), q, pt.to_tensor(b), scale).numpy()
        ref = x @ w + b
        # int8 quantization error ~ scale * sqrt(K)/2 per output element
        tol = float(scale.numpy().max()) * np.sqrt(16)
        assert np.abs(out - ref).max() < tol, np.abs(out - ref).max()
