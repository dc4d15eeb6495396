"""Device-side sampling + double-buffered pump (ISSUE 8): the
pipelined step loop must be TOKEN-IDENTICAL to the synchronous one —
greedy and seeded sampling both — across every engine mode, and the
one-step-deep pipeline must drain correctly through every slow path
(cancel, TTL expiry, replica kill, _fail_all, preemption)."""
import time

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama_serving import (PipelineStall, Request,
                                             ServingEngine)
from paddle_tpu.serving.metrics import MetricsRegistry
from paddle_tpu.serving.replica import Replica
from paddle_tpu.serving.scheduler import (DeadlineExceededError,
                                          RequestScheduler,
                                          SchedulerError)

CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


def _submit_mixed(eng, n=4, max_new=10):
    """A workload touching both sampler paths: greedy, seeded
    sampling, and logprobs."""
    eng.submit(Request("g0", [1, 5, 9, 3, 7], max_new_tokens=max_new))
    eng.submit(Request("s0", [2, 4, 6], max_new_tokens=max_new,
                       temperature=0.8, top_k=8, top_p=0.9, seed=123))
    eng.submit(Request("g1", [9, 9, 2], max_new_tokens=max_new,
                       logprobs=True))
    eng.submit(Request("s1", [7, 1], max_new_tokens=max_new,
                       temperature=1.1, seed=7, logprobs=True))


def _outputs(done):
    return {r.rid: (list(r.output), None if r.logprobs is None
                    else [round(v, 5) for v in r.logprobs])
            for r in done}


MODES = {
    "plain": {},
    "int8": {"cache_dtype": "int8"},
    "prefix": {"prefix_cache": True},
    "tier": {"prefix_cache": True, "host_tier_bytes": 1 << 20},
    "recompute": {"preempt_policy": "recompute"},
    # spec/chunked fall back to the synchronous loop inside
    # run_pipelined (drafting needs host-current context): the
    # pipelined DRIVER must still give identical tokens
    "spec": {"spec_decode": 4},
    "chunked": {"spec_decode": 4, "chunked_prefill": True},
}
# every mode is covered; the tier-1 budget carries the four that
# exercise distinct code paths (plain carry, quantized scatter,
# shared-page admission, spec fallback) — the remaining three are
# compositions of those and run in the slow lane
_SLOW_MODES = {"tier", "recompute", "chunked"}
_MODE_PARAMS = [pytest.param(m, marks=pytest.mark.slow)
                if m in _SLOW_MODES else m for m in sorted(MODES)]


class TestTokenIdentity:
    """run_pipelined == run, token for token, per engine mode."""

    MODES = MODES

    @pytest.mark.parametrize("mode", _MODE_PARAMS)
    def test_pipelined_equals_sync(self, params, mode):
        kw = self.MODES[mode]
        outs = []
        for pipelined in (False, True):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=8, use_pallas=False, **kw)
            _submit_mixed(eng)
            done = eng.run_pipelined() if pipelined else eng.run()
            assert len(done) == 4
            outs.append(_outputs(done))
        assert outs[0] == outs[1], f"mode {mode} diverged"

    def test_pipelined_under_preemption(self, params):
        """An oversubscribed pool forces preemption mid-run: the
        pipelined loop must drain (PipelineStall) and still emit the
        unpressured engine's exact tokens."""
        outs = []
        for num_pages in (None, 6):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                                page_size=8, num_pages=num_pages,
                                use_pallas=False)
            eng.submit(Request("s", [3, 7, 2, 9], max_new_tokens=20,
                               temperature=0.8, top_k=8, seed=123))
            eng.submit(Request("g", [1, 4, 6, 2], max_new_tokens=20))
            done = eng.run_pipelined(max_steps=500)
            outs.append({r.rid: r.output for r in done})
            if num_pages is not None:
                assert eng.preemptions > 0, num_pages
        assert outs[0] == outs[1]

    def test_eos_finish_rolls_back_overrun(self, params):
        """An eos finish is not host-predictable: the pipelined loop
        runs the slot one zombie step past its end, discards that
        token, and the final state (output AND device_steps ledger
        consistency) matches the sync loop."""
        prompt = [2, 4, 2, 4, 2]
        probe = ServingEngine(params, CFG, max_seqs=1, max_seq_len=64,
                              page_size=8, use_pallas=False)
        probe.submit(Request("p", prompt, max_new_tokens=12))
        ref = probe.run()[0].output
        eos = ref[5]
        outs = []
        for pipelined in (False, True):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=8, use_pallas=False,
                                prefix_cache=True)
            eng.submit(Request("e", prompt, max_new_tokens=12,
                               eos_id=eos))
            eng.submit(Request("g", [9, 8, 7], max_new_tokens=9))
            done = eng.run_pipelined() if pipelined else eng.run()
            outs.append(_outputs(done))
            # prefix-cache indexing after the rollback must agree with
            # the sync loop: pool conservation stays intact
            c = eng.pool.counts()
            assert c["free"] + c["cached"] + c["live"] \
                == eng.num_pages - 1
        assert outs[0] == outs[1]
        assert outs[0]["e"][0][-1] == eos
        # the eos token's FIRST appearance ends the request (it can turn
        # up before index 5 of the probe's output)
        assert len(outs[0]["e"][0]) == ref.index(eos) + 1

    def test_max_tokens_finish_has_no_zombie_steps(self, params):
        """Budget-bound finishes are host-predictable: the pipelined
        loop must NOT spend device steps past them (same device-step
        count as sync for an eos-free workload)."""
        counts = []
        for pipelined in (False, True):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=8, use_pallas=False)
            _submit_mixed(eng)
            (eng.run_pipelined() if pipelined else eng.run())
            counts.append(eng.device_steps)
        assert counts[0] == counts[1]

    def test_max_new_tokens_one(self, params):
        """Admission-time finishes (the request never reaches the
        decode loop) under the pipelined driver."""
        for pipelined in (False, True):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=8, use_pallas=False)
            eng.submit(Request("one", [1, 2, 3], max_new_tokens=1))
            eng.submit(Request("two", [4, 5], max_new_tokens=6))
            done = eng.run_pipelined() if pipelined else eng.run()
            assert {r.rid: len(r.output) for r in done} == \
                {"one": 1, "two": 6}

    def test_seeded_sampling_reproducible_across_pumps(self, params):
        """Same seed -> same trajectory, and the scheduler's pumps (the
        synchronous one over a bucketed engine, the deep one over a
        ragged engine) agree with the bare engine drivers."""
        ref = None
        for driver in ("run", "run_pipelined", "sched", "sched_pipe"):
            if driver.startswith("sched"):
                eng = ServingEngine(params, CFG, max_seqs=2,
                                    max_seq_len=64, page_size=8,
                                    use_pallas=False,
                                    ragged=driver == "sched_pipe")
                sch = RequestScheduler(eng, max_queue=8,
                                       metrics=MetricsRegistry())
                assert sch._pipeline is (driver == "sched_pipe")
                h = sch.submit([2, 4, 6], max_new_tokens=10,
                               temperature=0.8, top_k=8, top_p=0.9,
                               seed=123)
                out = h.result(timeout=60)
                sch.shutdown(drain=True, timeout=30)
            else:
                eng = ServingEngine(params, CFG, max_seqs=2,
                                    max_seq_len=64, page_size=8,
                                    use_pallas=False)
                eng.submit(Request("s", [2, 4, 6], max_new_tokens=10,
                                   temperature=0.8, top_k=8, top_p=0.9,
                                   seed=123))
                out = getattr(eng, driver)()[0].output
            if ref is None:
                ref = out
            assert out == ref, driver


class TestOneStepOneRule:
    """ISSUE 37: nothing is left to choose between serving steps or
    between pumps; what decides is what the engine is."""

    @pytest.mark.parametrize("target,word", [
        ("ServingEngine", "lean"), ("ServingEngine", "tokbuf"),
        ("RequestScheduler", "pipeline"), ("Replica", "pipeline"),
        ("build_replicas", "pipeline"), ("_env", "PT_SERVE_LEAN"),
        ("_env", "PT_SERVE_TOKBUF"), ("_env", "PT_SERVE_PIPELINE")])
    def test_no_switch_is_left(self, params, monkeypatch, target, word):
        import inspect
        from paddle_tpu import _env
        from paddle_tpu.serving.replica import build_replicas
        if target == "_env":
            assert not _env.is_declared(word)
            # and setting it changes nothing an engine or a pump is
            monkeypatch.setenv(word, "0")
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=8, use_pallas=False)
            assert eng.tok_buf is not None
            assert RequestScheduler(eng, start=False)._pipeline is True
            return
        fn = {"ServingEngine": ServingEngine,
              "RequestScheduler": RequestScheduler, "Replica": Replica,
              "build_replicas": build_replicas}[target]
        assert word not in inspect.signature(fn).parameters

        def make(**kw):
            return ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                 page_size=8, use_pallas=False, **kw)
        with pytest.raises(TypeError, match=word):
            if target == "ServingEngine":
                make(**{word: True})
            elif target == "RequestScheduler":
                RequestScheduler(make(), start=False, **{word: True})
            elif target == "Replica":
                # (what it does not know it hands to its scheduler)
                Replica("r0", make(), start=False, **{word: True})
            else:
                build_replicas(lambda i: make(), 1, start=False,
                               **{word: True})

    def test_run_pipelined_on_a_bucketed_engine_is_run(self, params):
        """A bucketed step returns new pools, so it is never driven one
        step deep: `run_pipelined()` is `run()` there, tokens, logprobs
        and device steps, and no launch ever saw a step in flight."""
        outs = []
        for driver in ("run", "run_pipelined"):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=8, use_pallas=False,
                                ragged=False)
            depths = []
            note = eng._note_launch_gap
            eng._note_launch_gap = lambda d, note=note, depths=depths: (
                depths.append(d), note(d))
            _submit_mixed(eng)
            done = getattr(eng, driver)()
            assert len(done) == 4 and set(depths) == {0}
            outs.append((_outputs(done), eng.device_steps))
        assert outs[0] == outs[1]
        with pytest.raises(ValueError, match="synchronously"):
            eng.submit(Request("x", [1, 2], max_new_tokens=4))
            eng.step_launch(carry=eng.step_launch())


class TestDeviceSampler:
    """The sampler runs INSIDE the jitted step with traced params."""

    def test_no_retrace_across_sampling_params(self, params):
        """Acceptance: changing temperature/top_k/top_p/seed between
        requests must not retrace decode_step (sampling params are
        traced arrays, not static)."""
        from paddle_tpu.observability.compile_telemetry import REGISTRY
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        # same contract for both step entry points: ragged engines
        # dispatch serving.unified_step, bucketed ones decode_step
        fn = "serving.unified_step" if eng.ragged \
            else "serving.decode_step"
        eng.submit(Request("a", [1, 2, 3], max_new_tokens=4,
                           temperature=0.7, top_k=5, seed=1))
        eng.run()
        snap = REGISTRY.snapshot()
        fns = snap.get("functions", snap)
        before = fns[fn]["compiles"]
        for i, kw in enumerate((
                {"temperature": 1.3, "top_k": 50, "top_p": 0.5,
                 "seed": 9},
                {"temperature": 0.0},
                {"temperature": 0.2, "top_p": 0.99, "seed": 2,
                 "logprobs": True})):
            eng.submit(Request(f"r{i}", [4 + i, 2], max_new_tokens=4,
                               **kw))
            eng.run()
        snap = REGISTRY.snapshot()
        fns = snap.get("functions", snap)
        assert fns[fn]["compiles"] == before

    def test_greedy_record_matches_legacy_logits(self, params):
        """decode_step's record must agree with its own logits output:
        argmax(logits) == record token for a greedy slot, and the
        logprob is the raw-model log-softmax at that token."""
        from paddle_tpu.models.llama_serving import decode_step
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        eng.submit(Request("a", [1, 2, 3, 4], max_new_tokens=6))
        eng.step()
        B = eng.max_seqs
        tokens = np.zeros((B,), np.int32)
        tokens[0] = eng._slots[0].next_token
        active = np.zeros((B,), bool)
        active[0] = True
        lengths = eng.lengths.copy()
        lengths[0] += 1
        sample = {"temp": jnp.zeros((B,), jnp.float32),
                  "top_k": jnp.zeros((B,), jnp.int32),
                  "top_p": jnp.ones((B,), jnp.float32),
                  "key": jnp.zeros((B, 2), jnp.uint32),
                  "eos": jnp.full((B,), -1, jnp.int32),
                  "remaining": jnp.full((B,), 5, jnp.int32)}
        _, _, _, _, logits, (tok, done, lp) = decode_step(
            eng.params, eng.k_pool, eng.v_pool,
            jnp.asarray(eng.page_table.copy()), jnp.asarray(lengths),
            jnp.asarray(tokens), jnp.asarray(active), eng.config,
            eng.page_size, use_pallas=False, sample=sample)
        row = np.asarray(logits[0], np.float64)
        assert int(tok[0]) == int(np.argmax(row))
        ref_lp = row[int(tok[0])] - (np.log(np.sum(np.exp(row - row.max())))
                                     + row.max())
        np.testing.assert_allclose(float(lp[0]), ref_lp, atol=2e-4)
        assert not bool(done[0])  # remaining 5, no eos

    def test_done_flag_semantics(self, params):
        from paddle_tpu.models.llama_serving import decode_step
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        eng.submit(Request("a", [1, 2, 3, 4], max_new_tokens=6))
        eng.step()
        B = eng.max_seqs
        tokens = np.zeros((B,), np.int32)
        tokens[0] = eng._slots[0].next_token
        active = np.zeros((B,), bool)
        active[0] = True
        lengths = eng.lengths.copy()
        lengths[0] += 1
        base = {"temp": jnp.zeros((B,), jnp.float32),
                "top_k": jnp.zeros((B,), jnp.int32),
                "top_p": jnp.ones((B,), jnp.float32),
                "key": jnp.zeros((B, 2), jnp.uint32)}
        # remaining == 1 -> done regardless of the token
        out = decode_step(
            eng.params, eng.k_pool, eng.v_pool,
            jnp.asarray(eng.page_table.copy()), jnp.asarray(lengths),
            jnp.asarray(tokens), jnp.asarray(active), eng.config,
            eng.page_size, use_pallas=False,
            sample=dict(base, eos=jnp.full((B,), -1, jnp.int32),
                        remaining=jnp.ones((B,), jnp.int32)))
        tok, done, _ = out[5]
        assert bool(done[0])
        # eos hit -> done even with budget left
        out = decode_step(
            eng.params, eng.k_pool, eng.v_pool,
            jnp.asarray(eng.page_table.copy()), jnp.asarray(lengths),
            jnp.asarray(tokens), jnp.asarray(active), eng.config,
            eng.page_size, use_pallas=False,
            sample=dict(base, eos=tok,
                        remaining=jnp.full((B,), 9, jnp.int32)))
        _, done2, _ = out[5]
        assert bool(done2[0])
        # inactive slots are never done
        assert not bool(done[1]) and not bool(done2[1])


class TestPipelineDraining:
    """Cancel / TTL / kill / _fail_all with one step in flight: no
    lost or duplicated tokens, monotonic ledger, clean engine."""

    def _engine(self, params, **kw):
        kw.setdefault("max_seqs", 2)
        kw.setdefault("max_seq_len", 512)
        kw.setdefault("page_size", 8)
        kw.setdefault("use_pallas", False)
        return ServingEngine(params, CFG, **kw)

    def _ledger_consistent(self, sched):
        st = sched.stats()
        led = st["requests"]
        assert led["submitted"] == (led["completed"] + led["failed"]
                                    + led["cancelled"] + led["expired"]
                                    + st["queued"] + st["inflight"])
        return led

    def test_cancel_with_step_in_flight(self, params):
        eng = self._engine(params)
        sched = RequestScheduler(eng, max_queue=8,
                                 metrics=MetricsRegistry())
        h = sched.submit([1, 2, 3], max_new_tokens=400)
        # stream a few chunks so the pipeline is demonstrably rolling
        got = []
        for chunk in h.stream(timeout=30):
            got.extend(chunk)
            if len(got) >= 4:
                h.cancel()
                break
        for chunk in h.stream(timeout=30):
            got.extend(chunk)
        deadline = time.time() + 15
        while h.state == "running" and time.time() < deadline:
            time.sleep(0.01)
        assert h.state == "cancelled"
        # no lost or duplicated tokens: the stream saw exactly the
        # request's final output
        assert got == h.output
        assert len(set([tuple(got)])) == 1
        assert len(h.output) < 400
        sched.drain(timeout=10)
        assert all(r is None for r in eng._slots)
        assert not eng._live
        led = self._ledger_consistent(sched)
        assert led["cancelled"] == 1
        sched.shutdown(drain=True, timeout=30)

    def test_ttl_expiry_with_step_in_flight(self, params):
        eng = self._engine(params)
        sched = RequestScheduler(eng, max_queue=8,
                                 metrics=MetricsRegistry())
        h = sched.submit([4, 5, 6], max_new_tokens=400, ttl_s=0.25)
        with pytest.raises(DeadlineExceededError):
            h.result(timeout=30)
        assert 0 < len(h.output) < 400
        sched.drain(timeout=10)
        assert not eng._live
        led = self._ledger_consistent(sched)
        assert led["expired"] == 1
        # the engine keeps serving afterwards
        h2 = sched.submit([1, 1, 2], max_new_tokens=5)
        assert len(h2.result(timeout=30)) == 5
        sched.shutdown(drain=True, timeout=30)

    def test_replica_kill_with_step_in_flight(self, params):
        rep = Replica("r0", self._engine(params))
        assert rep.scheduler._pipeline
        h = rep.submit([7, 8, 9], max_new_tokens=400)
        # wait until it is demonstrably mid-decode
        deadline = time.time() + 15
        while not h.output and time.time() < deadline:
            time.sleep(0.01)
        rep.kill()
        with pytest.raises(SchedulerError):
            h.result(timeout=30)
        assert h.state == "failed"
        eng = rep.engine
        assert all(r is None for r in eng._slots)
        assert not eng._live
        # pool conservation after the drain: nothing leaked
        c = eng.pool.counts()
        assert c["free"] + c["cached"] + c["live"] == eng.num_pages - 1
        rep.revive()
        h2 = rep.submit([7, 8, 9], max_new_tokens=5)
        assert len(h2.result(timeout=30)) == 5
        led = self._ledger_consistent(rep.scheduler)
        assert led["failed"] == 1 and led["completed"] == 1
        rep.shutdown(drain=True, timeout=30)

    def test_fail_all_drops_pending_ticket(self, params):
        """An exception from the in-flight step surfaces at the async
        read: _fail_all must clear the ticket and fail the requests
        exactly once."""
        eng = self._engine(params)
        sched = RequestScheduler(eng, max_queue=8,
                                 metrics=MetricsRegistry())
        h = sched.submit([1, 2, 3], max_new_tokens=400)
        deadline = time.time() + 15
        while not h.output and time.time() < deadline:
            time.sleep(0.01)
        boom = RuntimeError("injected mid-pipeline failure")

        def _dead(*a, **k):
            raise boom
        eng.step_launch = _dead
        with pytest.raises(SchedulerError):
            h.result(timeout=30)
        del eng.__dict__["step_launch"]
        assert not eng._live and not eng._waiting
        led = self._ledger_consistent(sched)
        assert led["failed"] == 1
        sched.shutdown(drain=True, timeout=30)

    def test_shutdown_drains_pipeline(self, params):
        eng = self._engine(params)
        sched = RequestScheduler(eng, max_queue=8,
                                 metrics=MetricsRegistry())
        hs = [sched.submit([i + 1, 2], max_new_tokens=20)
              for i in range(4)]
        assert sched.shutdown(drain=True, timeout=60)
        for h in hs:
            assert h.state == "done"
            assert len(h.output) == 20


class TestPipelineMetrics:
    def test_host_gap_and_depth_surfaced(self, params):
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        sched = RequestScheduler(eng, max_queue=8,
                                 metrics=MetricsRegistry())
        hs = [sched.submit([i + 1, 2, 3], max_new_tokens=12)
              for i in range(3)]
        [h.result(timeout=60) for h in hs]
        snap = sched.metrics_snapshot()
        assert snap["pt_step_host_gap_seconds"]["count"] > 0
        assert snap["pt_pipeline_depth"]["value"] == 1
        text = sched.render_prometheus()
        assert "pt_step_host_gap_seconds_bucket" in text
        assert "pt_pipeline_depth" in text
        sched.shutdown(drain=True, timeout=30)

    def test_sync_pump_reports_depth_zero(self, params):
        # a bucketed engine: the scheduler drives it synchronously
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, ragged=False)
        sched = RequestScheduler(eng, max_queue=8,
                                 metrics=MetricsRegistry())
        assert sched._pipeline is False
        sched.submit([1, 2, 3], max_new_tokens=8).result(timeout=60)
        snap = sched.metrics_snapshot()
        assert snap["pt_pipeline_depth"]["value"] == 0
        assert snap["pt_step_host_gap_seconds"]["count"] > 0
        sched.shutdown(drain=True, timeout=30)

    def test_spec_engine_forces_sync_pump(self, params):
        """spec_decode engines are driven by the synchronous pump
        (drafting needs host-current context)."""
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, spec_decode=4)
        sched = RequestScheduler(eng, max_queue=8,
                                 metrics=MetricsRegistry())
        assert sched._pipeline is False
        out = sched.submit([3, 9, 4, 3, 9, 4, 3, 9],
                           max_new_tokens=8).result(timeout=60)
        assert len(out) == 8
        sched.shutdown(drain=True, timeout=30)


def test_ptdump_rolls_up_serving_steps(tmp_path, capsys):
    """tools/ptdump.py must surface the step-loop rollup (step time,
    host gap, pipeline depth) from a flight dump's serving.step
    records."""
    import importlib.util
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "ptdump", os.path.join(root, "tools", "ptdump.py"))
    ptdump = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ptdump)
    doc = {"pid": 1, "dumped_at": 0.0, "reason": "test", "capacity": 16,
           "dropped": 0, "events": [
               {"kind": "serving.step", "ts": 1.0, "step_s": 0.002,
                "host_gap_s": 0.0001, "pipeline_depth": 1},
               {"kind": "serving.step", "ts": 2.0, "step_s": 0.004,
                "host_gap_s": 0.0003, "pipeline_depth": 1}]}
    path = tmp_path / "dump.json"
    path.write_text(json.dumps(doc))
    assert ptdump.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "serving steps: 2 sampled" in out
    assert "avg step 3.00ms" in out
    assert "avg host gap 200us" in out
    assert "pipeline depth 1" in out
