"""Engine crash recovery (ISSUE 9): deterministic fault injection
(serving/faults.py), warm restart with request requeue, poison-request
quarantine, and the crash-loop breaker — proven by replayable chaos
drills over real engines (and real HTTP where the acceptance criteria
ask for it), under BOTH the synchronous and pipelined pumps."""
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama_serving import Request, ServingEngine
from paddle_tpu.serving import (CrashLoopError, FaultPlan, HostTier,
                                InjectedFault, MetricsRegistry,
                                PoisonedRequestError, Replica,
                                RequestScheduler, Router, SchedulerError,
                                ServingClient, ServingHTTPError,
                                ServingServer, build_replicas)

CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)
PAGE = 8


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


def _engine(params, faults=None, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("use_pallas", False)
    kw.setdefault("prefix_cache", True)
    return ServingEngine(params, CFG, faults=faults, **kw)


def _pool_conserved(eng, drained=False):
    """Conservation always; with `drained=True` additionally no page
    may still be LIVE — an incref leaked across a crash would satisfy
    conservation (the page counts as live) but never be reclaimable."""
    c = eng.pool.counts()
    ok = c["free"] + c["cached"] + c["live"] == eng.num_pages - 1
    if drained:
        ok = ok and c["live"] == 0
    return ok


# ---------------------------------------------------------------------------
# FaultPlan: the deterministic harness itself
# ---------------------------------------------------------------------------
class TestFaultPlan:
    def test_grammar_round_trip(self):
        plan = FaultPlan("seed=7;step_launch:raise@3;"
                         "tier_spill:delay@1x2:delay=0.0;"
                         "step_finish:raise@2x*:rid=bad,msg=boom")
        assert plan.seed == 7
        st = plan.stats()
        assert [r["rule"] for r in st["rules"]] == [
            "step_launch:raise@3x1", "tier_spill:delay@1x2",
            "step_finish:raise@2x*:rid=bad"]

    @pytest.mark.parametrize("spec", [
        "nope:raise@1",            # unknown point
        "step_launch:explode@1",   # unknown action
        "step_launch:raise",       # missing @first
        "step_launch@1",           # missing action
        "step_launch:raise@0",     # hits are 1-based
        "step_launch:raise@1:wat=1",  # unknown arg
    ])
    def test_bad_specs_fail_fast(self, spec):
        with pytest.raises(ValueError):
            FaultPlan(spec)

    def test_nth_hit_and_run_length(self):
        plan = FaultPlan("step_launch:raise@3x2")
        for hit in range(1, 7):
            if hit in (3, 4):
                with pytest.raises(InjectedFault) as ei:
                    plan.fire("step_launch")
                assert ei.value.point == "step_launch"
                assert ei.value.hit == hit
            else:
                plan.fire("step_launch")
        assert plan.hits["step_launch"] == 6
        assert len(plan.fired) == 2

    def test_rid_scoped_rule_counts_matching_hits_only(self):
        plan = FaultPlan("step_launch:raise@2x*:rid=bad")
        plan.fire("step_launch", rids=["bad"])       # match 1: below first
        plan.fire("step_launch", rids=["good"])      # no match
        with pytest.raises(InjectedFault):
            plan.fire("step_launch", rids=["good", "bad"])  # match 2
        with pytest.raises(InjectedFault):
            plan.fire("step_launch", rids=["bad"])          # match 3
        assert len(plan.fired) == 2
        assert plan.hits["step_launch"] == 4

    def test_corrupt_is_deterministic_and_seeded(self):
        a = np.arange(32, dtype=np.float32).reshape(4, 8)
        flips = []
        for _ in range(2):
            plan = FaultPlan("tier_spill:corrupt@1", seed=5)
            out = plan.fire("tier_spill", a.copy())
            assert (out != a).sum() == 1      # exactly one element hit
            flips.append(np.argwhere(out != a).tolist())
        assert flips[0] == flips[1]           # same seed -> same flip
        # untouched input: corrupt copies, never mutates in place
        ref = FaultPlan("tier_spill:corrupt@1", seed=5)
        src = a.copy()
        ref.fire("tier_spill", src)
        assert np.array_equal(src, a)

    def test_delay_and_infinite_count(self):
        plan = FaultPlan("router_dispatch:delay@1x*:delay=0.01")
        t0 = time.perf_counter()
        plan.fire("router_dispatch")
        plan.fire("router_dispatch")
        assert time.perf_counter() - t0 >= 0.02

    def test_from_env(self, monkeypatch):
        monkeypatch.delenv("PT_FAULTS", raising=False)
        assert FaultPlan.from_env() is None
        monkeypatch.setenv("PT_FAULTS", "step_launch:raise@1")
        plan = FaultPlan.from_env()
        assert plan is not None
        eng_plan = FaultPlan.from_env({"PT_FAULTS": "seed=3;"
                                       "tier_spill:raise@2"})
        assert eng_plan.seed == 3

    def test_engine_defaults_off(self, params):
        """faults disabled (no PT_FAULTS, no kwarg) must cost nothing:
        plan is None and the engine behaves exactly as seeded."""
        eng = _engine(params)
        assert eng.faults is None and eng.host_tier.faults is None
        eng.submit(Request("a", [1, 2, 3], max_new_tokens=4))
        done = eng.run()
        assert len(done[0].output) == 4


# ---------------------------------------------------------------------------
# Acceptance e2e: chaos drill over real HTTP, both pumps
# ---------------------------------------------------------------------------
class TestChaosDrillHTTP:
    """N concurrent HTTP requests, an injected device failure
    mid-decode: ZERO requests fail (transient fault), every output is
    token-identical to an undisturbed run, pt_engine_restarts_total
    >= 1 on /metrics, and the requeue ledger balances — under both the
    synchronous and the pipelined pump."""

    N = 5

    def _drill(self, params, faults, pipeline):
        # the pump follows the engine: ragged -> one step deep,
        # bucketed -> synchronous
        eng = _engine(params, faults=faults, ragged=pipeline)
        sched = RequestScheduler(eng, max_queue=32,
                                 metrics=MetricsRegistry())
        assert sched._pipeline is pipeline
        srv = ServingServer(sched, port=0).start()
        cl = ServingClient(port=srv.port)
        sched.pause()
        results = {}

        def call(i):
            kw = {"max_tokens": 10}
            if i % 2:
                kw.update(temperature=0.8, top_k=8, seed=100 + i)
            results[i] = cl.complete([1 + i, 5, 9, 3], **kw)
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(self.N)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and \
                sched.stats()["queued"] < self.N:
            time.sleep(0.01)
        sched.resume()
        for t in threads:
            t.join(timeout=90)
        assert not any(t.is_alive() for t in threads)
        text = cl.metrics_text()
        health = cl.healthz()
        srv.stop(drain=True, timeout=30)
        assert _pool_conserved(eng)
        return results, text, health

    @pytest.mark.parametrize("pipeline", [False, True])
    def test_transient_fault_is_invisible(self, params, pipeline):
        base, _, _ = self._drill(params, None, pipeline)
        assert all(r["state"] == "done" for r in base.values())
        chaos, text, health = self._drill(
            params, FaultPlan("step_launch:raise@4"), pipeline)
        # zero casualties, token-identical to the undisturbed run
        for i in range(self.N):
            assert chaos[i]["state"] == "done", (pipeline, i, chaos[i])
            assert chaos[i]["tokens"] == base[i]["tokens"], (pipeline, i)
        # the restart really happened and is on /metrics
        restarts = [ln for ln in text.splitlines()
                    if ln.startswith("pt_engine_restarts_total ")][0]
        assert float(restarts.split()[-1]) >= 1
        requeued = [ln for ln in text.splitlines()
                    if ln.startswith("pt_requests_requeued_total ")][0]
        assert float(requeued.split()[-1]) >= 1
        assert "pt_engine_restart_seconds_bucket" in text
        # requeue ledger balances: conservation with requeues counted
        # once, surfaced on /healthz
        led = health["requests"]
        assert led["requeued"] >= 1
        assert led["submitted"] == (
            led["completed"] + led["failed"] + led["cancelled"]
            + led["expired"] + health["queued"] + health["inflight"])
        assert health["recovery"]["restarts"] >= 1
        assert health["recovery"]["breaker_open"] is False


# ---------------------------------------------------------------------------
# Poison quarantine: exactly the poisoned request fails
# ---------------------------------------------------------------------------
class TestPoisonQuarantine:
    def _run(self, params, faults, pipeline, poison_after=2):
        eng = _engine(params, faults=faults, ragged=pipeline)
        sched = RequestScheduler(eng, max_queue=16,
                                 metrics=MetricsRegistry(),
                                 poison_after=poison_after,
                                 max_restarts=50)
        sched.pause()
        hs = [sched.submit([1 + i, 5, 9, 3], rid=f"r{i}",
                           max_new_tokens=8) for i in range(3)]
        bad = sched.submit([9, 9, 9, 9], rid="bad", max_new_tokens=8) \
            if faults is not None else None
        sched.resume()
        outs = {h.rid: h.result(timeout=90) for h in hs}
        err = None
        if bad is not None:
            with pytest.raises(PoisonedRequestError) as ei:
                bad.result(timeout=90)
            err = ei.value
        st = sched.stats()
        snap = sched.metrics_snapshot()
        sched.shutdown(drain=True, timeout=30)
        assert _pool_conserved(eng)
        return outs, err, st, snap

    @pytest.mark.parametrize("pipeline", [False, True])
    def test_poison_fails_alone_innocents_complete(self, params,
                                                   pipeline):
        base, _, _, _ = self._run(params, None, pipeline)
        outs, err, st, snap = self._run(
            params, FaultPlan("step_launch:raise@1x*:rid=bad"), pipeline)
        # exactly the poisoned request failed, with a client-readable
        # `poisoned` error; every innocent is token-identical
        assert outs == base
        assert "poisoned" in str(err)
        assert st["requests"]["failed"] == 1
        assert st["recovery"]["quarantined"] == 1
        assert snap["pt_poison_quarantined"]["value"] == 1
        assert snap["pt_engine_restarts"]["value"] >= 2

    def test_quarantine_leaves_flight_trail(self, params):
        from paddle_tpu.observability import flight_recorder as _flight
        self._run(params, FaultPlan("step_launch:raise@1x*:rid=bad"),
                  False)
        evs = _flight.snapshot()["events"]
        q = [e for e in evs if e.get("kind") == "poison.quarantine"]
        assert q and q[-1]["rid"] == "bad" and q[-1].get("trace_id")
        r = [e for e in evs if e.get("kind") == "engine.restart"]
        assert r and all("trace_ids" in e for e in r)
        inj = [e for e in evs if e.get("kind") == "fault.injected"]
        assert inj and inj[-1]["point"] == "step_launch"

    def test_mid_stream_crash_fails_not_requeues(self, params):
        """A request whose consumer has SEEN bytes must fail on crash
        (never silently replay), and it publishes nothing further."""
        eng = _engine(params, max_seq_len=512)
        sched = RequestScheduler(eng, max_queue=8,
                                 metrics=MetricsRegistry())
        h = sched.submit([1, 2, 3], max_new_tokens=400)
        got = []
        it = h.stream(timeout=30)
        got.extend(next(it))
        plan = eng.faults = FaultPlan()
        plan.add("step_launch", "raise", count=None,
                 exc=RuntimeError("mid-stream crash"))
        with pytest.raises(SchedulerError):
            for chunk in it:
                got.extend(chunk)
        assert h.state == "failed"
        assert h._streamed and h._requeues == 0
        # no bytes published after the failure
        assert len(got) == h._emitted
        sched.shutdown(drain=False, timeout=30)


# ---------------------------------------------------------------------------
# Crash-loop breaker: intra-replica exhaustion -> cross-replica failover
# ---------------------------------------------------------------------------
class TestCrashLoopBreaker:
    def test_breaker_flips_readyz_and_refuses_with_retry_after(
            self, params):
        rep = Replica("r0", _engine(params), max_restarts=2,
                      restart_window_s=60.0, poison_after=99)
        srv = ServingServer(rep.scheduler, port=0).start()
        try:
            cl = ServingClient(port=srv.port)
            assert cl.readyz()["ready"] is True
            rep.kill()
            h = rep.submit([1, 2, 3], max_new_tokens=8)
            with pytest.raises(SchedulerError):
                h.result(timeout=60)
            # breaker open: /readyz 503 with the reason, admission 503
            # with Retry-After
            with pytest.raises(ServingHTTPError) as ei:
                cl.readyz()
            assert ei.value.status == 503
            assert ei.value.body["detail"] == "crash_loop"
            with pytest.raises(ServingHTTPError) as ei:
                cl.complete([1, 2, 3], max_tokens=2)
            assert ei.value.status == 503
            assert ei.value.retry_after_s is not None
            with pytest.raises(CrashLoopError):
                rep.submit([1, 2, 3], max_new_tokens=2)
            # revive closes the breaker and the replica serves again
            rep.revive()
            assert cl.readyz()["ready"] is True
            out = cl.complete([1, 2, 3], max_tokens=4)
            assert out["state"] == "done" and len(out["tokens"]) == 4
        finally:
            srv.stop(drain=False, timeout=30)

    def test_client_retries_breaker_503_honoring_retry_after(
            self, params):
        """Satellite: a crash-loop-breaker replica behind a
        single-replica deployment is retried by the client (bounded,
        Retry-After honored) instead of surfaced."""
        rep = Replica("r0", _engine(params), max_restarts=1,
                      restart_window_s=60.0, poison_after=99,
                      breaker_retry_after_s=1.0)
        srv = ServingServer(rep.scheduler, port=0).start()
        try:
            rep.kill()
            h = rep.submit([4, 4, 4], max_new_tokens=4)
            with pytest.raises(SchedulerError):
                h.result(timeout=60)
            assert not rep.ready()
            reviver = threading.Timer(0.3, rep.revive)
            reviver.start()
            try:
                cl = ServingClient(port=srv.port, timeout=30, retries=8,
                                   retry_cap_s=0.4)
                out = cl.complete([1, 2, 3], max_tokens=4)
                assert out["state"] == "done"
            finally:
                reviver.cancel()
            # a bare 503 (shutdown, no Retry-After) is NOT retried
            rep.shutdown(drain=True, timeout=30)
            with pytest.raises(ServingHTTPError) as ei:
                ServingClient(port=srv.port, retries=3).complete(
                    [1, 2, 3], max_tokens=2)
            assert ei.value.status == 503
            assert ei.value.retry_after_s is None
        finally:
            srv.stop(drain=False, timeout=30)

    def test_breaker_fails_over_to_healthy_replica(self, params):
        """Acceptance crash-loop drill: a persistent fault burns
        through requeues, trips the breaker, the router marks the
        replica unhealthy and fails queued work over token-identically;
        revive + probe recovery restores rotation."""
        def factory(i):
            return _engine(params, max_seqs=2)
        reps = build_replicas(factory, 2, max_queue=16,
                              max_restarts=2, restart_window_s=60.0,
                              poison_after=99)
        router = Router(reps, unhealthy_after=2, probe_after_s=30.0)
        try:
            prompt = [3, 1, 4, 1, 5]
            ref = None
            probe = _engine(params)
            probe.submit(Request("ref", prompt, max_new_tokens=6))
            ref = probe.run()[0].output
            target = router.affinity_target(prompt)
            rep = router.replica(target)
            rep.pause()
            held = [router.submit(prompt, max_new_tokens=6)
                    for _ in range(2)]
            rep.kill()
            rep.resume()
            outs = [r.result(timeout=90) for r in held]
            assert outs == [ref, ref]
            assert all(r.state == "done" and r.failovers >= 1
                       for r in held)
            assert all(r.replica_id != target for r in held)
            # the dead replica: breaker open, router marked unhealthy
            assert not rep.ready()
            assert rep.scheduler.readiness()[1] == "crash_loop"
            st = router.stats()["replicas"][target]
            assert st["health"] == "open" and st["ready"] is False
            # revive + probe recovery restores rotation
            rep.revive()
            assert rep.ready()
            with router._lock:
                router._replicas[target].opened_at = \
                    time.monotonic() - 31.0
            rr = router.submit(prompt, max_new_tokens=6)
            assert rr.replica_id == target
            assert rr.result(timeout=60) == ref
            assert router.stats()["replicas"][target]["health"] == "ok"
        finally:
            router.shutdown(drain=False, timeout=30)


    def test_a_failed_step_that_consumed_its_pools_ends_in_the_breaker(
            self, params):
        """`unified_step` donates its pools ([donate-pools]): a step that
        dies AFTER the runtime took them leaves the engine holding deleted
        arrays, and a warm restart cannot give them back. The engine does
        not serve from them: every retry raises, the restarts trip the
        crash-loop breaker, the request fails with the cause and admission
        refuses, as for any persistent fault (a router then fails over)."""
        import dataclasses
        eng = _engine(params)
        real, died = eng.model.step, []

        def step(p, caches, *a, **kw):
            if not died:
                died.append(1)
                for stack in caches[0]:
                    for arr in stack:
                        if arr is not None:
                            arr.delete()
                raise RuntimeError("the device lost the step")
            return real(p, caches, *a, **kw)
        eng.model = dataclasses.replace(eng.model, step=step)
        rep = Replica("r0", eng, max_restarts=3, restart_window_s=60.0,
                      poison_after=99)
        try:
            h = rep.submit([1, 2, 3], max_new_tokens=8)
            with pytest.raises(SchedulerError, match="has been deleted"):
                h.result(timeout=60)
            assert eng.restarts == 3 and not rep.ready()
            assert rep.scheduler.readiness()[1] == "crash_loop"
            with pytest.raises(CrashLoopError):
                rep.submit([1, 2, 3], max_new_tokens=2)
            assert _pool_conserved(eng, drained=True)
        finally:
            rep.scheduler.shutdown(drain=False, timeout=30)


# ---------------------------------------------------------------------------
# Fault points beyond the decode dispatch
# ---------------------------------------------------------------------------
class TestOtherFaultPoints:
    @pytest.mark.parametrize("pipeline", [False, True])
    def test_step_finish_fault_with_pending_ticket(self, params,
                                                   pipeline):
        """A crash at the async result read — under the pipelined pump
        that is a pending step_finish ticket at crash time — recovers
        token-identically."""
        outs = []
        for spec in (None, "step_finish:raise@3"):
            eng = _engine(params, ragged=pipeline,
                          faults=None if spec is None
                          else FaultPlan(spec))
            sched = RequestScheduler(eng, max_queue=8,
                                     metrics=MetricsRegistry())
            assert sched._pipeline is pipeline
            sched.pause()
            hs = [sched.submit([2 + i, 7, 1], max_new_tokens=8,
                               **({"temperature": 0.7, "seed": 42}
                                  if i == 1 else {}))
                  for i in range(3)]
            sched.resume()
            outs.append([h.result(timeout=90) for h in hs])
            if spec is not None:
                assert sched.stats()["requests"]["requeued"] >= 1
            sched.shutdown(drain=True, timeout=30)
            assert _pool_conserved(eng)
        assert outs[0] == outs[1]

    def test_suffix_prefill_fault_recovers_conserving_pool(self, params):
        """A crash inside the prefix-cache suffix prefill (mid-
        admission: pages mapped, slot not yet attached) must release
        everything and recover."""
        # bucketed machinery under test: the ragged engine admits via
        # the chunked feed and never enters the suffix-prefill entry
        # point (its fault drill lives in test_ragged_step.py)
        eng = _engine(params, ragged=False,
                      faults=FaultPlan("suffix_prefill:raise@2"))
        sched = RequestScheduler(eng, max_queue=8,
                                 metrics=MetricsRegistry())
        h = [1, 2, 3, 4, 5, 6, 7, 8, 9]   # > one full page
        a = sched.submit(h + [1], max_new_tokens=4)
        a.result(timeout=60)
        # same header: the second admission goes suffix-prefill; hit 2
        # of the point crashes it mid-admission
        b = sched.submit(h + [2], max_new_tokens=4)
        c = sched.submit(h + [3], max_new_tokens=4)
        rb, rc = b.result(timeout=90), c.result(timeout=90)
        assert len(rb) == 4 and len(rc) == 4
        assert sched.stats()["requests"]["requeued"] >= 1
        sched.shutdown(drain=True, timeout=30)
        assert _pool_conserved(eng, drained=True)

    def test_tier_restore_fault_recovers(self, params):
        eng = _engine(params, host_tier_bytes=1 << 20,
                      faults=FaultPlan("tier_restore:raise@1"))
        sched = RequestScheduler(eng, max_queue=8,
                                 metrics=MetricsRegistry())
        h = [5, 6, 7, 8, 1, 2, 3, 4, 9]
        sched.submit(h + [1], max_new_tokens=4).result(timeout=60)
        sched.drain(timeout=10)
        # force the header's pages out of the device cache into the tier
        eng.host_tier.flush(timeout=10)
        evict = [sched.submit([11 + i, 13, 17, 19] * 4, max_new_tokens=4)
                 for i in range(4)]
        [e.result(timeout=60) for e in evict]
        sched.drain(timeout=10)
        eng.host_tier.flush(timeout=10)
        # returning conversation: tier restore fires the fault once,
        # recovery retries and completes
        out = sched.submit(h + [1], max_new_tokens=4).result(timeout=90)
        assert len(out) == 4
        sched.shutdown(drain=True, timeout=30)
        assert _pool_conserved(eng, drained=True)

    def test_kill_is_a_fault_plan_rule(self, params):
        rep = Replica("rX", _engine(params))
        assert rep.engine.faults is None
        rep.kill()
        st = rep.engine.faults.stats()
        assert any(r["label"] == "kill:rX" for r in st["rules"])
        rep.revive()
        assert not rep.engine.faults.stats()["rules"]
        out = rep.submit([1, 2, 3], max_new_tokens=3).result(timeout=60)
        assert len(out) == 3
        rep.shutdown(drain=True, timeout=30)


# ---------------------------------------------------------------------------
# Satellite: kvtier copy-thread hardening
# ---------------------------------------------------------------------------
class TestTierCopyHardening:
    def test_one_bad_copy_costs_one_page(self):
        """A spill copy that raises drops THAT page, counts the error,
        records the evidence, and the worker keeps landing later
        spills."""
        from paddle_tpu.observability import flight_recorder as _flight
        tier = HostTier(page_size=4, tier_bytes=1 << 20)
        tier.faults = FaultPlan("tier_spill:raise@1")
        k = np.ones((2, 2, 4, 8), np.float32)
        tier.spill_async(b"p0", (1, 2, 3, 4), 0, k, k)   # injected fail
        tier.spill_async(b"p1", (5, 6, 7, 8), 0, k, k)   # must land
        assert tier.flush(timeout=10)
        st = tier.stats()
        assert st["copy_errors"] == 1
        assert st["spills"] == 1 and st["spilled_pages"] == 1
        assert tier._worker.is_alive()
        evs = _flight.snapshot()["events"]
        assert any(e.get("kind") == "kvtier.error" for e in evs)
        # exactly the SECOND page landed
        assert len(tier._entries) == 1
        (entry,) = tier._entries.values()
        assert entry["block"] == (5, 6, 7, 8)

    def test_copy_error_counter_on_metrics(self, params):
        """pt_prefix_tier_copy_errors_total mirrors the tier's rollup
        through the same single-writer on_step delta path as the other
        tier counters."""
        eng = _engine(params, host_tier_bytes=1 << 20)
        eng.host_tier.faults = FaultPlan("tier_spill:raise@1x*")
        reg = MetricsRegistry()
        from paddle_tpu.serving.metrics import EngineMetrics
        eng.metrics = EngineMetrics(reg)
        k = np.ones((2, 2, PAGE, 8), np.float32)
        eng.host_tier.spill_async(b"p", (1,) * PAGE, 0, k, k)
        assert eng.host_tier.flush(timeout=10)
        assert eng.host_tier.copy_errors == 1
        # a device step mirrors the tier rollups onto the registry
        eng.submit(Request("z", [2, 4, 6], max_new_tokens=2))
        eng.run()
        text = reg.render_prometheus()
        line = [ln for ln in text.splitlines()
                if ln.startswith("pt_prefix_tier_copy_errors_total ")]
        assert line and float(line[0].split()[-1]) == 1


# ---------------------------------------------------------------------------
# Satellite: ledger + ptdump
# ---------------------------------------------------------------------------
def test_ledger_requeued_monotonic_and_conserved(params):
    eng = _engine(params, faults=FaultPlan("step_launch:raise@2"))
    sched = RequestScheduler(eng, max_queue=8, metrics=MetricsRegistry())
    hs = [sched.submit([1 + i, 2], max_new_tokens=5) for i in range(3)]
    [h.result(timeout=60) for h in hs]
    st = sched.stats()
    led = st["requests"]
    assert led["requeued"] >= 1
    assert led["submitted"] == (
        led["completed"] + led["failed"] + led["cancelled"]
        + led["expired"] + st["queued"] + st["inflight"])
    # requeues counted once each: never more than restarts * inflight
    assert led["requeued"] <= st["recovery"]["restarts"] * 3
    sched.shutdown(drain=True, timeout=30)


def test_ptdump_rolls_up_restarts(tmp_path, capsys):
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
               {"kind": "fault.injected", "ts": 0.5,
                "point": "step_launch", "hit": 4, "action": "raise"},
               {"kind": "engine.restart", "ts": 1.0, "requeued": 3,
                "failed": 0, "quarantined": 0, "broken": False,
                "duration_s": 0.002},
               {"kind": "engine.restart", "ts": 2.0, "requeued": 0,
                "failed": 2, "quarantined": 1, "broken": True,
                "duration_s": 0.001,
                "error": "ReplicaKilledError('dead')"}]}
    path = tmp_path / "dump.json"
    path.write_text(json.dumps(doc))
    assert ptdump.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "engine restarts: 2" in out
    assert "3 requeued, 2 failed, 1 quarantined" in out
    assert "1 injected faults" in out
    assert "crash-loop breaker OPEN" in out
