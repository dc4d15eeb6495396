"""Scale-out serving tier (serving/router.py + serving/replica.py):
prefix-affinity dispatch over a replica pool, least-loaded spill under
backpressure, circuit-breaker health with half-open probes, failover of
queued-but-unstarted requests on replica death (token-identical to an
undisturbed run), graceful per-replica drain, and aggregated /metrics
with replica labels — all end-to-end in-process on CPU over real
engines, and over real HTTP where the acceptance criteria ask for it.
"""
import threading
import time

import jax.numpy as jnp
import pytest

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama_serving import Request, ServingEngine
from paddle_tpu.serving import (BackpressureError, ReplicaKilledError,
                                Router, ServingClient, ServingHTTPError,
                                ServingServer, build_replicas,
                                prefix_key)

CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)
PAGE = 8


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


def make_factory(params, max_seqs=2, max_seq_len=64, **kw):
    def factory(_i=0):
        return ServingEngine(params, CFG, max_seqs=max_seqs,
                             max_seq_len=max_seq_len, page_size=PAGE,
                             use_pallas=False, prefix_cache=True, **kw)
    return factory


def make_router(params, n=2, max_queue=16, **router_kw):
    reps = build_replicas(make_factory(params), n, max_queue=max_queue)
    return Router(reps, **router_kw)


def test_replicas_sit_on_their_own_devices(params):
    """build_replicas hands replica i local device i (the suite runs on
    8 virtual devices): weights, KV pools and the token ring are all
    committed there, and a served request leaves them there."""
    import jax
    reps = build_replicas(make_factory(params), 2)
    try:
        want = [{d} for d in jax.local_devices()[:2]]
        assert [r.engine.device for r in reps] == jax.local_devices()[:2]
        for attr in ("k_pool", "v_pool", "tok_buf"):
            assert [getattr(r.engine, attr).devices() for r in reps] == want
        assert [r.engine.params["lm_head"].devices() for r in reps] == want
        for r in reps:
            assert len(r.submit(header(1), max_new_tokens=2)
                       .result(timeout=120)) == 2
        assert [r.engine.k_pool.devices() for r in reps] == want
    finally:
        for r in reps:
            r.shutdown(drain=False, timeout=10)


def greedy_reference(params, prompt, n_new):
    ids = list(prompt)
    out = []
    for _ in range(n_new):
        logits = M.forward(params, jnp.asarray([ids]), CFG, mesh=None,
                           remat=False)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        ids.append(nxt)
    return out


def header(seed, blocks=2):
    """A deterministic shared system-prompt header of full pages."""
    return [(seed * 31 + i) % 60 + 1 for i in range(blocks * PAGE)]


class TestPrefixKey:
    def test_same_header_same_key_any_tail(self):
        h = header(1)
        k1, n1 = prefix_key(h + [7, 8], PAGE)
        k2, n2 = prefix_key(h + [9], PAGE)
        assert k1 == k2 and n1 == n2 == 2

    def test_matches_prefix_cache_cap(self):
        # exactly 2 blocks: capped one token short, like
        # PrefixCache.match — only 1 full block participates
        h = header(1)          # 16 tokens
        _, n = prefix_key(h, PAGE)
        assert n == (len(h) - 1) // PAGE == 1
        _, n_plus = prefix_key(h + [5], PAGE)
        assert n_plus == 2

    def test_short_prompts_colocate_by_raw_tokens(self):
        k1, n1 = prefix_key([1, 2, 3], PAGE)
        k2, _ = prefix_key([1, 2, 3], PAGE)
        k3, _ = prefix_key([1, 2, 4], PAGE)
        assert n1 == 0 and k1 == k2 and k1 != k3

    def test_different_headers_different_keys(self):
        ks = {prefix_key(header(s) + [1], PAGE)[0] for s in range(8)}
        assert len(ks) == 8


class TestAffinity:
    def test_shared_prefix_sticks_to_one_replica(self, params):
        router = make_router(params)
        try:
            h = header(3)
            target = router.affinity_target(h + [40])
            rids = []
            for t in range(4):
                rr = router.submit(h + [40 + t], max_new_tokens=3)
                rr.result(timeout=60)
                rids.append(rr.replica_id)
            assert rids == [target] * 4
            snap = router.registry.snapshot()
            assert snap["pt_router_affinity_hits"]["value"] == 4
            assert snap["pt_router_dispatches"]["value"] == 4
            # the affinity replica's prefix cache engaged: first
            # request missed, the rest hit the shared header
            pc = router.replica(target).engine.prefix_cache
            assert pc.hits == 3 and pc.lookups == 4
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_affinity_beats_round_robin_hit_rate(self, params):
        """4 prompt groups x 4 requests: affinity routing misses once
        per group (the whole group lands on one replica); round-robin
        spreads each group over both replicas, so every group misses
        once PER REPLICA — measurably lower pt_prefix_hit_rate."""
        def run(policy):
            router = make_router(params, policy=policy)
            try:
                for g in range(4):
                    h = header(10 + g)
                    for t in range(4):
                        router.submit(h + [30 + t],
                                      max_new_tokens=3).result(timeout=60)
                hits = lookups = 0
                for rid in router.replica_ids:
                    pc = router.replica(rid).engine.prefix_cache
                    hits += pc.hits
                    lookups += pc.lookups
                return hits / lookups
            finally:
                router.shutdown(drain=True, timeout=30)
        affinity_rate = run("affinity")
        rr_rate = run("round_robin")
        assert affinity_rate == pytest.approx(12 / 16)
        assert rr_rate == pytest.approx(8 / 16)
        assert affinity_rate > rr_rate

    def test_outputs_token_identical_to_reference(self, params):
        router = make_router(params)
        try:
            h = header(5)
            for t in (1, 2):
                out = router.submit(h + [t],
                                    max_new_tokens=4).result(timeout=60)
                assert out == greedy_reference(params, h + [t], 4)
        finally:
            router.shutdown(drain=True, timeout=30)


class TestSpill:
    def test_backpressured_target_spills_to_least_loaded(self, params):
        router = make_router(params, max_queue=2)
        try:
            h = header(7)
            target = router.affinity_target(h + [1])
            other = [r for r in router.replica_ids if r != target][0]
            # freeze the affinity target's pump and fill its queue
            router.replica(target).pause()
            held = [router.submit(h + [1 + t], max_new_tokens=3)
                    for t in range(2)]
            assert all(r.replica_id == target for r in held)
            # target full -> the next request spills to the other one
            spilled = router.submit(h + [9], max_new_tokens=3)
            assert spilled.replica_id == other
            assert spilled.result(timeout=60) == greedy_reference(
                params, h + [9], 3)
            snap = router.registry.snapshot()
            assert snap["pt_router_spills"]["value"] >= 1
            router.replica(target).resume()
            for r in held:
                r.result(timeout=60)
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_all_full_raises_backpressure(self, params):
        router = make_router(params, max_queue=1)
        try:
            router.pause()
            h = header(8)
            for rid in router.replica_ids:
                # fill each replica's queue (router walks the plan)
                router.submit(header(8) + [rid.__hash__() % 5],
                              max_new_tokens=2)
            with pytest.raises(BackpressureError):
                router.submit(h + [50], max_new_tokens=2)
            assert router.registry.snapshot()[
                "pt_router_rejects"]["value"] >= 1
        finally:
            router.resume()
            router.shutdown(drain=True, timeout=30)


class TestFailover:
    def test_replica_death_fails_over_queued_requests(self, params):
        router = make_router(params, max_queue=16, unhealthy_after=2)
        try:
            h = header(11)
            target = router.affinity_target(h + [1])
            rep = router.replica(target)
            # park requests in the target's queue, then kill it
            rep.pause()
            held = [router.submit(h + [1 + t], max_new_tokens=3)
                    for t in range(3)]
            rep.kill()
            rep.resume()
            outs = [r.result(timeout=60) for r in held]
            # token-identical to an undisturbed run
            for t, out in enumerate(outs):
                assert out == greedy_reference(params, h + [1 + t], 3)
            assert all(r.state == "done" for r in held)
            assert all(r.failovers >= 1 for r in held)
            assert all(r.replica_id != target for r in held)
            snap = router.registry.snapshot()
            assert snap["pt_router_failovers"]["value"] >= 3
            # consecutive failures opened the breaker
            st = router.stats()["replicas"][target]
            assert st["health"] == "open"
            assert snap["pt_router_unhealthy_transitions"]["value"] == 1
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_unhealthy_replica_skipped_then_probe_recovers(self, params):
        router = make_router(params, unhealthy_after=1,
                             probe_after_s=30.0)
        try:
            h = header(12)
            target = router.affinity_target(h + [1])
            rep = router.replica(target)
            rep.kill()
            rr = router.submit(h + [1], max_new_tokens=2)
            assert rr.result(timeout=60) == greedy_reference(
                params, h + [1], 2)
            assert rr.failovers == 1
            assert router.stats()["replicas"][target]["health"] == "open"
            # while open (cooldown not elapsed): dispatch avoids the
            # corpse entirely
            rr2 = router.submit(h + [2], max_new_tokens=2)
            assert rr2.replica_id != target
            rr2.result(timeout=60)
            # replica restarts; rewind the breaker clock (determinism
            # instead of sleeping out a real cooldown) -> ONE probe
            # goes in, succeeds, closes the breaker
            rep.revive()
            with router._lock:
                router._replicas[target].opened_at = \
                    time.monotonic() - 31.0
            rr3 = router.submit(h + [3], max_new_tokens=2)
            assert rr3.replica_id == target
            assert rr3.result(timeout=60) == greedy_reference(
                params, h + [3], 2)
            assert router.stats()["replicas"][target]["health"] == "ok"
            assert router.registry.snapshot()[
                "pt_router_probes"]["value"] >= 1
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_streams_fail_over_before_first_byte_only(self, params):
        router = make_router(params, max_queue=16)
        try:
            h = header(13)
            target = router.affinity_target(h + [1])
            rep = router.replica(target)
            rep.pause()
            rr = router.submit(h + [1], max_new_tokens=3)
            rep.kill()
            rep.resume()
            toks = [t for chunk in rr.stream(timeout=60) for t in chunk]
            assert toks == greedy_reference(params, h + [1], 3)
            assert rr.failovers == 1
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_no_replica_left_raises_original_error(self, params):
        router = make_router(params)
        try:
            for rid in router.replica_ids:
                router.replica(rid).pause()
            held = router.submit(header(14) + [1], max_new_tokens=2)
            for rid in router.replica_ids:
                router.replica(rid).kill()
                router.replica(rid).resume()
            with pytest.raises(Exception) as ei:
                held.result(timeout=60)
            assert "killed" in str(ei.value) or "failed" in str(ei.value)
        finally:
            router.shutdown(drain=False, timeout=30)


class TestDrain:
    def test_graceful_drain_finishes_running_then_removes(self, params):
        router = make_router(params)
        try:
            h = header(15)
            target = router.affinity_target(h + [1])
            rr = router.submit(h + [1], max_new_tokens=20)
            # rolling restart: drain flips readiness off, lets the
            # running request finish, then drops the replica
            assert router.drain_replica(target, timeout=60)
            assert rr.state == "done"
            assert rr.result(timeout=5) == greedy_reference(
                params, h + [1], 20)
            assert target not in router.replica_ids
            # the drained replica's keys re-home deterministically
            rr2 = router.submit(h + [2], max_new_tokens=2)
            assert rr2.replica_id != target
            rr2.result(timeout=60)
            ready, detail = router.readiness()
            assert ready and target not in detail
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_drain_last_replica_closes_router(self, params):
        router = make_router(params, n=1)
        assert router.drain_replica(router.replica_ids[0], timeout=60)
        ready, _ = router.readiness()
        assert not ready
        with pytest.raises(Exception):
            router.submit([1, 2, 3], max_new_tokens=2)


class TestRouterHTTP:
    """The acceptance e2e: router + 2 in-process replicas behind the
    real HTTP server, shared-system-prompt workload, replica killed
    mid-run -> queued requests fail over and complete token-identical,
    /metrics aggregates with replica labels and counts the failover."""

    @pytest.fixture()
    def served(self, params):
        router = make_router(params, max_queue=16, unhealthy_after=2)
        srv = ServingServer(router, port=0).start()
        yield srv, router
        srv.stop(drain=False, timeout=30)

    def test_acceptance_affinity_failover_metrics(self, served, params):
        srv, router = served
        cl = ServingClient(port=srv.port)
        h = header(21)
        ref = {t: greedy_reference(params, h + [t], 3)
               for t in (1, 2, 3, 4, 5, 6)}

        # (a) affinity-routed requests hit the affinity replica's cache
        target = router.affinity_target(h + [1])
        for t in (1, 2, 3):
            out = cl.complete(h + [t], max_tokens=3)
            assert out["state"] == "done" and out["tokens"] == ref[t]
        text = cl.metrics_text()
        assert f'pt_prefix_hit_rate{{replica="{target}"}} ' in text
        hit_line = [ln for ln in text.splitlines()
                    if ln.startswith(
                        f'pt_prefix_hit_rate{{replica="{target}"}}')][0]
        assert float(hit_line.split()[-1]) > 0

        # (b) kill the affinity replica with requests parked on it:
        # they fail over and complete token-identical over live HTTP
        rep = router.replica(target)
        rep.pause()
        results = {}

        def call(t):
            results[t] = cl.complete(h + [t], max_tokens=3)
        threads = [threading.Thread(target=call, args=(t,))
                   for t in (4, 5, 6)]
        for th in threads:
            th.start()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and \
                rep.stats()["queued"] < 3:
            time.sleep(0.01)
        assert rep.stats()["queued"] == 3
        rep.kill()
        rep.resume()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
        for t in (4, 5, 6):
            assert results[t]["state"] == "done"
            assert results[t]["tokens"] == ref[t], t

        # (c) aggregated /metrics: failover counted, replica labels on
        # per-replica series, router counters flat
        text = cl.metrics_text()
        fo = [ln for ln in text.splitlines()
              if ln.startswith("pt_router_failovers_total ")][0]
        assert float(fo.split()[-1]) >= 1
        for rid in router.replica_ids + [target]:
            assert f'replica="{rid}"' in text
        assert "pt_router_dispatches_total " in text
        assert "pt_router_affinity_hits_total " in text
        # JSON snapshot nests per-replica registries
        snap = cl.metrics()
        assert set(snap["replicas"]) >= set(router.replica_ids)
        # the failover's flight-recorder trail carries trace ids
        fr = cl._json_call("GET", "/debug/flightrecorder")
        evs = [e for e in fr["events"]
               if e.get("kind") == "router.failover"]
        assert evs and all(e.get("trace_id") for e in evs)
        disp = [e for e in fr["events"]
                if e.get("kind") == "router.dispatch"]
        assert disp and all(e.get("trace_id") for e in disp)

    def test_healthz_and_readyz(self, served):
        srv, router = served
        cl = ServingClient(port=srv.port)
        h = cl.healthz()
        assert h["status"] == "ok" and h["replicas_ready"] == 2
        assert set(h["replicas"]) == set(router.replica_ids)
        r = cl.readyz()
        assert r["ready"] is True
        router.pause()
        try:
            # every replica paused -> the pool takes no traffic:
            # readiness flips (503) while liveness stays 200
            with pytest.raises(ServingHTTPError) as ei:
                cl.readyz()
            assert ei.value.status == 503
            # liveness unaffected: a fully paused pool is alive ("ok"),
            # not "draining" — closed means every pump actually exited
            assert cl.healthz()["status"] == "ok"
        finally:
            router.resume()
        assert cl.readyz()["ready"] is True


class TestSchedulerLedger:
    """Satellite: scheduler.stats() monotonic started/completed/failed
    ledger, surfaced on /healthz and /metrics."""

    def test_ledger_counts_lifecycle(self, params):
        from paddle_tpu.serving import RequestScheduler
        # a bucketed engine, so the synchronous pump: it is `eng.step`
        # that dies below
        eng = make_factory(params, ragged=False)(0)
        sched = RequestScheduler(eng, max_queue=8)
        try:
            sched.submit([1, 2, 3], max_new_tokens=3).result(timeout=60)
            sched.submit([4, 5, 6], max_new_tokens=3).result(timeout=60)
            lg = sched.stats()["requests"]
            assert lg["submitted"] == lg["started"] == 2
            assert lg["completed"] == 2 and lg["failed"] == 0
            # engine death -> failed, monotonic (nothing decrements)
            def boom():
                raise ReplicaKilledError("dead")
            eng.step = boom
            sr = sched.submit([7, 8, 9], max_new_tokens=3)
            with pytest.raises(Exception):
                sr.result(timeout=60)
            lg = sched.stats()["requests"]
            assert lg["failed"] == 1 and lg["submitted"] == 3
            snap = sched.registry.snapshot()
            assert snap["pt_serving_requests_started"]["value"] == 3
            assert snap["pt_serving_requests_failed"]["value"] == 1
        finally:
            sched.shutdown(drain=False, timeout=30)

    def test_ledger_on_http_surfaces(self, params):
        eng = make_factory(params)(0)
        srv = ServingServer(eng, port=0).start()
        try:
            cl = ServingClient(port=srv.port)
            cl.complete([1, 5, 9], max_tokens=3)
            lg = cl.healthz()["requests"]
            assert lg["completed"] == 1 and lg["started"] == 1
            text = cl.metrics_text()
            assert "pt_serving_requests_started_total 1" in text
            assert "pt_serving_requests_failed_total 0" in text
        finally:
            srv.stop(drain=True, timeout=30)


class TestReadyz:
    """Satellite: /readyz is readiness (503 while paused/draining),
    /healthz stays liveness."""

    def test_readyz_flips_on_pause_and_drain(self, params):
        eng = make_factory(params)(0)
        srv = ServingServer(eng, port=0).start()
        cl = ServingClient(port=srv.port)
        try:
            assert cl.readyz()["ready"] is True
            srv.scheduler.pause()
            with pytest.raises(ServingHTTPError) as ei:
                cl.readyz()
            assert ei.value.status == 503
            assert ei.value.body["detail"] == "paused"
            assert cl.healthz()["status"] == "ok"   # still alive
            srv.scheduler.resume()
            assert cl.readyz()["ready"] is True
        finally:
            srv.stop(drain=True, timeout=30)


class TestClientConnRetries:
    """Satellite: bounded client retries now also cover idempotent
    connection-refused/reset before the first streamed byte."""

    def _flaky_conn(self, client, fail, exc):
        calls = {"n": 0}

        def fn(method, path, body=None):
            calls["n"] += 1
            if calls["n"] <= fail:
                raise exc
            return {"ok": True}
        client._json_call = fn
        return calls

    def test_refused_retried_then_succeeds(self, monkeypatch):
        from paddle_tpu.serving import client as C
        sleeps = []
        monkeypatch.setattr(C.time, "sleep", sleeps.append)
        cl = ServingClient(retries=3)
        calls = self._flaky_conn(cl, 2, ConnectionRefusedError(
            "connection refused"))
        assert cl.complete([1, 2])["ok"] is True
        assert calls["n"] == 3 and len(sleeps) == 2

    def test_reset_retried(self, monkeypatch):
        from paddle_tpu.serving import client as C
        monkeypatch.setattr(C.time, "sleep", lambda s: None)
        cl = ServingClient(retries=1)
        calls = self._flaky_conn(cl, 1, ConnectionResetError("reset"))
        assert cl.complete([1, 2])["ok"] is True
        assert calls["n"] == 2

    def test_exhausted_reraises(self, monkeypatch):
        from paddle_tpu.serving import client as C
        monkeypatch.setattr(C.time, "sleep", lambda s: None)
        cl = ServingClient(retries=2)
        calls = self._flaky_conn(cl, 99, ConnectionRefusedError("no"))
        with pytest.raises(ConnectionRefusedError):
            cl.complete([1, 2])
        assert calls["n"] == 3

    def test_default_no_conn_retry(self):
        cl = ServingClient()
        calls = self._flaky_conn(cl, 99, ConnectionRefusedError("no"))
        with pytest.raises(ConnectionRefusedError):
            cl.complete([1, 2])
        assert calls["n"] == 1

    def test_rolling_restart_invisible_with_retries(self, params):
        """Real sockets: the server goes away and comes back on the
        same port; a client with retries rides through the refused
        connections (what a rolling replica restart looks like from
        outside the router)."""
        eng = make_factory(params)(0)
        srv = ServingServer(eng, port=0).start()
        port = srv.port
        cl = ServingClient(port=port, timeout=10, retries=8,
                           retry_cap_s=0.2)
        assert cl.complete([1, 2, 3], max_tokens=2)["state"] == "done"
        srv.stop(drain=True, timeout=30)

        def restart():
            time.sleep(0.3)
            eng2 = make_factory(params)(0)
            srv2 = ServingServer(eng2, host="127.0.0.1", port=port)
            srv2.start()
            results["srv"] = srv2
        results = {}
        th = threading.Thread(target=restart)
        th.start()
        try:
            out = cl.complete([1, 2, 3], max_tokens=2)
            assert out["state"] == "done"
        finally:
            th.join(timeout=30)
            if "srv" in results:
                results["srv"].stop(drain=True, timeout=30)
