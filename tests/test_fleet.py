"""Fleet plane (serving/fleet.py + serving/wire.py): multi-host
disaggregated serving over the rpc layer.

Loopback-socket drills over REAL wire paths: workers run in-process
(several rpc agents + bulk servers sharing the test process — every
byte still crosses a socket) except the subprocess drill, which spawns
true worker processes. Covers: wire framing round-trips, router-over-
RemoteReplica token identity vs the in-process router, host= labels on
aggregated metrics and /debug payloads, worker kill mid-decode
(requests survive via failover, token-identical), drain, KV handoff
migration across workers (prefill -> decode over the bulk channel,
pt_handoff_seconds observed on a real socket), prefix-page spill/fetch
round-trip (the global prefix cache), and heartbeat loss -> the worker
degrades without dropping a request.
"""
import socket
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed import rpc as _rpc
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models.llama_serving import ServingEngine
from paddle_tpu.serving import (FleetPlane, FleetWorker, KVHandoff,
                                Replica, Router, SchedulerClosedError,
                                WireError, fleet, wire)
from paddle_tpu.serving.kvcache import _SEED, block_hash

CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)
PAGE = 8


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


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
    return [(seed * 31 + i) % 60 + 1 for i in range(blocks * PAGE)]


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def sockpair():
    a, b = socket.socketpair()
    a.settimeout(5.0)
    b.settimeout(5.0)
    return a, b


# ---------------------------------------------------------------------------
# wire framing


class TestWire:
    def test_json_round_trip(self):
        a, b = sockpair()
        with a, b:
            obj = {"op": "x", "n": 7, "l": [1, 2], "none": None}
            wire.send_json(a, obj)
            assert wire.recv_json(b) == obj

    def test_json_oversize_refused_both_ends(self):
        a, b = sockpair()
        with a, b:
            with pytest.raises(WireError):
                wire.send_json(a, {"x": "y" * (wire.MAX_JSON_FRAME + 8)})
            # a corrupt length prefix fails before allocation
            a.sendall(b"\xff\xff\xff\xff")
            with pytest.raises(WireError):
                wire.recv_json(b)

    def test_bytes_chunked_round_trip(self):
        a, b = sockpair()
        data = bytes(range(256)) * 512
        got = {}
        t = threading.Thread(
            target=lambda: got.update(d=wire.recv_bytes(b)))
        t.start()
        with a:
            wire.send_bytes(a, data)
        t.join(timeout=10)
        b.close()
        assert got["d"] == data

    def test_array_round_trip_and_none(self):
        a, b = sockpair()
        arr = np.arange(-120, 120, dtype=np.int8).reshape(2, 120)
        got = []
        t = threading.Thread(
            target=lambda: got.extend([wire.recv_array(b),
                                       wire.recv_array(b)]))
        t.start()
        with a:
            n = wire.send_array(a, arr)
            assert n == arr.nbytes
            assert wire.send_array(a, None) == 0
        t.join(timeout=10)
        b.close()
        np.testing.assert_array_equal(got[0], arr)
        assert got[0].dtype == np.int8 and got[1] is None

    def test_handoff_round_trip_bit_exact(self):
        k = np.random.default_rng(0).integers(
            -127, 127, size=(2, 2, 3, PAGE, 8), dtype=np.int8)
        v = np.array(k[::-1])
        ks = np.random.default_rng(1).random(
            (2, 2, 3, PAGE, 1), dtype=np.float32)
        h = KVHandoff("rid-1", [1, 2, 3], [4, 5], 6, 5, 3, k, v,
                      ks=ks, vs=np.array(ks), quantized=True,
                      trace_id="t-1", cached_tokens=2,
                      timeline={"marks": [["submit", 0.0]]})
        a, b = sockpair()
        got = []
        t = threading.Thread(target=lambda: got.append(
            wire.recv_handoff(b)))
        t.start()
        with a:
            n = wire.send_handoff(a, h)
        t.join(timeout=10)
        b.close()
        h2 = got[0]
        assert isinstance(h2, KVHandoff)
        assert n == h.nbytes == h2.nbytes
        np.testing.assert_array_equal(h2.k, k)
        np.testing.assert_array_equal(h2.v, v)
        np.testing.assert_array_equal(h2.ks, ks)
        assert (h2.rid, h2.prompt, h2.output, h2.next_token, h2.length,
                h2.pages, h2.quantized, h2.trace_id, h2.cached_tokens) \
            == ("rid-1", [1, 2, 3], [4, 5], 6, 5, 3, True, "t-1", 2)
        assert h2.timeline == {"marks": [["submit", 0.0]]}

    def test_deterministic_ring_points_cross_process_safe(self):
        # blake2b ring points are a pure function of the string —
        # unlike hash(str), which PYTHONHASHSEED salts per process
        assert fleet._ring_point("p0|0") == fleet._ring_point("p0|0")
        pts = {fleet._ring_point(f"r{i}|{j}")
               for i in range(4) for j in range(64)}
        assert len(pts) == 256
        assert all(-(1 << 63) <= p < (1 << 63) for p in pts)


# ---------------------------------------------------------------------------
# in-process fleet harness (real sockets, one process)


class FleetHarness:
    """N FleetWorkers + a FleetPlane on loopback in one process. Every
    control call and token byte still crosses real TCP sockets; only
    the python interpreter is shared (the subprocess drill covers true
    process isolation)."""

    def __init__(self, params, roles, max_queue=16, hb_timeout_s=None,
                 **engine_kw):
        port = free_port()
        endpoint = f"127.0.0.1:{port}"
        names = [f"w{i}" for i in range(len(roles))]
        self.workers = [None] * len(roles)
        errors = []

        def build(i):
            try:
                engine = ServingEngine(
                    params, CFG, max_seqs=2, max_seq_len=64,
                    page_size=PAGE, use_pallas=False,
                    prefix_cache=True, **engine_kw)
                rep = Replica(f"fr{i}", engine, max_queue=max_queue,
                              role=roles[i])
                self.workers[i] = FleetWorker(
                    names[i], rep, master_endpoint=endpoint,
                    rank=i + 1, world_size=len(roles) + 1,
                    host=f"host{i}")
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [threading.Thread(target=build, args=(i,), daemon=True)
                   for i in range(len(roles))]
        for t in threads:
            t.start()
        # rank 0: hosts the store; returns once every worker is up
        self.plane = FleetPlane(endpoint, names,
                                hb_timeout_s=hb_timeout_s)
        for t in threads:
            t.join(timeout=60)
        if errors:
            raise errors[0]
        self.replicas = self.plane.replicas

    def worker_for(self, rep):
        return self.workers[self.replicas.index(rep)]

    def close(self):
        for w in self.workers:
            if w is None:
                continue
            try:
                w.replica.shutdown(drain=False, timeout=10)
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
            w.close()
        self.plane.close()


@pytest.fixture()
def make_fleet(params):
    made = []

    def _make(roles=("both", "both"), **kw):
        h = FleetHarness(params, list(roles), **kw)
        made.append(h)
        return h

    yield _make
    for h in made:
        h.close()


# ---------------------------------------------------------------------------
# basics: duck-type fidelity + token identity vs in-process router


class TestFleetBasics:
    def test_remote_replica_duck_type_and_stats(self, make_fleet):
        fl = make_fleet(("both", "both"))
        rep = fl.replicas[0]
        assert rep.prefill_eligible() and rep.decode_eligible()
        assert rep.page_size == PAGE and rep.ready()
        st = rep.stats()
        assert st["replica_id"] == "fr0" and st["host"] == "host0"
        assert st["requests"]["submitted"] == 0
        assert rep.load() == 0

    def test_router_over_fleet_token_identical(self, params, make_fleet):
        fl = make_fleet(("both", "both"))
        router = Router(fl.replicas)
        try:
            h = header(3)
            outs = {}
            for t in range(4):
                rr = router.submit(h + [40 + t], max_new_tokens=4)
                outs[t] = rr.result(timeout=60)
                assert rr.state == "done"
            for t, out in outs.items():
                assert out == greedy_reference(params, h + [40 + t], 4)
            # affinity held: one replica served the shared header
            snap = router.registry.snapshot()
            assert snap["pt_router_affinity_hits"]["value"] == 4
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_streaming_chunks_and_first_token(self, params, make_fleet):
        fl = make_fleet(("both",))
        router = Router(fl.replicas)
        try:
            prompt = header(5) + [9]
            rr = router.submit(prompt, max_new_tokens=5)
            toks = [t for chunk in rr.stream(timeout=60) for t in chunk]
            assert toks == greedy_reference(params, prompt, 5)
            assert rr._sr._streamed and rr._sr.t_first_token is not None
            assert rr._sr.timeline is not None
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_host_label_on_metrics_and_debug(self, make_fleet):
        fl = make_fleet(("both", "both"))
        router = Router(fl.replicas)
        try:
            rr = router.submit(header(6) + [3], max_new_tokens=2)
            rr.result(timeout=60)
            text = router.render_prometheus()
            assert 'replica="fr0",host="host0"' in text
            assert 'replica="fr1",host="host1"' in text
            st = router.stats()
            assert st["replicas"]["fr0"]["host"] == "host0"
            snap = router.metrics_snapshot()
            assert snap["replicas"]["fr1"]["host"] == "host1"
            recent = router.recent_requests(10)
            assert recent and all("host" in e for e in recent)
            served = rr.replica_id
            assert any(e["host"] == f"host{served[-1]}"
                       for e in recent)
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_backpressure_and_errors_cross_the_wire(self, make_fleet):
        fl = make_fleet(("both",), max_queue=16)
        rep = fl.replicas[0]
        with pytest.raises(ValueError):
            rep.submit([], max_new_tokens=2)
        rep.pause()
        assert not rep.ready()
        rep.resume()
        assert rep.ready()


# ---------------------------------------------------------------------------
# kill / failover / drain drills


class TestFleetFailover:
    def test_worker_kill_mid_decode_requests_survive(
            self, params, make_fleet):
        fl = make_fleet(("both", "both"))
        router = Router(fl.replicas, unhealthy_after=2)
        try:
            h = header(12)
            target = router.affinity_target(h + [1])
            rep = router.replica(target)
            rep.pause()
            held = [router.submit(h + [1 + t], max_new_tokens=3)
                    for t in range(3)]
            rep.kill()          # rpc: arms the fault on the REMOTE engine
            rep.resume()
            outs = [r.result(timeout=90) for r in held]
            for t, out in enumerate(outs):
                assert out == greedy_reference(params, h + [1 + t], 3)
            assert all(r.state == "done" for r in held)
            assert all(r.failovers >= 1 for r in held)
            assert all(r.replica_id != target for r in held)
            assert router.stats()["replicas"][target]["health"] == "open"
            # revive over the wire: the worker serves again
            rep.revive()
            with router._lock:
                router._replicas[target].opened_at = \
                    time.monotonic() - 1e6
            rr = router.submit(h + [9], max_new_tokens=2)
            assert rr.result(timeout=60) == greedy_reference(
                params, h + [9], 2)
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_drain_finishes_running_then_removes(self, params,
                                                 make_fleet):
        fl = make_fleet(("both", "both"))
        router = Router(fl.replicas)
        try:
            h = header(15)
            target = router.affinity_target(h + [1])
            rr = router.submit(h + [1], max_new_tokens=10)
            assert router.drain_replica(target, timeout=90)
            assert rr.state == "done"
            assert rr.result(timeout=5) == greedy_reference(
                params, h + [1], 10)
            assert target not in router.replica_ids
            rr2 = router.submit(h + [2], max_new_tokens=2)
            assert rr2.replica_id != target
            rr2.result(timeout=60)
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_dead_worker_submit_refused_and_load_degrades(
            self, make_fleet):
        fl = make_fleet(("both", "both"))
        rep = fl.replicas[0]
        rep._mark_dead("test")
        with pytest.raises(SchedulerClosedError):
            rep.submit([1, 2, 3], max_new_tokens=1)
        assert rep.load() == fleet._DEAD_LOAD
        assert rep.ready() is False
        st = rep.stats()
        assert st["ready"] is False and st["closed"] is True

    def test_heartbeat_loss_degrades_without_dropping(
            self, params, make_fleet, monkeypatch):
        monkeypatch.setenv("PT_FLEET_HB_S", "0.1")
        fl = make_fleet(("both", "both"), hb_timeout_s=0.6)
        router = Router(fl.replicas, unhealthy_after=1)
        try:
            h = header(21)
            target = router.affinity_target(h + [1])
            rep = router.replica(target)
            w = fl.worker_for(rep)
            # park a request unstarted, then silence ONLY the beat —
            # the worker stays up, but the plane must declare it dead
            rep.pause()
            held = router.submit(h + [1], max_new_tokens=3)
            w.stop_heartbeat()
            deadline = time.monotonic() + 20
            while rep.alive and time.monotonic() < deadline:
                time.sleep(0.05)
            assert not rep.alive
            assert fl.plane.hb_misses.value >= 1
            # the parked request failed over to the healthy worker and
            # completed token-identical — degradation, no drop
            assert held.result(timeout=90) == greedy_reference(
                params, h + [1], 3)
            assert held.replica_id != target
            assert held.failovers >= 1
        finally:
            router.shutdown(drain=True, timeout=30)


# ---------------------------------------------------------------------------
# disaggregated prefill/decode with handoff over the bulk socket


class TestFleetHandoff:
    def test_migration_across_workers_token_identical(
            self, params, make_fleet):
        fl = make_fleet(("prefill", "decode"),
                        host_tier_bytes=8 << 20)
        router = Router(fl.replicas)
        try:
            prompts = [header(7) + [30 + t] for t in range(3)]
            held = [router.submit(p, max_new_tokens=4) for p in prompts]
            outs = [r.result(timeout=90) for r in held]
            for p, out in zip(prompts, outs):
                assert out == greedy_reference(params, p, 4)
            assert all(r.state == "done" for r in held)
            # every request migrated prefill -> decode
            assert all(r.replica_id == "fr1" for r in held)
            snap = router.registry.snapshot()
            assert snap["pt_router_handoffs"]["value"] == 3
            # the pages crossed a REAL socket: the prefill worker
            # served them over its bulk channel and measured the hop
            src = fl.workers[0]
            assert src.handoff_serves.value == 3
            assert src.handoff_wire_bytes.value > 0
            reg = src.replica.registry.snapshot()
            # 3 engine exports + 3 socket hops: both halves of each
            # migration land in the same transfer-time histogram
            assert reg["pt_handoff_seconds"]["count"] == 6
            assert reg["pt_handoff_bytes"]["value"] > 0
        finally:
            router.shutdown(drain=True, timeout=30)

    def test_remote_handoff_ref_fetch_and_miss(self, make_fleet):
        fl = make_fleet(("both",), host_tier_bytes=8 << 20)
        w = fl.workers[0]
        k = np.ones((2, 2, 1, PAGE, 8), np.int8)
        h = KVHandoff("hand-1", [1, 2], [3], 4, 3, 1, k, np.array(k),
                      quantized=True)
        with w._req_lock:
            w._handoffs["hand-1"] = h
        ref = fleet.RemoteHandoffRef(w.bulk_addr, "hand-1",
                                     nbytes=h.nbytes, pages=1)
        got = ref.resolve()
        np.testing.assert_array_equal(got.k, k)
        # lazy attribute access delegates to the resolved payload and
        # repeat fetches hit the worker-side cache (not popped)
        assert ref.next_token == 4 and ref.resolve() is got
        assert fleet.RemoteHandoffRef(w.bulk_addr, "hand-1").resolve() \
            .length == 3
        missing = fleet.RemoteHandoffRef(w.bulk_addr, "nope")
        with pytest.raises(WireError):
            missing.resolve()


# ---------------------------------------------------------------------------
# global prefix-page cache: spill to owner, fetch on miss


def _tier_payload(fill, nbytes=4096):
    k = np.full((nbytes // 2,), fill, np.int8)
    return {"k": k, "v": np.array(k), "ks": None, "vs": None}


class TestFleetPages:
    def _owned_block(self, pages, owner_rid, parent=_SEED, lo=1):
        """First token block whose chained hash the ring assigns to
        `owner_rid` (deterministic: the ring is content-hashed)."""
        for s in range(lo, 4096):
            block = tuple((s * 13 + i) % 60 + 1 for i in range(PAGE))
            key = block_hash(parent, block)
            if pages.owner_of(key) == owner_rid:
                return block, key
        raise AssertionError("no owned block found")

    @staticmethod
    def _until(cond, deadline):
        """A counter on the far side of a socket is bumped after the
        bytes it counts are visible on this side: poll, do not assume."""
        while not cond() and time.monotonic() < deadline:
            time.sleep(0.05)

    def test_spill_lands_at_owner_and_fetch_returns(self, make_fleet):
        fl = make_fleet(("prefill", "prefill"),
                        host_tier_bytes=10_000)
        wa, wb = fl.workers
        assert wa.pages is not None and wb.pages is not None
        # a block OWNED BY B, inserted on A at depth 9: budget pressure
        # must ship it to B, not drop it
        block, key = self._owned_block(wa.pages, "fr1")
        payload = _tier_payload(7)
        assert wa.replica.engine.host_tier.insert(
            _SEED, block, 9, payload)
        # filler at depth 0 blows the budget -> the deep block spills
        fill_block = tuple(range(1, PAGE + 1))
        wa.replica.engine.host_tier.insert(
            _SEED, fill_block, 0, _tier_payload(1, 8192))
        deadline = time.monotonic() + 15
        while wb.replica.engine.host_tier.peek(key) is None \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        landed = wb.replica.engine.host_tier.peek(key)
        assert landed is not None and landed["block"] == block
        np.testing.assert_array_equal(landed["payload"]["k"],
                                      payload["k"])
        # the page is visible at B before B counts it, and B's
        # acknowledgement is what A counts on: wait for the books too
        self._until(lambda: (wa.pages.spill_pages.value,
                             wb.pages.recv_pages.value) == (1, 1), deadline)
        assert wa.pages.spill_pages.value == 1
        assert wa.pages.spill_bytes.value > 0
        assert wb.pages.recv_pages.value == 1
        # fetch-on-miss: A's local match is short; the hook pulls the
        # chain block back from B over the bulk channel
        tokens = list(block) + [1]
        got = wa.replica.engine.host_tier.match(tokens, 0)
        assert len(got) == 1
        np.testing.assert_array_equal(got[0]["k"], payload["k"])
        assert wa.pages.fetch_pages.value == 1
        # (B counts a serve once the last array is sent, A has it by then)
        self._until(lambda: wb.pages.page_serves.value == 1,
                    time.monotonic() + 15)
        assert wb.pages.page_serves.value == 1
        # fetched page is now local: the next match is a pure local hit
        assert len(wa.replica.engine.host_tier.match(tokens, 0)) == 1
        assert wa.pages.fetch_pages.value == 1

    def test_fleet_entries_never_respill(self, make_fleet):
        fl = make_fleet(("prefill", "prefill"),
                        host_tier_bytes=10_000)
        wa = fl.workers[0]
        tier = wa.replica.engine.host_tier
        block, key = self._owned_block(wa.pages, "fr1")
        # peer-originated entry (fleet=True) at max depth...
        tier.insert(_SEED, block, 9, _tier_payload(3), fleet=True)
        # ...evicted by budget pressure: dropped, NOT shipped back
        tier.insert(_SEED, tuple(range(1, PAGE + 1)), 0,
                    _tier_payload(1, 8192))
        time.sleep(0.3)
        assert tier.peek(key) is None
        assert wa.pages.spill_pages.value == 0

    def _bare_pages(self):
        """A FleetPages shell with only the ring machinery: enough to
        drive _ensure_ring without sockets or engines."""
        pages = fleet.FleetPages.__new__(fleet.FleetPages)
        pages._ring_lock = threading.Lock()
        pages._points = None
        pages._peers = {}
        return pages

    class _Info:
        def __init__(self, rank, name):
            self.rank, self.name = rank, name

    def test_ring_membership_fetch_runs_outside_ring_lock(self):
        """Regression (found by tpuracer's TPL009 pass): _ensure_ring
        used to hold _ring_lock across the per-peer store/rpc round
        trips, so one slow peer stalled the spill loop and every
        owner_of() caller. Pin the fix: the agent/store I/O must see
        the lock released; only the publish happens under it."""
        pages = self._bare_pages()
        io_lock_states = []

        class Agent:
            def all_worker_infos(_):
                io_lock_states.append(pages._ring_lock.locked())
                return [TestFleetPages._Info(0, "router"),
                        TestFleetPages._Info(1, "w1"),
                        TestFleetPages._Info(2, "w2")]

        class Store:
            def get(_, key):
                io_lock_states.append(pages._ring_lock.locked())
                rid = "fr" + key.rsplit("/w", 1)[-1]
                return {"replica_id": rid, "role": "prefill"}

        class Worker:
            agent = Agent()
            store = Store()

        pages.worker = Worker()
        pts, peers = pages._ensure_ring()
        assert io_lock_states == [False, False, False]
        assert set(peers) == {"fr1", "fr2"}
        assert len(pts) == 128 and pts == sorted(pts)
        # second call is served from the published ring: no more I/O
        pts2, peers2 = pages._ensure_ring()
        assert pts2 is pts and peers2 == peers
        assert len(io_lock_states) == 3

    def test_racing_ring_builders_both_complete(self):
        """Two threads build the ring at once: each fetches its own
        snapshot outside the lock, the first publish wins, both return
        the identical ring. (With the membership fetch under the lock
        the second builder could never reach the barrier.)"""
        pages = self._bare_pages()
        barrier = threading.Barrier(2, timeout=5)

        class Agent:
            def all_worker_infos(_):
                barrier.wait()     # both builders in flight at once
                return [TestFleetPages._Info(1, "w1")]

        class Store:
            def get(_, key):
                return {"replica_id": "fr1", "role": "both"}

        class Worker:
            agent = Agent()
            store = Store()

        pages.worker = Worker()
        results, errors = [], []

        def build():
            try:
                results.append(pages._ensure_ring())
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [threading.Thread(target=build) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert not errors
        assert len(results) == 2
        assert results[0][0] == results[1][0]
        assert results[0][1] == results[1][1] == pages._peers
        assert pages._points is results[0][0] is results[1][0]

    def test_owner_miss_is_clean(self, make_fleet):
        fl = make_fleet(("prefill", "prefill"),
                        host_tier_bytes=10_000)
        wa = fl.workers[0]
        # a block owned by the peer that the peer never received:
        # fetch_missing counts a miss and the match stays short
        block, _ = self._owned_block(wa.pages, "fr1")
        tokens = list(block) + [1]
        assert wa.replica.engine.host_tier.match(tokens, 0) == []
        assert wa.pages.fetch_misses.value == 1


# ---------------------------------------------------------------------------
# true process isolation: spawned workers, handoff across processes


class TestFleetSubprocess:
    def test_spawned_prefill_decode_token_identical(self, params):
        port = free_port()
        endpoint = f"127.0.0.1:{port}"
        spec = {"master": endpoint, "world_size": 3, "seed": 0,
                "model": vars(CFG), "dtype": "float32",
                "engine": {"max_seqs": 2, "max_seq_len": 64,
                           "page_size": PAGE, "use_pallas": False,
                           "prefix_cache": True,
                           "host_tier_bytes": 8 << 20}}
        procs = [
            fleet.spawn_worker(dict(spec, name="p0", rank=1,
                                    role="prefill", host="hostA"),
                               env={"JAX_PLATFORMS": "cpu"}),
            fleet.spawn_worker(dict(spec, name="d0", rank=2,
                                    role="decode", host="hostB"),
                               env={"JAX_PLATFORMS": "cpu"}),
        ]
        plane = None
        router = None
        try:
            plane = FleetPlane(endpoint, ["p0", "d0"])
            router = Router(plane.replicas)
            prompt = header(9) + [11]
            rr = router.submit(prompt, max_new_tokens=4)
            out = rr.result(timeout=300)
            assert out == greedy_reference(params, prompt, 4)
            assert rr.state == "done"
            # served by the decode worker in the OTHER process, KV
            # moved host-to-host over the bulk socket
            assert rr.replica_id == "d0"
            text = router.render_prometheus()
            assert 'host="hostB"' in text
            assert router.shutdown(drain=True, timeout=60)
            for p in procs:
                assert p.wait(timeout=30) == 0
        finally:
            if router is not None:
                router.shutdown(drain=False, timeout=5)
            if plane is not None:
                plane.close()
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=10)
