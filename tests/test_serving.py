"""Serving engine: paged continuous-batching decode == dense reference."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama_serving import ServingEngine, Request


CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


def greedy_reference(params, prompt, n_new):
    """Dense recompute greedy decode (no cache) — ground truth."""
    ids = list(prompt)
    out = []
    for _ in range(n_new):
        logits = M.forward(params, jnp.asarray([ids]), CFG, mesh=None,
                           remat=False)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        ids.append(nxt)
    return out


class TestServing:
    def test_single_request_matches_dense(self, params):
        prompt = [1, 5, 9, 3, 7]
        ref = greedy_reference(params, prompt, 8)
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        eng.submit(Request("a", prompt, max_new_tokens=8))
        done = eng.run()
        assert len(done) == 1
        assert done[0].output == ref

    def test_continuous_batching_more_requests_than_slots(self, params):
        prompts = [[1, 2, 3], [9, 8, 7, 6, 5, 4], [11, 12], [13] * 9]
        refs = [greedy_reference(params, p, 6) for p in prompts]
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        for i, p in enumerate(prompts):
            eng.submit(Request(f"r{i}", p, max_new_tokens=6))
        done = eng.run()
        assert len(done) == 4
        by_id = {r.rid: r.output for r in done}
        for i, ref in enumerate(refs):
            assert by_id[f"r{i}"] == ref, f"request {i} diverged"

    def test_page_boundary_crossing(self, params):
        # prompt fills exactly one page; decode crosses into new pages
        prompt = list(range(1, 9))  # len 8 == page_size
        ref = greedy_reference(params, prompt, 10)
        eng = ServingEngine(params, CFG, max_seqs=1, max_seq_len=64,
                            page_size=8, use_pallas=False)
        eng.submit(Request("b", prompt, max_new_tokens=10))
        done = eng.run()
        assert done[0].output == ref

    def test_eos_stops_early(self, params):
        prompt = [1, 5, 9, 3, 7]
        ref = greedy_reference(params, prompt, 8)
        eos = ref[2]
        stop_at = ref.index(eos)  # eos may repeat earlier in a tiny model
        eng = ServingEngine(params, CFG, max_seqs=1, max_seq_len=64,
                            page_size=8, use_pallas=False)
        eng.submit(Request("c", prompt, max_new_tokens=8, eos_id=eos))
        done = eng.run()
        assert done[0].output == ref[:stop_at + 1]

    def test_pages_recycled_after_finish(self, params):
        eng = ServingEngine(params, CFG, max_seqs=1, max_seq_len=32,
                            page_size=8, use_pallas=False)
        free0 = len(eng._free)
        for i in range(3):
            eng.submit(Request(f"x{i}", [1, 2, 3, 4], max_new_tokens=4))
        eng.run()
        assert len(eng.finished) == 3
        assert len(eng._free) == free0

    def test_kernel_interpret_path_matches(self, params):
        # decode attention through the pallas kernel (interpret mode)
        prompt = [2, 4, 6]
        ref = greedy_reference(params, prompt, 4)
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, interpret=True)
        eng.submit(Request("k", prompt, max_new_tokens=4))
        done = eng.run()
        assert done[0].output == ref

    def test_ragged_batch_prefill_one_call(self, params):
        """All admitted prompts prefill in ONE varlen call (no per-sequence
        dense loop) and still match the dense reference."""
        from paddle_tpu.models import llama_serving as S
        prompts = [[1, 2, 3], [9, 8, 7, 6, 5, 4], [11, 12], [13] * 9]
        refs = [greedy_reference(params, p, 4) for p in prompts]
        calls = {"varlen": 0, "single": 0}
        orig_v, orig_s = S.prefill_varlen, S.prefill

        def spy_v(*a, **k):
            calls["varlen"] += 1
            return orig_v(*a, **k)

        def spy_s(*a, **k):
            calls["single"] += 1
            return orig_s(*a, **k)

        S.prefill_varlen, S.prefill = spy_v, spy_s
        try:
            # bucketed-machinery test: the varlen prefill entry point
            # only runs with the ragged step off
            eng = ServingEngine(params, CFG, max_seqs=4, max_seq_len=64,
                                page_size=8, use_pallas=False,
                                ragged=False)
            for i, p in enumerate(prompts):
                eng.submit(Request(f"r{i}", p, max_new_tokens=4))
            done = eng.run()
        finally:
            S.prefill_varlen, S.prefill = orig_v, orig_s
        assert calls["varlen"] == 1 and calls["single"] == 0
        by_id = {r.rid: r.output for r in done}
        for i, ref in enumerate(refs):
            assert by_id[f"r{i}"] == ref, f"request {i} diverged"

    def test_admission_respects_page_capacity(self, params):
        """Admission must not pop requests it cannot scatter: with pages
        for only some waiting requests, the rest stay queued and finish
        later (no dropped/lost requests)."""
        prompts = [[1, 2, 3, 4, 5, 6]] * 4   # 6+2 tokens fit 1 page (ps=8)
        eng = ServingEngine(params, CFG, max_seqs=4, max_seq_len=16,
                            page_size=8, use_pallas=False)
        # only 2 free pages: capacity admits 2 seqs; the other 2 must stay
        # queued (NOT be popped and lost) until pages free up
        eng._free = eng._free[:2]
        for i, p in enumerate(prompts):
            eng.submit(Request(f"r{i}", p, max_new_tokens=2))
        done = eng.run(max_steps=200)
        assert sorted(r.rid for r in done) == [f"r{i}" for i in range(4)]
        refs = [greedy_reference(params, p, 2) for p in prompts]
        by_id = {r.rid: r.output for r in done}
        for i, ref in enumerate(refs):
            assert by_id[f"r{i}"] == ref


class TestServingRobustness:
    """VERDICT r3 item 8: engine-level admission control, pool
    exhaustion, preemption under pressure, sampling determinism."""

    def test_submit_rejects_over_max_seq_len(self, params):
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=16,
                            page_size=8, use_pallas=False)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit(Request("r", list(range(1, 14)), max_new_tokens=8))
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(Request("r", [], max_new_tokens=4))
        # exactly at the limit is accepted
        eng.submit(Request("ok", list(range(1, 9)), max_new_tokens=8))
        assert len(eng._waiting) == 1

    def test_ctor_rejects_pool_below_one_sequence(self, params):
        with pytest.raises(ValueError, match="num_pages"):
            ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                          page_size=8, num_pages=4, use_pallas=False)

    def test_oversubscribed_pool_preempts_and_completes(self, params):
        """Pool holds ~1.5 sequences' worst case; two long generations
        must BOTH finish via preemption (default offload policy), with
        outputs identical to the fully-provisioned run (greedy
        determinism across eviction/resume)."""
        prompts = [[1, 5, 9, 3], [2, 6, 4, 8]]
        n_new = 24  # crosses several 8-token page boundaries
        refs = [greedy_reference(params, p, n_new) for p in prompts]
        # worst case per seq: 32 tokens -> 4 pages; pool = 6 + trash
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                            page_size=8, num_pages=7, use_pallas=False)
        for i, p in enumerate(prompts):
            eng.submit(Request(f"r{i}", p, max_new_tokens=n_new))
        done = eng.run(max_steps=500)
        assert sorted(r.rid for r in done) == ["r0", "r1"]
        assert eng.preemptions > 0, "test did not exercise preemption"
        by_id = {r.rid: r.output for r in done}
        for i, ref in enumerate(refs):
            assert by_id[f"r{i}"] == ref, \
                f"r{i} diverged after preemption (preempts=" \
                f"{eng.preemptions})"
        # pool fully recycled
        assert len(eng._free) == 6

    def test_single_sequence_pool_exhaustion_raises_clearly(self, params):
        """With one active sequence and nothing to preempt, exhaustion
        must surface as the engine-level error, not an allocator
        stack."""
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                            page_size=8, num_pages=5, use_pallas=False)
        eng.submit(Request("r", [1, 2, 3, 4, 5, 6], max_new_tokens=26))
        eng._free = eng._free[:1]  # artificially shrink below growth need
        with pytest.raises(RuntimeError, match="pool exhausted"):
            eng.run(max_steps=200)

    def test_preempted_sampled_request_keeps_its_tokens(self, params):
        """A temperature>0 request preempted mid-generation must resume
        WITHOUT re-sampling already-emitted tokens: same seed ==> same
        output as an unpressured engine."""
        prompt = [3, 7, 2, 9]
        n_new = 20
        outs = []
        for num_pages in (None, 7):  # roomy vs oversubscribed
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                                page_size=8, num_pages=num_pages,
                                use_pallas=False)
            eng.submit(Request("s", prompt, max_new_tokens=n_new,
                               temperature=0.8, top_k=8, seed=123))
            eng.submit(Request("g", [1, 4, 6, 2], max_new_tokens=n_new))
            done = eng.run(max_steps=500)
            outs.append({r.rid: r.output for r in done})
        # the greedy request is deterministic either way; the sampled
        # one must also match because resume never re-picks
        assert outs[0]["g"] == outs[1]["g"]
        assert outs[0]["s"] == outs[1]["s"]


class TestServingSampling:
    def test_temperature_zero_equals_greedy(self, params):
        prompt = [1, 5, 9, 3, 7]
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        eng.submit(Request("g", prompt, max_new_tokens=6, temperature=0.0))
        ref = greedy_reference(params, prompt, 6)
        assert eng.run()[0].output == ref

    def test_sampled_decode_seeded_and_valid(self, params):
        prompt = [2, 4, 6]
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        eng.submit(Request("s1", prompt, max_new_tokens=8, temperature=0.8,
                           top_k=8, top_p=0.9, seed=123))
        eng.submit(Request("s2", prompt, max_new_tokens=8, temperature=0.8,
                           top_k=8, top_p=0.9, seed=123))
        done = {r.rid: r for r in eng.run()}
        # same seed + same prompt → identical stochastic decode
        assert done["s1"].output == done["s2"].output
        assert all(0 <= t < CFG.vocab_size for t in done["s1"].output)

    def test_mixed_greedy_and_sampled_batch(self, params):
        prompt = [1, 2, 3]
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        eng.submit(Request("g", prompt, max_new_tokens=5, temperature=0.0))
        eng.submit(Request("s", prompt, max_new_tokens=5, temperature=1.0,
                           seed=7))
        done = {r.rid: r for r in eng.run()}
        assert done["g"].output == greedy_reference(params, prompt, 5)
        assert len(done["s"].output) == 5

    def test_huge_top_k_clamped(self, params):
        eng = ServingEngine(params, CFG, max_seqs=1, max_seq_len=64,
                            page_size=8, use_pallas=False)
        eng.submit(Request("k", [1, 2], max_new_tokens=4, temperature=0.9,
                           top_k=10 ** 6, seed=0))
        done = eng.run()
        assert len(done[0].output) == 4


class TestInt8CacheServing:
    """cache_dtype='int8' (VERDICT r4 item 4): quantized KV pool with
    per-token scales, dequant-in-kernel on read. Reference parity:
    cachekv-quant in phi/kernels/fusion/gpu/block_attn.h."""

    def test_int8_engine_matches_fp_engine_greedy(self, params):
        prompts = [[1, 5, 9, 3, 7], [9, 8, 7, 6, 5, 4]]
        outs = {}
        for tag, kw in (("fp", {}), ("int8", {"cache_dtype": "int8"})):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=8, use_pallas=False, **kw)
            for i, p in enumerate(prompts):
                eng.submit(Request(f"r{i}", p, max_new_tokens=8))
            done = eng.run()
            outs[tag] = {r.rid: r.output for r in done}
        # absmax-per-token int8 KV keeps greedy decode on-trajectory
        # at this scale — token-exact against the fp cache engine
        assert outs["int8"] == outs["fp"]

    def test_int8_pool_bytes_halved(self, params):
        fp = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                           page_size=8, dtype=jnp.bfloat16)
        q = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                          page_size=8, cache_dtype="int8")
        fp_bytes = fp.k_pool.nbytes + fp.v_pool.nbytes
        q_bytes = (q.k_pool.nbytes + q.v_pool.nbytes
                   + q.k_scale.nbytes + q.v_scale.nbytes)
        # head_dim 8 at this tiny config → scales cost 4/8 of the pool;
        # real head dims (64-128) approach 2x. Check the dtype plumbing
        # and that we beat bf16 even in the worst tiny case.
        assert q.k_pool.dtype == jnp.int8
        assert q_bytes < fp_bytes, (q_bytes, fp_bytes)

    def test_int8_with_interpret_kernel(self, params):
        """int8 decode through the pallas kernel (interpret) — the
        in-kernel dequant path an on-chip run would take."""
        prompt = [1, 5, 9, 3, 7]
        ref = greedy_reference(params, prompt, 6)
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=True, interpret=True,
                            cache_dtype="int8")
        eng.submit(Request("a", prompt, max_new_tokens=6))
        done = eng.run()
        assert done[0].output == ref

    def test_int8_survives_preemption(self, params):
        """Oversubscribed pool + int8 cache: eviction and re-prefill
        must re-quantize cleanly."""
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                            page_size=8, use_pallas=False,
                            num_pages=6, cache_dtype="int8",
                            preempt_policy="recompute")
        refs = {}
        for i, p in enumerate([[1, 2, 3], [7, 6, 5]]):
            refs[f"r{i}"] = greedy_reference(params, p, 10)
            eng.submit(Request(f"r{i}", p, max_new_tokens=10))
        done = eng.run()
        assert len(done) == 2
        for r in done:
            assert r.output == refs[r.rid]


class TestPreemptOffload:
    """preempt_policy="offload": evicted KV pages swap to host and back
    (reference BlockManager swap-out/swap-in) — zero recompute."""

    def test_bad_policy_rejected(self, params):
        with pytest.raises(ValueError, match="preempt_policy"):
            ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                          page_size=8, preempt_policy="swap")

    def test_offload_matches_and_skips_recompute(self, params):
        """Both policies produce greedy-identical outputs under pool
        pressure, but offload's prefill compute is exactly the original
        prompts — eviction costs no re-prefill."""
        prompts = [[1, 5, 9, 3], [2, 6, 4, 8]]
        n_new = 24
        refs = [greedy_reference(params, p, n_new) for p in prompts]
        outs, prefills = {}, {}
        for pol in ("offload", "recompute"):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                                page_size=8, num_pages=7, use_pallas=False,
                                preempt_policy=pol)
            for i, p in enumerate(prompts):
                eng.submit(Request(f"r{i}", p, max_new_tokens=n_new))
            done = eng.run(max_steps=500)
            assert eng.preemptions > 0, f"{pol}: no preemption exercised"
            assert len(eng._free) == 6, f"{pol}: pool not fully recycled"
            outs[pol] = {r.rid: r.output for r in done}
            prefills[pol] = eng.prefill_tokens
        for i, ref in enumerate(refs):
            assert outs["offload"][f"r{i}"] == ref
            assert outs["recompute"][f"r{i}"] == ref
        assert prefills["offload"] == sum(len(p) for p in prompts), \
            "offload resume must not re-prefill"
        assert prefills["recompute"] > prefills["offload"]

    def test_offload_int8_restores_scales(self, params):
        """Quantized pool offload must round-trip pages AND per-token
        scales; greedy outputs stay identical to the reference."""
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                            page_size=8, use_pallas=False, num_pages=7,
                            cache_dtype="int8", preempt_policy="offload")
        refs = {}
        for i, p in enumerate([[1, 2, 3, 4], [7, 6, 5, 2]]):
            refs[f"r{i}"] = greedy_reference(params, p, 24)
            eng.submit(Request(f"r{i}", p, max_new_tokens=24))
        done = eng.run(max_steps=500)
        assert eng.preemptions > 0
        assert len(done) == 2
        for r in done:
            assert r.output == refs[r.rid]

    def test_offload_sampled_request_keeps_tokens(self, params):
        """temperature>0 + offload: resume re-samples nothing; output
        matches the unpressured engine with the same seed."""
        prompt = [3, 7, 2, 9]
        outs = []
        for num_pages in (None, 7):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                                page_size=8, num_pages=num_pages,
                                use_pallas=False, preempt_policy="offload")
            eng.submit(Request("s", prompt, max_new_tokens=20,
                               temperature=0.8, top_k=8, seed=123))
            eng.submit(Request("g", [1, 4, 6, 2], max_new_tokens=20))
            done = eng.run(max_steps=500)
            outs.append({r.rid: r.output for r in done})
        assert outs[0]["g"] == outs[1]["g"]
        assert outs[0]["s"] == outs[1]["s"]


class TestSpeculativeDecoding:
    """Prompt-lookup speculative decoding (reference: PaddleNLP
    speculative / 'inference with reference'): one verify forward per
    chunk, exact greedy equivalence, fewer device steps on repetitive
    text."""

    def test_prompt_lookup_draft(self):
        from paddle_tpu.models.llama_serving import prompt_lookup_draft
        ctx = [1, 2, 3, 4, 1, 2]
        assert prompt_lookup_draft(ctx, 3, ngram=2) == [3, 4, 1]
        assert prompt_lookup_draft(ctx, 1, ngram=2) == [3]
        assert prompt_lookup_draft([1, 2, 3], 4, ngram=2) == []  # no match
        assert prompt_lookup_draft([5], 4, ngram=2) == []        # too short
        # most RECENT earlier occurrence wins
        ctx2 = [7, 8, 1, 7, 8, 2, 7, 8]
        assert prompt_lookup_draft(ctx2, 2, ngram=2) == [2, 7]

    def test_spec_greedy_exact_match_and_fewer_steps(self, params):
        # a highly repetitive prompt: prompt-lookup drafts well, so the
        # engine must finish in strictly fewer device steps while
        # emitting EXACTLY the plain-decode tokens
        prompt = [3, 9, 4, 3, 9, 4, 3, 9, 4, 3, 9]
        n_new = 16
        ref = greedy_reference(params, prompt, n_new)

        base = ServingEngine(params, CFG, max_seqs=2, max_seq_len=128,
                             page_size=8, use_pallas=False)
        base.submit(Request("p", prompt, max_new_tokens=n_new))
        base.run()
        assert base.finished[0].output == ref

        spec = ServingEngine(params, CFG, max_seqs=2, max_seq_len=128,
                             page_size=8, use_pallas=False, spec_decode=4)
        spec.submit(Request("s", prompt, max_new_tokens=n_new))
        spec.run()
        assert spec.finished[0].output == ref
        assert spec.device_steps < base.device_steps, (
            spec.device_steps, base.device_steps)
        assert spec.spec_accepted > 0

    def test_spec_matches_on_random_prompts(self, params):
        # non-repetitive prompts: drafts often rejected — output must
        # STILL match plain greedy exactly, batch of 3 with different
        # lengths
        rng = np.random.RandomState(7)
        prompts = [list(map(int, rng.randint(0, 64, n)))
                   for n in (5, 11, 8)]
        refs = [greedy_reference(params, p, 10) for p in prompts]
        eng = ServingEngine(params, CFG, max_seqs=3, max_seq_len=128,
                            page_size=8, use_pallas=False, spec_decode=3)
        for i, p in enumerate(prompts):
            eng.submit(Request(f"r{i}", p, max_new_tokens=10))
        eng.run()
        got = {r.rid: r.output for r in eng.finished}
        for i, ref in enumerate(refs):
            assert got[f"r{i}"] == ref, f"request r{i} diverged"

    def test_spec_mixed_with_sampling_and_eos(self, params):
        # sampling requests ride the verify step un-drafted and stay
        # seeded-deterministic; eos mid-chunk stops exactly like plain
        prompt = [2, 4, 2, 4, 2, 4, 2]
        ref = greedy_reference(params, prompt, 12)
        eos = ref[5]
        plain = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                              page_size=8, use_pallas=False)
        plain.submit(Request("g", prompt, max_new_tokens=12, eos_id=eos))
        plain.submit(Request("t", prompt, max_new_tokens=6,
                             temperature=0.8, top_k=8, seed=11))
        plain.run()
        spec = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                             page_size=8, use_pallas=False, spec_decode=4)
        spec.submit(Request("g", prompt, max_new_tokens=12, eos_id=eos))
        spec.submit(Request("t", prompt, max_new_tokens=6,
                            temperature=0.8, top_k=8, seed=11))
        spec.run()
        pg = {r.rid: r.output for r in plain.finished}
        sg = {r.rid: r.output for r in spec.finished}
        assert sg["g"] == pg["g"]          # eos honored mid-chunk
        assert sg["t"] == pg["t"]          # seeded sampling unchanged

    def test_spec_int8_cache(self, params):
        prompt = [3, 9, 4, 3, 9, 4, 3, 9, 4]
        fp = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                           page_size=8, use_pallas=False, spec_decode=4)
        fp.submit(Request("a", prompt, max_new_tokens=8))
        fp.run()
        q = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                          page_size=8, use_pallas=False, spec_decode=4,
                          cache_dtype="int8")
        q.submit(Request("a", prompt, max_new_tokens=8))
        q.run()
        # int8 quant noise may flip a token eventually; prefix must agree
        a, b = fp.finished[0].output, q.finished[0].output
        assert a[:4] == b[:4]

    def test_verify_step_equals_sequential_decode(self, params):
        """Device-level: one verify_step over a 3-token chunk produces
        the same logits trajectory and pool state as 3 decode_steps."""
        from paddle_tpu.models.llama_serving import (decode_step,
                                                     verify_step)
        # bucketed-machinery test: drives verify_step/decode_step
        # directly and needs _admit's seed-at-admission behavior
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, ragged=False)
        eng.submit(Request("a", [1, 5, 9, 3], max_new_tokens=8))
        eng._admit()
        chunk = [int(eng._slots[0].next_token), 7, 2]
        # pages for the chunk
        need = -(-(int(eng.lengths[0]) + 3) // eng.page_size)
        while len(eng._seq_pages[0]) < need:
            eng._alloc_pages(0, 1)
        n_tok = jnp.asarray([3, 0], jnp.int32)
        active = jnp.asarray([True, False])
        toks = jnp.asarray([[chunk[0], chunk[1], chunk[2]], [0, 0, 0]],
                           jnp.int64)
        k1, v1, _, _, logits_v = verify_step(
            eng.params, eng.k_pool, eng.v_pool, eng.page_table,
            eng.lengths, toks, n_tok, active, CFG, eng.page_size)

        ks, vs = eng.k_pool, eng.v_pool
        lens = np.array(eng.lengths)   # engine keeps host np state now
        seq_logits = []
        for g in range(3):
            lens[0] += 1
            # a snapshot: the dispatch may read a numpy operand in place
            # after it returns, and the next turn of this loop writes it
            ks, vs, _, _, lg = decode_step(
                eng.params, ks, vs, eng.page_table, lens.copy(),
                jnp.asarray([chunk[g], 0], jnp.int64), active, CFG,
                eng.page_size, use_pallas=False)
            seq_logits.append(lg[0])
        for g in range(3):
            np.testing.assert_allclose(np.asarray(logits_v[0, g]),
                                       np.asarray(seq_logits[g]),
                                       atol=2e-4)
        # trash page (last) holds masked junk by design — exclude it
        np.testing.assert_allclose(np.asarray(k1[:, :, :-1]),
                                   np.asarray(ks[:, :, :-1]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(v1[:, :, :-1]),
                                   np.asarray(vs[:, :, :-1]), atol=2e-5)

    def test_spec_oversubscribed_pool_no_page_leak(self, params):
        """Spec decode + preemption: pool accounting must balance after
        all requests finish (a stale-slot alloc would leak pages)."""
        eng = ServingEngine(params, CFG, max_seqs=3, max_seq_len=64,
                            page_size=8, use_pallas=False, spec_decode=4,
                            num_pages=12)   # < worst case 3*8+1
        prompt = [3, 9, 4, 3, 9, 4, 3, 9]
        for i in range(4):
            eng.submit(Request(f"o{i}", prompt, max_new_tokens=20))
        eng.run()
        assert len(eng.finished) == 4
        ref = greedy_reference(params, prompt, 20)
        for r in eng.finished:
            assert r.output == ref
        # every page back on the free list (trash page never joins)
        assert sorted(eng._free) == list(range(12 - 1))
        assert all(not p for p in eng._seq_pages.values())


class TestChunkedPrefill:
    """Chunked prefill over the verify chunk (reference parity:
    PaddleNLP/vLLM split-fuse): prompts feed G tokens per step so
    decoders never stall behind a long prompt; outputs stay exact."""

    def test_requires_spec(self, params):
        with pytest.raises(ValueError, match="spec_decode"):
            ServingEngine(params, CFG, chunked_prefill=True)

    def test_chunked_matches_dense(self, params):
        prompt = list(np.random.RandomState(3).randint(1, 64, 21))
        ref = greedy_reference(params, prompt, 8)
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, spec_decode=4,
                            chunked_prefill=True)
        eng.submit(Request("c", prompt, max_new_tokens=8))
        done = eng.run()
        assert done[0].output == ref
        # prompt fed in ceil(21/4)=6 chunks, all through verify steps
        assert eng.prefill_tokens == 21

    def test_decode_interleaves_with_long_prefill(self, params):
        """A decoding request must EMIT tokens during the very steps a
        long prompt is still chunk-feeding — not merely coexist."""
        short, long = [5, 3], list(np.random.RandomState(4).randint(1, 64, 40))
        ref_s = greedy_reference(params, short, 10)
        ref_l = greedy_reference(params, long, 6)
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, spec_decode=4,
                            chunked_prefill=True)
        eng.submit(Request("short", short, max_new_tokens=10))
        eng.step()   # admits short, feeds its first chunk
        eng.submit(Request("long", long, max_new_tokens=6))
        progressed_during_prefill = 0
        for _ in range(40):
            sreq = next((r for r in eng._slots
                         if r is not None and r.rid == "short"), None)
            lreq = next((r for r in eng._slots
                         if r is not None and r.rid == "long"), None)
            before = len(sreq.output) if sreq is not None else None
            mid_prefill = lreq is not None and eng._prefilling(lreq)
            if not eng.step():
                break
            if (before is not None and mid_prefill
                    and sreq.output and len(sreq.output) > before):
                progressed_during_prefill += 1
        got = {r.rid: r.output for r in eng.finished}
        assert got["short"] == ref_s and got["long"] == ref_l
        assert progressed_during_prefill > 0, (
            "short emitted nothing while the long prompt prefilled")

    def test_chunked_with_sampling_and_mixed_batch(self, params):
        prompt = list(np.random.RandomState(5).randint(1, 64, 17))
        plain = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                              page_size=8, use_pallas=False)
        plain.submit(Request("t", prompt, max_new_tokens=5,
                             temperature=0.7, top_k=8, seed=3))
        plain.run()
        chunked = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=8, use_pallas=False,
                                spec_decode=4, chunked_prefill=True)
        chunked.submit(Request("t", prompt, max_new_tokens=5,
                               temperature=0.7, top_k=8, seed=3))
        chunked.run()
        assert chunked.finished[0].output == plain.finished[0].output

    def test_two_long_prompts_small_pool_no_deadlock(self, params):
        """Admission must reserve a chunked prompt's REMAINING pages:
        with a pool that holds only one long prompt, the second queues
        instead of deadlocking mid-prefill (no evictable victim)."""
        long_a = list(np.random.RandomState(8).randint(1, 64, 40))
        long_b = list(np.random.RandomState(9).randint(1, 64, 40))
        refs = {"a": greedy_reference(params, long_a, 4),
                "b": greedy_reference(params, long_b, 4)}
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=48,
                            page_size=8, use_pallas=False, spec_decode=4,
                            chunked_prefill=True, num_pages=10)
        eng.submit(Request("a", long_a, max_new_tokens=4))
        eng.submit(Request("b", long_b, max_new_tokens=4))
        done = eng.run(max_steps=300)
        got = {r.rid: r.output for r in done}
        assert got == refs

    def test_mid_prefill_slot_is_evictable(self, params):
        """Decode growth under pool pressure may evict a mid-prefill
        neighbor; both requests still finish with exact outputs (the
        victim resumes its feed via offload, or re-feeds via
        recompute)."""
        for policy in ("offload", "recompute"):
            deco = list(np.random.RandomState(10).randint(1, 64, 6))
            long_p = list(np.random.RandomState(11).randint(1, 64, 32))
            ref_d = greedy_reference(params, deco, 26)
            ref_l = greedy_reference(params, long_p, 4)
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=40,
                                page_size=8, use_pallas=False,
                                spec_decode=4, chunked_prefill=True,
                                num_pages=7, preempt_policy=policy)
            eng.submit(Request("d", deco, max_new_tokens=26))
            for _ in range(3):
                eng.step()      # d decoding, holds pages
            eng.submit(Request("l", long_p, max_new_tokens=4))
            done = eng.run(max_steps=400)
            got = {r.rid: r.output for r in done}
            assert got["d"] == ref_d, policy
            assert got["l"] == ref_l, policy


class TestSpeculativeSampling:
    """spec_sample=True: drafts for sampled requests accepted by
    rejection sampling — marginally EXACT vs the request's filtered
    sampling distribution."""

    def test_marginal_distribution_exact(self):
        """Empirical check of the core guarantee: whatever the draft
        is, the emitted token at each position ~ p exactly."""
        from paddle_tpu.models.llama_serving import speculative_sample
        rng0 = np.random.RandomState(0)
        V = 6
        p0 = rng0.dirichlet(np.ones(V))
        p1 = rng0.dirichlet(np.ones(V))
        for draft in (int(np.argmax(p0)), int(np.argmin(p0))):
            counts0 = np.zeros(V)
            trials = 40000
            rng = np.random.RandomState(1)
            for _ in range(trials):
                toks, _ = speculative_sample([p0, p1], [draft], rng)
                counts0[toks[0]] += 1
            emp = counts0 / trials
            # first emitted token must follow p0 regardless of draft
            assert np.abs(emp - p0).max() < 0.015, (draft, emp, p0)

    def test_acceptance_advances_multiple_tokens(self):
        from paddle_tpu.models.llama_serving import speculative_sample
        # point-mass rows: drafts matching the mass are always accepted
        V = 4
        rows = [np.eye(V)[1], np.eye(V)[2], np.eye(V)[3]]
        toks, a = speculative_sample(rows, [1, 2], np.random.RandomState(0))
        assert toks == [1, 2, 3] and a == 2

    def test_engine_spec_sample_runs_and_counts(self, params, monkeypatch):
        """Force drafts every step (prompt-lookup hits depend on the
        sampled trajectory, so patch a constant proposal): the
        rejection-sampling path must run, keep the cache bookkeeping
        exact, and stay deterministic for a fixed seed."""
        from paddle_tpu.models import llama_serving as S
        monkeypatch.setattr(S, "prompt_lookup_draft",
                            lambda ctx, G, ngram=2: [7, 9, 11][:G])
        prompt = [2, 4, 2, 4, 2, 4, 2, 4]
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, spec_decode=4,
                            spec_sample=True)
        eng.submit(Request("t", prompt, max_new_tokens=12,
                           temperature=0.6, top_k=8, seed=5))
        done = eng.run()
        out = done[0].output
        assert len(out) == 12 and all(0 <= t < 64 for t in out)
        assert eng.spec_drafted > 0
        # determinism for a fixed seed and engine config
        eng2 = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                             page_size=8, use_pallas=False, spec_decode=4,
                             spec_sample=True)
        eng2.submit(Request("t", prompt, max_new_tokens=12,
                            temperature=0.6, top_k=8, seed=5))
        assert eng2.run()[0].output == out

    def test_flag_gating(self, params):
        with pytest.raises(ValueError, match="spec_decode"):
            ServingEngine(params, CFG, spec_sample=True)
        # without the flag, sampled requests stay trajectory-identical
        # to the plain engine (covered by test_spec_mixed_with_sampling)


class TestLogprobs:
    """Request(logprobs=True): per-emitted-token raw-model logprob
    (reference parity: predictor logprob outputs)."""

    def _manual(self, params, prompt, out):
        """log p(out[i] | prompt+out[:i]) from the dense reference."""
        lps = []
        ids = list(prompt)
        for tok in out:
            logits = np.asarray(M.forward(params, jnp.asarray([ids]), CFG,
                                          mesh=None, remat=False)[0, -1],
                                np.float64)
            x = logits - logits.max()
            lps.append(float(x[tok] - np.log(np.exp(x).sum())))
            ids.append(tok)
        return lps

    def test_greedy_logprobs_match_dense(self, params):
        prompt = [1, 5, 9, 3, 7]
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False)
        eng.submit(Request("a", prompt, max_new_tokens=6, logprobs=True))
        done = eng.run()
        out, lps = done[0].output, done[0].logprobs
        assert len(lps) == len(out) == 6
        np.testing.assert_allclose(lps, self._manual(params, prompt, out),
                                   atol=2e-4)

    def test_spec_logprobs_match_plain(self, params):
        prompt = [3, 9, 4, 3, 9, 4, 3, 9, 4, 3, 9]
        plain = ServingEngine(params, CFG, max_seqs=2, max_seq_len=128,
                              page_size=8, use_pallas=False)
        plain.submit(Request("p", prompt, max_new_tokens=10, logprobs=True))
        plain.run()
        spec = ServingEngine(params, CFG, max_seqs=2, max_seq_len=128,
                             page_size=8, use_pallas=False, spec_decode=4)
        spec.submit(Request("p", prompt, max_new_tokens=10, logprobs=True))
        spec.run()
        assert spec.finished[0].output == plain.finished[0].output
        assert spec.spec_accepted > 0   # the verify path actually ran
        np.testing.assert_allclose(spec.finished[0].logprobs,
                                   plain.finished[0].logprobs, atol=2e-4)

    def test_sampled_logprobs_are_raw_model(self, params):
        prompt = [2, 4, 6, 8]
        eng = ServingEngine(params, CFG, max_seqs=1, max_seq_len=64,
                            page_size=8, use_pallas=False)
        eng.submit(Request("t", prompt, max_new_tokens=5, temperature=0.9,
                           top_k=8, seed=3, logprobs=True))
        done = eng.run()
        out, lps = done[0].output, done[0].logprobs
        assert len(lps) == 5 and all(lp <= 0.0 for lp in lps)
        np.testing.assert_allclose(lps, self._manual(params, prompt, out),
                                   atol=2e-4)

    def test_disabled_by_default(self, params):
        eng = ServingEngine(params, CFG, max_seqs=1, max_seq_len=32,
                            page_size=8, use_pallas=False)
        eng.submit(Request("a", [1, 2], max_new_tokens=3))
        done = eng.run()
        assert done[0].logprobs is None


class TestTensorParallelServing:
    """TP-sharded engine (VERDICT r4 item 3): weights under megatron
    NamedShardings, KV pool sharded over KV heads, paged kernels under
    shard_map — outputs must match the single-device engine token for
    token (reference: fleet TP under the predictor, mp_layers.py +
    block_multi_head_attention_kernel.cu)."""

    PROMPTS = [[3, 7, 2, 9, 11], [5, 1, 4], [8, 8, 2, 6, 7, 1]]

    def _mesh(self, tp):
        from jax.sharding import Mesh
        return Mesh(np.asarray(jax.devices()[:tp]).reshape(tp), ("tp",))

    def _run(self, params, mesh, **kw):
        eng = ServingEngine(params, CFG, max_seqs=3, max_seq_len=64,
                            page_size=8, use_pallas=False, mesh=mesh, **kw)
        for i, p in enumerate(self.PROMPTS):
            eng.submit(Request(f"r{i}", p, max_new_tokens=10))
        eng.run()
        return {r.rid: r.output for r in eng.finished}

    def test_tp2_greedy_matches_single_device(self, params):
        assert self._run(params, self._mesh(2)) == self._run(params, None)

    def test_tp2_int8_cache_matches(self, params):
        assert self._run(params, self._mesh(2), cache_dtype="int8") == \
            self._run(params, None, cache_dtype="int8")

    def test_tp2_spec_decode_matches(self, params):
        mesh = self._mesh(2)
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=128,
                            page_size=8, use_pallas=False, mesh=mesh,
                            spec_decode=4)
        prompt = [3, 9, 4, 3, 9, 4, 3, 9, 4, 3, 9]
        eng.submit(Request("s", prompt, max_new_tokens=16))
        eng.run()
        assert eng.finished[0].output == greedy_reference(params, prompt, 16)
        assert eng.spec_accepted > 0

    def test_tp2_pallas_interpret_kernels(self, params):
        # the shard_map-wrapped pallas kernels (interpret mode off-TPU)
        # agree with the jnp path under the same tp mesh
        mesh = self._mesh(2)
        got = self._run(params, mesh)
        eng = ServingEngine(params, CFG, max_seqs=3, max_seq_len=64,
                            page_size=8, use_pallas=True, interpret=True,
                            mesh=mesh)
        for i, p in enumerate(self.PROMPTS):
            eng.submit(Request(f"r{i}", p, max_new_tokens=10))
        eng.run()
        assert {r.rid: r.output for r in eng.finished} == got

    def test_tp2_offload_preemption(self, params):
        # page pressure under tp: evict (host-gather sharded pages),
        # resume (scatter back) — identical outputs to the unsharded,
        # unpressured engine
        mesh = self._mesh(2)
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                            page_size=8, num_pages=5, use_pallas=False,
                            mesh=mesh, preempt_policy="offload")
        eng.submit(Request("a", [3, 7, 2, 9], max_new_tokens=20))
        eng.submit(Request("b", [1, 4, 6, 2], max_new_tokens=20))
        got = {r.rid: r.output for r in eng.run(max_steps=500)}
        assert eng.preemptions > 0
        ref = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                            page_size=8, use_pallas=False)
        ref.submit(Request("a", [3, 7, 2, 9], max_new_tokens=20))
        ref.submit(Request("b", [1, 4, 6, 2], max_new_tokens=20))
        assert got == {r.rid: r.output for r in ref.run(max_steps=500)}

    def test_degenerate_gqa_sharding_rejected(self, params):
        with pytest.raises(ValueError, match="num_key_value_heads"):
            ServingEngine(params, CFG, max_seqs=2, mesh=self._mesh(4))

    def test_dp_only_mesh_is_single_device(self, params):
        # a mesh without a tp axis leaves the engine unsharded
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("dp",))
        assert self._run(params, mesh) == self._run(params, None)

    def test_tp2_chunked_spec_int8_composition(self, params):
        # the deepest feature stack in one engine: chunked prefill
        # riding the spec verify chunk, int8 KV pool, all tp-sharded —
        # still token-exact vs the single-device engine
        prompt = list(np.random.RandomState(3).randint(1, 64, 21))
        outs = []
        for m in (None, self._mesh(2)):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=8, use_pallas=False, mesh=m,
                                spec_decode=4, chunked_prefill=True,
                                cache_dtype="int8")
            eng.submit(Request("c", prompt, max_new_tokens=10))
            eng.run()
            outs.append(eng.finished[0].output)
        assert outs[0] == outs[1], outs
