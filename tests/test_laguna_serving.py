"""Laguna through `ServingEngine`, at toy size on the CPU: prefill then
decode through both page pools against the plain reference's full forward
pass (logits compared, through the served tokens' log-probabilities and the
reference's first choice), under chunked prompts, preemption and slot
reuse; the two-pool page accounting; what refuses at construction."""
import numpy as np
import pytest

import jax

from paddle_tpu.models.laguna import LagunaConfig, init_params

from laguna_tiny import (against_reference, engine, requests, tiny_model)

# prompts shorter and longer than the 16-row buffer and the window of 8,
# outputs that run several windows deep; six requests over four slots, so
# two slots are used again after a release
MIX = [(5, 20), (23, 30), (11, 25), (30, 12), (7, 40), (9, 9)]


@pytest.fixture(scope="module")
def model():
    m = tiny_model()
    return m, init_params(LagunaConfig.from_dict(m), seed=1)


@pytest.fixture(scope="module")
def served(model):
    m, params = model
    eng = engine(m, params)
    reqs = requests(MIX)
    for r in reqs:
        eng.submit(r)
    eng.run_pipelined()
    return eng, reqs


def test_prefill_then_decode_agrees_with_the_reference(model, served):
    m, params = model
    eng, reqs = served
    for r, (n, k) in zip(reqs, MIX):
        assert len(r.output) == k
        first, lp = against_reference(m, params, r)
        assert first == 1.0 and lp < 1e-4, (r.rid, first, lp)
    assert eng.preemptions == 0


def test_the_sync_loop_serves_the_same_tokens(model, served):
    m, params = model
    eng = engine(m, params)
    reqs = requests(MIX)
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert [r.output for r in reqs] == [r.output for r in served[1]]


def test_both_pools_drain_and_the_window_gave_pages_back(served):
    eng, _ = served
    full, window = eng._caches
    assert (full.spec.name, window.spec.name) == ("full", "window")
    assert full.spec.window is None and window.spec.window == 8
    for gc in eng._caches:
        assert gc.pool.conserved(drained=True), gc.pool.counts()
        assert (gc.table == gc.num_pages - 1).all()
    assert full.released == 0 and window.released > 0
    # six requests of 14 to 53 tokens: a windowed slot never held more
    # than the window, one buffer of rows and the slack
    assert window.slot_cap == (8 + 16) // 4 + 1


@pytest.mark.parametrize("policy", ["recompute", "offload"])
def test_a_preempted_request_resumes_to_the_same_tokens(model, served, policy):
    """Pools too small for four long requests at once: the newest admission
    is evicted from BOTH pools and comes back, by either policy, to the
    tokens an engine with room serves."""
    m, params = model
    eng = engine(m, params, num_pages={"full": 26, "window": 40},
                 preempt_policy=policy)
    reqs = requests(MIX)
    for r in reqs:
        eng.submit(r)
    steps = 0
    while (eng._live or eng._waiting) and steps < 2000:
        eng.step()
        steps += 1
    assert eng.preemptions > 0
    assert [r.output for r in reqs] == [r.output for r in served[1]]
    for gc in eng._caches:
        assert gc.pool.conserved(drained=True), gc.pool.counts()


def test_the_window_pool_running_dry_preempts_too(model, served):
    m, params = model
    eng = engine(m, params, num_pages={"full": 65, "window": 9},
                 preempt_policy="recompute")
    reqs = requests(MIX)
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert eng.preemptions > 0
    assert [r.output for r in reqs] == [r.output for r in served[1]]


def _reads(eng, tables, tok_slot, tok_pos):
    """{group: [(slot, page)...]} the kernel fetches this step: for every
    run-less row, the pages from the first column it sees to its own."""
    out = {}
    for gc, table in zip(eng._caches, tables):
        w, ps = gc.spec.window, eng.page_size
        got = []
        for s, p in zip(np.asarray(tok_slot), np.asarray(tok_pos)):
            if p < 0:
                continue
            lo = 0 if w is None else max(0, p - (w - 1))
            got += [(int(s), int(table[s, o]))
                    for o in range(lo // ps, p // ps + 1)]
        out[gc.spec.name] = got
    return out


def test_a_released_page_is_never_read_and_nothing_leaks(model):
    """200 mixed requests through the pipelined loop, every step's page
    reads checked as it is dispatched: each page a row reads is one its own
    slot holds at that moment in that group (so never the trash page, never
    a page given back, never another slot's), and at the end both pools
    hold every page again."""
    m, params = model
    eng = engine(m, params)
    rng = np.random.default_rng(7)
    shapes = [(int(rng.integers(1, 30)), int(rng.integers(1, 30)))
              for _ in range(200)]
    reqs = requests(shapes, seed=3)
    real = eng.model.step
    checked = [0]

    def step(params, caches, tables, tokens, tok_slot, tok_pos, *a, **kw):
        for gc, (name, got) in zip(eng._caches, _reads(
                eng, [np.asarray(t) for t in tables], tok_slot,
                tok_pos).items()):
            for s, page in got:
                assert page != gc.num_pages - 1, (name, s)
                assert page in gc.seq_pages[s], (name, s, page)
                assert gc.pool.refcount[page] == 1
            checked[0] += len(got)
        return real(params, caches, tables, tokens, tok_slot, tok_pos, *a, **kw)

    object.__setattr__(eng.model, "step", step)
    for r in reqs:
        eng.submit(r)
    eng.run_pipelined(max_steps=20000)
    assert all(len(r.output) == k for r, (_, k) in zip(reqs, shapes))
    assert checked[0] > 5000
    for gc in eng._caches:
        assert gc.pool.conserved(drained=True), gc.pool.counts()
        held = [p for pages in gc.seq_pages.values() for p in pages]
        assert not held
    assert eng._caches[1].released > 200


def test_the_steps_record_counts_the_experts_rows(served):
    eng, _ = served
    sparse = 4
    assert eng.moe_assignments == 2 * sparse * eng.ragged_tokens
    assert 0 < eng.moe_experts_touched <= 8 * sparse * eng.device_steps
    assert eng.moe_rows_max_expert * 8 >= eng.moe_assignments // 8
    full, window = (eng.ragged_by_type[k] for k in ("full", "window"))
    assert full == [eng.ragged_kv_tokens, eng.ragged_attn_pairs]
    assert 0 < window[0] < full[0] and 0 < window[1] < full[1]


@pytest.mark.parametrize("kw, word", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefix_cache=True, host_tier_bytes=1 << 20), "prefix_cache"),
    (dict(spec_decode=4), "spec_decode"),
    (dict(ragged=False), "bucketed"),
    (dict(num_pages=64), "pages apart"),
    (dict(num_pages={"full": 65, "ring": 9}), "ring"),
])
def test_what_the_engine_cannot_do_for_it_refuses_at_construction(
        model, kw, word):
    m, params = model
    with pytest.raises(ValueError, match=word):
        engine(m, params, **{"num_pages": {"full": 65, "window": 40}, **kw})


def test_llama_answers_the_same_interface():
    """The engine asks every configuration the same question."""
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.serving.model_spec import ServingModel
    for c in (LlamaConfig.tiny(), LagunaConfig.from_dict(tiny_model())):
        sm = c.serving_model()
        assert isinstance(sm, ServingModel) and callable(sm.step)
        assert sum(g.layers for g in sm.groups) == c.num_hidden_layers
    assert LlamaConfig.tiny().serving_model().unsupported == {}


def test_every_wave_is_dispatched_with_what_both_page_tables_hold(
        model, served):
    """Each cache group's table goes to the device as a snapshot of the
    host's at dispatch: window pages given back this turn are already the
    trash page in it, pages taken this turn already there."""
    m, params = model
    eng = engine(m, params)
    real, seen = eng.model.step, [0]

    def step(params, caches, tables, *a, **kw):
        for gc, table in zip(eng._caches, tables):
            assert (np.asarray(table) == gc.table).all()
        seen[0] += 1
        return real(params, caches, tables, *a, **kw)
    object.__setattr__(eng.model, "step", step)
    reqs = requests(MIX)
    for r in reqs:
        eng.submit(r)
    eng.run_pipelined()
    assert seen[0] > 20
    assert [r.output for r in reqs] == [r.output for r in served[1]]


def _parents_scatter_kv(kp, vp, ksp, vsp, li, page_ids, off, kt, vt, quant,
                        flat=False):
    """`llama_serving._scatter_kv` as PR 30 left it (commit e1dc66b), the
    flat form `laguna_step` calls, line for line."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import quantize_kv
    assert flat
    kl = jax.lax.dynamic_index_in_dim(kp, li, 0, keepdims=False)
    vl = jax.lax.dynamic_index_in_dim(vp, li, 0, keepdims=False)
    kvh, n_pages, page = kl.shape[:3]
    rows = ((jnp.arange(kvh, dtype=jnp.int32) * n_pages)[:, None]
            + page_ids[None, :]) * page + off[None, :]

    def put(pool, new):
        return pool.reshape(-1, pool.shape[-1]).at[rows.reshape(-1)].set(
            new.reshape(-1, new.shape[-1])).reshape(pool.shape)
    ksl = vsl = None
    if quant:
        kt, kts = quantize_kv(kt)
        vt, vts = quantize_kv(vt)
        ksl = jax.lax.dynamic_index_in_dim(ksp, li, 0, keepdims=False)
        vsl = jax.lax.dynamic_index_in_dim(vsp, li, 0, keepdims=False)
        ksl = put(ksl, kts)
        vsl = put(vsl, vts)
        ksp = jax.lax.dynamic_update_index_in_dim(ksp, ksl, li, 0)
        vsp = jax.lax.dynamic_update_index_in_dim(vsp, vsl, li, 0)
    kl = put(kl, kt.astype(kl.dtype))
    vl = put(vl, vt.astype(vl.dtype))
    kp = jax.lax.dynamic_update_index_in_dim(kp, kl, li, 0)
    vp = jax.lax.dynamic_update_index_in_dim(vp, vl, li, 0)
    return kp, vp, ksp, vsp, kl, vl, ksl, vsl


@pytest.mark.parametrize("cache", [None, "int8"])
def test_laguna_step_traces_the_program_it_did_before_the_stacked_path(
        model, monkeypatch, cache):
    """`unified_step` now writes its carried stack flat and reads it
    through a layer index ([donate-pools]); `laguna_step` shares the
    scatter's helpers and the kernel and must not have moved. The engine's
    own first step, traced with the Pallas kernel in it: its jaxpr,
    kernel body included, is character for character what it is over
    PR 30's `_scatter_kv`, and what the kernel is handed in HBM is still a
    layer's pool (four dimensions; an int8 pool's scales three), never a
    stack."""
    import re
    import jax
    from paddle_tpu.models import laguna
    m, params = model
    eng = engine(m, params, cache_dtype=cache)
    real, seen = eng.model.step, []

    def step(*a, **kw):
        seen.append((a, kw))
        return real(*a, **kw)
    object.__setattr__(eng.model, "step", step)
    for r in requests([(9, 3)]):
        eng.submit(r)
    eng.step()
    # shapes in the arrays' place: the pools themselves are gone
    a, kw = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if hasattr(x, "shape") else x, seen[0])
    config, page_size = a[6:8]
    static = dict(kw, use_pallas=True, interpret=False)   # the kernel in
    static = {k: static[k] for k in ("block_q", "block_pages", "use_pallas",
                                     "interpret")}
    kw = {k: v for k, v in kw.items() if k not in static}
    step_fn = laguna.laguna_step.__wrapped__.__wrapped__

    def text():
        jaxpr = jax.make_jaxpr(
            lambda *arrays, **kws: step_fn(*arrays, config, page_size,
                                           **static, **kws))(*a[:6], **kw)
        return re.sub(r" at /\S+?:\d+", " at FILE", str(jaxpr))
    now = text()
    monkeypatch.setattr(laguna, "_scatter_kv", _parents_scatter_kv)
    assert now == text()
    in_hbm = re.findall(r"Ref<any>\{\w+\[([\d,]+)\]\}", now)
    assert "pallas_call" in now and in_hbm
    assert {len(dims.split(",")) for dims in in_hbm} <= {3, 4}


def _first_pool_after_a_step(eng, submit):
    """-> the array that held the first group's first K pool BEFORE one
    step: deleted by now iff the step donated it."""
    k0 = eng._caches[0].k[0]
    submit(eng)
    eng.step()
    return k0


def _llama_engine(**kw):
    import jax.numpy as jnp
    from paddle_tpu.models import llama_spmd
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.llama_serving import ServingEngine
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                           kv_heads=2)
    return ServingEngine(
        llama_spmd.init_params(cfg, seed=0, dtype=jnp.float32), cfg,
        max_seqs=2, max_seq_len=64, page_size=8, use_pallas=False, **kw)


@pytest.mark.parametrize("family", ["laguna", "llama"])
def test_the_step_donates_its_pools(model, family):
    """`ServingModel.step`'s contract: Laguna's step and Llama's
    `unified_step` donate their pools (the array that held one is gone
    after a step), so a second step in flight holds no further copy of
    them."""
    from paddle_tpu.models.llama_serving import Request
    if family == "laguna":
        eng = engine(*model)
        submit = lambda e: [e.submit(r)                      # noqa: E731
                            for r in requests([(5, 4)])]
    else:
        eng = _llama_engine()
        submit = lambda e: e.submit(                         # noqa: E731
            Request("a", [1, 2, 3], max_new_tokens=4))
    assert _first_pool_after_a_step(eng, submit).is_deleted()


@pytest.mark.parametrize("family, kw, deep", [
    ("llama", dict(), True),
    ("llama", dict(ragged=False), False),
    ("llama", dict(spec_decode=4), False),
    ("laguna", dict(), True),
], ids=["llama-ragged", "llama-bucketed", "llama-spec", "laguna"])
def test_the_pump_follows_what_the_engine_is(model, monkeypatch, family, kw,
                                             deep):
    """The one rule: one step deep for a ragged, non-speculative engine
    (a bucketed step returns new pools; drafting needs host-current
    context), and no variable of the environment has a say."""
    from paddle_tpu.serving import RequestScheduler
    make = _llama_engine if family == "llama" else \
        (lambda **k: engine(*model, **k))
    assert RequestScheduler(make(**kw), start=False)._pipeline is deep
    monkeypatch.setenv("PT_SERVE_PIPELINE", "0" if deep else "1")
    assert RequestScheduler(make(**kw), start=False)._pipeline is deep


@pytest.mark.parametrize("cache", [None, "int8"], ids=["bf16", "int8"])
def test_behind_the_scheduler_the_deep_pump_serves_the_sync_loops_tokens(
        model, cache):
    """The scheduler drives this engine one step deep (the rule above);
    `engine.run()` is the synchronous loop. Both serve the same tokens,
    over plain and over int8 pages."""
    from paddle_tpu.serving import RequestScheduler
    m, params = model
    eng, reqs = engine(m, params, cache_dtype=cache), requests(MIX)
    for r in reqs:
        eng.submit(r)
    eng.run()
    sched = RequestScheduler(engine(m, params, cache_dtype=cache))
    assert sched._pipeline is True
    try:
        handles = [sched.submit(r.prompt, max_new_tokens=r.max_new_tokens,
                                eos_id=None) for r in reqs]
        outs = [[t for chunk in h.stream() for t in chunk] for h in handles]
    finally:
        sched.shutdown(drain=False, timeout=60)
    assert outs == [r.output for r in reqs]
