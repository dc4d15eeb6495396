"""LongCat-Flash (`models/longcat_flash.py`) through `ServingEngine`, at toy
size on the CPU: prefill then decode through the two latent rows a layer
keeps a token against the plain reference's full forward pass (logits
compared, through the served tokens' log-probabilities and the reference's
first choice), across page edges; the dense latent kernel under
`interpret=True` against its `jax.numpy` path; the step's donation; the
identity experts' bookkeeping; and that the shares of a layer add up."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import ragged_latent as rl
from paddle_tpu.models import longcat_flash as lc
from paddle_tpu.parallel.moe import dropless_experts

import latent_buffers
from longcat_tiny import (against_reference, engine, init, program_config,
                          reference, requests, tiny_model)

# prompts shorter and longer than the 16-row buffer, outputs that cross
# several pages of 4; six requests over four slots, so two slots are used
# again after a release
MIX = [(5, 20), (23, 30), (11, 25), (30, 12), (7, 40), (9, 9)]


@pytest.fixture(scope="module")
def model():
    m = tiny_model()
    return m, init(m)


@pytest.fixture(scope="module")
def served(model):
    m, params = model
    eng = engine(m, params)
    reqs = requests(MIX)
    for r in reqs:
        eng.submit(r)
    eng.run_pipelined()
    return eng, reqs


@pytest.mark.parametrize("i", range(len(MIX)))
def test_prefill_then_decode_agrees_with_the_reference(model, served, i):
    """The float32 engine is the reference to rounding: every served token
    its first choice, log p to 1e-4."""
    m, params = model
    eng, reqs = served
    assert len(reqs[i].output) == MIX[i][1]
    first, lp = against_reference(m, params, reqs[i])
    assert first == 1.0 and lp < 1e-4, (reqs[i].rid, first, lp)
    assert eng.preemptions == 0


def test_a_bfloat16_engine_stays_within_its_tolerance(model, served):
    """Weights and cache in bfloat16 against the float32 reference on the
    same weights' rounded values: log p of the served tokens within 0.25
    (bfloat16 keeps 8 bits; the toy logits run to a few units), the mean
    far under it, up to the first token the two engines disagree on."""
    m, params = model
    half = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.ndim > 1 else x, params)
    eng = engine(m, half, dtype=jnp.bfloat16)
    assert eng._caches[0].pools[0][0].dtype == jnp.bfloat16
    reqs = requests(MIX[:3])
    for r in reqs:
        eng.submit(r)
    eng.run()
    gaps = []
    for r in reqs:
        with jax.default_matmul_precision("highest"):
            lg = reference.logits(half, jnp.asarray(r.prompt + r.output),
                                  m, q_block=1)
        at = np.asarray(jax.nn.log_softmax(lg[len(r.prompt) - 1:-1], -1))
        gaps.append(np.abs(at[np.arange(len(r.output)), r.output]
                           - np.asarray(r.logprobs)))
    gaps = np.concatenate(gaps)
    assert gaps.max() < 0.25 and 1e-6 < gaps.mean() < 0.05, (
        gaps.max(), gaps.mean())


def test_every_assignment_is_counted_once_and_identity_ones_apart(served):
    """2 layers x 3 assignments a row: to the 2 held experts, to real
    experts held elsewhere, or to identity experts, which are nobody's."""
    eng, reqs = served
    rows = sum(len(r.prompt) + len(r.output) - 1 for r in reqs)
    assert int(eng.moe_rows_by_expert.sum()) == eng.moe_assignments > 0
    assert eng.moe_assignments + eng.moe_rows_elsewhere \
        + eng.moe_assignments_zero == 2 * 3 * rows
    # 4 of the 12 experts are identity experts: about a third
    assert 0.2 < eng.moe_assignments_zero / (2 * 3 * rows) < 0.5
    assert eng.moe_rows_elsewhere > eng.moe_assignments


def test_the_metrics_export_the_identity_assignments(model):
    from paddle_tpu.serving import RequestScheduler
    m, params = model
    sched = RequestScheduler(engine(m, params), max_queue=8)
    try:
        h = sched.submit(list(range(1, 12)), max_new_tokens=6, eos_id=None)
        assert len(list(h.result())) == 6
        snap = sched.registry.snapshot()
    finally:
        sched.shutdown(drain=False, timeout=30)
    zero = snap["pt_moe_assignments_zero"]["value"]
    assert zero > 0
    assert zero + snap["pt_moe_assignments"]["value"] \
        + snap["pt_moe_rows_elsewhere"]["value"] == 2 * 3 * (11 + 5)


def test_the_plan_counts_the_latent_walk_by_kind(model):
    """`pt_latent_runs` / `pt_latent_trips` from the plan's descriptors, at
    the latent kernels' tile of 16 rows: a decode row, a chunk of 32 rows
    from buffer row 13 on (a piece of 3 rows 3 deep, a whole q block 19
    deep, a piece of 13 rows 32 deep), at a block of 8 tokens."""
    from paddle_tpu.models.llama_serving import _latent_walk
    slot, pos = latent_buffers.BUFFERS["chunk_inside"]()
    on = pos >= 0
    cont = (on[1:] & on[:-1] & (slot[1:] == slot[:-1])
            & (pos[1:] == pos[:-1] + 1))
    live = (pos + 1).astype(np.int64)
    #        whole, row, piece; a piece's rows each walk the context
    assert _latent_walk(on, cont, live, 8) == [
        1, 1, 2, 3, 2, 3 * 1 + 13 * 4]
    # and a served engine books them under its group, the metrics export them
    from paddle_tpu.serving import RequestScheduler
    m, params = model
    eng = engine(m, params)
    sched = RequestScheduler(eng, max_queue=8)
    try:
        h = sched.submit(list(range(1, 12)), max_new_tokens=6, eos_id=None)
        assert len(list(h.result())) == 6
        text = sched.registry.render_prometheus()
        walk = list(eng.latent_walk[lc.GROUP])
    finally:
        sched.shutdown(drain=False, timeout=30)
    # the prompt's 11 rows are one piece, each row a walk of its own; then
    # a decode row a step (the pump may have planned one step more)
    assert walk[0] == walk[3] == 0 and walk[2] == 1 and walk[1] in (5, 6)
    assert walk[4] >= 5 and walk[5] >= 11
    for kind, n in zip(("whole", "row", "piece"), walk[:3]):
        want = 'pt_latent_runs_total{kind="%s",layer_type="%s"} %d' % (
            kind, lc.GROUP, n)
        assert want in text or n == 0, text


def test_the_sync_loop_serves_the_same_tokens(model, served):
    m, params = model
    eng = engine(m, params)
    reqs = requests(MIX)
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert [r.output for r in reqs] == [r.output for r in served[1]]


def test_the_interpreted_kernel_serves_the_same_logits(model, served):
    m, params = model
    eng = engine(m, params, interpret=True)
    reqs = requests([(5, 6), (23, 4)])
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r, ref in zip(reqs, served[1]):
        n = len(r.output)
        assert r.output == ref.output[:n]
        assert np.abs(np.asarray(r.logprobs) - ref.logprobs[:n]).max() < 1e-4


def test_a_model_layer_keeps_two_cache_layers(model, served):
    """One group of 2 x num_layers layers, a pool each, one plane: the
    engine counts a token's bytes over cache layers, not model layers."""
    m, _ = model
    eng, _ = served
    gc, = eng._caches
    assert gc.pool.available() == gc.pool.num_pages
    assert gc.names == ["latent"]
    assert gc.spec.layers == 2 * m["num_layers"] == len(gc.pools[0])
    # a row all heads share lies in whole lane tiles
    assert gc.pools[0][0].shape == (1, 1, 70, 4, 128)
    assert gc.spec.bytes_per_token(4) == 2 * m["num_layers"] * (8 + 4) * 4
    published = lc.LongcatFlashConfig().serving_model().groups[0]
    assert published.layers == 56 and published.bytes_per_token(2) == 64512
    assert dataclasses.replace(
        lc.LongcatFlashConfig(), num_layers=4).serving_model().groups[
        0].bytes_per_token(2) == 9216


def test_preemption_offloads_every_sublayers_rows_and_resumes_exactly(
        model, served):
    m, params = model
    eng = engine(m, params, num_pages=31)
    reqs = requests(MIX)
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert eng.preemptions > 0
    assert [r.output for r in reqs] == [r.output for r in served[1]]


@pytest.mark.parametrize("kw, word", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(host_tier_bytes=1 << 20), "host_tier"),
    (dict(spec_decode=4), "spec_decode"),
    (dict(ragged=False), "bucketed"),
    (dict(cache_dtype="int8"), "int8_cache"),
    (dict(tp=True), "tensor_parallel"),
    (dict(handoff=True), "handoff")])
def test_what_the_engine_cannot_do_yet_refuses_with_its_reason(model, kw,
                                                               word):
    m, params = model
    kw = dict(kw)
    assert word in lc._NOT_YET
    reason = f"LongcatFlashConfig does not serve under {word}"
    if kw.pop("handoff", False):
        from paddle_tpu.models.llama_serving import Request
        req = Request("h", [1, 2, 3], max_new_tokens=2)
        req._handoff_export = True
        with pytest.raises(ValueError, match=reason):
            engine(m, params).submit(req)
        return
    if kw.pop("tp", False):
        from jax.sharding import Mesh
        if len(jax.devices()) < 2:
            pytest.skip("one device: no tp mesh to ask for")
        kw["mesh"] = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match=reason):
        engine(m, params, **kw)


def test_the_model_says_how_many_rows_a_step_holds(model):
    m, params = model
    assert engine(m, params, ragged_tokens=None).ragged_buf == lc.ROWS_A_STEP


def test_the_step_donates_its_pools_and_the_pump_runs_one_step_deep(model):
    from paddle_tpu.serving import RequestScheduler
    m, params = model
    eng = engine(m, params)
    pools = list(eng._caches[0].pools[0])
    assert len(pools) == 2 * m["num_layers"]
    for r in requests([(5, 4)]):
        eng.submit(r)
    eng.step()
    assert all(p.is_deleted() for p in pools)
    sched = RequestScheduler(engine(m, params), max_queue=8)
    try:
        assert sched._pipeline     # one step deep: a ragged engine
    finally:
        sched.shutdown(drain=False, timeout=30)


def test_the_step_aliases_its_pools_to_its_outputs(model):
    """The lowered program: every sublayer's pool is an input whose buffer
    an output takes (`tf.aliasing_output`)."""
    m, params = model
    eng = engine(m, params)
    B, T = eng.max_seqs, eng.ragged_buf
    sample = {"temp": jnp.zeros((B,), jnp.float32),
              "top_k": jnp.zeros((B,), jnp.int32),
              "top_p": jnp.ones((B,), jnp.float32),
              "key": jnp.zeros((B, 2), jnp.uint32),
              "eos": jnp.full((B,), -1, jnp.int32),
              "remaining": jnp.ones((B,), jnp.int32)}
    z = jnp.zeros((T,), jnp.int32)
    text = lc.longcat_step.__wrapped__.lower(
        params, tuple(gc.device() for gc in eng._caches),
        (jnp.asarray(eng._caches[0].table),), z, z, z - 1, eng.config, 4,
        sample=sample, need_rows=jnp.full((B,), -1, jnp.int32),
        tok_buf=eng.tok_buf, buf_write=jnp.zeros((B,), bool)).as_text()
    assert text.count("tf.aliasing_output") >= 2 * m["num_layers"]


def test_latent_rows_kept_in_float8_move_the_logits_and_nothing_else(
        model, served):
    """`latent_dtype="float8_e4m3fn"`: every sublayer's pool is float8,
    the kernel widens a block in fast memory (interpreted, it serves the
    `jax.numpy` path's tokens), and log p moves by hundredths."""
    m, params = model
    config = dataclasses.replace(program_config(m),
                                 latent_dtype="float8_e4m3fn")
    outs = []
    for kw, mix in ((dict(), MIX[:3]), (dict(use_pallas=True, interpret=True),
                                        [(5, 6), (23, 4)])):
        eng = engine(m, params, config=config, **kw)
        assert all(p.dtype == jnp.float8_e4m3fn
                   for p in eng._caches[0].pools[0])
        reqs = requests(mix)
        for r in reqs:
            eng.submit(r)
        eng.run()
        outs.append(reqs)
    for a, b in zip(*outs):
        n = len(b.output)
        assert a.output[:n] == b.output
        assert np.abs(np.asarray(a.logprobs)[:n] - b.logprobs).max() < 1e-4
    gap = np.concatenate([
        np.abs(np.asarray(r.logprobs) - ref.logprobs)[:1 + next(
            (i for i, (x, y) in enumerate(zip(r.output, ref.output))
             if x != y), len(r.output))]
        for r, ref in zip(outs[0], served[1])])
    assert 1e-4 < gap.mean() < 0.2 and gap.max() < 2.0


# -- the kernel ---------------------------------------------------------------
def _rows(seed=0, T=32, S=4, n_pages=8, page=4, P=40, dtype=jnp.float32):
    """A decode row 20 deep, one 3 deep, a prefill chunk of 13 rows from
    position 5 on, a slack row, and a chunk of 16 rows from position 7 on
    that fills the kernel's second q block (its one-product path); pages
    drawn apart."""
    rng = np.random.default_rng(seed)
    table = rng.permutation(P - 1)[:S * n_pages].reshape(S, n_pages)
    slot, pos = np.zeros(T, np.int32), np.full(T, -1, np.int32)
    slot[0], pos[0] = 0, 20
    slot[1], pos[1] = 2, 3
    slot[2:15], pos[2:15] = 1, 5 + np.arange(13)
    slot[16:32], pos[16:32] = 3, 7 + np.arange(16)
    draw = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return dict(table=jnp.asarray(table, jnp.int32), slot=jnp.asarray(slot),
                pos=jnp.asarray(pos), latent=draw(1, P, page, 24).astype(dtype),
                q=draw(T, 4, 24))


# (buffer, pages a trip, the latent rows' type): every kind of run at its
# edges (`latent_buffers.BUFFERS`), in one block and in four, in float8
DENSE_CASES = [
    ("mixed", 2, "float32"), ("mixed", 8, "float32"),
    ("mixed", 2, "float8_e4m3fn"), ("mixed", 8, "float8_e4m3fn"),
    ("decode_only", 2, "float32"), ("decode_only", 2, "float8_e4m3fn"),
    ("chunk_inside", 2, "float32"), ("chunk_inside", 2, "float8_e4m3fn"),
    ("one_page_tail", 2, "float32"), ("gaps", 2, "float32"),
    ("sixteen_slots", 2, "float32"), ("sixteen_slots", 8, "float32")]


@pytest.mark.parametrize("buffer,block_pages,stored", DENSE_CASES,
                         ids=["-".join(map(str, c)) for c in DENSE_CASES])
def test_dense_latent_attention_interpreted_against_jnp(buffer, block_pages,
                                                        stored):
    """Ragged rows of prompts and decodes in one call, against the
    `jax.numpy` path and against the definition: softmax over EVERY
    position up to the row's own; rows of no run come back zero."""
    d = latent_buffers.rows(buffer, dtype=stored)
    a = (d["q"], d["latent"], d["table"], d["slot"], d["pos"])
    ref = np.asarray(rl.ragged_latent_attention(
        *a, rank=16, sm_scale=0.2, use_pallas=False))
    got = np.asarray(rl.ragged_latent_attention(
        *a, rank=16, sm_scale=0.2, interpret=True, block_pages=block_pages))
    assert np.abs(ref - got).max() < 1e-5
    pos = np.asarray(d["pos"])
    assert not got[pos < 0].any()
    for t in np.nonzero(pos >= 0)[0]:
        want = latent_buffers.by_definition(d, t)
        assert np.abs(want - got[t]).max() < 1e-5, t


def _dots(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("dot_general")


def test_the_attention_kernels_hold_two_bodies_and_not_one_a_row():
    """A kernel's jaxpr holds the products of TWO bodies (a whole q block;
    one row, entered at the run's row): two each, four a kernel. One copy
    a row of the q block (34 and 35 products) cannot come back unseen."""
    d = latent_buffers.rows("mixed")
    desc = (d["table"], d["slot"], d["pos"])
    assert _dots(lambda q, lat: rl.ragged_latent_attention(
        q, lat, *desc, rank=16, sm_scale=0.2, interpret=True, block_pages=2),
        d["q"], d["latent"]) <= 4
    scores = jnp.zeros((4, 32, 8), jnp.float32)
    flat = jnp.full((32,), 32, jnp.int32)
    assert _dots(lambda q, lat: rl.ragged_sparse_latent_attention(
        q, lat, scores, flat, flat, *desc, rank=16, sm_scale=0.2,
        interpret=True), d["q"], d["latent"]) <= 4


def test_the_dense_kernel_is_the_sparse_one_with_everything_selected():
    """One `_attend` under both: a selection that keeps every position
    gives the dense kernel's rows."""
    d = _rows()
    desc = (d["table"], d["slot"], d["pos"])
    dense = rl.ragged_latent_attention(d["q"], d["latent"], *desc, rank=16,
                                       sm_scale=0.2, interpret=True,
                                       block_pages=2)
    scores = jnp.zeros((4, 32, 8), jnp.float32)
    every = jnp.full((32,), 32, jnp.int32)
    sparse = rl.ragged_sparse_latent_attention(
        d["q"], d["latent"], scores, jnp.full((32,), -2 ** 31, jnp.int32),
        every, *desc, rank=16, sm_scale=0.2, interpret=True)
    assert np.abs(np.asarray(dense) - np.asarray(sparse)).max() < 1e-6


# -- the router and the identity experts -------------------------------------
def test_the_router_scores_real_and_identity_experts_and_does_not_renormalise(
        model):
    m, params = model
    c = program_config(m)
    lp = params["layers"][0]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(9, 32)), jnp.float32)
    expert, weight = lc.route(x, lp["router"], lp["router_bias"], c,
                              jnp.arange(9) < 8)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.router_weights(lp, x, m))
    assert (np.asarray(expert[8]) == -1).all()
    for t in range(8):
        on = np.nonzero(want[t])[0]
        assert set(on) == set(np.asarray(expert[t])) and len(on) == 3
        assert np.allclose(want[t][np.asarray(expert[t])], weight[t],
                           rtol=1e-6)
    # 6 x the softmax scores of three of twelve experts: no row's sum to 6
    assert (np.asarray(weight[:8]).sum(-1) < 5.9).all()
    assert int(np.asarray(expert).max()) >= 8           # an identity pick


def test_a_row_of_identity_picks_costs_no_product_and_is_not_elsewhere(
        model, monkeypatch):
    """A correction bias that sends every row to the identity experts: no
    held expert gets a row, the grouped products run over no row, nothing
    is booked elsewhere, and the result is the weights' sum times the row."""
    m, params = model
    c = program_config(m)
    lp = dict(params["layers"][0])
    lp["router_bias"] = jnp.where(jnp.arange(12) >= 8, 10.0, 0.0)
    rng = np.random.default_rng(6)
    xf = jnp.asarray(rng.normal(size=(7, 32)), jnp.float32)
    row_on = jnp.arange(7) < 6
    out, got, elsewhere, zero = lc._moe(lp, xf, xf, c, row_on)
    assert int(got.sum()) == 0 and int(elsewhere) == 0
    assert int(zero) == 6 * 3
    _, weight = lc.route(xf, lp["router"], lp["router_bias"], c, row_on)
    want = np.asarray(weight.sum(-1, keepdims=True) * xf)
    assert np.abs(np.asarray(out) - want)[:6].max() < 1e-6
    # and the grouped products were given no row at all
    seen = []
    real = lc.dropless_experts

    def spy(x, expert, weight, *w, **kw):
        out = real(x, expert, weight, *w, **kw)
        seen.append(int(out[1].sum()))
        return out
    monkeypatch.setattr(lc, "dropless_experts", spy)
    lc._moe(lp, xf, xf, c, row_on)
    assert seen == [0]


# -- the shares add up ------------------------------------------------------------
def test_the_32_shares_of_a_layer_add_up_to_the_uncut_layer():
    """A layer whose 64 real experts lie on 32 chips, two each, all routing
    over the 64 and the 4 identity experts: the chips' expert parts, with
    the identity experts' part, both dense feed-forwards and both attention
    sublayers counted ONCE (what a row's home chip computes), are the uncut
    reference's whole layer."""
    whole = dict(tiny_model(layers=1, held=64, first=0), router_experts=64,
                 moe_topk=6)
    params = init(whole)
    lp = params["layers"][0]
    c = program_config(whole)
    S = 11
    rng = np.random.default_rng(3)
    h0 = jnp.asarray(rng.normal(size=(S, 32)), jnp.float32)
    table = reference.rope_table(whole, jnp.arange(S))
    eps = whole["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        want = reference._layer(lp, h0, table, whole, 1)
        # the home chip's part: attention, the dense feed-forwards, the
        # identity experts; then every chip's share of the real experts
        a0, a1 = lp["attn"]
        f0, f1 = lp["ffn"]
        h = h0 + reference.attention(a0, reference._rms(h0, a0["ln"], eps),
                                     table, whole, 1)
        x1 = reference._rms(h, f0["ln"], eps)
    row_on = jnp.ones((S,), bool)
    expert, weight = lc.route(x1, lp["router"], lp["router_bias"], c, row_on)
    zero = expert >= 64
    m = jnp.sum(jnp.where(zero, weight, 0.0), -1, keepdims=True) * x1
    got = []
    for first in range(0, 64, 2):
        part, rows = dropless_experts(
            x1, expert, weight, *(lp[k][first:first + 2]
                                  for k in ("w_gate", "w_up", "w_down")),
            first=first, num_experts=64)
        m, got = m + part, got + [np.asarray(rows)]
    with jax.default_matmul_precision("highest"):
        h = h + reference.dense_ffn(f0, x1)
        h = h + reference.attention(a1, reference._rms(h, a1["ln"], eps),
                                    table, whole, 1)
        h = h + reference.dense_ffn(f1, reference._rms(h, f1["ln"], eps)) + m
    assert np.abs(np.asarray(h - want)).max() < 1e-4
    # every assignment once: to a real expert on some chip, or to identity
    assert np.concatenate(got).sum() + int(zero.sum()) == S * 6
    assert int(zero.sum()) > 0
    # and one share through the program's own `_moe` is the reference's
    # layer for that share
    share = dict(whole, n_routed_experts=2, first_expert=4)
    lp4 = dict(lp, **{k: lp[k][4:6] for k in ("w_gate", "w_up", "w_down")})
    out, rows, elsewhere, n_zero = lc._moe(lp4, x1, x1, program_config(share),
                                           row_on)
    with jax.default_matmul_precision("highest"):
        want = reference.moe(lp4, x1, share)
    assert np.abs(np.asarray(out - want)).max() < 1e-5
    assert int(rows.sum()) + int(elsewhere) + int(n_zero) == S * 6
