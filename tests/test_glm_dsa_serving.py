"""GLM-5 (`models/glm_dsa.py`) through `ServingEngine`, at toy size on the
CPU: prefill then decode through both planes of the latent cache against
the plain reference's full forward pass (logits compared, through the
served tokens' log-probabilities and the reference's first choice), across
contexts longer than the toy `index_topk` and across page edges; the three
latent kernels under `interpret=True` against their `jax.numpy` paths; the
step's donation; and that the shares of a layer's experts add up."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import ragged_latent as rl

import latent_buffers
from glm_tiny import (against_reference, engine, init, program_config,
                      reference, requests, tiny_model)

# prompts shorter and longer than the 16-row buffer and the 8 positions the
# indexer keeps, outputs that cross several pages of 4; six requests over
# four slots, so two slots are used again after a release
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


def test_prefill_then_decode_agrees_with_the_reference(model, served):
    m, params = model
    eng, reqs = served
    for r, (n, k) in zip(reqs, MIX):
        assert len(r.output) == k
        first, lp = against_reference(m, params, r)
        assert first == 1.0 and lp < 1e-4, (r.rid, first, lp)
    assert eng.preemptions == 0


def test_the_selection_was_at_work_and_is_counted(served):
    """Most rows stood deeper than the 8 positions the indexer keeps; the
    engine books rows, columns scored and positions kept a layer."""
    eng, reqs = served
    rows, scored, kept, dense = eng.dsa_by_type["latent"]
    assert rows == sum(len(r.prompt) + len(r.output) - 1 for r in reqs)
    assert dense < rows / 4 and kept < scored / 2
    assert kept == sum(min(p + 1, 8) for r in reqs
                       for p in range(len(r.prompt) + len(r.output) - 1))
    # 2 sparse layers x 2 assignments a row, to the 2 held experts or not
    assert int(eng.moe_rows_by_expert.sum()) == eng.moe_assignments
    assert eng.moe_assignments + eng.moe_rows_elsewhere == 2 * 2 * rows
    assert eng.moe_rows_elsewhere > eng.moe_assignments > 0


def test_the_sync_loop_serves_the_same_tokens(model, served):
    m, params = model
    eng = engine(m, params)
    reqs = requests(MIX)
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert [r.output for r in reqs] == [r.output for r in served[1]]


def test_the_interpreted_kernels_serve_the_same_logits(model, served):
    """The engine with the three Pallas kernels interpreted: the tokens of
    the `jax.numpy` paths, log-probabilities to float32 rounding."""
    m, params = model
    eng = engine(m, params, interpret=True)
    reqs = requests(MIX[:2])
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r, ref in zip(reqs, served[1]):
        assert r.output == ref.output
        assert np.abs(np.asarray(r.logprobs) - ref.logprobs).max() < 1e-4


def test_the_pool_drains_and_pages_are_counted_by_plane(model, served):
    eng, _ = served
    gc, = eng._caches
    assert gc.pool.available() == gc.pool.num_pages
    assert gc.names == ["latent", "index_key"]
    latent, index = gc.pools[0][0], gc.pools[1][0]
    # a row all heads share lies in whole lane tiles
    assert latent.shape == index.shape == (1, 1, 70, 4, 128)
    # a token keeps 16 + 4 latent values and 8 of an index key, a layer
    assert gc.spec.bytes_per_token(4) == 3 * (20 + 8) * 4


def test_preemption_offloads_both_planes_and_resumes_exactly(model, served):
    """A pool too small for four long requests: the newest is preempted,
    its latent rows and index keys go to the host and come back."""
    m, params = model
    eng = engine(m, params, num_pages=31)
    reqs = requests(MIX)
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert eng.preemptions > 0
    assert [r.output for r in reqs] == [r.output for r in served[1]]


def test_what_the_engine_cannot_do_yet_refuses_at_construction(model):
    m, params = model
    for kw, word in [(dict(prefix_cache=True), "prefix_cache"),
                     (dict(spec_decode=4), "spec_decode"),
                     (dict(ragged=False), "bucketed"),
                     (dict(cache_dtype="int8"), "int8_cache")]:
        with pytest.raises(ValueError, match=word):
            engine(m, params, **kw)
    from paddle_tpu.models.llama_serving import Request
    eng = engine(m, params)
    req = Request("h", [1, 2, 3], max_new_tokens=2)
    req._handoff_export = True
    with pytest.raises(ValueError, match="handoff"):
        eng.submit(req)


def test_index_keys_kept_in_float8_move_the_selection_and_little_else(
        model, served):
    """`index_key_dtype="float8_e4m3fn"` (what the published code keeps
    them in): the plane's pool is float8 beside a latent pool in the
    cache's type, the kernel widens a block in fast memory (interpreted,
    it serves the `jax.numpy` path's tokens), and what a row selects
    changes at its threshold: log p moves by hundredths in the mean."""
    import dataclasses
    m, params = model
    config = dataclasses.replace(program_config(m),
                                 index_key_dtype="float8_e4m3fn")
    outs = []
    for kw in (dict(), dict(use_pallas=True, interpret=True)):
        eng = engine(m, params, config=config, **kw)
        gc, = eng._caches
        assert gc.pools[1][0].dtype == jnp.float8_e4m3fn
        assert gc.pools[0][0].dtype == jnp.float32
        reqs = requests(MIX)
        for r in reqs:
            eng.submit(r)
        eng.run()
        outs.append(reqs)
    for a, b in zip(*outs):
        assert a.output == b.output
        assert np.abs(np.asarray(a.logprobs) - b.logprobs).max() < 1e-4
    # up to the first token that differs the two engines saw one context
    gap = np.concatenate([
        np.abs(np.asarray(r.logprobs) - ref.logprobs)[:1 + next(
            (i for i, (x, y) in enumerate(zip(r.output, ref.output))
             if x != y), len(r.output))]
        for r, ref in zip(outs[0], served[1])])
    assert 1e-6 < gap.mean() < 0.1 and gap.max() < 2.0


def test_the_model_says_how_many_rows_a_step_holds(model):
    """`ServingModel.rows`: the flat buffer is the model's word where it
    gives one, and the engine's rule (a power of two over the slots) for
    the families that give none."""
    from paddle_tpu.models import glm_dsa
    from paddle_tpu.models.laguna import LagunaConfig
    m, params = model
    assert engine(m, params, ragged_tokens=None).ragged_buf \
        == glm_dsa.ROWS_A_STEP
    assert LagunaConfig().serving_model().rows is None


def test_the_step_donates_its_pools(model):
    m, params = model
    eng = engine(m, params)
    latent, index = eng._caches[0].pools[0][0], eng._caches[0].pools[1][0]
    for r in requests([(5, 4)]):
        eng.submit(r)
    eng.step()
    assert latent.is_deleted() and index.is_deleted()


def test_the_step_aliases_its_pools_to_its_outputs(model):
    """The lowered program: every pool of both planes is an input whose
    buffer an output takes (`tf.aliasing_output`), as Laguna's are."""
    from paddle_tpu.models import glm_dsa
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
    text = glm_dsa.glm_step.__wrapped__.lower(
        params, tuple(gc.device() for gc in eng._caches),
        (jnp.asarray(eng._caches[0].table),), z, z, z - 1, eng.config, 4,
        sample=sample, need_rows=jnp.full((B,), -1, jnp.int32),
        tok_buf=eng.tok_buf, buf_write=jnp.zeros((B,), bool)).as_text()
    pools = 2 * m["num_hidden_layers"]
    assert text.count("tf.aliasing_output") >= pools


# -- the kernels ---------------------------------------------------------------
def _rows(seed=0, T=32, S=4, n_pages=8, page=4, P=40):
    """A decode row 20 deep, one 3 deep, a prefill chunk of 13 rows from
    position 5 on, a slack row, and a chunk of 16 rows from position 7 on
    that fills the attention kernel's second q block (its one-product
    path); pages drawn apart."""
    rng = np.random.default_rng(seed)
    table = rng.permutation(P - 1)[:S * n_pages].reshape(S, n_pages)
    slot, pos = np.zeros(T, np.int32), np.full(T, -1, np.int32)
    slot[0], pos[0] = 0, 20
    slot[1], pos[1] = 2, 3
    slot[2:15], pos[2:15] = 1, 5 + np.arange(13)
    slot[16:32], pos[16:32] = 3, 7 + np.arange(16)
    draw = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return dict(table=jnp.asarray(table, jnp.int32), slot=jnp.asarray(slot),
                pos=jnp.asarray(pos), keys=draw(1, P, page, 16),
                latent=draw(1, P, page, 24), qi=draw(T, 4, 16), w=draw(T, 4),
                q=draw(T, 4, 24))


def _chosen(scores, thr, at, pos):
    pos = np.asarray(pos)
    seen = np.asarray(rl._selected(scores, thr, at))
    return seen & (np.arange(seen.shape[1])[None, :] <= pos[:, None])


@pytest.mark.parametrize("block_pages", [2, 8], ids=["4_blocks", "1_block"])
@pytest.mark.parametrize("keys", ["float32", "float8_e4m3fn"])
def test_index_scores_interpreted_against_jnp(block_pages, keys):
    d = _rows()
    stored = d["keys"].astype(keys)
    a = (d["qi"], d["w"], stored, d["table"], d["slot"], d["pos"])
    ref = np.asarray(rl.ragged_index_scores(
        *a, use_pallas=False, block_pages=block_pages))
    got = np.asarray(rl.ragged_index_scores(
        *a, interpret=True, block_pages=block_pages))
    live = np.isfinite(ref)
    # narrower keys are widened to the queries' type: the scores of the
    # keys' rounded values, exactly
    assert (ref == np.asarray(rl.ragged_index_scores(
        d["qi"], d["w"], stored.astype(jnp.float32), *a[3:],
        use_pallas=False, block_pages=block_pages))).all()
    assert (live == np.isfinite(got)).all()
    # a row's live columns are its context and nothing else
    flat = live.swapaxes(0, 1).reshape(live.shape[1], -1)
    assert (flat.sum(1) == np.asarray(d["pos"]) + 1).all()
    assert np.allclose(ref[live], got[live], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", [3, 6, 64])
@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_the_selection_is_the_stable_sorts_top_k(k, ties):
    """Both paths of `dsa_select` against a stable sort of each row: its k
    largest, ties to the lower position, all where it has no more than k.
    Rounded scores make ties by the dozen."""
    d = _rows()
    scores = rl.ragged_index_scores(d["qi"], d["w"], d["keys"], d["table"],
                                    d["slot"], d["pos"], use_pallas=False,
                                    block_pages=2)
    if ties:
        scores = jnp.round(scores) + 0.0
    flat = np.asarray(scores).swapaxes(0, 1).reshape(scores.shape[1], -1)
    pos = np.asarray(d["pos"])
    for kw in (dict(use_pallas=False), dict(interpret=True)):
        chosen = _chosen(scores, *rl.dsa_select(scores, d["pos"], k, **kw),
                         pos)
        for t in np.nonzero(pos >= 0)[0]:
            best = np.argsort(-flat[t], kind="stable")[:min(k, pos[t] + 1)]
            assert set(best) == set(np.nonzero(chosen[t])[0]), (kw, t)


def _as_blocks(d, sc):
    """Scores (T, 32) as `ragged_index_scores` leaves them, (blocks, T,
    block) at a block of 8, -inf past a row's own position."""
    pos = np.asarray(d["pos"])
    sc = np.where(np.arange(32)[None, :] <= pos[:, None], sc, -np.inf)
    return jnp.asarray(sc.reshape(len(pos), 4, 8).swapaxes(0, 1),
                       jnp.float32)


def _drawn_scores(d, seed=1):
    return _as_blocks(d, np.random.default_rng(seed).normal(
        size=(len(np.asarray(d["pos"])), 32)))


def _tied_at_the_edge(d):
    """Four positions above every other and four tied behind them, two on
    each side of the first block's edge (6, 7 | 8, 9): a selection of six
    takes the ties up to position 7, the block's last column, and leaves
    the next block's first."""
    sc = np.full((len(np.asarray(d["pos"])), 32), -1.0)
    sc[:, :4], sc[:, 6:10] = 5.0, 1.0
    return _as_blocks(d, sc)


# (buffer, k, how the scores are made)
SPARSE_CASES = [
    ("mixed", 6, "index"), ("mixed", 64, "index"),
    ("decode_only", 6, "drawn"), ("chunk_inside", 6, "drawn"),
    ("one_page_tail", 6, "drawn"), ("gaps", 6, "drawn"),
    ("sixteen_slots", 6, "drawn"), ("sixteen_slots", 64, "drawn"),
    ("mixed", 6, "tied"), ("decode_only", 6, "tied")]


@pytest.mark.parametrize("buffer,k,how", SPARSE_CASES,
                         ids=["-".join(map(str, c)) for c in SPARSE_CASES])
def test_sparse_latent_attention_interpreted_against_jnp(buffer, k, how):
    """Every kind of run at its edges (`latent_buffers.BUFFERS`) under a
    selection, against the `jax.numpy` path and against the definition:
    softmax over the chosen positions only; rows of no run zero."""
    if how == "index":
        d = _rows()
        scores = rl.ragged_index_scores(
            d["qi"], d["w"], d["keys"], d["table"], d["slot"], d["pos"],
            use_pallas=False, block_pages=2)
    else:
        d = latent_buffers.rows(buffer)
        scores = _tied_at_the_edge(d) if how == "tied" else _drawn_scores(d)
    thr, at = rl.dsa_select(scores, d["pos"], k, use_pallas=False)
    pos = np.asarray(d["pos"])
    if how == "tied":
        assert (np.asarray(at)[pos >= 9] == 7).all()
    a = (d["q"], d["latent"], scores, thr, at, d["table"], d["slot"],
         d["pos"])
    ref = np.asarray(rl.ragged_sparse_latent_attention(
        *a, rank=16, sm_scale=0.2, use_pallas=False))
    got = np.asarray(rl.ragged_sparse_latent_attention(
        *a, rank=16, sm_scale=0.2, interpret=True))
    assert np.abs(ref - got).max() < 1e-5
    assert not got[pos < 0].any()
    chosen = _chosen(scores, thr, at, d["pos"])
    for t in np.nonzero(pos >= 0)[0]:
        want = latent_buffers.by_definition(d, t, seen=chosen[t])
        assert np.abs(want - got[t]).max() < 1e-5, t


# -- the shares add up ------------------------------------------------------------
def test_the_shares_of_a_layers_experts_add_up(model):
    """Four chips hold two of the eight experts each and all route over the
    eight: their routed parts, and the shared expert counted once, are the
    uncut reference's layer."""
    from paddle_tpu.models import glm_dsa
    from paddle_tpu.parallel.moe import dropless_experts
    whole = tiny_model(held=8, first=0)
    params = init(whole)
    lp = params["layers"][1]
    c = program_config(whole)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(11, 32)), jnp.float32)
    row_on = jnp.arange(11) < 10                    # one slack row
    expert, weight = glm_dsa.route(x, lp["router"], lp["router_bias"], c,
                                   row_on)
    total, got = jnp.zeros_like(x), []
    for first in range(0, 8, 2):
        part, rows = dropless_experts(
            x, expert, weight, *(lp[k][first:first + 2]
                                 for k in ("w_gate", "w_up", "w_down")),
            first=first, num_experts=8)
        total, got = total + part, got + [np.asarray(rows)]
    total = total + glm_dsa._swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
    with jax.default_matmul_precision("highest"):
        want = reference.routed_experts(lp, x, whole) + reference._swiglu(
            x, lp["s_gate"], lp["s_up"], lp["s_down"])
    assert np.abs(np.asarray(total - want))[:10].max() < 1e-5
    assert np.concatenate(got).sum() == 10 * 2      # every assignment, once
    # and one share alone is the reference's layer for that share
    share = dict(whole, n_routed_experts=2, first_expert=4)
    lp4 = dict(lp, **{k: lp[k][4:6] for k in ("w_gate", "w_up", "w_down")})
    part, _ = dropless_experts(x, expert, weight, lp4["w_gate"], lp4["w_up"],
                               lp4["w_down"], first=4, num_experts=8)
    with jax.default_matmul_precision("highest"):
        want = reference.routed_experts(lp4, x, share)
    assert np.abs(np.asarray(part - want))[:10].max() < 1e-5


def test_the_whole_layer_case_traces_the_program_it_always_did():
    """`first=0` with every expert held is no argument at all: Laguna's call
    traces, equation for equation, what it traced before shares."""
    from paddle_tpu.parallel.moe import dropless_experts
    x = jnp.ones((6, 8))
    e = jnp.zeros((6, 2), jnp.int32)
    w = jnp.ones((6, 2))
    wg, wd = jnp.ones((4, 8, 5)), jnp.ones((4, 5, 8))
    text = lambda **kw: str(jax.make_jaxpr(                  # noqa: E731
        lambda x: dropless_experts(x, e, w, wg, wg, wd, **kw))(x))
    assert text() == text(first=0, num_experts=4) == text(num_experts=None)
    assert text(first=1, num_experts=8) != text()
