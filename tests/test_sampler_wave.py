"""The sampler does what a wave's rows ask for (ISSUE 40): the whole-
vocabulary sort runs only in a wave that holds a sampled row with a
top_k / top_p that cuts, the categorical draw only in a wave that holds
a sampled row, both under a `lax.cond` on the wave's own traced
parameters, and a row's token is what it was: the reference here is an
independent NumPy filter (sort, cumulative mass, crossing token kept,
ties at the threshold kept) drawn from with the row's (seed, position)
key."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import llama_serving as ls
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models.llama_serving import Request, ServingEngine
from paddle_tpu.serving.scheduler import RequestScheduler

SHAPES = [(8, 1000), (32, 32768)]
SAMPLED = (0.8, 50, 0.9)


def _wave(name, n):
    """(temp, top_k, top_p) of a wave's n rows."""
    t, k, p = {"greedy": (0.0, 0, 1.0), "sampled_cut": SAMPLED,
               "sampled_no_cut": (0.8, 0, 1.0), "one_sampled": (0.0, 0, 1.0),
               "top_k_alone": (1.1, 40, 1.0),
               "top_p_alone": (1.1, 0, 0.95)}[name]
    temp, top_k, top_p = (np.full((n,), t, np.float32),
                          np.full((n,), k, np.int32),
                          np.full((n,), p, np.float32))
    if name == "one_sampled":
        temp[3], top_k[3], top_p[3] = SAMPLED
    return temp, top_k, top_p


WAVES = ["greedy", "sampled_cut", "sampled_no_cut", "one_sampled",
         "top_k_alone", "top_p_alone"]


def _np_filter(lg, temp, top_k, top_p, unsure=False):
    """Row by row in NumPy, the masses in float64: temperature, the k
    largest, then the smallest head of them whose mass reaches top_p of
    theirs (the crossing token stays), as a threshold on the value (ties
    stay). A greedy row asks for no filter: its token is its argmax.
    `unsure=True` -> also the tokens whose mass before them lies within
    1e-6 of the crossing, where float32 sums may fall either way."""
    n, v = lg.shape
    out, edge = lg.copy(), np.zeros(lg.shape, bool)
    for i in np.flatnonzero(temp > 0):
        lt = lg[i] / np.float32(max(temp[i], 1e-6))
        k = min(int(top_k[i]), v) if top_k[i] > 0 else v
        sv = np.sort(lt)[::-1]
        e = np.exp(sv.astype(np.float64) - sv[0])
        probs = e / e.sum()
        before = np.cumsum(probs) - probs
        head = np.arange(v) < k
        limit = float(top_p[i]) * probs[:k].sum()
        thresh = sv[max(int((head & (before <= limit)).sum()), 1) - 1]
        out[i] = np.where(lt < thresh, np.float32(-1e30), lt)
        edge[i] = np.isin(lt, sv[head & (np.abs(before - limit) <= 1e-6)])
    return (out, edge) if unsure else out


def _np_draw(lg, temp, top_k, top_p, key, fold):
    """-> (token, the raw model's logprob at it): the argmax of a greedy
    row, a categorical draw under the row's own fold of its key from
    the NumPy filter's logits for a sampled one."""
    flt = _np_filter(lg, temp, top_k, top_p)
    tok = np.argmax(lg, axis=-1).astype(np.int32)
    for i in np.flatnonzero(temp > 0):
        k = jax.random.fold_in(jnp.asarray(key[i]), int(fold[i]))
        tok[i] = int(jax.random.categorical(k, jnp.asarray(flt[i])))
    lp = np.asarray(jax.nn.log_softmax(jnp.asarray(lg), axis=-1))
    return tok, lp[np.arange(len(tok)), tok]


def _rows(n, v):
    rng = np.random.default_rng(n * v)
    lg = (rng.standard_normal((n, v)) * 3).astype(np.float32)
    key = rng.integers(0, 2 ** 32, size=(n, 2), dtype=np.uint32)
    fold = rng.integers(1, 4000, size=(n,)).astype(np.int32)
    return lg, key, fold


_JITS = {}


def _jit(fn):
    """One jitted callable a function: the wave's parameters are traced,
    so every wave of a shape runs ONE program (`_no_retrace`)."""
    return _JITS.setdefault(fn, jax.jit(fn))


def _no_retrace(fn, shapes=1):
    assert _jit(fn)._cache_size() <= shapes * len(SHAPES)


@pytest.mark.parametrize("wave", WAVES)
@pytest.mark.parametrize("n, v", SHAPES)
def test_filter_draw_matches_the_numpy_filter(n, v, wave):
    lg, key, fold = _rows(n, v)
    temp, top_k, top_p = _wave(wave, n)
    tok, lp = _jit(ls._filter_draw)(lg, temp, top_k, top_p, key, fold)
    want_tok, want_lp = _np_draw(lg, temp, top_k, top_p, key, fold)
    np.testing.assert_array_equal(np.asarray(tok), want_tok)
    np.testing.assert_array_equal(np.asarray(lp), want_lp)
    # a sampled row draws the same token whatever its neighbours are:
    # by itself, a wave of one, what it draws in the wave
    for i in np.flatnonzero(temp > 0)[:2]:
        alone, _ = ls._filter_draw(*(a[i:i + 1] for a in (
            lg, temp, top_k, top_p, key, fold)))
        assert int(alone[0]) == int(tok[i])


@pytest.mark.parametrize("wave", WAVES)
@pytest.mark.parametrize("n, v", SHAPES)
def test_sample_flat_matches_the_numpy_filter(n, v, wave):
    """The ragged step's rows: a slot's parameters gathered through
    `tok_slot`, the fold `tok_pos + 1`, `done` from the slot's budget
    and eos."""
    lg, key, fold = _rows(n, v)
    temp, top_k, top_p = _wave(wave, n)
    slot = np.random.default_rng(7).permutation(n).astype(np.int32)
    greedy = np.argmax(lg, axis=-1)
    sample = {"temp": temp, "top_k": top_k, "top_p": top_p, "key": key,
              "eos": np.where(np.arange(n) % 4 == 0,
                              greedy[np.argsort(slot)], -1).astype(np.int32),
              "remaining": np.where(np.arange(n) % 5 == 1, 1, 9)
              .astype(np.int32)}
    row_on = np.arange(n) != n - 1
    tok, done, lp = _jit(ls._sample_flat)(lg, slot, fold - 1, row_on, sample)
    want_tok, want_lp = _np_draw(lg, temp[slot], top_k[slot], top_p[slot],
                                 key[slot], fold)
    np.testing.assert_array_equal(np.asarray(tok), want_tok)
    np.testing.assert_array_equal(np.asarray(lp), want_lp)
    want_done = row_on & ((sample["remaining"][slot] <= 1) |
                          ((sample["eos"][slot] >= 0) &
                           (want_tok == sample["eos"][slot])))
    np.testing.assert_array_equal(np.asarray(done), want_done)


@pytest.mark.parametrize("wave", WAVES)
@pytest.mark.parametrize("n, v", SHAPES)
def test_sample_grid_matches_the_numpy_filter(n, v, wave):
    """The verify grid: (B, G) rows, a slot's parameters for each of its
    G positions, the fold `lengths + g + 1`."""
    G = 2
    lg, key, _ = _rows(n, v)
    B = n // G
    temp, top_k, top_p = _wave(wave, B)
    lengths = np.arange(B, dtype=np.int32) * 11 + 5
    sample = {"temp": temp, "top_k": top_k, "top_p": top_p, "key": key[:B]}
    tok, lp = _jit(ls._sample_grid)(lg.reshape(B, G, v), lengths, sample)
    pos = (lengths[:, None] + np.arange(G)[None, :] + 1).reshape(-1)

    def rep(a):
        return np.repeat(a, G, axis=0)
    want_tok, want_lp = _np_draw(lg, rep(temp), rep(top_k), rep(top_p),
                                 rep(key[:B]), pos)
    np.testing.assert_array_equal(np.asarray(tok).reshape(-1), want_tok)
    np.testing.assert_array_equal(np.asarray(lp).reshape(-1), want_lp)


DROPPED = np.float32(-1e30)


def _softmax(flt):
    return np.asarray(jax.nn.softmax(jnp.asarray(flt), axis=-1))


@pytest.mark.parametrize("wave", WAVES)
@pytest.mark.parametrize("n, v", SHAPES)
def test_cand_probs_match_the_numpy_filter(n, v, wave):
    """A draft's probability under the filtered distribution, and the
    filtered logits the step's draw then shares: the draw from them is
    the draw `_sample_flat` makes by itself."""
    lg, key, fold = _rows(n, v)
    temp, top_k, top_p = _wave(wave, n)
    slot = np.arange(n, dtype=np.int32)[::-1].copy()
    sample = {"temp": temp, "top_k": top_k, "top_p": top_p, "key": key}
    cand = np.argsort(lg, axis=-1)[:, -3].astype(np.int32)   # third largest
    cand[0] = np.argmin(lg[0])                  # a token every cut drops
    got, lt = _jit(ls._cand_probs)(lg, slot, sample, cand)
    flt, edge = _np_filter(lg, temp[slot], top_k[slot], top_p[slot], True)
    np.testing.assert_array_equal(np.where(edge, flt, np.asarray(lt)), flt)
    np.testing.assert_allclose(np.asarray(got),
                               _softmax(flt)[np.arange(n), cand],
                               rtol=1e-4, atol=1e-9)
    assert got[0] == 0.0 or not (temp[slot[0]] > 0 and
                                 (top_k[slot[0]] > 0 or top_p[slot[0]] < 1))
    row_on = np.ones((n,), bool)
    shared = _jit(ls._sample_flat)(lg, slot, fold - 1, row_on, sample, lt)
    alone = _jit(ls._sample_flat)(lg, slot, fold - 1, row_on, sample)
    for a, b in zip(shared, alone):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("wave", WAVES)
@pytest.mark.parametrize("n, v", SHAPES)
def test_spec_dist_rows_match_the_numpy_filter(n, v, wave):
    lg, _, _ = _rows(n, v)
    temp, top_k, top_p = _wave(wave, n)
    got = np.asarray(ls._spec_dist_rows(lg, temp, top_k, top_p))
    flt, edge = _np_filter(lg, temp, top_k, top_p, True)
    # the same tokens dropped, and the kept ones' probabilities
    np.testing.assert_array_equal((got == 0.0) & ~edge,
                                  (flt == DROPPED) & ~edge)
    np.testing.assert_allclose(np.where(edge, 0.0, got),
                               np.where(edge, 0.0, _softmax(flt)),
                               rtol=1e-4, atol=1e-9)
    np.testing.assert_allclose(got.sum(-1), 1.0, rtol=1e-5)


def test_every_wave_of_a_shape_ran_one_program():
    """The waves above differ in VALUES of traced arrays alone: a
    request that samples never retraces a step."""
    if not _JITS:
        pytest.skip("runs after the waves, in one process with them")
    _no_retrace(ls._filter_draw)
    # a stopping engine's pytree; a speculative one's, with and without `lt`
    _no_retrace(ls._sample_flat, shapes=3)
    _no_retrace(ls._sample_grid)
    _no_retrace(ls._cand_probs)


def _conds_and_outside(jaxpr, inside=False, found=None):
    """(`cond`s, primitives outside every cond) of a jaxpr, nested
    ones included."""
    found = found if found is not None else {"cond": 0, "outside": set()}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        found["cond"] += name == "cond"
        if not inside:
            found["outside"].add(name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _conds_and_outside(sub, inside or name == "cond", found)
    return found["cond"], found["outside"]


def test_filter_draw_traces_two_conds_and_sorts_under_them():
    """The skip is in the program: `_filter_draw` holds exactly two
    `cond`s (the draw, and the filter's cut inside it), and neither a
    sort nor a random bit is generated outside them."""
    lg, key, fold = _rows(8, 1000)
    temp, top_k, top_p = _wave("one_sampled", 8)
    jaxpr = jax.make_jaxpr(ls._filter_draw)(lg, temp, top_k, top_p, key,
                                            fold)
    conds, outside = _conds_and_outside(jaxpr.jaxpr)
    assert conds == 2
    assert not outside & {"sort", "cumsum", "random_bits", "threefry2x32"}
    assert {"argmax", "cond"} <= outside
    # the filter by itself: one cond, the sort under it
    conds, outside = _conds_and_outside(jax.make_jaxpr(ls._filtered_logits)(
        lg, temp, top_k, top_p).jaxpr)
    assert conds == 1 and "sort" not in outside


# -- the engine: parity among neighbours, and the counters ------------------
CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


def _sampled():
    return ("s", [2, 4, 6], dict(max_new_tokens=6, temperature=0.8,
                                 top_k=50, top_p=0.9, seed=7))


def _greedy(n, max_new):
    return [(f"g{i}", [1 + i, 5, 9, 3], dict(max_new_tokens=max_new))
            for i in range(n)]


def _serve(params, pump, requests):
    """-> ({rid: tokens}, the engine, the scheduler's metrics) once
    `requests` (rid, prompt, parameters) have run through a ragged
    engine: `pump` "sync" is `engine.run()`, "deep" the scheduler's
    pump, one step deep."""
    eng = ServingEngine(params, CFG, max_seqs=4, max_seq_len=64,
                        page_size=8, use_pallas=False, ragged=True)
    if pump == "sync":
        for rid, prompt, kw in requests:
            eng.submit(Request(rid, prompt, **kw))
        done = eng.run()
        return {r.rid: list(r.output) for r in done}, eng, None
    sched = RequestScheduler(eng, max_queue=16)
    try:
        handles = [sched.submit(prompt, rid=rid, **kw)
                   for rid, prompt, kw in requests]
        out = {h.rid: list(h.result(timeout=120)) for h in handles}
        snap = sched.metrics_snapshot()
    finally:
        sched.shutdown(drain=False, timeout=60)
    return out, eng, snap


@pytest.mark.parametrize("pump", ["sync", "deep"])
def test_a_sampled_request_among_greedy_ones_emits_what_it_emits_alone(
        params, pump):
    """... and the counters rise only in the steps it was live: its six
    tokens are one seeded by the host and five drawn on the device, so
    five steps draw and cut, whatever ran beside them."""
    greedy = _greedy(3, 12)
    alone, eng, _ = _serve(params, pump, [_sampled()])
    assert eng.sampler_draw_steps == eng.sampler_filter_steps == 5
    among, eng, snap = _serve(params, pump, greedy[:2] + [_sampled()]
                              + greedy[2:])
    assert among["s"] == alone["s"] and len(among["s"]) == 6
    assert eng.sampler_draw_steps == eng.sampler_filter_steps == 5
    assert eng.device_steps > 5
    if snap is not None:
        assert snap["pt_sampler_filter_steps"]["value"] == 5
        assert snap["pt_sampler_draw_steps"]["value"] == 5
        assert snap["pt_serving_device_steps"]["value"] > 5


@pytest.mark.parametrize("pump", ["sync", "deep"])
def test_the_counters_tell_a_cut_from_a_draw_and_read_0_when_all_greedy(
        params, pump):
    greedy = _greedy(3, 8)
    _, eng, snap = _serve(params, pump, greedy)
    assert eng.device_steps > 0
    assert eng.sampler_draw_steps == eng.sampler_filter_steps == 0
    if snap is not None:
        assert snap["pt_sampler_filter_steps"]["value"] == 0
        assert snap["pt_sampler_draw_steps"]["value"] == 0
    no_cut = ("n", [2, 4, 6], dict(max_new_tokens=4, temperature=1.1,
                                   seed=3))
    _, eng, _ = _serve(params, pump, greedy + [no_cut])
    assert (eng.sampler_draw_steps, eng.sampler_filter_steps) == (3, 0)


@pytest.mark.parametrize("mode", ["bucketed", "spec"])
def test_the_bucketed_engines_count_their_waves_too(params, mode):
    kw = {"bucketed": {}, "spec": {"spec_decode": 4}}[mode]
    eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                        page_size=8, use_pallas=False, ragged=False, **kw)
    eng.submit(Request("g", [1, 5, 9, 3], max_new_tokens=8))
    eng.run()
    rid, prompt, kw = _sampled()
    assert eng.device_steps > 0
    assert eng.sampler_draw_steps == eng.sampler_filter_steps == 0
    eng.submit(Request("g2", [1, 5, 9, 3], max_new_tokens=8))
    eng.submit(Request(rid, prompt, **kw))
    eng.run()
    assert 0 < eng.sampler_draw_steps == eng.sampler_filter_steps \
        <= eng.device_steps
