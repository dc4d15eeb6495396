"""Nemotron-H (`models/nemotron_h.py`) through `ServingEngine`, at toy size
on the CPU: prefill then decode through the one attention layer's pages
and the Mamba layers' per-slot state against the plain reference's full
forward pass (logits compared, through the served tokens' log-
probabilities and the reference's first choice); the segmented scan and
the convolution against the recurrence written token by token; the state
across steps, across a slot's owners and across a preemption; the experts'
ungated form; the refusals; the counters."""
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import ragged_ssm
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.parallel.moe import dropless_experts

from nemotron_tiny import (against_reference, engine, init, program_config,
                           reference, requests, tiny_model)

# prompts shorter and longer than the 16-row buffer, outputs that cross
# several pages of 4; six requests over four slots, so two slots are taken
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


def run(eng, shapes, seed=0):
    reqs = requests(shapes, seed)
    for r in reqs:
        eng.submit(r)
    eng.run()
    return reqs


@pytest.mark.parametrize("i", range(len(MIX)))
def test_prefill_then_decode_agrees_with_the_reference(model, served, i):
    """The float32 engine is the reference to rounding (the same float32
    sums in another order: a state advanced in place against one scanned
    from zero, a page's online softmax against a row's): every served token
    its first choice, log p to 1e-4."""
    m, params = model
    eng, reqs = served
    assert len(reqs[i].output) == MIX[i][1]
    first, lp = against_reference(m, params, reqs[i])
    assert first == 1.0 and lp < 1e-4, (reqs[i].rid, first, lp)
    assert eng.preemptions == 0


def test_a_bfloat16_engine_stays_within_its_tolerance(model):
    """Weights, pages and carried convolution rows in bfloat16 (the state
    float32, as the configuration states) against the float32 reference on
    the same weights' rounded values: the median |log p| difference of the
    served tokens under 0.05 and the mean under 0.15 (bfloat16 keeps 8
    bits; the toy logits run to a few units). The widest is left to 2.0:
    with 2 experts of 8 a row, a rounded score flips a row's second expert
    at a near tie, and that row is another row (it read 1.3 here)."""
    m, params = model
    half = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.ndim > 1 else x, params)
    eng = engine(m, half, dtype=jnp.bfloat16)
    ssm, conv = eng._slot_state
    assert ssm.dtype == jnp.float32 and conv.dtype == jnp.bfloat16
    gaps = []
    for r in run(eng, MIX[:3]):
        with jax.default_matmul_precision("highest"):
            lg = reference.logits(half, jnp.asarray(r.prompt + r.output),
                                  m, q_block=1)
        at = np.asarray(jax.nn.log_softmax(lg[len(r.prompt) - 1:-1], -1))
        gaps.append(np.abs(at[np.arange(len(r.output)), r.output]
                           - np.asarray(r.logprobs)))
    gaps = np.concatenate(gaps)
    assert gaps.max() < 2.0 and 1e-6 < gaps.mean() < 0.15 \
        and np.median(gaps) < 0.05, (gaps.max(), gaps.mean(),
                                     np.median(gaps))


# -- the kernels ---------------------------------------------------------------
# a decode row, a prompt's chunk from position 0, a decode row, a chunk that
# carries on from position 9, slack rows, a one-row prompt, slack
SLOTS = [3] + [1] * 6 + [0] + [4] * 4 + [0] * 3 + [5] + [0] * 8
POSITIONS = [7] + list(range(6)) + [2] + list(range(9, 13)) + [-1] * 3 \
    + [0] + [-1] * 8


def _rows(seed=0, heads=4, p=8, g=2, n=16, k=4, slots=6, layers=2):
    rng = np.random.default_rng(seed)
    t = len(SLOTS)
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    return dict(
        x=f(t, heads, p), dt=rng.uniform(0.01, 0.5, (t, heads)).astype("f4"),
        a=-rng.uniform(1, 16, (heads,)).astype("f4"), b=f(t, g, n),
        c=f(t, g, n), state=f(layers, slots, n, heads * p),
        u=f(t, heads * p + 2 * g * n), conv=f(layers, slots, k - 1, 1,
                                              heads * p + 2 * g * n),
        w=f(k, heads * p + 2 * g * n), bias=f(heads * p + 2 * g * n))


def _scan_by_hand(r, layer):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t, a head's
    state (head size, N), token by token in numpy float64."""
    t, heads, p = r["x"].shape
    g, n = r["b"].shape[1:]
    state = r["state"][layer].astype(np.float64).reshape(-1, n, heads, p)
    state = state.transpose(0, 2, 3, 1).copy()           # (slots, h, p, n)
    y = np.zeros((t, heads, p))
    for i, (s, pos) in enumerate(zip(SLOTS, POSITIONS)):
        if pos < 0:
            continue
        if pos == 0:
            state[s] = 0.0
        for h in range(heads):
            grp = h // (heads // g)
            state[s, h] = np.exp(r["dt"][i, h] * r["a"][h]) * state[s, h] \
                + r["dt"][i, h] * np.outer(r["x"][i, h], r["b"][i, grp])
            y[i, h] = state[s, h] @ r["c"][i, grp]
    return y, state.transpose(0, 3, 1, 2).reshape(-1, n, heads * p)


@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_the_segmented_scan_is_the_recurrence_token_by_token(path):
    r = _rows()
    slot, pos = jnp.asarray(SLOTS, jnp.int32), jnp.asarray(POSITIONS,
                                                           jnp.int32)
    y, state = ragged_ssm.ragged_scan(
        *(jnp.asarray(r[k]) for k in ("x", "dt", "a", "b", "c", "state")),
        1, slot, pos, interpret=path == "kernel")
    want_y, want_state = _scan_by_hand(r, 1)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state[1], want_state, rtol=1e-5, atol=1e-5)
    # the other layer's state, and the slots no run touched, as they were
    np.testing.assert_array_equal(state[0], r["state"][0])
    np.testing.assert_array_equal(state[1, 2], r["state"][1, 2])


def test_the_scan_keeps_a_bfloat16_state_and_sums_in_float32():
    """The control's path: the state rounded once a row as it is stored,
    the kernel's and the `jax.numpy` path's bit for bit."""
    r = _rows()
    slot, pos = jnp.asarray(SLOTS, jnp.int32), jnp.asarray(POSITIONS,
                                                           jnp.int32)
    args = [jnp.asarray(r[k]) for k in ("x", "dt", "a", "b", "c")]
    state = jnp.asarray(r["state"], jnp.bfloat16)
    y0, s0 = ragged_ssm.ragged_scan(*args, state, 0, slot, pos)
    y1, s1 = ragged_ssm.ragged_scan(*args, state, 0, slot, pos,
                                    interpret=True)
    assert s1.dtype == jnp.bfloat16
    np.testing.assert_allclose(y0, y1, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s0, np.float32),
                                  np.asarray(s1, np.float32))
    want_y, _ = _scan_by_hand(r, 0)
    assert 1e-4 < np.abs(np.asarray(y1) - want_y).max() < 0.5


@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_the_convolution_carries_its_last_rows_a_slot(path):
    r = _rows()
    slot, pos = jnp.asarray(SLOTS, jnp.int32), jnp.asarray(POSITIONS,
                                                           jnp.int32)
    y, conv = ragged_ssm.ragged_conv(
        jnp.asarray(r["u"]), jnp.asarray(r["conv"]), 0, jnp.asarray(r["w"]),
        jnp.asarray(r["bias"]), slot, pos, interpret=path == "kernel")
    hist = r["conv"][0, :, :, 0].astype(np.float64).copy()
    want = np.zeros_like(r["u"], np.float64)
    for i, (s, p) in enumerate(zip(SLOTS, POSITIONS)):
        if p < 0:
            continue
        if p == 0:
            hist[s] = 0.0
        win = np.concatenate([hist[s], r["u"][i:i + 1]])
        acc = (win * r["w"]).sum(0) + r["bias"]
        want[i] = acc / (1 + np.exp(-acc))
        hist[s] = win[1:]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(conv[0, :, :, 0], hist, rtol=1e-6)
    np.testing.assert_array_equal(conv[1], r["conv"][1])


def test_runs_are_a_slots_consecutive_rows():
    runs, n, fresh, rows = ragged_ssm.ssm_runs(
        jnp.asarray(SLOTS, jnp.int32), jnp.asarray(POSITIONS, jnp.int32), 6)
    assert (int(n), int(fresh), int(rows)) == (5, 2, 13)
    assert np.asarray(runs)[:, :5].tolist() == [
        [3, 1, 0, 4, 5], [0, 1, 7, 8, 15], [1, 6, 1, 4, 1], [0, 1, 0, 0, 1]]
    # past the last run: its slot again (the block stays), and no rows
    assert np.asarray(runs)[:, 5].tolist() == [5, 0, 0, 0]


# -- the state across steps, owners and preemptions ------------------------------
def test_a_prompt_longer_than_the_row_buffer_gives_the_logits_of_one_fed_whole(
        model):
    """30 and 23 prompt rows through a 16-row buffer carry their state over
    two steps; through a 64-row buffer they are one run. The same sums row
    by row, so the same logits."""
    m, params = model
    shapes = [(30, 6), (23, 6)]
    chunked = run(engine(m, params, ragged_tokens=16), shapes)
    whole = run(engine(m, params, ragged_tokens=64), shapes)
    for a, b in zip(chunked, whole):
        assert a.output == b.output
        np.testing.assert_allclose(a.logprobs, b.logprobs, rtol=0, atol=1e-5)


def test_a_slot_taken_again_gives_what_a_fresh_engine_gives(model):
    """One slot, three requests one after another: the second and third
    find the state their predecessors left and begin from zero all the
    same."""
    m, params = model
    shapes = [(9, 8), (20, 8), (3, 8)]
    eng = engine(m, params, max_seqs=1)
    again = run(eng, shapes)
    assert eng.ssm_runs_fresh == 3
    for i, r in enumerate(again):
        fresh = requests(shapes)[i]
        one = engine(m, params, max_seqs=1)
        one.submit(fresh)
        one.run()
        assert r.output == fresh.output and r.logprobs == fresh.logprobs


def test_preemption_by_recompute_gives_the_same_logits(model, served):
    """A pool too small for the mix evicts the newest admission; fed again
    from its first token, it begins from zero state and serves the tokens
    it would have."""
    m, params = model
    eng = engine(m, params, num_pages=31)
    assert eng.preempt_policy == "recompute"
    reqs = run(eng, MIX)
    assert eng.preemptions > 0
    assert eng.ssm_runs_fresh == len(MIX) + eng.preemptions
    for r, want in zip(reqs, served[1]):
        assert r.output == want.output
        np.testing.assert_allclose(r.logprobs, want.logprobs, rtol=0,
                                   atol=1e-5)


def test_the_step_donates_pools_and_state_and_the_pump_runs_one_step_deep(
        model):
    from paddle_tpu.serving import RequestScheduler
    m, params = model
    eng = engine(m, params)
    held = list(eng._caches[0].pools[0]) + list(eng._slot_state)
    assert len(held) == 1 + 2
    assert [a.shape for a in eng._slot_state] == [(4, 4, 16, 32),
                                                  (4, 4, 3, 1, 96)]
    assert eng.slot_state_bytes == 4 * 4 * (16 * 32 + 3 * 96) * 4
    for r in requests([(5, 4)]):
        eng.submit(r)
    eng.step()
    assert all(a.is_deleted() for a in held)
    sched = RequestScheduler(engine(m, params), max_queue=8)
    try:
        assert sched._pipeline     # one step deep: a ragged engine
    finally:
        sched.shutdown(drain=False, timeout=30)


def test_the_step_aliases_pools_and_state_to_its_outputs(model):
    """The lowered program: the attention layer's pools and both slot
    states are inputs whose buffers an output takes."""
    m, params = model
    eng = engine(m, params)
    t = eng.ragged_buf
    sample = {k: jnp.zeros((4,) + s, d) for k, s, d in (
        ("temp", (), jnp.float32), ("top_k", (), jnp.int32),
        ("top_p", (), jnp.float32), ("key", (2,), jnp.uint32),
        ("eos", (), jnp.int32), ("remaining", (), jnp.int32))}
    text = nh.nemotron_step.__wrapped__.lower(
        params, tuple(gc.device() for gc in eng._caches)
        + tuple(eng._slot_state), (jnp.asarray(eng._caches[0].table),),
        jnp.zeros((t,), jnp.int32), jnp.zeros((t,), jnp.int32),
        jnp.full((t,), -1, jnp.int32), eng.config, eng.page_size,
        sample=sample, need_rows=jnp.full((4,), -1, jnp.int32),
        tok_buf=eng.tok_buf, buf_write=jnp.zeros((4,), bool)).as_text()
    assert text.count("tf.aliasing_output") == 2 + 2


# -- the experts ---------------------------------------------------------------------
@pytest.mark.parametrize("sizes", [[3, 0, 9, 1, 0, 11], [0] * 6, [40] + [0] * 5,
                                   [1] * 6, [0, 0, 17, 0, 23, 0]])
def test_the_whole_matrix_grouped_product_is_ragged_dot(sizes):
    """`kernels/grouped_matmul` (interpreted) against `lax.ragged_dot` on
    the rows some group owns: groups with no row, a group over five tiles,
    a tile shared by six groups."""
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul, visits
    rng = np.random.default_rng(1)
    g, k, n, tile, m = len(sizes), 16, 24, 8, 40
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(g, k, n)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    want = jax.lax.ragged_dot(lhs, rhs, gs,
                              preferred_element_type=jnp.float32)
    got = grouped_matmul(lhs, rhs, gs, tile, interpret=True)
    held = sum(sizes)
    np.testing.assert_allclose(got[:held], want[:held], rtol=1e-5, atol=1e-5)
    got = grouped_matmul(lhs, rhs.swapaxes(1, 2), gs, tile, transposed=True,
                         interpret=True)
    np.testing.assert_allclose(got[:held], want[:held], rtol=1e-5, atol=1e-5)
    plan = np.asarray(visits(gs, m // tile, tile))
    live = plan[3] > plan[2]
    # every group's matrix is one run of visits: fetched once
    groups = plan[1][live]
    assert (np.diff(groups) >= 0).all() and (np.diff(plan[0]) >= 0).all()
    assert int((plan[3] - plan[2]).sum()) == held


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("first, held", [(0, 8), (2, 4)])
def test_ungated_experts_are_a_loop_over_experts(first, held, kernel):
    """`w_gate=None`: down(relu(up x)^2), two grouped products, the whole
    layer and a share of it; through the compiler's product and through
    the whole-matrix kernel (widths that 512 does not divide take it)."""
    rng = np.random.default_rng(3)
    t, k, h, e, f = 24, 2, 16, 8, 12
    x = rng.normal(size=(t, h)).astype("f4")
    up = rng.normal(size=(e, h, f)).astype("f4")
    down = rng.normal(size=(e, f, h)).astype("f4")
    expert = np.stack([rng.permutation(e)[:k] for _ in range(t)]).astype("i4")
    expert[5] = -1                                      # a slack row
    weight = rng.uniform(0.1, 1, (t, k)).astype("f4")
    out, rows = dropless_experts(
        jnp.asarray(x), jnp.asarray(expert), jnp.asarray(weight), None,
        jnp.asarray(up[first:first + held].swapaxes(1, 2) if kernel
                    else up[first:first + held]),
        jnp.asarray(down[first:first + held]), first=first, num_experts=e,
        up_transposed=kernel, use_pallas=False, interpret=kernel)
    want = np.zeros((t, h))
    for i in range(t):
        for j in range(k):
            ex = expert[i, j]
            if first <= ex < first + held:
                want[i] += weight[i, j] * (
                    np.maximum(x[i] @ up[ex], 0) ** 2 @ down[ex])
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)
    assert rows.tolist() == [int((expert == ex).sum())
                             for ex in range(first, first + held)]


def test_the_gated_form_lowers_to_the_text_it_did_for_lagunas_shapes():
    """`dropless_experts` with a gate, at Laguna-XS.2's widths over 128
    rows: the program the parent of PR 48 lowered (its text without the
    source locations, hashed there)."""
    t, k, h, e, f = 128, 8, 2048, 256, 512
    sd = jax.ShapeDtypeStruct
    text = jax.jit(dropless_experts).lower(
        sd((t, h), jnp.bfloat16), sd((t, k), jnp.int32),
        sd((t, k), jnp.float32), sd((e, h, f), jnp.bfloat16),
        sd((e, h, f), jnp.bfloat16), sd((e, f, h), jnp.bfloat16)).as_text()
    text = re.sub(r"loc\(.*?\)", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "c6940f10fadf13a23ceacb27c59b0a0346cf174a8e3626f94c49d80fa3d7142c"


def test_every_assignment_is_counted_once(served):
    eng, reqs = served
    rows = sum(len(r.prompt) + len(r.output) - 1 for r in reqs)
    assert eng.moe_assignments == 4 * 2 * rows      # E layers x top 2
    assert eng.ssm_rows == rows
    assert eng.ssm_state_slots > eng.ssm_runs_fresh == 6


# -- the seam -------------------------------------------------------------------------
@pytest.mark.parametrize("kw, word", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefix_cache=True, host_tier_bytes=1 << 20), "prefix_cache"),
    (dict(host_tier_bytes=1 << 20), "host_tier"),
    (dict(spec_decode=4), "spec_decode"),
    (dict(preempt_policy="offload"), "offload"),
    (dict(ragged=False), "bucketed"),
    (dict(cache_dtype="int8"), "int8_cache"),
    (dict(tp=True), "tensor_parallel"),
    (dict(handoff=True), "handoff")])
def test_what_the_engine_cannot_do_yet_refuses_with_its_reason(model, kw,
                                                               word):
    m, params = model
    kw = dict(kw)
    assert word in nh._NOT_YET
    reason = f"NemotronHConfig does not serve under {word}"
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


def test_a_model_without_slot_state_keeps_offload_as_its_default():
    from laguna_tiny import engine as laguna_engine, tiny_model as laguna
    from paddle_tpu.models.laguna import LagunaConfig, init_params
    m = laguna(layers=2)
    eng = laguna_engine(m, init_params(LagunaConfig.from_dict(m)))
    assert eng.preempt_policy == "offload" and eng._slot_state == []
    assert eng.slot_state_bytes == 0


def test_the_model_says_how_many_rows_a_step_holds(model):
    m, params = model
    assert engine(m, params, ragged_tokens=None,
                  max_seqs=2).ragged_buf == nh.ROWS_A_STEP


def test_a_cut_in_depth_is_the_patterns_first_letters():
    c = nh.NemotronHConfig(num_hidden_layers=9)
    assert c.hybrid_override_pattern == "MEMEM*EME"
    assert (c.count("M"), c.count("E"), c.count("*")) == (4, 4, 1)
    assert (c.d_inner, c.conv_dim) == (4096, 6144)
    full = nh.NemotronHConfig()
    assert (full.count("M"), full.count("E"), full.count("*")) == (23, 23, 6)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nh.NemotronHConfig(num_hidden_layers=53)
    with pytest.raises(ValueError, match="n_group 1"):
        nh.NemotronHConfig(n_group=8, topk_group=4)


def test_the_seeded_steps_lie_where_mamba2_starts_them():
    """softplus(dt_bias) log-uniform in [time_step_min, time_step_max],
    A in [-16, -1], D = 1, float32."""
    c = program_config(tiny_model())
    lp = nh.init_params(c, seed=3)["layers"][0]
    dt = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert ((dt >= 0.001 - 1e-6) & (dt <= 0.1 + 1e-6)).all()
    a = -np.exp(np.asarray(lp["A_log"]))
    assert ((a <= -1) & (a >= -16)).all() and (np.asarray(lp["D"]) == 1).all()
    assert {lp[k].dtype for k in ("dt_bias", "A_log", "D")} == {np.dtype("float32")}
    assert np.abs(np.asarray(lp["conv_w"])).max() <= 0.5


def test_the_metrics_export_the_state_counters(model):
    """`pt_ssm_*` from the step's record: one layer's runs, their rows,
    those from zero (one a request admitted), and the bytes allocated."""
    from paddle_tpu.serving import RequestScheduler
    m, params = model
    sched = RequestScheduler(engine(m, params), max_queue=8)
    try:
        hs = [sched.submit(list(range(1, n)), max_new_tokens=6, eos_id=None)
              for n in (12, 20)]
        assert [len(list(h.result())) for h in hs] == [6, 6]
        snap = sched.registry.snapshot()
    finally:
        sched.shutdown(drain=False, timeout=30)
    assert snap["pt_ssm_runs_fresh"]["value"] == 2
    assert snap["pt_ssm_rows"]["value"] == 11 + 19 + 2 * 5
    assert snap["pt_ssm_state_slots"]["value"] >= 2 + 2 * 5
    assert "pt_ssm_runs" not in snap    # a slot's one run a step: the slots
    assert snap["pt_ssm_state_bytes"]["value"] == 4 * 4 * (512 + 288) * 4
    assert snap["pt_moe_assignments"]["value"] == 4 * 2 * (11 + 19 + 2 * 5)
