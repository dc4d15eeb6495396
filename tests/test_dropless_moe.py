"""The dropless expert layer (`parallel/moe.dropless_experts`) against a
per-row loop over experts: slack rows that route nowhere, an expert that
gets no row, every row to one expert, and the rows in any order."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import moe
from paddle_tpu.parallel.moe import (dropless_experts,
                                     dropless_experts_blocked,
                                     grouped_product)

T, H, F, E, K = 12, 16, 8, 8, 2


def _problem(seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (T, H))
    w = [jax.random.normal(k, s) * 0.3 for k, s in zip(
        ks[1:4], [(E, H, F), (E, H, F), (E, F, H)])]
    expert = jax.random.randint(ks[4], (T, K), 0, E - 1)   # never E - 1
    weight = jax.random.uniform(ks[5], (T, K))
    return x, expert, weight, w


def _loop(x, expert, weight, w, first=0):
    """The experts `w` holds are the layer's `first .. first + len`; an
    assignment to any other, or to none (negative), adds nothing."""
    wg, wu, wd = (np.asarray(a, np.float64) for a in w)
    x = np.asarray(x, np.float64)
    out = np.zeros(x.shape)
    rows = np.zeros((len(wg),), np.int64)
    for t in range(len(x)):
        for j in range(expert.shape[1]):
            e = int(expert[t, j]) - first
            if not 0 <= e < len(wg):
                continue
            g = x[t] @ wg[e]
            out[t] += float(weight[t, j]) * ((g / (1 + np.exp(-g)))
                                             * (x[t] @ wu[e])) @ wd[e]
            rows[e] += 1
    return out, rows


def test_every_assignment_is_computed_and_an_idle_expert_costs_nothing():
    x, expert, weight, w = _problem()
    out, rows = dropless_experts(x, expert, weight, *w)
    want, want_rows = _loop(x, expert, weight, w)
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert rows.tolist() == want_rows.tolist()
    assert rows[E - 1] == 0 and rows.sum() == T * K


def test_slack_rows_route_nowhere():
    x, expert, weight, w = _problem(1)
    expert = expert.at[3].set(-1).at[T - 1].set(-1)
    out, rows = dropless_experts(x, expert, weight, *w)
    want, want_rows = _loop(x, expert, weight, w)
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert not np.asarray(out[3]).any() and not np.asarray(out[T - 1]).any()
    assert rows.sum() == (T - 2) * K
    # a buffer of nothing but slack rows
    out, rows = dropless_experts(x, jnp.full((T, K), -1), weight, *w)
    assert not np.asarray(out).any() and rows.sum() == 0


def test_all_rows_on_one_expert_none_dropped():
    """What a capacity-bounded dispatch would drop: T x K assignments on
    one expert of eight."""
    x, _, weight, w = _problem(2)
    expert = jnp.full((T, K), 5)
    out, rows = dropless_experts(x, expert, weight, *w)
    np.testing.assert_allclose(out, _loop(x, expert, weight, w)[0], atol=2e-5)
    assert rows.tolist() == [0, 0, 0, 0, 0, T * K, 0, 0]


@pytest.mark.parametrize("seed", [4, 5])
def test_a_row_gets_its_own_result_wherever_it_lies_in_the_buffer(seed):
    """The sort by expert and the way back are a permutation and its
    inverse: rows shuffled in, the same rows shuffled out, and each
    expert's count unmoved."""
    x, expert, weight, w = _problem(3)
    whole, rows = dropless_experts(x, expert, weight, *w)
    perm = np.random.default_rng(seed).permutation(T)
    out, rows_p = dropless_experts(x[perm], expert[perm], weight[perm], *w)
    np.testing.assert_allclose(out, np.asarray(whole)[perm], atol=2e-5)
    assert rows_p.tolist() == rows.tolist()


def test_grouped_product_is_a_matmul_per_group():
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.standard_normal((10, 6)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, 6, 5)), jnp.float32)
    sizes = jnp.asarray([3, 0, 5, 2], jnp.int32)
    got = np.asarray(grouped_product(lhs, rhs, sizes))
    at = 0
    for g, n in enumerate([3, 0, 5, 2]):
        np.testing.assert_allclose(got[at:at + n], lhs[at:at + n] @ rhs[g],
                                   atol=1e-5)
        at += n


# ---------------------------------------------------------------------------
# the row tile (ISSUE 44): the buffer a product is launched over
# ---------------------------------------------------------------------------
# (rows the products run over, groups) of the three MoE cells:
# Laguna's whole layer, GLM-5's and LongCat's shares
CELLS = [(1024, 256), (1024, 16), (384, 16)]


@pytest.mark.parametrize("rows, groups, tile, length", [
    (1024, 256, 32, 1056), (1024, 16, 64, 1088), (384, 16, 32, 416),
    # a share's fall-back to all T k: 256 and 192 rows a group
    (4096, 16, 256, 4352), (3072, 16, 256, 3328),
    # many rows a group: the compiler's own 512, and no smaller
    (65536, 16, 512, 66048), (24, 8, 32, 32), (1, 1, 32, 32)])
def test_the_tile_holds_a_groups_rows_and_the_length_gives_it(rows, groups,
                                                             tile, length):
    from paddle_tpu.parallel.moe import row_tile, tiled_rows
    assert row_tile(rows, groups) == tile
    assert tiled_rows(rows, groups) == length >= rows
    # the compiler's rule: the largest power of two up to 512 that
    # divides the length (tests/test_tpu_lowering.py holds it to that)
    assert length % tile == 0 and (length // tile) % 2 == 1


@pytest.mark.parametrize("rows, groups", CELLS)
def test_grouped_product_at_a_padded_length_is_a_matmul_per_group(rows,
                                                                  groups):
    from paddle_tpu.parallel.moe import tiled_rows
    rng = np.random.default_rng(rows + groups)
    length = tiled_rows(rows, groups)
    real = rows * 7 // 8 if groups == 256 else rows // 8
    sizes = rng.multinomial(real, np.full(groups, 1 / groups))
    lhs = jnp.asarray(rng.standard_normal((length, 6)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((groups, 6, 5)), jnp.float32)
    got = np.asarray(grouped_product(lhs, rhs, jnp.asarray(sizes, jnp.int32)))
    assert got.shape == (length, 5)
    group_of = np.repeat(np.arange(groups), sizes)
    want = np.einsum("mk,mkn->mn", np.asarray(lhs)[:real],
                     np.asarray(rhs)[group_of])
    np.testing.assert_allclose(got[:real], want, atol=1e-5)


def _share(t, k, held, among, hit, seed):
    """A share's problem: `hit` of the t rows send every pick to the
    held experts, the others none; row 0, which the padded gather reads,
    is a slack row of NaNs."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (t, H)).at[0].set(jnp.nan)
    w = [jax.random.normal(kk, s) * 0.3 for kk, s in zip(
        ks[1:4], [(held, H, F), (held, H, F), (held, F, H)])]
    mine = jax.random.randint(ks[4], (t, k), 3, 3 + held)
    other = jax.random.randint(ks[4], (t, k), 3 + held, among)
    expert = jnp.where((jnp.arange(t) <= hit)[:, None], mine, other)
    expert = expert.at[0].set(-1)
    return x, expert, jax.random.uniform(ks[5], (t, k)), w


@pytest.mark.parametrize("hit, branch", [(10, "few"), (16, "few"),
                                         (17, "all"), (63, "all")])
def test_a_share_falls_back_to_every_row_when_the_few_cannot_hold_it(hit,
                                                                     branch):
    """64 rows x 8 picks, 4 of 64 experts held: the products run over 128
    sorted rows (launched over 160) while they hold every held
    assignment, `hit` x 8 of them, and over all 512 (launched over 640)
    in a step where they do not. Either way a row gets its own, and the
    rows past the experts' sum (NaNs here: the padded gather reads row
    0) add nothing to any row."""
    from paddle_tpu.parallel.moe import _product_rows, tiled_rows
    t, k, held, among = 64, 8, 4, 64
    assert _product_rows(t * k, held, among) == 128
    assert (tiled_rows(128, held), tiled_rows(512, held)) == (160, 640)
    x, expert, weight, w = _share(t, k, held, among, hit, seed=hit)
    out, rows = dropless_experts(x, expert, weight, *w, first=3,
                                 num_experts=among)
    assert int(rows.sum()) == hit * k
    assert (int(rows.sum()) <= 128) == (branch == "few")
    assert np.isfinite(np.asarray(out)).all()
    assert not np.asarray(out[0]).any() and not np.asarray(out[hit + 1:]).any()
    want, want_rows = _loop(jnp.nan_to_num(x), expert, weight, w, first=3)
    np.testing.assert_allclose(out, want, atol=5e-5)
    assert rows.tolist() == want_rows.tolist()


# (rows, picks a row, experts held, real experts, experts routed over)
# of the two shares at toy width: GLM-5's 8 of 256, and LongCat's 12 of
# 768 of which the last 256 are identity experts, held by nobody
SHARES = {"glm": (64, 8, 16, 256, 256), "longcat": (64, 12, 16, 512, 768)}


def _routed_share(name, seed, first=32):
    """A step's rows routed as the share's model routes them: distinct
    picks over the router's whole width, the last six rows slack."""
    t, k, held, among, width = SHARES[name]
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (t, H))
    w = [jax.random.normal(kk, s) * 0.3 for kk, s in zip(
        ks[1:4], [(held, H, F), (held, H, F), (held, F, H)])]
    expert = np.argsort(rng.random((t, width)), axis=1)[:, :k]
    expert[t - 6:] = -1
    weight = rng.random((t, k)).astype(np.float32)
    return (x, jnp.asarray(expert, jnp.int32), jnp.asarray(weight), w,
            dict(first=first, num_experts=among))


def _every_row(monkeypatch, few=8):
    """Make a share's few sorted rows fewer than any step holds, so that
    `dropless_experts` takes its branch over all T k."""
    monkeypatch.setattr(moe, "_product_rows", lambda *a: few)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("share", list(SHARES))
def test_both_branches_of_a_share_give_each_row_the_same_sum(share, seed,
                                                             monkeypatch):
    """The branch over the few sorted rows and the one over all T k, on
    the SAME routing: each returns the (T, H) sum, equal to float32
    rounding (the order of a row's at most k terms may differ), and the
    per-row loop's."""
    t, k, held, among, _ = SHARES[share]
    x, expert, weight, w, kw = _routed_share(share, seed)
    few = moe._product_rows(t * k, held, among)
    assert few == 128 < t * k
    lean, rows = dropless_experts(x, expert, weight, *w, **kw)
    assert 8 < int(rows.sum()) <= few
    _every_row(monkeypatch)
    every, rows_e = dropless_experts(x, expert, weight, *w, **kw)
    assert lean.shape == every.shape == (t, H) and lean.dtype == jnp.float32
    np.testing.assert_allclose(lean, every, rtol=1e-5, atol=1e-6)
    assert rows.tolist() == rows_e.tolist()
    want, want_rows = _loop(x, expert, weight, w, first=kw["first"])
    np.testing.assert_allclose(lean, want, atol=5e-5)
    assert rows.tolist() == want_rows.tolist()


@pytest.mark.parametrize("branch", ["few", "every_row"])
@pytest.mark.parametrize("left", [np.nan, np.inf, -3e38])
def test_what_the_chip_leaves_in_nobodys_rows_reaches_no_row_of_a_share(
        left, branch, monkeypatch):
    """The serving twin of the training step's test below: the TPU's
    grouped matmul leaves rows past the groups' sum as it found them.
    Here every product's rows that nobody owns hold `left`: they are
    dropped before a weight or a sum meets them, in both branches."""
    real = moe.grouped_product

    def poisoned(lhs, rhs, sizes):
        out = real(lhs, rhs, sizes)
        owned = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
        return jnp.where(owned[:, None], out, left)
    x, expert, weight, w, kw = _routed_share("longcat", 2)
    want, _ = _loop(x, expert, weight, w, first=kw["first"])
    monkeypatch.setattr(moe, "grouped_product", poisoned)
    if branch == "every_row":
        _every_row(monkeypatch)
    out, _ = dropless_experts(x, expert, weight, *w, **kw)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, want, atol=5e-5)


@pytest.mark.parametrize("branch", ["few", "every_row"])
@pytest.mark.parametrize("share", list(SHARES))
def test_a_row_with_nothing_held_here_gets_exact_zeros(share, branch,
                                                       monkeypatch):
    """Slack rows (`expert < 0`) and rows whose every assignment is held
    elsewhere (or by nobody: an identity expert) add up nothing: exact
    zeros, not small numbers, whatever their weights and their x."""
    x, expert, weight, w, kw = _routed_share(share, 3)
    held = (np.asarray(expert) >= kw["first"]) \
        & (np.asarray(expert) < kw["first"] + w[0].shape[0])
    idle = ~held.any(1)
    assert idle[-6:].all() and 6 < idle.sum() < len(idle)
    x = x.at[-6:].set(jnp.nan)
    weight = jnp.where(idle[:, None], 1e30, weight).at[-6:].set(jnp.nan)
    if branch == "every_row":
        _every_row(monkeypatch)
    out, rows = dropless_experts(x, expert, weight, *w, **kw)
    assert int(rows.sum()) == held.sum()
    assert (np.asarray(out)[idle] == 0).all()
    assert np.asarray(out)[~idle].any(1).all()
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("branch", ["few", "every_row", "whole_layer"])
@pytest.mark.parametrize("left", [np.nan, np.inf])
def test_a_row_that_is_not_finite_stays_its_own(left, branch, monkeypatch):
    """One request's row holds NaN or infinity: its own sum is not
    finite and every other row's is what it was, in a whole layer (a
    gather) and in both branches of a share (a product with ones and
    zeros, where 0 x NaN would reach every row)."""
    x, expert, weight, w, kw = _routed_share("glm", 4)
    if branch == "whole_layer":
        expert, kw = jnp.where(expert < 0, -1, expert % 16), {}
    bad = 7
    clean, _ = dropless_experts(x, expert, weight, *w, **kw)
    assert np.asarray(clean[bad]).any()
    if branch == "every_row":
        _every_row(monkeypatch)
    out, _ = dropless_experts(x.at[bad].set(left), expert, weight, *w, **kw)
    assert not np.isfinite(np.asarray(out[bad])).any()
    others = np.arange(len(x)) != bad
    np.testing.assert_allclose(np.asarray(out)[others],
                               np.asarray(clean)[others], atol=1e-6)


def test_rows_past_the_experts_sum_add_nothing_to_a_whole_layers_rows():
    """The whole layer launched over 32 rows for its 24 assignments: the
    slack rows' and the padded gather's rows are NaNs and nobody's."""
    x, expert, weight, w = _problem(6)
    expert = expert.at[0].set(-1).at[5].set(-1)
    x = x.at[0].set(jnp.nan).at[5].set(jnp.nan)
    out, rows = dropless_experts(x, expert, weight, *w)
    assert np.isfinite(np.asarray(out)).all() and rows.sum() == (T - 2) * K
    want, _ = _loop(jnp.nan_to_num(x), expert, weight, w)
    np.testing.assert_allclose(out, want, atol=2e-5)


@pytest.mark.parametrize("rows, assignments, among, visits", [
    # a whole layer of 4 experts over 128 assignments: tile 32. Runs
    # [0, 30) [30, 34) [] [34, 74): 1 + 2 + 0 + 2 tiles
    ([30, 4, 0, 40], 128, None, 5),
    # an expert of 4 rows can span two tiles; one of 65 spans three
    ([31, 4, 65, 0], 128, None, 1 + 2 + 3),
    # a share, 4 of 64 held, 512 assignments: the few are 128 rows at
    # tile 32 ...
    ([30, 4, 0, 40], 512, 64, 5),
    # ... and past them all 512 at tile 128: [0, 100) [100, 140) [] [140,
    # 180): 1 + 2 + 0 + 1
    ([100, 40, 0, 40], 512, 64, 4),
    ([0, 0, 0, 0], 128, None, 0)])
def test_row_tile_visits_against_a_hand_count(rows, assignments, among,
                                              visits):
    from paddle_tpu.parallel.moe import row_tile_visits
    assert row_tile_visits(np.asarray(rows), assignments, among) == visits
    assert visits >= sum(r > 0 for r in rows)


def test_the_engine_counts_the_row_tiles_its_steps_experts_span():
    """`pt_moe_row_tiles` beside `pt_moe_experts_touched`: the toy model's
    16 rows x 2 picks lie in one tile of 32, so every touched expert is
    one visit and the ratio reads 1.0; the counter is the engine's sum
    of `row_tile_visits` over the step's `moe_rows` record."""
    from laguna_tiny import engine, tiny_model
    from paddle_tpu.models.laguna import LagunaConfig, init_params
    from paddle_tpu.parallel.moe import row_tile_visits
    from paddle_tpu.serving import RequestScheduler
    m = tiny_model()
    eng = engine(m, init_params(LagunaConfig.from_dict(m), seed=1))
    picks, among = eng.model.experts
    seen, real = [], eng.model.step

    def step(*a, **kw):
        out = real(*a, **kw)
        seen.append(out[-1]["moe_rows"])
        return out

    object.__setattr__(eng.model, "step", step)
    sched = RequestScheduler(eng, max_queue=8)
    try:
        h = sched.submit(list(range(1, 24)), max_new_tokens=6, eos_id=None)
        assert len(list(h.result())) == 6
        snap = sched.registry.snapshot()
    finally:
        sched.shutdown(drain=False, timeout=30)
    by_hand = row_tile_visits(np.stack(seen), eng.ragged_buf * picks, among)
    assert snap["pt_moe_row_tiles"]["value"] == eng.moe_row_tiles == by_hand
    assert eng.moe_row_tiles == eng.moe_experts_touched > 0
    # a whole layer has no few rows to exceed
    assert snap["pt_moe_share_spills"]["value"] == eng.moe_share_spills == 0


@pytest.mark.parametrize("rows, assignments, among, spills", [
    # 4 of 64 held, 512 assignments: the few are 128 sorted rows
    ([30, 4, 0, 40], 512, 64, 0), ([100, 28, 0, 0], 512, 64, 0),
    ([100, 29, 0, 0], 512, 64, 1),
    # (steps, layers, experts): a layer and step is one count
    ([[[64, 64, 0, 0], [64, 64, 1, 0]], [[0, 0, 0, 129], [0, 0, 0, 0]]],
     512, 64, 2),
    # a whole layer's products run over every assignment: none
    ([128, 0, 0, 0], 128, None, 0), ([128, 0, 0, 0], 128, 4, 0)])
def test_share_spills_against_a_hand_count(rows, assignments, among, spills):
    from paddle_tpu.parallel.moe import share_spills
    assert share_spills(np.asarray(rows), assignments, among) == spills


def test_the_engine_counts_the_steps_a_share_could_not_hold():
    """`pt_moe_share_spills` beside `pt_moe_row_tiles`, from the same
    record: the toy GLM-5 holds 1 of 8 experts over 128 rows x 2 picks,
    so its products run over 128 of the 256 sorted rows. No step of its
    own can exceed them (a row picks an expert once); a hand-made record
    in the first step's place does, in one of its two expert layers."""
    from glm_tiny import engine, init, tiny_model
    from paddle_tpu.parallel.moe import _product_rows
    from paddle_tpu.serving import RequestScheduler
    m = tiny_model(held=1)
    eng = engine(m, init(m), ragged_tokens=128)
    picks, among = eng.model.experts
    assert _product_rows(eng.ragged_buf * picks, 1, among) == 128 < 256
    seen, real = [], eng.model.step

    def step(*a, **kw):
        out = real(*a, **kw)
        if not seen:
            out[-1]["moe_rows"] = np.asarray([[129], [128]], np.int32)
        seen.append(out[-1]["moe_rows"])
        return out

    object.__setattr__(eng.model, "step", step)
    sched = RequestScheduler(eng, max_queue=8)
    try:
        h = sched.submit(list(range(1, 24)), max_new_tokens=4, eos_id=None)
        assert len(list(h.result())) == 4
        snap = sched.registry.snapshot()
    finally:
        sched.shutdown(drain=False, timeout=30)
    assert len(seen) > 1 and all(np.asarray(r).shape == (2, 1) for r in seen)
    assert snap["pt_moe_share_spills"]["value"] == eng.moe_share_spills == 1


# ---------------------------------------------------------------------------
# Under jax.grad, in row blocks (the training step's form)
# ---------------------------------------------------------------------------
def _dense_loop(x, expert, weight, w, first=0):
    """`_loop` in jax.numpy, so that it can be differentiated: every held
    expert over every row, masked by the assignments it got."""
    wg, wu, wd = w
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(wg.shape[0]):
        g = jnp.sum(jnp.where(expert == e + first, weight, 0.0), -1)
        out += g[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return out


def _routers():
    x, expert, weight, w = _problem(3)
    return {
        "mixed": (expert, 0, None),
        # a share that holds experts 3..5 of 8
        "share": (expert, 3, 8),
        # every row's every assignment to ONE held expert
        "all_to_one_held": (jnp.full((T, K), 4), 3, 8),
        # no row to any held expert
        "none_held": (jnp.full((T, K), 1), 3, 8),
        "slack_rows": (expert.at[::3].set(-1), 0, None),
    }


@pytest.mark.parametrize("router", list(_routers()))
@pytest.mark.parametrize("row_block", [32, 8])
def test_gradients_through_row_blocks_match_a_dense_loop(router, row_block,
                                                         monkeypatch):
    """Output, and the gradient by x, by the assignments' weights and by
    each expert matrix, under any routing; 8 rows a block walks three
    blocks of the 24 assignments and splits an expert's run."""
    monkeypatch.setattr(moe, "ROW_BLOCK", row_block)
    x, _, weight, w = _problem(3)
    expert, first, num = _routers()[router]
    if num is not None:
        w = [a[first:first + 3] for a in w]
    probe = jax.random.normal(jax.random.key(9), (T, H))

    def got(x, weight, w):
        out, rows = dropless_experts_blocked(x, expert, weight, *w,
                                             first=first, num_experts=num)
        return jnp.sum(out * probe), rows

    def want(x, weight, w):
        return jnp.sum(_dense_loop(x, expert, weight, w, first) * probe)
    (val, rows), g = jax.value_and_grad(got, (0, 1, 2), has_aux=True)(
        x, weight, w)
    val_w, g_w = jax.value_and_grad(want, (0, 1, 2))(x, weight, w)
    np.testing.assert_allclose(val, val_w, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_w)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    held = (np.asarray(expert) >= first) & (np.asarray(expert) < first + len(w[0]))
    assert int(rows.sum()) == int(held.sum())
    if router == "none_held":
        assert float(val) == 0.0
        assert all(float(jnp.abs(a).max()) == 0.0
                   for a in jax.tree_util.tree_leaves(g))


def test_row_blocks_give_what_the_serving_form_gives(monkeypatch):
    monkeypatch.setattr(moe, "ROW_BLOCK", 8)
    x, expert, weight, w = _problem(5)
    a, ra = dropless_experts(x, expert, weight, *w)
    b, rb = dropless_experts_blocked(x, expert, weight, *w)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(ra, rb)


def test_what_the_chip_leaves_in_nobodys_rows_reaches_no_gradient(monkeypatch):
    """The TPU's grouped matmul leaves rows past the groups' sum as it
    found them (PERF.md, Findings PR 45: a first chip run read gradients
    20,000 times the reference's). Here every product's rows that nobody
    owns are poisoned with NaN: output and gradients stay those of the
    dense loop."""
    real = moe.grouped_product

    def poisoned(lhs, rhs, sizes):
        out = real(lhs, rhs, sizes)
        owned = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
        return jnp.where(owned[:, None], out, jnp.nan)
    monkeypatch.setattr(moe, "grouped_product", poisoned)
    monkeypatch.setattr(moe, "ROW_BLOCK", 8)
    x, expert, weight, w = _problem(3)
    w = [a[3:6] for a in w]
    probe = jax.random.normal(jax.random.key(9), (T, H))

    def got(x, weight, w):
        return jnp.sum(dropless_experts_blocked(
            x, expert, weight, *w, first=3, num_experts=8)[0] * probe)

    def want(x, weight, w):
        return jnp.sum(_dense_loop(x, expert, weight, w, 3) * probe)
    val, g = jax.value_and_grad(got, (0, 1, 2))(x, weight, w)
    val_w, g_w = jax.value_and_grad(want, (0, 1, 2))(x, weight, w)
    np.testing.assert_allclose(val, val_w, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_w)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
