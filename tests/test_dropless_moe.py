"""The dropless expert layer (`parallel/moe.dropless_experts`) against a
per-row loop over experts: slack rows that route nowhere, an expert that
gets no row, every row to one expert, and the rows in any order."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel.moe import dropless_experts, grouped_product

T, H, F, E, K = 12, 16, 8, 8, 2


def _problem(seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (T, H))
    w = [jax.random.normal(k, s) * 0.3 for k, s in zip(
        ks[1:4], [(E, H, F), (E, H, F), (E, F, H)])]
    expert = jax.random.randint(ks[4], (T, K), 0, E - 1)   # never E - 1
    weight = jax.random.uniform(ks[5], (T, K))
    return x, expert, weight, w


def _loop(x, expert, weight, w):
    wg, wu, wd = (np.asarray(a, np.float64) for a in w)
    x = np.asarray(x, np.float64)
    out = np.zeros((T, H))
    rows = np.zeros((E,), np.int64)
    for t in range(T):
        for j in range(K):
            e = int(expert[t, j])
            if e < 0:
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
