"""The traffic generator and the order statistics."""
import collections
import json
import os

import numpy as np
import pytest

from benchmarks import stats, traffic_gen

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _spec(name):
    with open(os.path.join(ROOT, "benchmarks", "traffic", name + ".json")) as f:
        return json.load(f)


def _multiset(offered, span):
    reqs = [o for o in offered if o.span == span]
    dues = sorted(o.due_s for o in reqs)
    return (sorted(len(o.prompt) for o in reqs), sorted(o.n_out for o in reqs),
            np.round(sorted(np.diff(dues)), 9).tolist())


def test_same_seed_same_requests():
    a = traffic_gen.serving_traffic(_spec("chat_open_0.8"), 2 ** 31 + 5, 51, 32768)
    b = traffic_gen.serving_traffic(_spec("chat_open_0.8"), 2 ** 31 + 5, 51, 32768)
    assert a[0] == b[0]
    assert [(o.due_s, o.prompt, o.n_out) for o in a[1]] == \
        [(o.due_s, o.prompt, o.n_out) for o in b[1]]


@pytest.mark.parametrize("span", ["ramp", "window"])
def test_two_seeds_same_lengths_and_times_other_contents(span):
    spec = _spec("chat_open_0.8")
    _, a = traffic_gen.serving_traffic(spec, 1, 51, 32768)
    _, b = traffic_gen.serving_traffic(spec, 2 ** 31 + 99, 51, 32768)
    assert _multiset(a, span) == _multiset(b, span)
    assert [(o.due_s, len(o.prompt), o.n_out) for o in a] == \
        [(o.due_s, len(o.prompt), o.n_out) for o in b]
    assert [o.prompt for o in a] != [o.prompt for o in b]
    n = round(spec["rate_rps"] * (51 if span == "window" else spec["ramp_s"]))
    assert sum(o.span == span for o in a) == n


@pytest.mark.parametrize("span", ["ramp", "window"])
def test_another_order_seed_same_multiset_other_order(span):
    spec = _spec("chat_open_0.8")
    _, a = traffic_gen.serving_traffic(spec, 1, 51, 32768)
    _, b = traffic_gen.serving_traffic(dict(spec, order_seed=5), 1, 51, 32768)
    assert _multiset(a, span) == _multiset(b, span)
    assert [len(o.prompt) for o in a] != [len(o.prompt) for o in b]


def test_window_requests_due_strictly_inside_and_fixed_beforehand():
    spec = _spec("chat_open_0.8")
    ramp, offered = traffic_gen.serving_traffic(spec, 7, 51, 32768)
    assert ramp == spec["ramp_s"]
    for o in offered:
        lo, hi = (0, ramp) if o.span == "ramp" else (ramp, ramp + 51)
        assert lo < o.due_s < hi
    # due times are data made before the run: nothing of the system enters
    assert [o.due_s for o in offered] == sorted(o.due_s for o in offered)


def test_short_run_ramps_for_its_own_length():
    ramp, offered = traffic_gen.serving_traffic(_spec("chat_open_0.8"), 7, 5, 32768)
    assert ramp == 5
    assert sum(o.span == "window" for o in offered) == round(0.18 * 5)


def test_lengths_are_the_clipped_lognormal():
    spec = _spec("chat_open_0.8")
    p = traffic_gen.length_quantiles(2000, spec["prompt"])
    assert p.min() >= 32 and p.max() <= 3072
    assert abs(np.median(p) - 384) <= 2
    o = traffic_gen.length_quantiles(2000, spec["output"])
    assert o.min() >= 16 and o.max() <= 512 and abs(np.median(o) - 128) <= 1
    g = traffic_gen.gap_quantiles(9, 51.0)
    assert abs(g.sum() - 51.0) < 1e-9


def test_backlog_is_due_at_once_and_blocks_are_stratified():
    spec = _spec("chat_backlog")
    _, a = traffic_gen.serving_traffic(spec, 3, 51, 32768)
    _, b = traffic_gen.serving_traffic(dict(spec, order_seed=9), 3, 51, 32768)
    assert len(a) == spec["backlog_requests"] and {o.due_s for o in a} == {0.0}
    assert collections.Counter(len(o.prompt) for o in a) == \
        collections.Counter(len(o.prompt) for o in b)
    # any block of `block` consecutive requests holds one length of each
    # stratum: its mean stays near the whole backlog's
    lens = np.array([len(o.prompt) for o in a], float)
    blocks = lens.reshape(-1, spec["block"]).mean(1)
    assert blocks.std() < 0.5 * lens.std()
    # the backlog outlasts ramp + window at the engine's best: 32 slots,
    # the rest queued, far more rows than 81 s of steps can serve
    rows = sum(len(o.prompt) + o.n_out for o in a)
    assert rows > 3 * 81 * 32 / 0.15


def test_packed_batches_hold_documents_and_mask_their_ends():
    spec = _spec("packed_4k")
    spec = dict(spec, batch=2, seq_len=256,
                documents=dict(spec["documents"], median=40, max=256))
    (ids, labels, doc), = traffic_gen.packed_batches(spec, 2 ** 31 + 1, 1, 1000)
    assert ids.shape == labels.shape == doc.shape == (2, 256)
    assert (np.diff(doc, axis=1) >= 0).all() and doc.max() >= 1
    ends = np.concatenate([doc[:, 1:] != doc[:, :-1], np.ones((2, 1), bool)], 1)
    assert (labels[ends] == -1).all() and (labels[~ends] >= 0).all()
    again = traffic_gen.packed_batches(spec, 2 ** 31 + 1, 1, 1000)[0]
    assert all((x == y).all() for x, y in zip((ids, labels, doc), again))


def test_harrell_davis_weights():
    w = stats.harrell_davis_weights(9)
    assert abs(w.sum() - 1) < 1e-12
    assert np.allclose(w, [0.001, 0.029, 0.114, 0.221, 0.269,
                           0.221, 0.114, 0.029, 0.001], atol=6e-4)
    x = np.random.default_rng(0).lognormal(8, 0.3, 1000)
    assert abs(stats.harrell_davis(x) / np.median(x) - 1) < 0.005


def test_harrell_davis_moves_less_than_the_sample_median():
    """Nine TTFTs, each shifted by a uniform phase of one step: over six
    runs the estimate ranges over less than the sample median does."""
    rng = np.random.default_rng(1)
    base = np.linspace(2000, 9000, 9)
    hd, med = [], []
    for _ in range(400):
        runs = [base + rng.uniform(0, 203, 9) for _ in range(6)]
        hd.append(np.ptp([stats.harrell_davis(r) for r in runs]))
        med.append(np.ptp([np.median(r) for r in runs]))
    assert np.mean(hd) < 0.75 * np.mean(med)


def test_quartile_spread_is_the_contracts():
    assert stats.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)
