"""run.py driven end to end on the `deepseek_v3` family's tiny manifest on
the CPU: sound, `correct` comes out true and the run prints counts and never
a rate; with one of the cell's own faults planted in the PROGRAM, or with the
`bf16_master` control in its place, `correct` comes out false.

The tiny configuration (2 heads, keys 16 + 8 and values 16 over a latent of
16, one dense and two expert layers, 3 of 8 experts a token of which this
share holds experts 4 and 5, 2 shared experts, 96 vocabulary rows) trains in
bfloat16 with float32 master weights on 2 x 64 packed tokens a step; its
limits are three times what six sound CPU seeds read."""
import json
import os

import jax
import pytest

from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.deepseek_v3.tiny.json")


def _run(capsys, control=None, seed=3_000_000_019):
    argv = ["--manifest", TINY, "--workload", "tiny_sparse_pretrain",
            "--seed", str(seed), "--seconds", "1", "--trace", "0",
            "--rehearse-cpu"]
    if control:
        argv += ["--control", control]
    assert bench_run.main(argv) == 0
    out = capsys.readouterr().out
    return out, json.loads(out.strip().splitlines()[-1])


def _failed(last):
    return {k for k, v in last["compared"].items() if v["value"] > v["limit"]}


@pytest.fixture
def fresh_programs():
    """A planted fault changes what the step traces, not its arguments:
    drop every compiled program before and after."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_rehearsal_is_correct_and_prints_counts_and_no_rate(capsys):
    out, last = _run(capsys)
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert "metrics" not in last and "device" not in last
    assert last["correct"] is True, out
    assert last["counts"]["steps_in_window"] > 0
    assert last["counts"]["tokens_per_step"] == 2 * 64
    assert "tokens_per_s" not in out.replace("tokens_per_step", "") \
        and "window of" not in out
    assert set(last["compared"]) >= {
        "loss_gap", "first_grad_norm_gap_worst_leaf",
        "param_change_norm_gap_worst_leaf", "compiles_in_window"}
    # the step's books, found where the reducers find them
    from benchmarks.models import deepseek_v3 as family
    from benchmarks.reducers import train_registry_ratio
    c = train_registry_ratio.counters({"config": {"family": "deepseek_v3"}})
    assert c["pt_train_steps"] == 3 + last["counts"]["steps_in_window"]
    assert 0 < c["pt_train_moe_assignments"] \
        < c["pt_train_steps"] * 2 * (2 * 64 * 3)
    assert family.TRAINERS


def test_the_bf16_master_control_is_not_correct(capsys):
    out, last = _run(capsys, control="bf16_master")
    assert last["correct"] is False, out
    assert _failed(last) == {"param_change_norm_gap_worst_leaf"}


def test_a_held_experts_weight_gradient_left_out_is_caught(
        capsys, monkeypatch, fresh_programs):
    """The first held expert's gate matrix takes no gradient: what a
    backward pass that skips an expert's weight product would leave."""
    from paddle_tpu.models import deepseek_spmd as ds
    real = ds.dropless_experts_blocked

    def faulty(x, expert, weight, w_gate, *rest, **kw):
        w_gate = w_gate.at[0].set(jax.lax.stop_gradient(w_gate[0]))
        return real(x, expert, weight, w_gate, *rest, **kw)
    monkeypatch.setattr(ds, "dropless_experts_blocked", faulty)
    out, last = _run(capsys)
    assert last["correct"] is False, out
    assert "first_grad_norm_gap_worst_leaf" in _failed(last)
    assert "param_change_norm_gap_worst_leaf" in _failed(last)


def test_chosen_scores_not_normalised_is_caught(capsys, monkeypatch,
                                               fresh_programs):
    """The experts' weights are 2.446 x the chosen scores, not divided by
    their sum."""
    from paddle_tpu.models import deepseek_spmd as ds
    real = ds.route

    def faulty(x, router, bias, c):
        idx, _ = real(x, router, bias, c)
        s = jax.nn.sigmoid(x.astype("float32") @ router.astype("float32"))
        return idx, c.routed_scaling_factor * jax.numpy.take_along_axis(
            s, idx, -1)
    monkeypatch.setattr(ds, "route", faulty)
    out, last = _run(capsys)
    assert last["correct"] is False, out
    assert {"loss_gap", "first_grad_norm_gap_worst_leaf"} & _failed(last)


def test_one_sequence_of_the_batch_left_out_is_caught(capsys, monkeypatch):
    """The last row of every batch counts for nothing."""
    from paddle_tpu.models import deepseek_spmd as ds
    real = ds.make_train_step

    def make(config, mesh, **kw):
        step = real(config, mesh, **kw)

        def altered(p, s, i, b):
            labels = jax.numpy.asarray(b[1]).at[-1].set(-1)
            return step(p, s, i, (b[0], labels) + tuple(b[2:]))
        altered.snapshot = step.snapshot
        return altered
    monkeypatch.setattr(ds, "make_train_step", make)
    out, last = _run(capsys)
    assert last["correct"] is False, out
    assert "first_grad_norm_gap_worst_leaf" in _failed(last)
