"""run.py driven end to end at tiny size on the CPU: it prints counts and
never a time or a rate; with the timed path broken underneath, or with a
control in the program's place, `correct` comes out false."""
import json
import os

import jax
import numpy as np
import pytest

from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")


def _run(capsys, cell, seed=3_000_000_019, control=None, trace=0):
    argv = ["--manifest", TINY, "--workload", cell, "--seed", str(seed),
            "--seconds", "2", "--trace", str(trace), "--rehearse-cpu"]
    if control:
        argv += ["--control", control]
    assert bench_run.main(argv) == 0
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    compared = dict(line.split(" = ")[0].split(": ")[1:] +
                    [line.split(" limit ")[1].split()[-1]]
                    for line in out.splitlines() if line.startswith("compare: "))
    return out, last, compared


@pytest.mark.parametrize("cell", ["tiny_steady", "tiny_saturated",
                                  "tiny_steady_f32"])
def test_serving_rehearsal_prints_counts_and_no_rate(capsys, cell):
    out, last, compared = _run(capsys, cell)
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert "metrics" not in last and "device" not in last
    assert last["correct"] is True, out
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["counts"]["tokens_in_window"] > 0
    assert last["counts"]["counters"]["pt_serving_device_steps"] > 0
    for word in ("tokens_per_s", "_ms", "setup_s", "lateness"):
        assert word not in out, word
    assert "in_flight_at_open=" in out and "samples: ttft=" in out
    assert set(compared) >= {"compiles_in_window", "served_gap",
                             "served_gap_sq_mean",
                             "requests_failed_or_missing"}


def test_backlog_never_empties_at_tiny_size(capsys):
    out, last, _ = _run(capsys, "tiny_saturated")
    line = next(l for l in out.splitlines() if l.startswith("traffic:"))
    assert "kind=backlog" in line and "due_in_window=0" in line
    assert int(line.split("in_flight_at_close=")[1].split()[0]) > 0


def test_training_rehearsal_follows_the_reference(capsys):
    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices")
    out, last, compared = _run(capsys, "tiny_pretrain")
    assert last["correct"] is True, out
    assert last["counts"]["steps_in_window"] > 0
    assert "tokens_per_s" not in out.replace("tokens_per_step", "") \
        and "window of" not in out
    assert set(compared) >= {"loss_gap", "first_grad_norm_gap_worst_leaf",
                             "param_change_norm_gap_worst_leaf"}


def test_a_token_altered_where_it_is_produced_is_caught(capsys, monkeypatch):
    from paddle_tpu.models import llama_serving as ls
    real = ls.ServingEngine._fetch_results

    def altered(self, tree):
        out = list(real(self, tree))
        out[0] = (np.asarray(out[0]) + 1) % self.config.vocab_size
        return tuple(out)
    monkeypatch.setattr(ls.ServingEngine, "_fetch_results", altered)
    out, last, compared = _run(capsys, "tiny_steady")
    assert last["correct"] is False
    assert compared["served_gap"] == "FAILED", out


def test_a_step_that_returns_its_state_unchanged_is_caught(capsys, monkeypatch):
    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices")
    from paddle_tpu.models import llama_spmd

    def frozen(config, mesh, **kw):
        loss = jax.jit(lambda p, b: llama_spmd.loss_fn(p, b, config))
        return lambda params, state, step, batch: (params, state,
                                                   loss(params, batch))
    monkeypatch.setattr(llama_spmd, "make_train_step", frozen)
    out, last, compared = _run(capsys, "tiny_pretrain")
    assert last["correct"] is False
    assert compared["param_change_norm_gap_worst_leaf"] == "FAILED", out
    assert compared["first_grad_norm_gap_worst_leaf"] == "FAILED", out


def _faulty_train_step(monkeypatch, alter):
    """The program's own step, handed altered arguments."""
    from paddle_tpu.models import llama_spmd
    real = llama_spmd.make_train_step

    def make(config, mesh, **kw):
        step = real(config, mesh, **kw)
        return lambda p, s, i, b: step(*alter(p, s, i, b))
    monkeypatch.setattr(llama_spmd, "make_train_step", make)


def test_a_part_of_the_batch_left_out_is_caught(capsys, monkeypatch):
    """The second half of the rows counts for nothing: a part of the batch
    left out, and what one `dp` replica computes when the gradient exchange
    across `dp` is missing."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices")

    def alter(p, s, i, b):
        labels = jax.numpy.asarray(b[1])
        half = labels.at[labels.shape[0] // 2:].set(-1)
        return p, s, i, (b[0], half) + tuple(b[2:])
    _faulty_train_step(monkeypatch, alter)
    out, last, compared = _run(capsys, "tiny_pretrain")
    assert last["correct"] is False
    assert compared["loss_gap"] == "FAILED", out
    assert compared["first_grad_norm_gap_worst_leaf"] == "FAILED", out


def test_a_missing_exchange_between_tp_chips_is_caught(capsys, monkeypatch):
    """The row-parallel products (`wo`, `w_down`) sum over one `tp` chip's
    half of the contraction alone: the forward pass without its all-reduce."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices")

    def alter(p, s, i, b):
        layers = dict(p["layers"])
        for name in ("wo", "w_down"):
            w = layers[name]
            layers[name] = w.at[:, w.shape[1] // 2:, :].set(0)
        return dict(p, layers=layers), s, i, b
    _faulty_train_step(monkeypatch, alter)
    out, last, compared = _run(capsys, "tiny_pretrain")
    assert last["correct"] is False
    assert compared["loss_gap"] == "FAILED", out
    assert compared["first_grad_norm_gap_worst_leaf"] == "FAILED", out


@pytest.mark.parametrize("cell,control,number", [
    ("tiny_steady_f32", "int8_kv", "served_gap_sq_mean"),
    ("tiny_pretrain", "bf16_master", "param_change_norm_gap_worst_leaf")])
def test_the_control_comes_out_not_correct(capsys, cell, control, number):
    """One precision below what the configuration states, in the program's
    place: the engine's own int8 KV pages switched on, and the reference
    with bfloat16 master weights (on the chip at the cells' own size:
    PERF.md, Findings, PR 24)."""
    if cell == "tiny_pretrain" and jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices")
    out, last, compared = _run(capsys, cell, control=control)
    assert last["correct"] is False
    assert compared[number] == "FAILED", out
