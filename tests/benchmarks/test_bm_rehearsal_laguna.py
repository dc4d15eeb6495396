"""run.py driven end to end on the `laguna` family's tiny manifest on the
CPU: sound, `correct` comes out true and the run prints counts and never a
rate; with one of the family's own faults planted in the PROGRAM, or with
the `int8_kv` control in its place, `correct` comes out false.

The tiny configuration (a window of 8 over pages of 4, query groups of 6
and 8 over 2 KV heads, 2 experts a token of 8 beside a shared one, one
dense and four sparse layers) runs in float32, so a sound run reads gaps of
0 or float32 rounding and every fault below moves served logits by far
more. Outputs run to 80 tokens, ten windows deep."""
import json
import os

import jax
import pytest

from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.laguna.tiny.json")


def _run(capsys, control=None, seed=3_000_000_019):
    argv = ["--manifest", TINY, "--workload", "tiny_reason", "--seed",
            str(seed), "--seconds", "1", "--trace", "0", "--rehearse-cpu"]
    if control:
        argv += ["--control", control]
    assert bench_run.main(argv) == 0
    out = capsys.readouterr().out
    return out, json.loads(out.strip().splitlines()[-1])


@pytest.fixture
def fresh_programs():
    """A planted fault changes what `laguna_step` traces, not its
    arguments: drop every compiled program before and after."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_rehearsal_is_correct_and_prints_counts_and_no_rate(capsys):
    out, last = _run(capsys)
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert "metrics" not in last and "device" not in last
    assert last["correct"] is True, out
    assert last["failed"] == 0 and last["attempted"] > 0
    c = last["counts"]["counters"]
    steps = c["pt_serving_device_steps"]
    assert steps > 0 and c["pt_serving_preemptions"] == 0
    # every row makes 2 assignments in each of 4 sparse layers. Rows are
    # booked when a step is launched and its experts' rows when its record
    # is read: the window's deltas differ by the rows of the steps in
    # flight at its two edges, each at most the flat buffer's 16
    assert abs(c["pt_moe_assignments"] - 2 * 4 * c["pt_ragged_tokens"]) \
        <= 2 * 4 * 2 * 16
    assert 0 < c["pt_moe_experts_touched"] <= 8 * 4 * (steps + 1)
    assert c['pt_kv_pages_released{pool="window"}'] > 0
    assert c['pt_kv_pages_released{pool="full"}'] == 0
    assert 0 < c['pt_ragged_kv_tokens{layer_type="window"}'] \
        < c['pt_ragged_kv_tokens{layer_type="full"}'] == c["pt_ragged_kv_tokens"]
    for word in ("tokens_per_s", "_ms", "setup_s", "lateness"):
        assert word not in out, word
    assert set(last["compared"]) >= {"compiles_in_window", "served_gap",
                                     "served_gap_sq_mean",
                                     "requests_failed_or_missing"}


def test_the_int8_kv_control_is_not_correct(capsys):
    out, last = _run(capsys, control="int8_kv")
    assert last["correct"] is False, out
    c = last["compared"]["served_gap_sq_mean"]
    assert c["value"] > c["limit"]


def _window_one_page_short(mp):
    """The kernel's mask and walk one page (4 columns) short of the model's
    window; the engine's release rule keeps the model's."""
    from paddle_tpu.models import laguna
    real = laguna.ragged_paged_attention
    mp.setattr(laguna, "ragged_paged_attention", lambda *a, **kw: real(
        *a, **dict(kw, window=kw["window"] and kw["window"] - 4)))


def _page_released_one_step_early(mp):
    """The engine gives a page back while the next row still sees its last
    column: its table entry then points at the trash page."""
    from paddle_tpu.models.llama_serving import ServingEngine
    real = ServingEngine._window_release

    def early(self):
        self.lengths += 1
        try:
            real(self)
        finally:
            self.lengths -= 1
    mp.setattr(ServingEngine, "_window_release", early)


def _shared_expert_left_out(mp):
    from paddle_tpu.models import laguna
    real = laguna._swiglu
    mp.setattr(laguna, "_swiglu", lambda x, gate, up, down:
               real(x, gate, up, down) * (gate.shape[-1] != 16))


def _top_weights_not_scaled(mp):
    """The chosen experts' weights renormalised but not scaled by 2.5."""
    from paddle_tpu.models import laguna
    real = laguna.route

    def unscaled(x, router, c, row_on):
        expert, w = real(x, router, c, row_on)
        return expert, w / c.moe_routed_scaling_factor
    mp.setattr(laguna, "route", unscaled)


def _full_layers_rotated_on_every_dim(mp):
    """partial_rotary_factor 0.5 ignored in the full-attention layers."""
    from paddle_tpu.models import laguna
    real = laguna.rope_inv_freq
    mp.setattr(laguna, "rope_inv_freq", lambda rp, hd: real(
        dict(rp, partial_rotary_factor=1), hd))


FAULTS = [_window_one_page_short, _page_released_one_step_early,
          _shared_expert_left_out, _top_weights_not_scaled,
          _full_layers_rotated_on_every_dim]


@pytest.mark.parametrize("plant", FAULTS,
                         ids=[f.__name__.strip("_") for f in FAULTS])
def test_a_planted_fault_of_this_family_is_caught(capsys, monkeypatch,
                                                  fresh_programs, plant):
    plant(monkeypatch)
    out, last = _run(capsys)
    assert last["correct"] is False, out
    failed = [k for k, v in last["compared"].items()
              if v["limit"] is None or v["value"] > v["limit"]]
    assert set(failed) & {"served_gap", "served_gap_sq_mean"}, out


def test_the_cells_file_is_the_source_with_one_number_changed():
    """The driver's catalog check reads the TOP level of the file: every
    key of the source is there and equal to the published one but for
    `num_hidden_layers`, `model` says the same, and the rule on widths
    finds nothing."""
    from benchmarks import widths
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "benchmarks", "configs",
                           "laguna-xs.2.serve1.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["num_hidden_layers"]
    for k, v in cfg["published"].items():
        assert cfg[k] == cfg["model"][k], k
        assert (cfg[k] == v) != (k in cfg["reduced"]), k
    assert cfg["model"]["num_hidden_layers"] == 5
    assert widths.faults(cfg) == []
    cut = dict(cfg, model=dict(cfg["model"], layer_types=["full_attention"] * 5))
    assert any("layer_types" in r for r in widths.faults(cut))
