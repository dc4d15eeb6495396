"""run.py driven end to end on the `glm_dsa` family's tiny manifest on the
CPU: sound, `correct` comes out true and the run prints counts and never a
rate; with one of the family's own faults planted in the PROGRAM, or with
the `fp8_index_keys` control in its place, `correct` comes out false.

The tiny configuration (4 heads over a latent of 16 + 4 in pages of 4, an
indexer of 2 heads that keeps 8 positions, 2 experts a token of 8 of which
this share holds experts 2 and 3, one dense and two expert layers) runs in
float32, so a sound run reads gaps of 0 or float32 rounding and every
fault below moves served logits by far more. Contexts run to 120 tokens,
fifteen times what the indexer keeps."""
import json
import os

import jax
import pytest

from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.glm_dsa.tiny.json")


def _run(capsys, control=None, seed=3_000_000_019):
    argv = ["--manifest", TINY, "--workload", "tiny_longctx", "--seed",
            str(seed), "--seconds", "1", "--trace", "0", "--rehearse-cpu"]
    if control:
        argv += ["--control", control]
    assert bench_run.main(argv) == 0
    out = capsys.readouterr().out
    return out, json.loads(out.strip().splitlines()[-1])


@pytest.fixture
def fresh_programs():
    """A planted fault changes what `glm_step` traces, not its arguments:
    drop every compiled program before and after."""
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
    lab = '{layer_type="latent"}'
    # the rows a launch booked went through the selection, a layer; each
    # scored its whole context and kept at most 8 positions of it
    assert c["pt_dsa_rows" + lab] == c["pt_ragged_tokens"]
    assert c["pt_dsa_context_tokens" + lab] == c["pt_ragged_attn_pairs"]
    assert c["pt_dsa_selected_tokens" + lab] <= 8 * c["pt_dsa_rows" + lab]
    assert c["pt_dsa_selected_tokens" + lab] < \
        c["pt_dsa_context_tokens" + lab] / 2
    assert c['pt_latent_pages_in_use{pool="latent"}'] \
        == c['pt_kv_pages_in_use{pool="latent"}']
    # every row makes 2 assignments in each of 2 sparse layers, to the two
    # held experts or elsewhere. Rows are booked when a step is launched
    # and its experts' rows when its record is read: the window's deltas
    # differ by the steps in flight at its two edges, at most 16 rows each
    held = c['pt_moe_rows{expert="0"}'] + c['pt_moe_rows{expert="1"}']
    assert held == c["pt_moe_assignments"] > 0
    assert abs(held + c["pt_moe_rows_elsewhere"]
               - 2 * 2 * c["pt_ragged_tokens"]) <= 2 * 2 * 2 * 16
    for word in ("tokens_per_s", "_ms", "setup_s", "lateness"):
        assert word not in out, word
    assert set(last["compared"]) >= {"compiles_in_window", "served_gap",
                                     "served_gap_sq_mean",
                                     "requests_failed_or_missing"}


def test_the_fp8_index_keys_control_is_not_correct(capsys):
    out, last = _run(capsys, control="fp8_index_keys")
    assert last["correct"] is False, out
    c = last["compared"]["served_gap_sq_mean"]
    assert c["value"] > c["limit"]


def _correction_bias_left_out(mp):
    """The experts chosen by score alone (`noaux_tc`'s bias forgotten)."""
    from paddle_tpu.models import glm_dsa
    real = glm_dsa.route
    mp.setattr(glm_dsa, "route", lambda x, router, bias, c, row_on: real(
        x, router, bias * 0, c, row_on))


def _indexer_keeps_half(mp):
    """The selection keeps `index_topk // 2` positions a row."""
    from paddle_tpu.models import glm_dsa
    real = glm_dsa.dsa_select
    mp.setattr(glm_dsa, "dsa_select", lambda scores, pos, k, **kw: real(
        scores, pos, k // 2, **kw))


def _absent_experts_computed_as_held(mp):
    """`first_expert` forgotten: the held weights stand in for experts 0
    and 1, which this share does not hold."""
    from paddle_tpu.models import glm_dsa
    real = glm_dsa.dropless_experts
    mp.setattr(glm_dsa, "dropless_experts", lambda *a, **kw: real(
        *a, **dict(kw, first=0)))


def _index_keys_not_rotated(mp):
    """The index key cached without its rotary part turned."""
    from paddle_tpu.models import glm_dsa
    real = glm_dsa._rotate_first
    mp.setattr(glm_dsa, "_rotate_first", lambda x, cos, sin, rot:
               x if x.ndim == 2 else real(x, cos, sin, rot))


FAULTS = [_correction_bias_left_out, _indexer_keeps_half,
          _absent_experts_computed_as_held, _index_keys_not_rotated]


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


def test_the_cells_file_is_the_source_with_five_counts_changed():
    """The driver's catalog check reads the TOP level of the file: every
    key of the source is there and equal to the published one but for the
    five reduced counts, `model` says the same, every width is as
    published, and the rule on widths finds nothing."""
    from benchmarks import widths
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "benchmarks", "configs",
                           "glm-5.serve1.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size",
                              "num_nextn_predict_layers"]
    for k, v in cfg["published"].items():
        assert cfg[k] == cfg["model"][k], k
        assert (cfg[k] == v) != (k in cfg["reduced"]), k
    held = {k: cfg["model"][k] for k in cfg["reduced"]}
    assert held == {"num_hidden_layers": 5, "first_k_dense_replace": 1,
                    "n_routed_experts": 16, "vocab_size": 19360,
                    "num_nextn_predict_layers": 0}
    assert cfg["model"]["router_experts"] == 256
    assert cfg["deployment"]["chips_per_layer"] == 16
    assert widths.faults(cfg) == []
    assert set(cfg["widths"]) >= {
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "qk_head_dim", "v_head_dim", "head_dim", "index_head_dim",
        "index_n_heads", "index_topk", "num_experts_per_tok"}
    narrow = dict(cfg, model=dict(cfg["model"], router_experts=16))
    assert any("router_experts" in r for r in widths.faults(narrow))
    for departure in ("no_hadamard_rotation", "bf16_index_keys",
                      "multi_token_head_not_served", "indexer_rotary_split"):
        assert departure in cfg["assumed"]
