"""run.py driven end to end on the `longcat_flash` family's tiny manifest on
the CPU: sound, `correct` comes out true and the run prints counts and never
a rate; with one of the family's own faults planted in the PROGRAM, or with
the `fp8_latent_rows` control in its place, `correct` comes out false.

The tiny configuration (two double layers of 4 heads over a latent of 8 + 4
in pages of 4, dense feed-forwards of 64, a router over 8 real and 4
identity experts that keeps 3, of which this share holds real experts 2
and 3) runs in float32, so a sound run reads gaps of 0 or float32 rounding
and every fault below moves served logits by far more."""
import json
import os

import jax
import pytest

from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.longcat_flash.tiny.json")


def _run(capsys, control=None, seed=3_000_000_041):
    argv = ["--manifest", TINY, "--workload", "tiny_agent", "--seed",
            str(seed), "--seconds", "1", "--trace", "0", "--rehearse-cpu"]
    if control:
        argv += ["--control", control]
    assert bench_run.main(argv) == 0
    out = capsys.readouterr().out
    return out, json.loads(out.strip().splitlines()[-1])


@pytest.fixture
def fresh_programs():
    """A planted fault changes what `longcat_step` traces, not its
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
    lab = '{layer_type="latent"}'
    # a dense layer: every row attends its whole context, booked a layer
    # of the cache group (of which a model layer has two)
    assert c["pt_ragged_attn_pairs" + lab] == c["pt_ragged_attn_pairs"] > 0
    assert "pt_dsa_rows" + lab not in c
    # every row makes 3 assignments in each of 2 layers: to the two held
    # experts, to real experts elsewhere, or to identity experts. Rows are
    # booked when a step is launched and its experts' rows when its record
    # is read: the window's deltas differ by the steps in flight at its two
    # edges, at most 16 rows each
    held = c['pt_moe_rows{expert="0"}'] + c['pt_moe_rows{expert="1"}']
    assert held == c["pt_moe_assignments"] > 0
    total = held + c["pt_moe_rows_elsewhere"] + c["pt_moe_assignments_zero"]
    assert abs(total - 2 * 3 * c["pt_ragged_tokens"]) <= 2 * 2 * 3 * 16
    assert 0.2 < c["pt_moe_assignments_zero"] / total < 0.5   # 4 of 12
    for word in ("tokens_per_s", "_ms", "setup_s", "lateness"):
        assert word not in out, word
    assert set(last["compared"]) >= {"compiles_in_window", "served_gap",
                                     "served_gap_sq_mean",
                                     "requests_failed_or_missing"}


def test_the_fp8_latent_rows_control_is_not_correct(capsys):
    out, last = _run(capsys, control="fp8_latent_rows")
    assert last["correct"] is False, out
    c = last["compared"]["served_gap_sq_mean"]
    assert c["value"] > c["limit"]


def identity_experts_left_out(mp):
    """An assignment to an identity expert adds nothing (its weight lost):
    the step computes the real experts' part alone."""
    from paddle_tpu.models import longcat_flash as lc
    import jax.numpy as jnp
    real = lc.route

    def route(x, router, bias, c, row_on):
        expert, weight = real(x, router, bias, c, row_on)
        return expert, jnp.where(expert >= c.n_routed_experts, 0.0, weight)
    mp.setattr(lc, "route", route)


def kv_scale_left_out(mp):
    """`mla_scale_kv_lora` forgotten: the normed latent cached unscaled."""
    from paddle_tpu.models import longcat_flash as lc
    mp.setattr(lc.LongcatFlashConfig, "kv_scale", property(lambda self: 1.0))


def shortcut_joins_after_the_first_ffn(mp):
    """The experts' result added with the FIRST feed-forward's, one
    attention sublayer early, where the shortcut joins after the second."""
    from paddle_tpu.models import longcat_flash as lc
    import jax.numpy as jnp
    real_moe, real_ffn = lc._moe, lc._swiglu
    early = []

    def moe(*a, **kw):
        out, *counts = real_moe(*a, **kw)
        early.append(out)
        return (jnp.zeros_like(out), *counts)

    def swiglu(x, *w):
        y = real_ffn(x, *w)
        return y + early.pop() if early else y
    mp.setattr(lc, "_moe", moe)
    mp.setattr(lc, "_swiglu", swiglu)


FAULTS = [identity_experts_left_out, kv_scale_left_out,
          shortcut_joins_after_the_first_ffn]


@pytest.mark.parametrize("plant", FAULTS, ids=[f.__name__ for f in FAULTS])
def test_a_planted_fault_of_this_family_is_caught(capsys, monkeypatch,
                                                  fresh_programs, plant):
    plant(monkeypatch)
    out, last = _run(capsys)
    assert last["correct"] is False, out
    failed = [k for k, v in last["compared"].items()
              if v["limit"] is None or v["value"] > v["limit"]]
    assert set(failed) & {"served_gap", "served_gap_sq_mean"}, out


def test_the_cells_file_is_the_source_with_three_counts_changed():
    """The driver's catalog check reads the TOP level of the file: every
    key of the source is there and equal to the published one but for the
    three reduced counts, `model` says the same, every width is as
    published (`zero_expert_num` and `moe_topk` among them), and the rule
    on widths finds nothing."""
    from benchmarks import widths
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "benchmarks", "configs",
                           "longcat-flash-chat.serve1.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    for k, v in cfg["published"].items():
        assert cfg[k] == cfg["model"][k], k
        assert (cfg[k] == v) != (k in cfg["reduced"]), k
    held = {k: cfg["model"][k] for k in cfg["reduced"]}
    assert held == {"num_layers": 4, "n_routed_experts": 16,
                    "vocab_size": 16384}
    assert cfg["model"]["router_experts"] == 512
    assert cfg["deployment"]["chips_per_layer"] == 32
    assert widths.faults(cfg) == []
    assert set(cfg["widths"]) >= {
        "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "moe_topk", "zero_expert_num"}
    for change, word in ((dict(router_experts=16), "router_experts"),
                         (dict(zero_expert_num=8), "zero_expert_num"),
                         (dict(moe_topk=6), "moe_topk"),
                         (dict(ffn_hidden_size=1024), "ffn_hidden_size")):
        bad = dict(cfg, model=dict(cfg["model"], **change))
        assert any(word in r for r in widths.faults(bad)), change
    for departure in ("rotary_interleaved_pairs", "where_the_two_scales_act",
                      "no_norm_topk_prob_no_router_bias_term",
                      "router_bias_range", "latent_row_lanes"):
        assert departure in cfg["assumed"]
    d = cfg["deployment"]
    assert d["max_queue"] >= 768 and d["num_pages"] * d["page_size"] \
        >= d["max_seqs"] * 2048
