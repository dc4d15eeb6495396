"""The functional training step of the DeepSeek-V3 family
(`models/deepseek_spmd.py`) against the benchmark's plain reference
(`benchmarks/reference/deepseek_v3.py`) at toy size on the CPU: the loss,
every leaf's gradient and three AdamW steps on seeded weights and packed
documents, for a whole layer's experts and for one chip's share; and the
shares of a layer adding up to the layer in training, outputs AND
gradients."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.models import deepseek_v3 as family            # noqa: E402
from benchmarks.reference import deepseek_v3 as ref            # noqa: E402
from paddle_tpu.models import deepseek_spmd as ds              # noqa: E402
from paddle_tpu.parallel.mesh import create_mesh               # noqa: E402

OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0}
B, S = 2, 64


def tiny(held=8, first=0):
    """One dense and two expert layers; keys 16 + 8, values 16; 3 of 8
    experts a token beside 2 shared ones."""
    return {"hidden_size": 32, "intermediate_size": 64,
            "moe_intermediate_size": 24, "num_hidden_layers": 3,
            "num_attention_heads": 2, "num_key_value_heads": 2,
            "kv_lora_rank": 16, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": held,
            "router_experts": 8, "first_expert": first, "n_shared_experts": 2,
            "num_experts_per_tok": 3, "first_k_dense_replace": 1,
            "vocab_size": 96, "rope_theta": 50000, "rms_norm_eps": 1e-5,
            "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "norm_topk_prob": True,
            "initializer_range": 0.05, "router_bias_range": 0.02,
            "max_position_embeddings": 64}


def batches(n, seed=0):
    """(ids, labels, doc_ids): packed documents, labels -1 at a document's
    last token, as `traffic_gen.packed_batches` makes them."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        toks = rng.integers(1, 96, (B, S + 1)).astype(np.int32)
        doc = np.zeros((B, S), np.int32)
        for b in range(B):
            for cut in sorted(rng.choice(np.arange(4, S - 4), 2, replace=False)):
                doc[b, cut:] += 1
        labels = toks[:, 1:].copy()
        last = np.ones((B, S), bool)
        last[:, :-1] = doc[:, 1:] != doc[:, :-1]
        labels[last] = -1
        out.append((toks[:, :-1], labels, doc))
    return out


def _f32(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), tree)


SHARES = {"whole_layer": (8, 0), "one_share": (2, 4)}


@pytest.mark.parametrize("share", list(SHARES))
def test_loss_and_every_leafs_gradient_match_the_reference(share):
    m = tiny(*SHARES[share])
    params = family.init_weights(m, 7, jnp.float32)
    batch = batches(1)[0]
    c = family.program_config(m)
    with jax.default_matmul_precision("highest"):
        (loss, rows), grads = jax.value_and_grad(ds.loss_fn, has_aux=True)(
            params, batch, c)
        want, want_g = ref.loss_and_grads(params, batch, m)
    assert abs(float(loss) - float(want)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), exp in zip(flat, jax.tree_util.tree_leaves(want_g)):
        scale = max(float(jnp.abs(exp).max()), 1e-6)
        assert float(jnp.abs(got - exp).max()) < 2e-4 * scale + 1e-7, path
    # the bias chooses and takes no gradient; the rows are the held
    # experts' own: 3 assignments a token over the layer's 8 experts
    assert not np.asarray(grads["moe"]["router_bias"]).any()
    assert rows.shape == (2, m["n_routed_experts"])
    if share == "whole_layer":
        assert (np.asarray(rows).sum(-1) == B * S * 3).all()
    else:
        assert (np.asarray(rows).sum(-1) < B * S * 3).all()


def _program_steps(m, dtype, n=3):
    c = family.program_config(m)
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    params = family.init_weights(m, 7, dtype)
    state = ds.init_opt_state(params)
    step = ds.make_train_step(c, mesh, lr=OPT["lr"], clip_norm=OPT["clip_norm"])
    out = {"losses": []}
    for i, batch in enumerate(batches(n)):
        params, state, loss = step(params, state, jnp.asarray(i), batch)
        out["losses"].append(float(loss))
        if i == 0:
            out["grad_norms"] = jax.tree_util.tree_map(
                lambda s: float(jnp.linalg.norm(s["m"].ravel())) / (1 - OPT["b1"]),
                state, is_leaf=lambda x: isinstance(x, dict) and "m" in x)
    p0 = family.init_weights(m, 7, dtype)
    out["delta_norms"] = jax.tree_util.tree_map(
        lambda s, q: float(jnp.linalg.norm(
            (s["master"] - q.astype(jnp.float32)).ravel())), state, p0,
        is_leaf=lambda x: isinstance(x, dict) and "master" in x)
    return out, step


def _worst(got, want):
    g = np.array(jax.tree_util.tree_leaves(got))
    r = np.array([float(x) for x in jax.tree_util.tree_leaves(want)])
    return float(np.max(np.abs(g - r) / np.maximum(r, np.median(r))))


@pytest.mark.parametrize("share,dtype,tol", [
    ("whole_layer", "float32", (2e-5, 2e-4, 2e-4)),
    ("one_share", "float32", (2e-5, 2e-4, 2e-4)),
    ("one_share", "bfloat16", (2e-2, 5e-2, 5e-2))])
def test_three_adamw_steps_follow_the_reference(share, dtype, tol):
    """float32 tight; bfloat16 parameters (float32 master and moments) to
    2e-2 on the loss and 5% on the worst leaf's gradient and change norms:
    a bfloat16 product carries 8 bits, and the toy's leaves are small."""
    m = tiny(*SHARES[share])
    with jax.default_matmul_precision("highest"):
        got, step = _program_steps(m, jnp.dtype(dtype))
        want = ref.adamw_steps(_f32(family.init_weights(m, 7, jnp.dtype(dtype))),
                               batches(3), m, OPT)
    assert max(abs(a - float(b)) for a, b in
               zip(got["losses"], want["losses"])) < tol[0]
    assert _worst(got["grad_norms"], want["grad_norms"]) < tol[1]
    assert _worst(got["delta_norms"], want["delta_norms"]) < tol[2]
    # the bias is handed back as it came, in program and reference alike
    assert got["delta_norms"]["moe"]["router_bias"] == 0.0
    assert float(want["delta_norms"]["moe"]["router_bias"]) == 0.0
    # the step's books: three steps, two expert layers
    c = {k: v["value"] for k, v in step.snapshot().items()}
    assert c["pt_train_steps"] == 3
    held = m["n_routed_experts"]
    assert 0 < c["pt_train_moe_experts_touched"] <= 3 * 2 * held
    assert c["pt_train_moe_assignments"] <= 3 * 2 * B * S * 3
    assert c["pt_train_moe_rows_max"] * held >= c["pt_train_moe_assignments"]
    if share == "whole_layer":
        assert c["pt_train_moe_assignments"] == 3 * 2 * B * S * 3


def test_the_shares_of_a_layer_add_up_in_training():
    """Eight chips hold one of the eight experts each and all route over
    the eight: their outputs, the shared experts counted once, sum to the
    uncut layer's, and so do the gradients by the layer's input, by the
    router (each share passes only what its own experts' weights carry)
    and, expert by expert, by the held matrices."""
    whole = tiny(8, 0)
    lp = jax.tree_util.tree_map(
        lambda a: a[1], family.init_weights(whole, 11, jnp.float32)["moe"])
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(B, S, 32)), jnp.float32)
    probe = jnp.asarray(rng.normal(size=(B, S, 32)), jnp.float32)
    routed = ("w_gate", "w_up", "w_down")

    def layer(m, lo, hi):
        c = family.program_config(m)

        def f(x, lp):
            own = dict(lp, **{k: lp[k][lo:hi] for k in routed})
            out, rows = ds.expert_ffn(own, x, c)
            return jnp.sum(out * probe), (out, rows)
        return f

    def shared(x, lp):
        out = ds._swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
        return jnp.sum(out * probe), out

    with jax.default_matmul_precision("highest"):
        (_, (want, want_rows)), want_g = jax.value_and_grad(
            layer(whole, 0, 8), (0, 1), has_aux=True)(x, lp)
        (_, alike), alike_g = jax.value_and_grad(shared, (0, 1),
                                                 has_aux=True)(x, lp)
        total, total_g, rows = 0.0, None, []
        for first in range(8):
            (_, (out, got)), g = jax.value_and_grad(
                layer(tiny(1, first), first, first + 1), (0, 1),
                has_aux=True)(x, lp)
            total = total + out
            total_g = g if total_g is None else \
                jax.tree_util.tree_map(jnp.add, total_g, g)
            rows.append(int(got[0]))
    once = lambda s, a: s - 7 * a       # what every chip computes alike
    np.testing.assert_array_equal(rows, np.asarray(want_rows))
    np.testing.assert_allclose(once(total, alike), want, atol=2e-5)
    np.testing.assert_allclose(once(total_g[0], alike_g[0]), want_g[0],
                               atol=2e-5)
    for k in want_g[1]:
        got = total_g[1][k]
        if k.startswith("s_"):
            got = once(got, alike_g[1][k])
        np.testing.assert_allclose(got, want_g[1][k], atol=2e-5, err_msg=k)
    assert float(jnp.abs(want_g[1]["router"]).max()) > 0


def test_a_mesh_of_more_than_one_chip_is_refused_not_imitated():
    if jax.device_count() < 2:
        pytest.skip("needs 2 virtual devices")
    mesh = create_mesh({"dp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="exchange"):
        ds.make_train_step(family.program_config(tiny()), mesh)


@pytest.mark.parametrize("other", [
    {"scoring_func": "softmax"}, {"topk_method": "greedy"},
    {"norm_topk_prob": False}, {"q_lora_rank": 8}])
def test_a_router_or_query_that_is_not_written_is_refused(other):
    """The step holds the published router alone (sigmoid scores, `noaux_tc`,
    normalised) and no compressed query: anything else raises where the
    parameters are laid out, and is not run as if it were the same."""
    import dataclasses
    c = dataclasses.replace(family.program_config(tiny()), **other)
    with pytest.raises(NotImplementedError, match="not written|written is"):
        ds.init_params(c)
