"""Plain reference of the `deepseek_v3` family in training (Moonlight-16B-A3B,
`modeling_deepseek.py` of the source): latent attention up-projected to a
head's keys and values, experts routed by sigmoid score plus a correction
bias beside shared experts, the masked mean next-token loss, its gradients,
a global-norm clip and AdamW.

Straightforward `jax.numpy` in float32, one sequence at a time, no kernels,
no sorting, no grouped products; it imports nothing of the program. Callers
run it under `jax.default_matmul_precision("highest")`. `m` is the
configuration's `model`: the published `config.json`'s own keys, and for one
chip's share of a deployment `router_experts` (the experts the router
chooses among, where `n_routed_experts` is how many are HELD) and
`first_expert`. The tree is the one `benchmarks/models/deepseek_v3.shapes`
lays out: `dense` and `moe` stacks over their layers.

Pre-norm residual layers, RMSNorm eps `rms_norm_eps`, x = RMSNorm(h):

  Attention. q = x W_q -> heads x [q_nope `qk_nope_head_dim` ; q_rope
    `qk_rope_head_dim`]; [c `kv_lora_rank` ; k_rope] = x W_dkv; a head's
    [k_nope ; v `v_head_dim`] = RMSNorm(c) W_ukv; rotary (theta
    `rope_theta`, interleaved pairs: dims 2i, 2i + 1 turn by the i-th
    angle; the published code de-interleaves q and k alike first, which
    leaves every product as it is) on q_rope and on k_rope, one for all
    heads. score(t, s) = (q_nope_t . k_nope_s + q_rope_t . k_rope_s) /
    sqrt(qk_nope_head_dim + qk_rope_head_dim) for s <= t in t's document,
    softmax in float32, o = sum p v, out = concat_heads(o) W_o.
  Feed-forward. The `first_k_dense_replace` leading layers: SwiGLU of
    `intermediate_size`. The others: s = sigmoid(x W_r) over all
    `router_experts`; chosen = the `num_experts_per_tok` largest of s + b
    (`noaux_tc`, `n_group` 1); g_e = `routed_scaling_factor` x s_e / (sum
    of s over the chosen + 1e-20); y = sum over the chosen AND HELD e of g_e
    SwiGLU_e(x) + SwiGLU_shared(x), the shared one `n_shared_experts` x
    `moe_intermediate_size` wide. Held: experts `first_expert ..
    first_expert + n_routed_experts`; what the absent ones would add is left
    out, as the program leaves it. Each held expert runs over EVERY row and
    is weighted by the gate it got there (0 where it was not chosen).
  Loss: mean over labels >= 0 of the next-token cross-entropy over the
    vocabulary held. AdamW on every leaf but the bias `b`, which takes no
    gradient and is left as it came (the program does the same).

Departures that bound memory and change no number (three steps at 4 x 8,192
tokens beside the float32 parameters, moments and gradient of the share):
weights may be stored in bfloat16 and are widened where they are used; a
batch is walked one sequence at a time INSIDE each layer (layers outermost),
so the gradient of a layer is summed over the sequences while that layer is
differentiated and no second copy of the tree exists; every (layer,
sequence), every block of query rows, every held expert and every block of
rows of the head is rematerialised in the backward pass.

`precision="bf16_master"` is the control of the `correct` comparison, the
reference one step below what the configuration states: AdamW keeps its
master weights in bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
Q_BLOCK = 512           # query rows whose scores exist at once
HEAD_ROWS = 2048        # rows whose logits exist at once
FROZEN = ("router_bias",)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _rotary(x, pos, theta):
    """Interleaved pairs on the last dim of x (S, ..., rot)."""
    rot = x.shape[-1]
    inv = float(theta) ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (rot // 2,))
    pairs = x.reshape(x.shape[:-1] + (rot // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)], -1).reshape(x.shape)


def _attention(q, k, v, doc, scale):
    """q, k (S, nh, d), v (S, nh, dv), doc (S,) document ids or None.
    Causal, held inside documents, in blocks of query rows."""
    S, nh, _ = q.shape
    qb = min(Q_BLOCK, S)
    if S % qb:
        raise ValueError(f"sequence {S} is not a multiple of {qb} query rows")
    cols = jnp.arange(S)

    @jax.checkpoint
    def block(i):
        rows = i * qb + jnp.arange(qb)
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        ok = cols[None, :] <= rows[:, None]
        if doc is not None:
            di = lax.dynamic_slice_in_dim(doc, i * qb, qb, 0)
            ok = ok & (doc[None, :] == di[:, None])
        s = jnp.where(ok[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = lax.map(block, jnp.arange(S // qb))
    return out.reshape(S, nh, v.shape[-1])


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.astype(F32)) * (x @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def gates(x, router, bias, m):
    """x (S, H) -> (S, `router_experts`): the weight every expert of the
    layer gets at every row, 0 where it was not chosen."""
    s = jax.nn.sigmoid(x @ router.astype(F32))
    _, idx = lax.top_k(s + bias.astype(F32), m["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    if m.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = m["routed_scaling_factor"] * w
    return jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=F32)
                   * w[..., None], -2)


def expert_layer(lp, x, m):
    """x (S, H) -> (S, H): the held experts' weighted sum and the shared
    expert."""
    g = gates(x, lp["router"], lp["router_bias"], m)
    first = m.get("first_expert", 0)
    held = lax.dynamic_slice_in_dim(g, first, lp["w_gate"].shape[0], 1)

    @jax.checkpoint
    def one(y, e):
        ge, wg, wu, wd = e
        return y + ge[:, None] * _swiglu(x, wg, wu, wd), None
    y, _ = lax.scan(one, _swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"]),
                    (held.T, lp["w_gate"], lp["w_up"], lp["w_down"]))
    return y


def layer(lp, h, pos, doc, m):
    """One layer over one sequence: h (S, H) float32 -> (S, H)."""
    nh = m["num_attention_heads"]
    nope, rot, vd, rank = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                           m["v_head_dim"], m["kv_lora_rank"])
    S, eps, theta = h.shape[0], m["rms_norm_eps"], m["rope_theta"]
    x = _rms(h, lp["ln1"], eps)
    q = (x @ lp["wq"].astype(F32)).reshape(S, nh, nope + rot)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], pos, theta)], -1)
    down = x @ lp["wkv_a"].astype(F32)
    c = _rms(down[:, :rank], lp["kv_norm"], eps)
    k_rope = _rotary(down[:, rank:], pos, theta)
    up = (c @ lp["wkv_b"].astype(F32)).reshape(S, nh, nope + vd)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(k_rope[:, None], (S, nh, rot))], -1)
    o = _attention(q, k, up[..., nope:], doc, float((nope + rot) ** -0.5))
    h = h + o.reshape(S, nh * vd) @ lp["wo"].astype(F32)
    x = _rms(h, lp["ln2"], eps)
    if "router" in lp:
        return h + expert_layer(lp, x, m)
    return h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def hidden(params, ids, m, doc=None):
    """ids (B, S), doc (B, S) or None -> final-normed hidden states (B, S,
    H). Layers outermost, the sequences one after another inside each."""
    B, S = ids.shape
    pos = jnp.arange(S)
    h = params["embed"].astype(F32)[ids]
    docs = doc if doc is not None else jnp.zeros((B, S), jnp.int32)

    @jax.checkpoint
    def one(lp, h1, d1):
        return layer(lp, h1, pos, d1 if doc is not None else None, m)

    @jax.checkpoint
    def all_sequences(lp, hs):
        return lax.map(lambda xs: one(lp, *xs), (hs, docs))

    for stack in ("dense", "moe"):
        if stack in params:
            h, _ = lax.scan(lambda hs, lp: (all_sequences(lp, hs), None), h,
                            params[stack])
    return _rms(h, params["final_norm"], m["rms_norm_eps"])


def nll_sum(h, labels, lm_head):
    """One sequence: h (S, H), labels (S,) -> summed next-token NLL over
    labels >= 0, the logits a block of rows at a time."""
    S = h.shape[0]
    rb = min(HEAD_ROWS, S)
    if S % rb:
        raise ValueError(f"sequence {S} is not a multiple of {rb} rows")

    @jax.checkpoint
    def block(acc, xs):
        hb, lb = xs
        logp = jax.nn.log_softmax(hb @ lm_head.astype(F32), -1)
        picked = jnp.take_along_axis(logp, jnp.maximum(lb, 0)[:, None], -1)[:, 0]
        return acc - jnp.sum(picked * (lb >= 0).astype(F32)), None
    acc, _ = lax.scan(block, F32(0.0), (h.reshape(S // rb, rb, -1),
                                        labels.reshape(S // rb, rb)))
    return acc


def batch_loss(params, batch, m):
    """Masked mean loss of a batch (ids, labels[, doc_ids])."""
    ids, labels = batch[0], batch[1]
    doc = batch[2] if len(batch) > 2 else None
    h = hidden(params, ids, m, doc)
    sums = lax.map(lambda xs: nll_sum(xs[0], xs[1], params["lm_head"]),
                   (h, labels))
    return jnp.sum(sums) / jnp.maximum(jnp.sum((labels >= 0).astype(F32)), 1.0)


def loss_and_grads(params, batch, m):
    return jax.value_and_grad(batch_loss)(params, batch, m)


def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))), tree)


def _frozen(path):
    return any(getattr(k, "key", None) in FROZEN for k in path)


def adamw_steps(params, batches, m, opt, precision=None):
    """Follow the first len(batches) optimizer steps from float32 `params`.
    `opt`: lr, b1, b2, eps, weight_decay, clip_norm. -> dict with the loss of
    each step, the per-leaf norm of the first gradient as the optimizer gets
    it (after the clip), and the per-leaf norm of the parameters' change."""
    # lax.reduce_precision, not astype: the TPU compiler may drop a
    # float32 -> bfloat16 -> float32 round trip as excess precision
    keep = (lambda x: lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)) \
        if precision == "bf16_master" else (lambda x: x)
    p0 = params
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    var = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for t, batch in enumerate(batches, start=1):
        loss, g = loss_and_grads(params, batch, m)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree_util.tree_leaves(g)))
        scale = opt["clip_norm"] / jnp.maximum(gn, opt["clip_norm"])
        g = jax.tree_util.tree_map(lambda x: x * scale, g)
        if first is None:
            first = leaf_norms(g)
        mom = jax.tree_util.tree_map(
            lambda a, x: opt["b1"] * a + (1 - opt["b1"]) * x, mom, g)
        var = jax.tree_util.tree_map(
            lambda a, x: opt["b2"] * a + (1 - opt["b2"]) * x * x, var, g)

        def upd(path, p, a, b):
            if _frozen(path):
                return p
            mhat = a / (1 - opt["b1"] ** t)
            vhat = b / (1 - opt["b2"] ** t)
            new = p * (1 - opt["lr"] * opt["weight_decay"]) \
                - opt["lr"] * mhat / (jnp.sqrt(vhat) + opt["eps"])
            return keep(new)
        params = jax.tree_util.tree_map_with_path(upd, params, mom, var)
        losses.append(loss)
    delta = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, p0))
    return {"losses": jnp.stack(losses), "grad_norms": first,
            "delta_norms": delta}


# the axis along which each leaf is split when the reference itself has to
# be spread over several chips to fit (None: replicated)
_ATTN = {"ln1": None, "wq": 2, "wkv_a": None, "kv_norm": None, "wkv_b": 2,
         "wo": 1, "ln2": None}
SHARD_AXIS = {
    "embed": 0, "final_norm": None, "lm_head": 1,
    "dense": dict(_ATTN, w_gate=2, w_up=2, w_down=1),
    "moe": dict(_ATTN, router=None, router_bias=None, w_gate=3, w_up=3,
                w_down=2, s_gate=2, s_up=2, s_down=1)}
