"""Plain reference of the `glm_dsa` family (zai-org GLM-5, `model_type:
glm_moe_dsa`): latent attention under a learned sparse selection, experts
routed by sigmoid score plus a correction bias.

Straightforward `jax.numpy` in float32, one sequence at a time, no kernels,
no cache, no batching; it imports nothing of the program. Callers run it
under `jax.default_matmul_precision("highest")`. `m` is the configuration's
`model`: the published `config.json`'s own keys, and for one chip's share
of a deployment `router_experts` (the experts the router chooses among,
where `n_routed_experts` is how many are HELD) and `first_expert`.

Pre-norm residual layers, RMSNorm eps `rms_norm_eps`, x = RMSNorm(h):

  Latent attention. c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads x
    [q_nope `qk_nope_head_dim` ; q_rope `qk_rope_head_dim`], rotary
    (theta `rope_parameters.rope_theta`, interleaved pairs: dims 2i, 2i+1
    turn by the i-th angle) on q_rope. [c_kv `kv_lora_rank` ; k_rope] =
    x W_kva; c_kv = RMSNorm(c_kv); rotary on k_rope, one for all heads.
    k_nope[h] = c_kv W_kb^K[h], v[h] = c_kv W_kb^V[h] (W_kb's columns are
    a head's [K `qk_nope_head_dim` ; V `v_head_dim`]): UP-PROJECTED here,
    never absorbed. score_h(t, s) = (q_nope[h]_t . k_nope[h]_s +
    q_rope[h]_t . k_rope_s) / sqrt(qk_nope_head_dim + qk_rope_head_dim),
    softmax over s in S_t, o_h = sum p v[h]_s, out = concat_h(o_h) W_o.
  The indexer. q^I = c_q W_qI -> `index_n_heads` x `index_head_dim`; k^I =
    LayerNorm(x W_kI) (eps 1e-6, with a bias), one for all heads; rotary on
    the first `qk_rope_head_dim` dims of both; w = x W_w x
    index_n_heads^-1/2 x index_head_dim^-1/2. I(t, s) = sum_j w_tj
    ReLU(q^I_tj . k^I_s) for s <= t, as a dense (T, T) matrix in blocks of
    rows. S_t = the `index_topk` largest I(t, .), by a stable sort (ties to
    the lower position); every s <= t while t < index_topk.
  Experts (layers from `first_k_dense_replace` on; the leading ones are
    dense SwiGLU `intermediate_size`). s = sigmoid(x W_r) over all
    `router_experts`; chosen = the `num_experts_per_tok` largest of s + b;
    weights = `routed_scaling_factor` x s_e / (sum of s over the chosen);
    y = sum over the chosen AND HELD e of w_e SwiGLU_e(x) + SwiGLU_shared(x).
    Held: experts `first_expert .. first_expert + n_routed_experts`; what
    the absent ones would add is left out, as the program leaves it.
Final RMSNorm, untied head. The multi-token head is no part of this pass.

Departures that bound memory and change no number (a request runs to 45k
tokens beside 7.8 GB of weights): weights may be stored in bfloat16 and are
widened a matrix at a time (a held expert at a time); index scores, the
selection and attention are computed in blocks of query rows, attention a
few heads at a time and each group's output through its rows of W_o at
once; the feed-forward parts in blocks of rows; the selection is kept as a
(T, T) mask of BITS between the two.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HEADS_AT_ONCE = 8
ROWS_AT_ONCE = 2048


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _layer_norm(x, w, b, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def rope_table(m, pos):
    rot = m["qk_rope_head_dim"]
    theta = float(m["rope_parameters"]["rope_theta"])
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    return jnp.cos(ang), jnp.sin(ang)                   # (S, rot / 2)


def _rotary(x, table):
    """Interleaved pairs on the last dim of x (S, ..., rot)."""
    cos, sin = table
    pad = (slice(None),) + (None,) * (x.ndim - 2)
    cos, sin = cos[pad], sin[pad]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _rotary_first(x, table, rot):
    return jnp.concatenate([_rotary(x[..., :rot], table), x[..., rot:]], -1)


def _blocks(S, q_block):
    qb = min(q_block, S)
    if S % qb:
        raise ValueError(f"sequence {S} is not a multiple of q_block {qb}")
    return qb, S // qb


def selection(lp, x, cq, table, m, q_block):
    """-> (S, ceil(S / 8)) uint8: S_t in row t, eight positions a byte
    (`jnp.packbits`: a (T, T) mask of bytes would be 2 GB at 45k)."""
    S = x.shape[0]
    nh, d, rot = m["index_n_heads"], m["index_head_dim"], m["qk_rope_head_dim"]
    k = min(m["index_topk"], S)
    ki = _rotary_first(_layer_norm(x @ lp["ik"].astype(F32), lp["ik_norm_w"],
                                   lp["ik_norm_b"]), table, rot)
    w = (x @ lp["iw"].astype(F32)) * F32(nh ** -0.5 * d ** -0.5)
    wq = lp["iq"].astype(F32)
    cols = jnp.arange(S)
    qb, n = _blocks(S, q_block)

    def block(i):
        rows = i * qb + jnp.arange(qb)
        here = (lax.dynamic_slice_in_dim(table[0], i * qb, qb, 0),
                lax.dynamic_slice_in_dim(table[1], i * qb, qb, 0))
        q = _rotary_first((lax.dynamic_slice_in_dim(cq, i * qb, qb, 0)
                           @ wq).reshape(qb, nh, d), here, rot)
        wi = lax.dynamic_slice_in_dim(w, i * qb, qb, 0)
        score = jnp.einsum("qj,qjs->qs", wi, jax.nn.relu(
            jnp.einsum("qjd,sd->qjs", q, ki)))
        seen = cols[None, :] <= rows[:, None]
        score = jnp.where(seen, score, -jnp.inf)
        best = jnp.argsort(-score, axis=-1, stable=True)[:, :k]
        return jnp.packbits(jnp.zeros((qb, S), bool).at[
            jnp.arange(qb)[:, None], best].set(True) & seen, axis=-1)

    return lax.map(block, jnp.arange(n)).reshape(S, -1)


def attention(lp, x, cq, table, chosen, m, q_block):
    """-> (S, hidden): a few heads at a time, each group's output through
    its rows of W_o at once, so that neither K and V of all heads nor the
    concatenated outputs ever stand whole."""
    S = x.shape[0]
    nh, rank = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rot, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    kva = x @ lp["wkv_a"].astype(F32)
    c = _rms(kva[:, :rank], lp["kv_norm"], m["rms_norm_eps"])
    k_rope = _rotary(kva[:, rank:], table)                      # (S, rot)
    wq_b = lp["wq_b"].reshape(-1, nh, nope + rot)
    wkv_b = lp["wkv_b"].reshape(rank, nh, nope + vd)
    wo = lp["wo"].reshape(nh, vd, -1)
    qb, n = _blocks(S, q_block)
    g = min(HEADS_AT_ONCE, nh)

    def heads(out, h0):
        sl = lambda w: lax.dynamic_slice_in_dim(w, h0, g, 1).astype(F32)  # noqa: E731
        q = jnp.einsum("sc,chd->shd", cq, sl(wq_b))
        q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], table)], -1)
        kv = jnp.einsum("sc,chd->shd", c, sl(wkv_b))
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope[:, None, :], (S, g, rot))], -1)
        v = kv[..., nope:]

        def block(i):
            qi = lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
            ok = jnp.unpackbits(lax.dynamic_slice_in_dim(chosen, i * qb, qb, 0),
                                axis=-1, count=S).astype(bool)
            s = jnp.einsum("qhd,khd->hqk", qi, k) / jnp.sqrt(F32(nope + rot))
            s = jnp.where(ok[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

        o = lax.map(block, jnp.arange(n)).reshape(S, g * vd)
        w = lax.dynamic_slice_in_dim(wo, h0, g, 0).astype(F32)
        return out + o @ w.reshape(g * vd, -1), None

    out, _ = lax.scan(heads, jnp.zeros((S, wo.shape[-1]), F32),
                      jnp.arange(0, nh, g))
    return out


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1.astype(F32)) * (x @ w3.astype(F32))) @ w2.astype(F32)


def _by_rows(f, x, n_rows=ROWS_AT_ONCE):
    """f over blocks of x's rows: what f makes of a block never stands for
    the whole sequence."""
    S = x.shape[0]
    r = min(n_rows, S)
    if S % r:
        return f(x)
    return lax.map(f, x.reshape(S // r, r, -1)).reshape(S, -1)


def router_weights(lp, x, m):
    """-> (S, router_experts) f32: each row's weight on every expert, zero
    where the expert was not chosen."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ lp["router"].astype(F32))
    _, idx = lax.top_k(s + lp["router_bias"].astype(F32), k)
    w = jnp.take_along_axis(s, idx, -1)
    if m.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(m["routed_scaling_factor"] * w)


def routed_experts(lp, x, m):
    """The HELD routed experts' weighted sum: a loop over them, each widened
    alone, each row weighted by w_e (zero where not chosen)."""
    weight = router_weights(lp, x, m)
    first = m.get("first_expert", 0)

    def one(acc, e):
        we = lax.dynamic_index_in_dim(weight, first + e, 1, keepdims=True)
        y = _by_rows(lambda xb: _swiglu(xb, lp["w_gate"][e], lp["w_up"][e],
                                        lp["w_down"][e]), x)
        return acc + we * y, None

    out, _ = lax.scan(one, jnp.zeros_like(x),
                      jnp.arange(lp["w_gate"].shape[0]))
    return out


def _layer(lp, h, table, m, q_block):
    eps = m["rms_norm_eps"]
    x = _rms(h, lp["ln1"], eps)
    cq = _rms(x @ lp["wq_a"].astype(F32), lp["q_norm"], eps)
    chosen = selection(lp, x, cq, table, m, q_block)
    h = h + attention(lp, x, cq, table, chosen, m, q_block)
    x = _rms(h, lp["ln2"], eps)
    if "router" not in lp:
        return h + _by_rows(lambda xb: _swiglu(
            xb, lp["w_gate"], lp["w_up"], lp["w_down"]), x)
    return h + routed_experts(lp, x, m) + _by_rows(lambda xb: _swiglu(
        xb, lp["s_gate"], lp["s_up"], lp["s_down"]), x)


def hidden(params, ids, m, q_block=128):
    """One sequence: ids (S,) -> final-normed hidden states (S, H)."""
    table = rope_table(m, jnp.arange(ids.shape[0]))
    h = params["embed"][ids].astype(F32)
    for lp in params["layers"]:
        h = _layer(lp, h, table, m, q_block)
    return _rms(h, params["final_norm"], m["rms_norm_eps"])


def logits(params, ids, m, q_block=128):
    return hidden(params, ids, m, q_block) @ params["lm_head"].astype(F32)


def served_gaps(params, tokens, first, count, m, n_max=512):
    """One request: `tokens` (S,) is its prompt, its served tokens, padding.
    Served token j (j < count) sits at tokens[first + j] and was chosen from
    the logits at position first + j - 1. -> (gap (n_max,), top (n_max,)):
    how far that token's logit lies below the best logit there, and the
    token this computation puts first; entries j >= count are 0 / -1."""
    h = hidden(params, tokens, m)
    j = jnp.arange(n_max)
    at = jnp.clip(first + j - 1, 0, tokens.shape[0] - 1)
    lg = h[at] @ params["lm_head"].astype(F32)
    served = tokens[jnp.clip(first + j, 0, tokens.shape[0] - 1)]
    picked = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
    live = j < count
    return (jnp.where(live, lg.max(-1) - picked, 0.0),
            jnp.where(live, lg.argmax(-1), -1))
