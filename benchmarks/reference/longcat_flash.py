"""Plain reference of the `longcat_flash` family (meituan-longcat
LongCat-Flash-Chat): shortcut-connected double layers of dense latent
attention, two dense feed-forwards and one expert layer whose router also
scores identity (zero-compute) experts.

Straightforward `jax.numpy` in float32, one sequence at a time, no kernels,
no cache, no batching; it imports nothing of the program. Callers run it
under `jax.default_matmul_precision("highest")`. `m` is the configuration's
`model`: the published `config.json`'s own keys, and for one chip's share
of a deployment `router_experts` (the REAL experts the router chooses
among, where `n_routed_experts` is how many are HELD) and `first_expert`.

RMSNorm eps `rms_norm_eps`, H = `hidden_size`. One layer, stream h, sublayers
s = 0, 1 with their own weights (`attn[s]`, `ffn[s]`):

  MLA_s(x). c_q = RMSNorm(x W_qa) x sqrt(H / q_lora_rank) (`mla_scale_q_lora`);
    q = c_q W_qb -> heads x [q_nope `qk_nope_head_dim` ; q_rope
    `qk_rope_head_dim`], rotary (theta `rope_theta`, interleaved pairs: dims
    2i, 2i+1 turn by the i-th angle) on q_rope. [c `kv_lora_rank` ; k_rope]
    = x W_kva; c = RMSNorm(c) x sqrt(H / kv_lora_rank) (`mla_scale_kv_lora`);
    rotary on k_rope, one for all heads. k_nope[h] = c W_kb^K[h], v[h] =
    c W_kb^V[h] (W_kb's columns are a head's [K `qk_nope_head_dim` ; V
    `v_head_dim`]): UP-PROJECTED here, never absorbed. score_h(t, s) =
    (q_nope[h]_t . k_nope[h]_s + q_rope[h]_t . k_rope_s) /
    sqrt(qk_nope_head_dim + qk_rope_head_dim), softmax over EVERY s <= t,
    o_h = sum p v[h]_s, out = concat_h(o_h) W_o.
  FFN_s(x) = (silu(x W_g) * (x W_u)) W_d, `ffn_hidden_size` wide.
  MoE(x). p = softmax(x W_r) over `router_experts + zero_expert_num`
    experts; chosen = the `moe_topk` largest of p + b; weights =
    `routed_scaling_factor` x p_e, NOT renormalised. y = sum over the chosen
    AND HELD real e of w_e SwiGLU_e(x) (`expert_ffn_hidden_size` wide)
    + (sum of w_e over the chosen identity experts, e >= router_experts) x.
    Held: experts `first_expert .. first_expert + n_routed_experts`; what
    the absent real ones would add is left out, as the program leaves it.

  h  = h + MLA_0(RMSNorm_a0(h))
  x1 = RMSNorm_p0(h);  m = MoE(x1);  h = h + FFN_0(x1)
  h  = h + MLA_1(RMSNorm_a1(h))
  h  = h + FFN_1(RMSNorm_p1(h)) + m          (the shortcut joins here)

Final RMSNorm, untied head.

Departures that bound memory and change no number (a request runs to 12k
tokens beside 10 GB of weights): weights may be stored in bfloat16 and are
widened a matrix at a time (a held expert at a time); attention is computed
in blocks of query rows, a few heads at a time and each group's output
through its rows of W_o at once; the feed-forward parts in blocks of rows.
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


def rope_table(m, pos):
    rot = m["qk_rope_head_dim"]
    inv = float(m["rope_theta"]) ** (-np.arange(0, rot, 2, dtype=np.float64)
                                     / rot)
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    return jnp.cos(ang), jnp.sin(ang)                   # (S, rot / 2)


def _rotary(x, table):
    """Interleaved pairs on the last dim of x (S, ..., rot)."""
    cos, sin = table
    pad = (slice(None),) + (None,) * (x.ndim - 2)
    cos, sin = cos[pad], sin[pad]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _blocks(S, q_block):
    qb = min(q_block, S)
    if S % qb:
        raise ValueError(f"sequence {S} is not a multiple of q_block {qb}")
    return qb, S // qb


def attention(lp, x, table, m, q_block):
    """-> (S, hidden): a few heads at a time, each group's output through
    its rows of W_o at once, so that neither K and V of all heads nor the
    concatenated outputs ever stand whole."""
    S, H = x.shape
    nh, rank, qr = m["num_attention_heads"], m["kv_lora_rank"], m["q_lora_rank"]
    nope, rot, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps = m["rms_norm_eps"]
    q_scale = (H / qr) ** 0.5 if m.get("mla_scale_q_lora", True) else 1.0
    kv_scale = (H / rank) ** 0.5 if m.get("mla_scale_kv_lora", True) else 1.0
    cq = _rms(x @ lp["wq_a"].astype(F32), lp["q_norm"], eps) * F32(q_scale)
    kva = x @ lp["wkv_a"].astype(F32)
    c = _rms(kva[:, :rank], lp["kv_norm"], eps) * F32(kv_scale)
    k_rope = _rotary(kva[:, rank:], table)                      # (S, rot)
    wq_b = lp["wq_b"].reshape(-1, nh, nope + rot)
    wkv_b = lp["wkv_b"].reshape(rank, nh, nope + vd)
    wo = lp["wo"].reshape(nh, vd, -1)
    qb, n = _blocks(S, q_block)
    g = min(HEADS_AT_ONCE, nh)
    cols = jnp.arange(S)

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
            ok = cols[None, :] <= (i * qb + jnp.arange(qb))[:, None]
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


def dense_ffn(lp, x):
    return _by_rows(lambda xb: _swiglu(xb, lp["w_gate"], lp["w_up"],
                                       lp["w_down"]), x)


def router_weights(lp, x, m):
    """-> (S, router_experts + zero_expert_num) f32: each row's weight on
    every expert, real and identity, zero where it was not chosen."""
    p = jax.nn.softmax(x @ lp["router"].astype(F32), -1)
    _, idx = lax.top_k(p + lp["router_bias"].astype(F32), m["moe_topk"])
    w = jnp.take_along_axis(p, idx, -1)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, idx].set(m["routed_scaling_factor"] * w)


def moe(lp, x, m):
    """The HELD real experts' weighted sum (a loop over them, each widened
    alone, each row weighted by w_e, zero where not chosen) and the
    identity experts' part: their weights' sum times the row."""
    weight = router_weights(lp, x, m)
    first, real = m.get("first_expert", 0), m["router_experts"]

    def one(acc, e):
        we = lax.dynamic_index_in_dim(weight, first + e, 1, keepdims=True)
        y = _by_rows(lambda xb: _swiglu(xb, lp["w_gate"][e], lp["w_up"][e],
                                        lp["w_down"][e]), x)
        return acc + we * y, None

    out, _ = lax.scan(one, jnp.zeros_like(x),
                      jnp.arange(lp["w_gate"].shape[0]))
    return out + jnp.sum(weight[:, real:], -1, keepdims=True) * x


def _layer(lp, h, table, m, q_block):
    eps = m["rms_norm_eps"]
    a0, a1 = lp["attn"]
    f0, f1 = lp["ffn"]
    h = h + attention(a0, _rms(h, a0["ln"], eps), table, m, q_block)
    x1 = _rms(h, f0["ln"], eps)
    shortcut = moe(lp, x1, m)
    h = h + dense_ffn(f0, x1)
    h = h + attention(a1, _rms(h, a1["ln"], eps), table, m, q_block)
    return h + dense_ffn(f1, _rms(h, f1["ln"], eps)) + shortcut


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
