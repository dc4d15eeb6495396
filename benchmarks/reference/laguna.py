"""Plain reference of the Laguna family (poolside, `model_type: laguna`).

Straightforward `jax.numpy` in float32, one sequence at a time, no kernels,
no cache, no batching; it imports nothing of the program. Callers run it
under `jax.default_matmul_precision("highest")`. `m` is the configuration's
`model`: the published `config.json`'s own keys.

For layer l of type t(l) = `layer_types[l]`, with H_l =
`num_attention_heads_per_layer[l]` query heads over `num_key_value_heads` KV
heads of `head_dim`:

  x = RMSNorm(h); q = x Wq, k = x Wk, v = x Wv (no bias).
  Rotary by `rope_parameters[t]`, rotate-half on the first
    `partial_rotary_factor` x head_dim dims of each head, the rest pass:
    `default`: inverse frequencies theta^(-2i/rot);
    `yarn`: those blended with themselves over `factor` by the linear ramp
    between the dims that turn `beta_fast` times and `beta_slow` times over
    `original_max_position_embeddings` positions, cos and sin scaled by
    `attention_factor`; static, applied at every length.
  o = softmax(q k^T / sqrt(head_dim) + mask) v, grouped. full_attention:
    causal. sliding_attention: the row at position p sees j with
    0 <= p - j < `sliding_window`.
  `gating`: g = sigmoid(x Wg), one scalar a head; o_h <- g_h o_h.
  h <- h + concat(o) Wo; x = RMSNorm(h).
  `mlp_layer_types[l]` dense: h <- h + (silu(x W1) * (x W3)) W2.
  sparse: s = sigmoid(x Wr) over all the experts; T = the
    `num_experts_per_tok` largest; w_e = `moe_routed_scaling_factor` x s_e /
    (sum of s over T), on the expert's OUTPUT; h <- h + sum over T of
    w_e E_e(x) + S(x), E_e and the one shared S SwiGLU.
Final RMSNorm, untied head.

Assumed where the `config.json` does not settle it (the configuration file
lists both under `assumed`): the gate's sigmoid, and the router's sigmoid
score renormalised over the chosen with no correction bias.

Departures that bound memory and change no number: weights may be stored in
bfloat16 and are widened a matrix at a time (a routed expert at a time, in a
loop over ALL the experts with each row's weight, zero where the expert was
not chosen); attention is computed in blocks of query rows; the embedding is
gathered before it is widened.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
SLIDING = "sliding_attention"


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def rope_table(rp, head_dim, pos):
    """-> (cos, sin (S, 1, rot), rot) for one layer type's `rope_parameters`."""
    rot = int(head_dim * rp.get("partial_rotary_factor", 1))
    theta = float(rp["rope_theta"])
    i = np.arange(0, rot, 2, dtype=np.float64)
    inv = theta ** (-i / rot)
    scale = 1.0
    if rp.get("rope_type", "default") == "yarn":
        orig = rp["original_max_position_embeddings"]

        def dim_turning(n):
            return rot * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(theta))
        low = max(math.floor(dim_turning(rp["beta_fast"])), 0)
        high = min(math.ceil(dim_turning(rp["beta_slow"])), rot - 1)
        high = high + 0.001 if low == high else high
        ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0, 1)
        inv = inv / rp["factor"] * ramp + inv * (1 - ramp)
        scale = rp.get("attention_factor", 0.1 * math.log(rp["factor"]) + 1.0)
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale, rot


def _rotary(x, table):
    cos, sin, rot = table
    xr, rest = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
    return jnp.concatenate(
        [xr * cos + jnp.concatenate([-x2, x1], -1) * sin, rest], -1)


def _attention(q, k, v, window, q_block):
    """q (S, nh, hd), k/v (S, nkv, hd). Causal, and under a window only the
    last `window` columns; blocks of query rows bound the scores."""
    S, nh, hd = q.shape
    rep = nh // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    cols = jnp.arange(S)
    qb = min(q_block, S)
    if S % qb:
        raise ValueError(f"sequence {S} is not a multiple of q_block {qb}")

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = jnp.einsum("qhd,khd->hqk", qi, k) / jnp.sqrt(F32(hd))
        ok = cols[None, :] <= rows[:, None]
        if window is not None:
            ok = ok & (rows[:, None] - cols[None, :] < window)
        s = jnp.where(ok[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    return lax.map(block, jnp.arange(S // qb)).reshape(S, nh, hd)


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1.astype(F32)) * (x @ w3.astype(F32))) @ w2.astype(F32)


def _experts(lp, x, m):
    """The routed experts' weighted sum: a loop over all the experts, each
    widened alone, each row weighted by w_e (zero where not chosen)."""
    E, k = m["num_experts"], m["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ lp["router"].astype(F32))             # (S, E)
    top, idx = lax.top_k(s, k)
    w = m["moe_routed_scaling_factor"] * top / jnp.sum(top, -1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    weight = jnp.zeros_like(s).at[rows, idx].set(w)              # (S, E)

    def one(acc, e):
        y = _swiglu(x, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
        return acc + weight[:, e][:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(E))
    return out


def _layer(lp, h, li, tables, m, q_block):
    lt = m["layer_types"][li]
    nh, nkv, hd = (m["num_attention_heads_per_layer"][li],
                   m["num_key_value_heads"], m["head_dim"])
    S = h.shape[0]
    x = _rms(h, lp["ln1"], m["rms_norm_eps"])
    q = _rotary((x @ lp["wq"].astype(F32)).reshape(S, nh, hd), tables[lt])
    k = _rotary((x @ lp["wk"].astype(F32)).reshape(S, nkv, hd), tables[lt])
    v = (x @ lp["wv"].astype(F32)).reshape(S, nkv, hd)
    o = _attention(q, k, v, m["sliding_window"] if lt == SLIDING else None,
                   q_block)
    if m.get("gating"):
        o = o * jax.nn.sigmoid(x @ lp["wg"].astype(F32))[:, :, None]
    h = h + o.reshape(S, nh * hd) @ lp["wo"].astype(F32)
    x = _rms(h, lp["ln2"], m["rms_norm_eps"])
    if m["mlp_layer_types"][li] == "dense":
        return h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
    return h + _experts(lp, x, m) + \
        _swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])


def hidden(params, ids, m, q_block=256):
    """One sequence: ids (S,) -> final-normed hidden states (S, H)."""
    pos = jnp.arange(ids.shape[0])
    tables = {lt: rope_table(m["rope_parameters"][lt], m["head_dim"], pos)
              for lt in set(m["layer_types"])}
    h = params["embed"][ids].astype(F32)
    for li, lp in enumerate(params["layers"]):
        h = _layer(lp, h, li, tables, m, q_block)
    return _rms(h, params["final_norm"], m["rms_norm_eps"])


def logits(params, ids, m, q_block=256):
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

