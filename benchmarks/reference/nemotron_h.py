"""Plain reference of the Nemotron-H family (nvidia, `model_type:
nemotron_h`).

Straightforward `jax.numpy` in float32, one sequence at a time, no kernels,
no cache, no state kept between calls, no batching; it imports nothing of
the program. Callers run it under `jax.default_matmul_precision("highest")`.
`m` is the configuration's `model`: the published `config.json`'s own keys.

Layer l is the mixer `hybrid_override_pattern[l]` behind a pre-norm and a
residual, h <- h + mixer(RMSNorm(h)), eps `layer_norm_epsilon`, no bias but
the convolution's:

  M  [z | xBC | dt] = x W_in, widths d_inner | d_inner + 2 G N | heads, with
     d_inner = `mamba_num_heads` x `mamba_head_dim` (NOT `expand` x hidden),
     G = `n_groups`, N = `ssm_state_size`.
     xBC <- silu(b + sum_k w_k xBC[t - (K-1) + k]), K = `conv_kernel`,
     zeros before the sequence's first row (causal, depthwise).
     Split xBC into x (heads x head size), B, C (G x N each; a group
     serves heads / G heads). dt <- softplus(dt + dt_bias), no clamp;
     A = -exp(A_log), one scalar a head. State a head S (head size x N):
       S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  y_t = S_t C_t + D x_t,
     token by token from S = 0. y <- RMSNorm over each of the G groups of
     d_inner / G channels of (y * silu(z)), times w (gate first, then norm);
     out = y W_out. `chunk_size` is the published kernel's block and no
     part of the value.
  E  s = sigmoid(x W_r) over all `n_routed_experts`; T = the
     `num_experts_per_tok` largest of s + e_score_correction_bias
     (`n_group` 1, `topk_group` 1: no group limit); w_e = s_e / (sum of s
     over T) x `routed_scaling_factor`; out = sum over T of w_e
     relu(x U_e)^2 D_e + relu(x U_s)^2 D_s, the shared expert at
     `moe_shared_expert_intermediate_size`. Not gated. The tree keeps a
     routed expert's U_e as a checkpoint keeps a linear layer, (width,
     hidden), rows its outputs.
  *  q = x W_q (`num_attention_heads` x `head_dim`), k, v = x W_k, x W_v
     (`num_key_value_heads`), causal softmax attention at
     1 / sqrt(head_dim), grouped, no window and NO rotary or other
     position term; out = o W_o.
Final RMSNorm, untied head.

Assumed where the `config.json` does not settle it (the configuration file
lists each under `assumed`): no rotary in the attention layers
(`rope_theta` and `partial_rotary_factor` are not read); the state in
float32.

Departures that bound memory and change no number: weights may be stored in
bfloat16 and are widened a matrix at a time (a routed expert at a time, in a
loop over ALL the experts with each row's weight, zero where the expert was
not chosen); attention is computed in blocks of query rows; the embedding is
gathered before it is widened.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _mamba(lp, x, m):
    S = x.shape[0]
    heads, P = m["mamba_num_heads"], m["mamba_head_dim"]
    G, N, K = m["n_groups"], m["ssm_state_size"], m["conv_kernel"]
    di = heads * P
    zxd = x @ lp["w_in"].astype(F32)
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + di + 2 * G * N], zxd[:, -heads:]
    w = lp["conv_w"].astype(F32)
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(lp["conv_b"].astype(F32) + sum(
        w[k] * lax.dynamic_slice_in_dim(padded, k, S, 0) for k in range(K)))
    xs = xbc[:, :di].reshape(S, heads, P)
    B = jnp.repeat(xbc[:, di:di + G * N].reshape(S, G, N), heads // G, 1)
    C = jnp.repeat(xbc[:, di + G * N:].reshape(S, G, N), heads // G, 1)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))      # (S, heads)
    A = -jnp.exp(lp["A_log"].astype(F32))

    def token(state, row):                  # state (heads, P, N)
        x_t, b_t, c_t, dt_t = row
        state = jnp.exp(dt_t * A)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], -1)

    _, y = lax.scan(token, jnp.zeros((heads, P, N), F32), (xs, B, C, dt))
    y = (y + lp["D"].astype(F32)[None, :, None] * xs).reshape(S, di)
    y = (y * jax.nn.silu(z)).reshape(S, G, di // G)
    y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                      + m["layer_norm_epsilon"])
    return (y.reshape(S, di) * lp["norm_w"].astype(F32)) \
        @ lp["w_out"].astype(F32)


def _attention(lp, x, m, q_block):
    """Causal, grouped; blocks of query rows bound the scores."""
    S = x.shape[0]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    q = (x @ lp["wq"].astype(F32)).reshape(S, nh, hd)
    k = jnp.repeat((x @ lp["wk"].astype(F32)).reshape(S, nkv, hd),
                   nh // nkv, axis=1)
    v = jnp.repeat((x @ lp["wv"].astype(F32)).reshape(S, nkv, hd),
                   nh // nkv, axis=1)
    cols = jnp.arange(S)
    qb = min(q_block, S)
    if S % qb:
        raise ValueError(f"sequence {S} is not a multiple of q_block {qb}")

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = jnp.einsum("qhd,khd->hqk", qi, k) / jnp.sqrt(F32(hd))
        s = jnp.where((cols[None, :] <= rows[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = lax.map(block, jnp.arange(S // qb)).reshape(S, nh * hd)
    return o @ lp["wo"].astype(F32)


def _relu2(x, up, down):
    return jnp.square(jax.nn.relu(x @ up.astype(F32))) @ down.astype(F32)


def _experts(lp, x, m):
    """The routed experts' weighted sum (a loop over all the experts, each
    widened alone, each row weighted by w_e, zero where not chosen) and the
    shared expert."""
    E, k = m["n_routed_experts"], m["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ lp["router"].astype(F32))             # (S, E)
    _, idx = lax.top_k(s + lp["router_bias"].astype(F32), k)
    w = jnp.take_along_axis(s, idx, -1)
    if m.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    w = m["routed_scaling_factor"] * w
    rows = jnp.arange(x.shape[0])[:, None]
    weight = jnp.zeros_like(s).at[rows, idx].set(w)              # (S, E)

    def one(acc, e):        # w_up[e] (F, H): rows its outputs
        y = _relu2(x, lp["w_up"][e].T, lp["w_down"][e])
        return acc + weight[:, e][:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(E))
    return out + _relu2(x, lp["s_up"], lp["s_down"])


def hidden(params, ids, m, q_block=256):
    """One sequence: ids (S,) -> final-normed hidden states (S, H)."""
    eps = m["layer_norm_epsilon"]
    pattern = m["hybrid_override_pattern"][:m["num_hidden_layers"]]
    h = params["embed"][ids].astype(F32)
    for letter, lp in zip(pattern, params["layers"]):
        x = _rms(h, lp["ln"], eps)
        if letter == "M":
            h = h + _mamba(lp, x, m)
        elif letter == "*":
            h = h + _attention(lp, x, m, q_block)
        else:
            h = h + _experts(lp, x, m)
    return _rms(h, params["final_norm"], eps)


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
