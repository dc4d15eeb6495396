"""Plain reference of a dense decoder of the Llama / Mistral kind.

Straightforward `jax.numpy` in float32: RMSNorm, rotary embedding
(rotate-half, as the published implementations), grouped-query causal
attention by explicit softmax, SwiGLU feed-forward, untied head; for
training the masked mean next-token loss, its gradients, a global-norm clip
and AdamW. No kernels, no cache, no batching tricks. It imports nothing of
the program. Callers run it under `jax.default_matmul_precision("highest")`:
on a TPU a float32 matrix product is otherwise computed in bfloat16 passes.

Departures from the published description, both to bound memory and neither
changing a number: weights may be stored in bfloat16 and are widened layer by
layer; attention is computed in blocks of query rows.

`precision="bf16_master"` is the training control of the `correct`
comparison, the reference computed one step below what the configuration
states: AdamW keeps its master weights in bfloat16. (The serving control is
the program's own int8 KV pages, switched on by the configuration.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (S, heads, hd), pos (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _attention(q, k, v, doc, q_block):
    """q (S, nh, hd), k/v (S, nkv, hd), doc (S,) document ids or None.
    Causal, held inside documents; blocks of query rows bound the scores."""
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
        if doc is not None:
            di = lax.dynamic_slice_in_dim(doc, i * qb, qb, 0)
            ok = ok & (doc[None, :] == di[:, None])
        s = jnp.where(ok[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = lax.map(block, jnp.arange(S // qb))
    return out.reshape(S, nh, hd)


def _layer(lp, h, pos, doc, m, q_block):
    lp = jax.tree_util.tree_map(lambda w: w.astype(F32), lp)
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // nh
    S = h.shape[0]
    x = _rms(h, lp["ln1"], m["rms_norm_eps"])
    q = _rope((x @ lp["wq"]).reshape(S, nh, hd), pos, m["rope_theta"])
    k = _rope((x @ lp["wk"]).reshape(S, nkv, hd), pos, m["rope_theta"])
    v = (x @ lp["wv"]).reshape(S, nkv, hd)
    o = _attention(q, k, v, doc, q_block).reshape(S, nh * hd)
    h = h + o @ lp["wo"]
    x = _rms(h, lp["ln2"], m["rms_norm_eps"])
    up = jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])
    return h + up @ lp["w_down"]


def hidden(params, ids, m, doc=None, q_block=512, remat=False):
    """One sequence: ids (S,) -> final-normed hidden states (S, H)."""
    pos = jnp.arange(ids.shape[0])
    h = params["embed"].astype(F32)[ids]
    layer = lambda lp, hh: _layer(lp, hh, pos, doc, m, q_block)
    if remat:
        layer = jax.checkpoint(layer)
    h, _ = lax.scan(lambda hh, lp: (layer(lp, hh), None), h, params["layers"])
    return _rms(h, params["final_norm"].astype(F32), m["rms_norm_eps"])


def served_gaps(params, tokens, first, count, m, n_max=512):
    """One request: `tokens` (S,) is its prompt, its served tokens, padding.
    Served token j (j < count) sits at tokens[first + j] and was chosen from
    the logits at position first + j - 1. -> (gap (n_max,), top (n_max,)):
    how far that token's logit lies below the best logit there, and the
    token this computation puts first; entries j >= count are 0 / -1."""
    h = hidden(params, tokens, m)
    j = jnp.arange(n_max)
    at = jnp.clip(first + j - 1, 0, tokens.shape[0] - 1)
    logits = h[at] @ params["lm_head"].astype(F32)
    served = tokens[jnp.clip(first + j, 0, tokens.shape[0] - 1)]
    picked = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    live = j < count
    return (jnp.where(live, logits.max(-1) - picked, 0.0),
            jnp.where(live, logits.argmax(-1), -1))


# ------------------------------------------------------------------ training
def loss_sum(params, ids, labels, doc, m, q_block=512):
    """One sequence -> (summed next-token NLL over labels >= 0, their count)."""
    h = hidden(params, ids, m, doc=doc, q_block=q_block, remat=True)
    logp = jax.nn.log_softmax(h @ params["lm_head"].astype(F32), -1)
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], -1)[:, 0]
    valid = (labels >= 0).astype(F32)
    return -jnp.sum(picked * valid), jnp.sum(valid)


def loss_and_grads(params, batch, m):
    """Masked mean loss of a batch (ids, labels[, doc_ids]) and its gradients,
    one row at a time so that the scores fit."""
    ids, labels = batch[0], batch[1]
    doc = batch[2] if len(batch) > 2 else None
    n_valid = jnp.maximum(jnp.sum((labels >= 0).astype(F32)), 1.0)

    def row(acc, xs):
        i, l = xs[0], xs[1]
        d = xs[2] if doc is not None else None
        (s, _), g = jax.value_and_grad(
            lambda p: loss_sum(p, i, l, d, m), has_aux=True)(params)
        return (acc[0] + s, jax.tree_util.tree_map(jnp.add, acc[1], g)), None

    zero = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, F32), params)
    xs = (ids, labels) if doc is None else (ids, labels, doc)
    (s, g), _ = lax.scan(row, (F32(0.0), zero), xs)
    return s / n_valid, jax.tree_util.tree_map(lambda x: x / n_valid, g)


def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))), tree)


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

        def upd(p, a, b):
            mhat = a / (1 - opt["b1"] ** t)
            vhat = b / (1 - opt["b2"] ** t)
            new = p * (1 - opt["lr"] * opt["weight_decay"]) \
                - opt["lr"] * mhat / (jnp.sqrt(vhat) + opt["eps"])
            return keep(new)
        params = jax.tree_util.tree_map(upd, params, mom, var)
        losses.append(loss)
    delta = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, p0))
    return {"losses": jnp.stack(losses), "grad_norms": first,
            "delta_norms": delta}


# the axis along which each leaf is split when the reference itself has to
# be spread over several chips to fit (None: replicated)
SHARD_AXIS = {"embed": 0, "final_norm": None, "lm_head": 1,
              "layers": {"ln1": None, "wq": 2, "wk": 2, "wv": 2, "wo": 1,
                         "ln2": None, "w_gate": 2, "w_up": 2, "w_down": 1}}
