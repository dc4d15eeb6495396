"""Laguna (poolside, `model_type: laguna`) on the serving path.

A decoder whose layers are not alike: full-attention layers (48 query
heads, rotary on half of each head, YaRN) beside sliding-window layers
(64 query heads, a window of 512, plain rotary), all over 8 KV heads
with a per-head sigmoid gate on the attention output; one leading dense
SwiGLU layer, then 256 routed experts (sigmoid scores, top 8,
renormalised, x 2.5 on the output) beside one shared expert.

`LagunaConfig.serving_model()` hands `ServingEngine` the cache spec
(one group of pools for the full layers, one for the windowed ones) and
`laguna_step`, which keeps `llama_serving.unified_step`'s descriptor
contract. Layers are unrolled: each has its own head count, rotary
table, mask and MLP, and each its own donated pool, written in place.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ragged_paged_attention import (ragged_paged_attention,
                                              ragged_runs)
from ..observability.compile_telemetry import track_jit
from ..parallel.moe import dropless_experts
from ..serving.model_spec import CacheGroup, ServingModel
from .llama_serving import _rms, _sample_flat, _scatter_kv

FULL, SLIDING = "full_attention", "sliding_attention"
# cache group names, by layer type (also the `pool=` / `layer_type=`
# label of the engine's counters)
GROUP_OF = {FULL: "full", SLIDING: "window"}


def _freeze(x):
    """Lists and dicts of a config.json -> hashable tuples."""
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    return x


@dataclasses.dataclass(frozen=True)  # hashable -> a static jit argument
class LagunaConfig:
    """The published `config.json`'s own keys (defaults: Laguna-XS.2)."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    gating: bool = True
    sliding_window: int = 512
    moe_routed_scaling_factor: float = 2.5
    rope_parameters: tuple = _freeze({
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}})
    layer_types: tuple = (FULL, SLIDING, SLIDING, SLIDING) * 10
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 39
    num_attention_heads_per_layer: tuple = (48, 64, 64, 64) * 10
    initializer_range: float = 0.02

    def __post_init__(self):
        for f in ("rope_parameters", "layer_types", "mlp_layer_types",
                  "num_attention_heads_per_layer"):
            object.__setattr__(self, f, _freeze(getattr(self, f)))
        # a cut in depth is nothing but a cut: the per-layer lists may
        # come whole, as published, and the model is their first
        # `num_hidden_layers` entries
        L = self.num_hidden_layers
        for f in ("layer_types", "mlp_layer_types",
                  "num_attention_heads_per_layer"):
            if len(getattr(self, f)) < L:
                raise ValueError(
                    f"LagunaConfig: {f} has {len(getattr(self, f))} "
                    f"entries for num_hidden_layers={L}")
            object.__setattr__(self, f, getattr(self, f)[:L])

    @classmethod
    def from_dict(cls, d):
        """A config.json's dictionary; keys the program has no use for
        (`model_type`, `attention_bias`: false, ...) are left out."""
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    def rope(self, layer_type):
        return dict(dict(self.rope_parameters)[layer_type])

    def serving_model(self):
        return _serving_model(self)


# -- rotary tables ----------------------------------------------------------
def rope_inv_freq(rp, head_dim):
    """-> (inverse frequencies (rot/2,) f64, rotary dims, cos/sin scale)
    of one layer type. `default`: theta^(-2i/rot). `yarn`: that blended
    with itself over `factor` by the linear ramp between the dims that
    turn `beta_fast` times and `beta_slow` times over the original
    context; cos and sin are scaled by `attention_factor`."""
    rot = int(head_dim * rp.get("partial_rotary_factor", 1))
    theta = float(rp["rope_theta"])
    freq = theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rp.get("rope_type", "default") == "default":
        return 1.0 / freq, rot, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"laguna: rope_type {rp['rope_type']!r}")
    factor, orig = float(rp["factor"]), rp["original_max_position_embeddings"]

    def turns_dim(n):   # the dim that turns n times over `orig` positions
        return rot * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(rp["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    inv = (1.0 / (factor * freq)) * ramp + (1.0 / freq) * (1 - ramp)
    scale = rp.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv, rot, float(scale)


def _rope_table(rp, head_dim, pos):
    inv, rot, scale = rope_inv_freq(rp, head_dim)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]      # (T, 1, rot)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale, rot


def _rotate(x, cos, sin, rot):
    """Rotate-half on the first `rot` dims of each head; the rest pass."""
    xf = x.astype(jnp.float32)
    xr, rest = xf[..., :rot], xf[..., rot:]
    x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
    xr = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([xr, rest], -1).astype(x.dtype)


# -- weights ------------------------------------------------------------------
def layer_shapes(c: LagunaConfig, li):
    H, hd = c.hidden_size, c.head_dim
    nh, kv = c.num_attention_heads_per_layer[li], c.num_key_value_heads
    s = {"ln1": (H,), "wq": (H, nh * hd), "wk": (H, kv * hd),
         "wv": (H, kv * hd), "wo": (nh * hd, H), "ln2": (H,)}
    if c.gating:
        s["wg"] = (H, nh)
    if c.mlp_layer_types[li] == "dense":
        F = c.intermediate_size
        s.update(w_gate=(H, F), w_up=(H, F), w_down=(F, H))
    else:
        E, I = c.num_experts, c.moe_intermediate_size
        S = c.shared_expert_intermediate_size
        s.update(router=(H, E), w_gate=(E, H, I), w_up=(E, H, I),
                 w_down=(E, I, H), s_gate=(H, S), s_up=(H, S), s_down=(S, H))
    return s


def param_shapes(c: LagunaConfig):
    H, V = c.hidden_size, c.vocab_size
    return {"embed": (V, H), "final_norm": (H,), "lm_head": (H, V),
            "layers": [layer_shapes(c, li)
                       for li in range(c.num_hidden_layers)]}


def init_params(c: LagunaConfig, seed=0, dtype=jnp.float32):
    """Seeded normal(0, initializer_range) weights, norms at 1."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = [jnp.ones(shape, dtype)
           if getattr(path[-1], "key", "") in ("ln1", "ln2", "final_norm")
           else (jax.random.normal(k, shape, jnp.float32)
                 * c.initializer_range).astype(dtype)
           for k, (path, shape) in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


# -- the step ---------------------------------------------------------------
def route(x, router, c: LagunaConfig, row_on):
    """Sigmoid scores over all the experts, the top k, their weights
    renormalised over the chosen and scaled. Slack rows route nowhere.
    `x` is the float32 normalised state and the product runs in full
    float32: which expert is eighth and which ninth hangs on the fourth
    digit of a score, and a row's whole expert changes with it.
    -> (expert (T, k) i32, weight (T, k) f32)."""
    s = jax.nn.sigmoid(jnp.dot(x, router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    top, idx = jax.lax.top_k(s, c.num_experts_per_tok)
    w = c.moe_routed_scaling_factor * top / jnp.sum(top, -1, keepdims=True)
    return jnp.where(row_on[:, None], idx.astype(jnp.int32), -1), w


def _swiglu(x, w_gate, w_up, w_down):
    """-> float32: what it adds to the residual stream is not rounded."""
    return jnp.dot(jax.nn.silu(x @ w_gate) * (x @ w_up), w_down,
                   preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("config", "page_size", "use_pallas",
                                    "interpret", "block_q", "block_pages"),
                   donate_argnames=("caches",))
def laguna_step(params, caches, tables, tokens, tok_slot, tok_pos,
                config: LagunaConfig, page_size, *, sample, need_rows,
                tok_buf, buf_write, use_pallas=False, interpret=False,
                block_q=None, block_pages=None):
    """`unified_step`'s contract (flat `tokens` / `tok_slot` / `tok_pos`
    rows, -1 an inactive row; `need_rows` the epilogue's rows; `tok_buf`
    / `buf_write` the device token ring, from which the rows' tokens
    are read (`tokens` is the contract's place for them and unused);
    `sample` the per-slot sampling arrays) over Laguna's layers,
    unrolled. `caches` / `tables`: one
    entry a cache group (`_serving_model`), a group's caches one
    `(k, v, k_scale, v_scale)` a layer with a leading 1; they are
    DONATED and come back in place. Returns `(caches, logits, rec,
    tok_buf, aux)`; `aux["moe_rows"]` is (sparse layers, experts) i32,
    the rows each expert got this step."""
    c = config
    kvh, hd = c.num_key_value_heads, c.head_dim
    t = tokens.shape[0]
    row_on = tok_pos >= 0
    pos = jnp.maximum(tok_pos, 0)
    tokens = tok_buf[tok_slot, pos]
    # the residual stream is float32 (T x H: nothing beside the weights);
    # the products take it in the weights' type and add to it unrounded
    wdt = params["embed"].dtype
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    names = [g.name for g in _groups(c)]
    per_type, runs = {}, {}
    for lt in dict.fromkeys(c.layer_types):
        gi = names.index(GROUP_OF[lt])
        table = tables[gi]
        trash = caches[gi][0][0].shape[2] - 1
        page_ids = jnp.where(row_on, table[tok_slot, pos // page_size], trash)
        per_type[lt] = (gi, table, page_ids,
                        _rope_table(c.rope(lt), hd, pos),
                        c.sliding_window if lt == SLIDING else None)
    off = pos % page_size
    for nh in dict.fromkeys(c.num_attention_heads_per_layer):
        runs[nh] = ragged_runs(tok_slot, tok_pos, nh // kvh, block_q)
    caches = [list(g) for g in caches]
    nth = [0] * len(caches)             # the next layer of each group
    moe_rows = []
    # tpulint: disable-next-line=TPL002 -- unrolled on purpose: the layers are not alike (heads, mask, rotary, MLP), and each donated pool is written in place
    for li, lp in enumerate(params["layers"]):
        lt, nh = c.layer_types[li], c.num_attention_heads_per_layer[li]
        gi, table, page_ids, (cos, sin, rot), window = per_type[lt]
        x = _rms(h, lp["ln1"], c.rms_norm_eps).astype(wdt)
        q = _rotate((x @ lp["wq"]).reshape(t, nh, hd), cos, sin, rot)
        k = _rotate((x @ lp["wk"]).reshape(t, kvh, hd), cos, sin, rot)
        v = (x @ lp["wv"]).reshape(t, kvh, hd)
        kp, vp, ksp, vsp = caches[gi][nth[gi]]
        kp, vp, ksp, vsp, kl, vl, ksl, vsl = _scatter_kv(
            kp, vp, ksp, vsp, 0, page_ids, off, k.swapaxes(0, 1),
            v.swapaxes(0, 1), ksp is not None, flat=True)
        caches[gi][nth[gi]] = (kp, vp, ksp, vsp)
        nth[gi] += 1
        o = ragged_paged_attention(
            q, kl, vl, table, tok_slot, tok_pos, use_pallas=use_pallas,
            interpret=interpret, k_scale=ksl, v_scale=vsl, block_q=block_q,
            block_pages=block_pages, runs=runs[nh], window=window)
        if c.gating:    # one scalar a head, on the attention's output
            gate = jax.nn.sigmoid(jnp.dot(
                x, lp["wg"], preferred_element_type=jnp.float32))
            o = o.astype(jnp.float32) * gate[..., None]
        h = h + jnp.dot(o.reshape(t, -1).astype(wdt), lp["wo"],
                        preferred_element_type=jnp.float32)
        xf = _rms(h, lp["ln2"], c.rms_norm_eps)
        x = xf.astype(wdt)
        if c.mlp_layer_types[li] == "dense":
            h = h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
            continue
        expert, weight = route(xf, lp["router"], c, row_on)
        routed, rows = dropless_experts(
            x, expert, weight, lp["w_gate"], lp["w_up"], lp["w_down"])
        moe_rows.append(rows)
        h = h + routed + _swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
    h = _rms(h, params["final_norm"], c.rms_norm_eps).astype(wdt)
    idx = jnp.maximum(need_rows, 0)         # the epilogue, as unified_step's
    h = h[idx]
    tok_slot = tok_slot[idx]
    tok_pos = tok_pos[idx]
    row_on = (need_rows >= 0) & (tok_pos >= 0)
    logits = jnp.dot(h, params["lm_head"],
                     preferred_element_type=jnp.float32)  # (N, V)
    rec = _sample_flat(logits, tok_slot, tok_pos, row_on, sample)
    B = tok_buf.shape[0]
    wslot = jnp.where(buf_write & row_on, tok_slot, B)
    tok_buf = tok_buf.at[wslot, jnp.maximum(tok_pos, 0) + 1].set(
        rec[0].astype(jnp.int32), mode="drop")
    aux = {"moe_rows": jnp.stack(moe_rows)} if moe_rows else {}
    return (tuple(tuple(g) for g in caches), logits, rec, tok_buf, aux)


laguna_step = track_jit("serving.laguna_step")(laguna_step)


def _groups(c: LagunaConfig):
    """The cache spec: a group a layer type present, in the order the
    types first appear; one pool array a layer."""
    out = []
    for lt in dict.fromkeys(c.layer_types):
        n = sum(1 for x in c.layer_types if x == lt)
        out.append(CacheGroup(
            GROUP_OF[lt], (1,) * n, c.num_key_value_heads, c.head_dim,
            window=c.sliding_window if lt == SLIDING else None))
    return tuple(out)


_NOT_YET = {
    "prefix_cache": "a page shared across requests would have to outlive "
                    "the window that releases it",
    "host_tier": "it spills the prefix cache's pages, which this model "
                 "cannot keep",
    "spec_decode": "the verify grid has no windowed form",
    "tensor_parallel": "the step is written for one chip",
    "bucketed": "it has no bucketed prefill or decode entry points, only "
                "the ragged step (ragged=True)",
    "handoff": "a handoff ships one pool's pages; this model has two",
}


def _serving_model(c: LagunaConfig):
    return ServingModel(
        groups=_groups(c),
        q_group=max(c.num_attention_heads_per_layer)
        // c.num_key_value_heads,
        step=laguna_step,
        experts=(c.num_experts_per_tok, c.num_experts),
        unsupported={k: f"LagunaConfig does not serve under {k}: {v}"
                     for k, v in _NOT_YET.items()})
