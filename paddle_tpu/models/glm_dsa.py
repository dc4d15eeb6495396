"""GLM-5 (`model_type: glm_moe_dsa`) on the serving path, as one chip's
share of a deployment.

A decoder of latent attention with a learned sparse selection: a layer
keeps, a token, ONE latent row shared by all its heads (`kv_lora_rank`
values that are keys and values at once, then `qk_rope_head_dim` rotary
values of the key) and an index key (`index_head_dim`) beside it. A row of
the step scores its whole cached context with the indexer's weighted ReLU
heads, keeps its `index_topk` best positions, and attends over those with
every head, the keys' up-projection absorbed into the query and the
values' applied after (`kernels/ragged_latent.py`). `first_k_dense_replace`
leading layers are dense SwiGLU; the others route every row over all
`n_routed_experts` (sigmoid scores, the top `num_experts_per_tok` of score
plus correction bias, weights from the scores alone) beside a shared
expert. This chip HOLDS `experts_held` of the routed experts, from
`first_expert`: it routes over all of them and computes its own
(`parallel/moe.dropless_experts`); what the absent ones would add is the
other chips' to add. The multi-token head is not served.

`GlmDsaConfig.serving_model()` hands `ServingEngine` one cache group of
two planes (the latent row, the index key) and `glm_step`, which keeps
`llama_serving.unified_step`'s descriptor contract. Layers are unrolled,
each with its own donated pools, written in place.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ragged_latent import (ATTN_ROWS, INDEX_ROWS, dsa_select,
                                     ragged_index_scores,
                                     ragged_sparse_latent_attention)
from ..kernels.ragged_paged_attention import ragged_runs
from ..observability.compile_telemetry import track_jit
from ..parallel.moe import dropless_experts
from ..serving.model_spec import CacheGroup, Plane, ServingModel
from .llama_serving import _put_rows, _rms, _sample_flat

GROUP = "latent"        # the cache group (`pool=` / `layer_type=` label)
# rows a step holds: where prompts run to tens of thousands of tokens the
# slots starve on a buffer sized for chat. Settled by a sweep on the chip
# under the benchmark's long-context backlog (PERF.md, PR 39)
ROWS_A_STEP = 512


@dataclasses.dataclass(frozen=True)  # hashable -> a static jit argument
class GlmDsaConfig:
    """The published `config.json`'s own keys (defaults: GLM-5), and what
    a share of a deployment adds: `experts_held` (None: all) from
    `first_expert`. `index_key_dtype`: what the index keys are cached
    in where that is not the cache's own type; the published code keeps
    them in `float8_e4m3fn`."""
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_head_dim: int = 128
    index_n_heads: int = 32
    index_topk: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    initializer_range: float = 0.02
    experts_held: int | None = None
    first_expert: int = 0
    index_key_dtype: str | None = None

    def __post_init__(self):
        held = self.n_routed_experts if self.experts_held is None \
            else self.experts_held
        object.__setattr__(self, "experts_held", held)
        if not 0 <= self.first_expert <= self.n_routed_experts - held:
            raise ValueError(
                f"GlmDsaConfig: experts [{self.first_expert}, "
                f"{self.first_expert + held}) of {self.n_routed_experts}")

    @classmethod
    def from_dict(cls, d):
        """A config.json's dictionary; `rope_parameters.rope_theta` is
        read where the file nests it, and keys the program has no use for
        (`model_type`, `n_group`: 1, ...) are left out."""
        fields = {f.name for f in dataclasses.fields(cls)}
        d = dict(d, **{k: v for k, v in d.get("rope_parameters", {}).items()
                       if k == "rope_theta"})
        return cls(**{k: v for k, v in d.items() if k in fields})

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    def serving_model(self):
        return _serving_model(self)


# -- weights ------------------------------------------------------------------
NORMS = ("ln1", "ln2", "final_norm", "q_norm", "kv_norm", "ik_norm_w")
ZEROS = ("ik_norm_b",)


def layer_shapes(c: GlmDsaConfig, li):
    H, nh, qr = c.hidden_size, c.num_attention_heads, c.q_lora_rank
    ih, idim = c.index_n_heads, c.index_head_dim
    s = {"ln1": (H,), "wq_a": (H, qr), "q_norm": (qr,),
         "wq_b": (qr, nh * c.qk_head_dim), "wkv_a": (H, c.latent_width),
         "kv_norm": (c.kv_lora_rank,),
         "wkv_b": (c.kv_lora_rank, nh * (c.qk_nope_head_dim + c.v_head_dim)),
         "wo": (nh * c.v_head_dim, H),
         "iq": (qr, ih * idim), "ik": (H, idim), "ik_norm_w": (idim,),
         "ik_norm_b": (idim,), "iw": (H, ih), "ln2": (H,)}
    if li < c.first_k_dense_replace:
        F = c.intermediate_size
        s.update(w_gate=(H, F), w_up=(H, F), w_down=(F, H))
    else:
        E, I = c.experts_held, c.moe_intermediate_size
        S = I * c.n_shared_experts
        s.update(router=(H, c.n_routed_experts),
                 router_bias=(c.n_routed_experts,),
                 w_gate=(E, H, I), w_up=(E, H, I), w_down=(E, I, H),
                 s_gate=(H, S), s_up=(H, S), s_down=(S, H))
    return s


def param_shapes(c: GlmDsaConfig):
    H, V = c.hidden_size, c.vocab_size
    return {"embed": (V, H), "final_norm": (H,), "lm_head": (H, V),
            "layers": [layer_shapes(c, li)
                       for li in range(c.num_hidden_layers)]}


def init_params(c: GlmDsaConfig, seed=0, dtype=jnp.float32):
    """Seeded normal(0, initializer_range) weights, norms at 1, the
    LayerNorm's bias at 0; the router's correction bias float32."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))

    def make(k, path, shape):
        name = getattr(path[-1], "key", "")
        if name in NORMS:
            return jnp.ones(shape, dtype)
        if name in ZEROS:
            return jnp.zeros(shape, dtype)
        x = jax.random.normal(k, shape, jnp.float32) * c.initializer_range
        return x if name == "router_bias" else x.astype(dtype)
    return jax.tree_util.tree_unflatten(
        treedef, [make(k, p, s) for k, (p, s) in zip(keys, leaves)])


# -- the step ---------------------------------------------------------------
def _rope_table(c: GlmDsaConfig, pos):
    rot = c.qk_rope_head_dim
    inv = 1.0 / c.rope_theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    return jnp.cos(ang), jnp.sin(ang)               # (T, rot / 2)


def _rotate(x, cos, sin):
    """Interleaved pairs (`rope_interleave`): dims (2i, 2i + 1) turn by
    the i-th angle. x (T, ..., rot) float32."""
    shape = x.shape
    x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
    extra = (None,) * (x.ndim - 3)
    cos, sin = cos[(slice(None),) + extra], sin[(slice(None),) + extra]
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(shape)


def _rotate_first(x, cos, sin, rot):
    return jnp.concatenate([_rotate(x[..., :rot], cos, sin), x[..., rot:]], -1)


def _layer_norm(x, w, b, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _dot(x, w):
    """-> float32: what feeds a norm, a cache row or the residual stream
    is not rounded on the way."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def route(x, router, bias, c: GlmDsaConfig, row_on):
    """Sigmoid scores over ALL the experts in full float32 (which expert
    is eighth hangs on a score's fourth digit); the top k of score plus
    correction bias (`noaux_tc`, one group); weights from the scores
    without the bias, renormalised over the chosen and scaled. Slack rows
    route nowhere. -> (expert (T, k) i32, weight (T, k) f32)."""
    s = jax.nn.sigmoid(jnp.dot(x, router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32),
                           c.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, -1)
    if c.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    return jnp.where(row_on[:, None], idx.astype(jnp.int32), -1), \
        c.routed_scaling_factor * w


def _swiglu(x, w_gate, w_up, w_down):
    return _dot(jax.nn.silu(x @ w_gate) * (x @ w_up), w_down)


def _put(pool, rows, new):
    """`new` (T, width) into rows `rows` of a plane's pool (1, 1, pages,
    page, row) seen flat, where it lies, in the pool's type; lanes past
    `width` stay zero."""
    new = jnp.pad(new, ((0, 0), (0, pool.shape[-1] - new.shape[-1])))
    return _put_rows(pool, rows, new.astype(pool.dtype))


@functools.partial(jax.jit,
                   static_argnames=("config", "page_size", "use_pallas",
                                    "interpret", "block_q", "block_pages"),
                   donate_argnames=("caches",))
def glm_step(params, caches, tables, tokens, tok_slot, tok_pos,
             config: GlmDsaConfig, page_size, *, sample, need_rows, tok_buf,
             buf_write, use_pallas=False, interpret=False, block_q=None,
             block_pages=None):
    """`unified_step`'s contract (flat `tok_slot` / `tok_pos` rows, -1 an
    inactive row; `need_rows` the epilogue's rows; the rows' tokens read
    from the device token ring `tok_buf`; `sample` the per-slot sampling
    arrays) over GLM-5's layers, unrolled. `caches`: the one group's
    stacks, a layer each `(latent, index key, None, None)` with a leading
    1; DONATED, they come back in place. `block_q` / `block_pages` are
    the K/V kernel's tile and unused: the latent kernels derive theirs.
    Returns `(caches, logits, rec, tok_buf, aux)`; `aux["moe_rows"]` is
    (sparse layers, experts held) i32, the rows each held expert got,
    and `aux["moe_elsewhere"]` (sparse layers,) the assignments that went
    to experts this chip does not hold."""
    c = config
    nh, rank, rope = c.num_attention_heads, c.kv_lora_rank, c.qk_rope_head_dim
    nope, vd = c.qk_nope_head_dim, c.v_head_dim
    ih, idim = c.index_n_heads, c.index_head_dim
    t = tok_slot.shape[0]
    row_on = tok_pos >= 0
    pos = jnp.maximum(tok_pos, 0)
    tokens = tok_buf[tok_slot, pos]
    wdt = params["embed"].dtype
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    (table,), (stacks,) = tables, caches
    n_pages = stacks[0][0].shape[2]
    rows = jnp.where(row_on, table[tok_slot, pos // page_size],
                     n_pages - 1) * page_size + pos % page_size
    cos, sin = _rope_table(c, pos)
    runs_i = ragged_runs(tok_slot, tok_pos, ih, INDEX_ROWS)
    runs_a = ragged_runs(tok_slot, tok_pos, nh, ATTN_ROWS)
    kw = dict(use_pallas=use_pallas, interpret=interpret)
    w_scale = np.float32(ih ** -0.5 * idim ** -0.5)
    stacks = list(stacks)
    moe_rows, elsewhere = [], []
    # tpulint: disable-next-line=TPL002 -- unrolled on purpose: a dense layer and expert layers, each donated pool written in place
    for li, lp in enumerate(params["layers"]):
        x = _rms(h, lp["ln1"], c.rms_norm_eps).astype(wdt)
        cq = _rms(_dot(x, lp["wq_a"]), lp["q_norm"], c.rms_norm_eps).astype(wdt)
        q = _dot(cq, lp["wq_b"]).reshape(t, nh, nope + rope)
        kva = _dot(x, lp["wkv_a"])
        row = jnp.concatenate([
            _rms(kva[:, :rank], lp["kv_norm"], c.rms_norm_eps),
            _rotate(kva[:, rank:], cos, sin)], -1)
        qi = _rotate_first(_dot(cq, lp["iq"]).reshape(t, ih, idim),
                           cos, sin, rope).astype(wdt)
        ki = _rotate_first(_layer_norm(_dot(x, lp["ik"]), lp["ik_norm_w"],
                                       lp["ik_norm_b"]), cos, sin, rope)
        wi = _dot(x, lp["iw"]) * w_scale
        latent, index = stacks[li][:2]
        latent, index = _put(latent, rows, row), _put(index, rows, ki)
        stacks[li] = (latent, index) + tuple(stacks[li][2:])
        qi = jnp.pad(qi, ((0, 0), (0, 0), (0, index.shape[-1] - idim)))
        with jax.named_scope("dsa_index"):
            scores = ragged_index_scores(
                qi, wi, index[0], table, tok_slot, tok_pos, runs=runs_i,
                **kw)
        with jax.named_scope("dsa_select"):
            thr, at = dsa_select(scores, tok_pos, c.index_topk, **kw)
        # the keys' up-projection absorbed into the query: every head
        # against the one cached row
        wkv_b = lp["wkv_b"].reshape(rank, nh, nope + vd)
        qa = jnp.einsum("thn,chn->thc", q[..., :nope].astype(wdt),
                        wkv_b[..., :nope],
                        preferred_element_type=jnp.float32)
        qf = jnp.concatenate([qa, _rotate(q[..., nope:], cos, sin)], -1)
        qf = jnp.pad(qf, ((0, 0), (0, 0),
                          (0, latent.shape[-1] - qf.shape[-1]))).astype(wdt)
        with jax.named_scope("latent_attn"):
            o = ragged_sparse_latent_attention(
                qf, latent[0], scores, thr, at, table, tok_slot, tok_pos,
                rank=rank, sm_scale=c.qk_head_dim ** -0.5, runs=runs_a, **kw)
        o = jnp.einsum("thc,chv->thv", o.astype(wdt), wkv_b[..., nope:])
        h = h + _dot(o.reshape(t, nh * vd).astype(wdt), lp["wo"])
        xf = _rms(h, lp["ln2"], c.rms_norm_eps)
        x = xf.astype(wdt)
        if "router" not in lp:
            h = h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
            continue
        expert, weight = route(xf, lp["router"], lp["router_bias"], c, row_on)
        routed, got = dropless_experts(
            x, expert, weight, lp["w_gate"], lp["w_up"], lp["w_down"],
            first=c.first_expert, num_experts=c.n_routed_experts)
        moe_rows.append(got)
        elsewhere.append(jnp.sum(expert >= 0, dtype=jnp.int32) - jnp.sum(got))
        h = h + routed + _swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
    h = _rms(h, params["final_norm"], c.rms_norm_eps).astype(wdt)
    idx = jnp.maximum(need_rows, 0)         # the epilogue, as unified_step's
    h = h[idx]
    tok_slot = tok_slot[idx]
    tok_pos = tok_pos[idx]
    row_on = (need_rows >= 0) & (tok_pos >= 0)
    logits = _dot(h, params["lm_head"])                     # (N, V)
    rec = _sample_flat(logits, tok_slot, tok_pos, row_on, sample)
    B = tok_buf.shape[0]
    wslot = jnp.where(buf_write & row_on, tok_slot, B)
    tok_buf = tok_buf.at[wslot, jnp.maximum(tok_pos, 0) + 1].set(
        rec[0].astype(jnp.int32), mode="drop")
    aux = {"moe_rows": jnp.stack(moe_rows),
           "moe_elsewhere": jnp.stack(elsewhere)} if moe_rows else {}
    return ((tuple(stacks),), logits, rec, tok_buf, aux)


glm_step = track_jit("serving.glm_step")(glm_step)


_NOT_YET = {
    "prefix_cache": "a shared page would hold latent rows and index keys, "
                    "which the cache's hand-over paths do not carry yet",
    "host_tier": "it spills the prefix cache's pages, which this model "
                 "cannot keep",
    "spec_decode": "the verify grid has no latent form, and the "
                   "multi-token head is not served",
    "tensor_parallel": "the step is written for one chip: an absorbed "
                       "latent row cannot be divided by heads",
    "bucketed": "it has no bucketed prefill or decode entry points, only "
                "the ragged step (ragged=True)",
    "handoff": "a handoff ships keys and values; this model keeps neither",
    "int8_cache": "the latent kernels read no scales: a plane states the "
                  "type it is kept in (`index_key_dtype`)",
}


def _serving_model(c: GlmDsaConfig):
    group = CacheGroup(
        GROUP, (1,) * c.num_hidden_layers, 1, c.latent_width,
        select=c.index_topk, planes=(
            Plane("latent", c.latent_width, per_head=False),
            Plane("index_key", c.index_head_dim, per_head=False,
                  dtype=c.index_key_dtype)))
    return ServingModel(
        groups=(group,), q_group=c.num_attention_heads, step=glm_step,
        rows=ROWS_A_STEP,
        experts=(c.num_experts_per_tok, c.n_routed_experts),
        unsupported={k: f"GlmDsaConfig does not serve under {k}: {v}"
                     for k, v in _NOT_YET.items()})
