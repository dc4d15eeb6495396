"""LongCat-Flash-Chat on the serving path, as one chip's share of a
deployment.

A decoder of DOUBLE layers. One layer is two latent-attention sublayers
and two dense SwiGLU feed-forwards in series, and ONE expert layer that
reads the first sublayer's normed output and joins the stream only after
the second feed-forward (the shortcut: a deployment hides the experts'
exchange behind a whole sublayer). So a model layer keeps TWO latent rows
a token, and a branch of the step lives across an attention call.

Latent attention is dense: a row attends its whole context, every head
against the one cached row (`kv_lora_rank` values that are keys and
values at once, then `qk_rope_head_dim` rotary values of the key), the
keys' up-projection absorbed into the query and the values' applied
after (`kernels/ragged_latent.ragged_latent_attention`). The query's
low-rank activations and the normed latent are scaled by sqrt(hidden /
rank) (`mla_scale_q_lora`, `mla_scale_kv_lora`); the scaled latent is
what the cache holds.

The router scores `n_routed_experts + zero_expert_num` experts by softmax
and keeps the top `moe_topk` of score plus correction bias, weights from
the scores alone, times `routed_scaling_factor`, not renormalised. The
last `zero_expert_num` are IDENTITY experts: an assignment to one adds
`weight x row` and costs no product, so the real experts a row pays for
vary. This chip HOLDS `experts_held` of the routed experts, from
`first_expert`: it routes over all of them and computes its own
(`parallel/moe.dropless_experts`); what the absent ones would add is the
other chips' to add, and the identity experts' part is added here, by
the chip whose row it is.

`LongcatFlashConfig.serving_model()` hands `ServingEngine` one cache
group of `2 * num_layers` layers of one plane (the latent row) and
`longcat_step`, which keeps `llama_serving.unified_step`'s descriptor
contract. Layers are unrolled, each sublayer with its own donated pool,
written in place.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..kernels.ragged_latent import ATTN_ROWS, ragged_latent_attention
from ..kernels.ragged_paged_attention import ragged_runs
from ..observability.compile_telemetry import track_jit
from ..parallel.moe import dropless_experts
from ..serving.model_spec import CacheGroup, Plane, ServingModel
from .glm_dsa import _dot, _put, _rope_table, _rotate, _swiglu
from .llama_serving import _rms, _sample_flat

GROUP = "latent"        # the cache group (`pool=` / `layer_type=` label)
# rows a step holds: every row goes through 10 GB of weights, which the
# chip reads in the time it multiplies about 256 rows through them; more
# rows a step and the products, not the bytes, bound it (PERF.md, PR 41)
ROWS_A_STEP = 256


@dataclasses.dataclass(frozen=True)  # hashable -> a static jit argument
class LongcatFlashConfig:
    """The published `config.json`'s own keys (defaults: LongCat-Flash-
    Chat), and what a share of a deployment adds: `experts_held` (None:
    all) from `first_expert`. `latent_dtype`: what the latent rows are
    cached in where that is not the cache's own type (`float8_e4m3fn`
    halves the cache's bytes; the kernel widens a block in fast memory)."""
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    initializer_range: float = 0.02
    experts_held: int | None = None
    first_expert: int = 0
    latent_dtype: str | None = None

    def __post_init__(self):
        held = self.n_routed_experts if self.experts_held is None \
            else self.experts_held
        object.__setattr__(self, "experts_held", held)
        if not 0 <= self.first_expert <= self.n_routed_experts - held:
            raise ValueError(
                f"LongcatFlashConfig: experts [{self.first_expert}, "
                f"{self.first_expert + held}) of {self.n_routed_experts}")

    @classmethod
    def from_dict(cls, d):
        """A config.json's dictionary; keys the program has no use for
        (`attention_method`, `zero_expert_type`: identity, ...) are left
        out."""
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def router_width(self):
        return self.n_routed_experts + self.zero_expert_num

    @property
    def q_scale(self):
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self):
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0

    def serving_model(self):
        return _serving_model(self)


# -- weights ------------------------------------------------------------------
NORMS = ("ln", "final_norm", "q_norm", "kv_norm")


def layer_shapes(c: LongcatFlashConfig):
    """One double layer: two attention sublayers, two dense feed-forwards,
    one router over real and identity experts, the experts held."""
    H, nh, qr = c.hidden_size, c.num_attention_heads, c.q_lora_rank
    F, E, I = c.ffn_hidden_size, c.experts_held, c.expert_ffn_hidden_size
    attn = {"ln": (H,), "wq_a": (H, qr), "q_norm": (qr,),
            "wq_b": (qr, nh * c.qk_head_dim), "wkv_a": (H, c.latent_width),
            "kv_norm": (c.kv_lora_rank,),
            "wkv_b": (c.kv_lora_rank,
                      nh * (c.qk_nope_head_dim + c.v_head_dim)),
            "wo": (nh * c.v_head_dim, H)}
    ffn = {"ln": (H,), "w_gate": (H, F), "w_up": (H, F), "w_down": (F, H)}
    return {"attn": [dict(attn), dict(attn)], "ffn": [dict(ffn), dict(ffn)],
            "router": (H, c.router_width), "router_bias": (c.router_width,),
            "w_gate": (E, H, I), "w_up": (E, H, I), "w_down": (E, I, H)}


def param_shapes(c: LongcatFlashConfig):
    H, V = c.hidden_size, c.vocab_size
    return {"embed": (V, H), "final_norm": (H,), "lm_head": (H, V),
            "layers": [layer_shapes(c) for _ in range(c.num_layers)]}


def init_params(c: LongcatFlashConfig, seed=0, dtype=jnp.float32):
    """Seeded normal(0, initializer_range) weights, norms at 1; the
    router's correction bias float32."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))

    def make(k, path, shape):
        name = getattr(path[-1], "key", "")
        if name in NORMS:
            return jnp.ones(shape, dtype)
        x = jax.random.normal(k, shape, jnp.float32) * c.initializer_range
        return x if name == "router_bias" else x.astype(dtype)
    return jax.tree_util.tree_unflatten(
        treedef, [make(k, p, s) for k, (p, s) in zip(keys, leaves)])


# -- the step ---------------------------------------------------------------
def route(x, router, bias, c: LongcatFlashConfig, row_on):
    """Softmax scores over ALL the experts, real and identity, in full
    float32 (which expert is twelfth hangs on a score's fourth digit);
    the top k of score plus correction bias; weights from the scores
    without the bias, scaled and NOT renormalised. Slack rows route
    nowhere. -> (expert (T, k) i32 over `router_width`, weight (T, k)
    f32); an expert from `n_routed_experts` on is an identity expert."""
    p = jax.nn.softmax(jnp.dot(x, router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST), -1)
    _, idx = jax.lax.top_k(p + bias.astype(jnp.float32), c.moe_topk)
    w = jnp.take_along_axis(p, idx, -1)
    return jnp.where(row_on[:, None], idx.astype(jnp.int32), -1), \
        c.routed_scaling_factor * w


def _moe(lp, xf, x, c: LongcatFlashConfig, row_on):
    """The expert layer of one double layer on rows `xf` (float32; `x` the
    same in the weights' type). Assignments to real experts go to the
    grouped products of the experts held (one to an absent expert adds
    nothing here); assignments to identity experts add their weights'
    sum times the row, with no product.
    -> (out (T, H) f32, rows (E,) the rows each held expert got,
    assignments to real experts held elsewhere, assignments to identity
    experts)."""
    with jax.named_scope("moe_route"):
        expert, weight = route(xf, lp["router"], lp["router_bias"], c, row_on)
        zero = expert >= c.n_routed_experts
    with jax.named_scope("moe_experts"):
        routed, got = dropless_experts(
            x, expert, weight, lp["w_gate"], lp["w_up"], lp["w_down"],
            first=c.first_expert, num_experts=c.n_routed_experts)
    out = routed + jnp.sum(jnp.where(zero, weight, 0.0), -1,
                           keepdims=True) * xf
    n_zero = jnp.sum(zero, dtype=jnp.int32)
    elsewhere = jnp.sum(expert >= 0, dtype=jnp.int32) - n_zero - jnp.sum(got)
    return out, got, elsewhere, n_zero


@functools.partial(jax.jit,
                   static_argnames=("config", "page_size", "use_pallas",
                                    "interpret", "block_q", "block_pages"),
                   donate_argnames=("caches",))
def longcat_step(params, caches, tables, tokens, tok_slot, tok_pos,
                 config: LongcatFlashConfig, page_size, *, sample, need_rows,
                 tok_buf, buf_write, use_pallas=False, interpret=False,
                 block_q=None, block_pages=None):
    """`unified_step`'s contract (flat `tok_slot` / `tok_pos` rows, -1 an
    inactive row; `need_rows` the epilogue's rows; the rows' tokens read
    from the device token ring `tok_buf`; `sample` the per-slot sampling
    arrays) over the double layers, unrolled. `caches`: the one group's
    stacks, `(latent, None)` with a leading 1 for each SUBLAYER, layer li's
    at 2 li and 2 li + 1; DONATED, they come back in place. `block_q` /
    `block_pages` are the K/V kernel's tile and unused: the latent kernel
    derives its own.
    Returns `(caches, logits, rec, tok_buf, aux)`; `aux["moe_rows"]` is
    (layers, experts held) i32, the rows each held expert got,
    `aux["moe_elsewhere"]` (layers,) the assignments to real experts this
    chip does not hold, and `aux["moe_zero"]` (layers,) the assignments to
    identity experts."""
    c = config
    nh, rank, rope = c.num_attention_heads, c.kv_lora_rank, c.qk_rope_head_dim
    nope, vd = c.qk_nope_head_dim, c.v_head_dim
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
    runs = ragged_runs(tok_slot, tok_pos, nh, ATTN_ROWS)
    stacks = list(stacks)

    def attention(lp, h, at):
        x = _rms(h, lp["ln"], c.rms_norm_eps).astype(wdt)
        cq = (_rms(_dot(x, lp["wq_a"]), lp["q_norm"], c.rms_norm_eps)
              * c.q_scale).astype(wdt)
        q = _dot(cq, lp["wq_b"]).reshape(t, nh, nope + rope)
        kva = _dot(x, lp["wkv_a"])
        row = jnp.concatenate([
            _rms(kva[:, :rank], lp["kv_norm"], c.rms_norm_eps) * c.kv_scale,
            _rotate(kva[:, rank:], cos, sin)], -1)
        latent = _put(stacks[at][0], rows, row)
        stacks[at] = (latent,) + tuple(stacks[at][1:])
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
            o = ragged_latent_attention(
                qf, latent[0], table, tok_slot, tok_pos, rank=rank,
                sm_scale=c.qk_head_dim ** -0.5, runs=runs,
                use_pallas=use_pallas, interpret=interpret)
        o = jnp.einsum("thc,chv->thv", o.astype(wdt), wkv_b[..., nope:])
        return _dot(o.reshape(t, nh * vd).astype(wdt), lp["wo"])

    def dense_ffn(lp, x):
        with jax.named_scope("dense_ffn"):
            return _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])

    moe_rows, elsewhere, zero = [], [], []
    # tpulint: disable-next-line=TPL002 -- unrolled on purpose: each sublayer's donated pool written in place
    for li, lp in enumerate(params["layers"]):
        h = h + attention(lp["attn"][0], h, 2 * li)
        xf = _rms(h, lp["ffn"][0]["ln"], c.rms_norm_eps)
        x = xf.astype(wdt)
        m, got, away, ident = _moe(lp, xf, x, c, row_on)
        moe_rows.append(got)
        elsewhere.append(away)
        zero.append(ident)
        h = h + dense_ffn(lp["ffn"][0], x)
        h = h + attention(lp["attn"][1], h, 2 * li + 1)
        x = _rms(h, lp["ffn"][1]["ln"], c.rms_norm_eps).astype(wdt)
        h = h + dense_ffn(lp["ffn"][1], x) + m      # the shortcut joins here
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
           "moe_elsewhere": jnp.stack(elsewhere),
           "moe_zero": jnp.stack(zero)}
    return ((tuple(stacks),), logits, rec, tok_buf, aux)


longcat_step = track_jit("serving.longcat_step")(longcat_step)


_NOT_YET = {
    "prefix_cache": "a shared page would hold latent rows, which the "
                    "cache's hand-over paths do not carry yet",
    "host_tier": "it spills the prefix cache's pages, which this model "
                 "cannot keep",
    "spec_decode": "the verify grid has no latent form",
    "tensor_parallel": "the step is written for one chip: an absorbed "
                       "latent row cannot be divided by heads",
    "bucketed": "it has no bucketed prefill or decode entry points, only "
                "the ragged step (ragged=True)",
    "handoff": "a handoff ships keys and values; this model keeps neither",
    "int8_cache": "the latent kernel reads no scales: the plane states the "
                  "type it is kept in (`latent_dtype`)",
}


def _serving_model(c: LongcatFlashConfig):
    group = CacheGroup(
        GROUP, (1,) * (2 * c.num_layers), 1, c.latent_width, planes=(
            Plane("latent", c.latent_width, per_head=False,
                  dtype=c.latent_dtype),))
    return ServingModel(
        groups=(group,), q_group=c.num_attention_heads, step=longcat_step,
        rows=ROWS_A_STEP,
        experts=(c.moe_topk, c.n_routed_experts),
        unsupported={k: f"LongcatFlashConfig does not serve under {k}: {v}"
                     for k, v in _NOT_YET.items()})
