"""DeepSeek-V3-style decoders (`model_type: deepseek_v3`; Moonlight-16B-A3B)
as ONE functional training step: latent attention in its up-projected form
through the flashmask kernels, sigmoid-routed dropless experts beside
shared ones, and the chip's share of a deployment (`experts_held` of
`n_routed_experts` from `first_expert`; the vocabulary's slice is simply
`vocab_size`).

The sibling of `llama_spmd.py` for this family and built from its parts:
`adamw_update`, `init_opt_state`, the fused cross-entropy and the document
ends are imported, not copied; each layer is rematerialised, as there.
Parameters are a tree of stacked arrays: the `first_k_dense_replace` leading layers under
`dense`, the expert layers under `moe`, each scanned.

  h += Attn(RMSNorm(h)); h += FFN(RMSNorm(h)); final RMSNorm, untied head.
  Attn: q = x W_q -> heads x [nope; rope]; [c; k_rope] = x W_dkv;
    [k_nope; v] = RMSNorm(c) W_ukv a head; rotary (interleaved pairs) on
    q_rope and on k_rope, which all heads share; scores over
    `qk_nope_head_dim + qk_rope_head_dim`, values `v_head_dim` wide: the
    flash kernels take the two widths as they are
    (`ops/flashmask_attention.py`), causal inside the token's document.
  Experts: s = sigmoid(x W_r) in float32 over ALL `n_routed_experts`; the
    `num_experts_per_tok` largest of s + b are chosen (`noaux_tc`, one
    group); g = `routed_scaling_factor` x s / sum of the chosen s; the
    gradient runs through the chosen scores alone. `b` is no parameter
    the optimizer moves: it takes no gradient and the step hands it back
    as it came. Every assignment to a held expert is computed, forward and
    backward, in row blocks (`parallel/moe.dropless_experts_blocked`);
    an assignment to an expert held elsewhere adds nothing here and passes
    no gradient: the chips that hold it would add theirs. On one chip the
    layer runs without its exchange, and nothing stands in for it.

Tracing (docs/observability.md § The training step): named regions
`train.latent_attention`, `train.moe_route`, `train.moe_experts`,
`train.shared_experts` in the compiled step, and the counters
`pt_train_steps`, `pt_train_moe_assignments`,
`pt_train_moe_experts_touched`, `pt_train_moe_rows_max` of `TrainStep`'s
registry, booked from the (expert layers, experts held) array of rows the
step hands back beside the loss and read only when a snapshot is asked for.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..observability.compile_telemetry import ensure_compile_cache
from ..ops.flashmask_attention import flashmask_attention_bhsd
from ..parallel.moe import dropless_experts_blocked
from ..serving.metrics import MetricsRegistry
from .deepseek import DeepSeekConfig
from .llama_spmd import (_fused_masked_nll, _rms, adamw_update,
                         doc_end_indices, init_opt_state)

__all__ = ["init_params", "param_shapes", "param_specs", "forward",
           "loss_fn", "make_train_step", "TrainStep", "init_opt_state"]

# vocabulary columns a chunk of the fused cross-entropy holds: three
# float32 (tokens, chunk) slabs live in its backward, 268 MB each at
# 32,768 tokens
CE_CHUNK = 2048
NORMS = ("ln1", "ln2", "kv_norm", "final_norm")
# leaves of the tree the optimizer leaves as they are
FROZEN = ("router_bias",)


# ---------------------------------------------------------------- params
def _held(c: DeepSeekConfig):
    return c.n_routed_experts if c.experts_held is None else c.experts_held


def param_shapes(config: DeepSeekConfig):
    """The tree of shapes: `dense` and `moe` stacks over their layers."""
    c = config
    H, nh = c.hidden_size, c.num_attention_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    Ld = min(c.first_k_dense_replace, c.num_hidden_layers)
    Lm = c.num_hidden_layers - Ld
    if c.q_lora_rank:
        raise NotImplementedError(
            "deepseek_spmd: a compressed query (q_lora_rank) is not "
            "written; Moonlight-16B-A3B has none")
    if (c.scoring_func, c.topk_method, c.norm_topk_prob) != \
            ("sigmoid", "noaux_tc", True):
        raise NotImplementedError(
            "deepseek_spmd: the router written is the published one "
            "(scoring_func 'sigmoid', topk_method 'noaux_tc', "
            f"norm_topk_prob true), not {c.scoring_func!r}, "
            f"{c.topk_method!r}, {c.norm_topk_prob}; the softmax gate is "
            "the eager stack's (models/deepseek.py)")

    def attn(L):
        return {"ln1": (L, H), "wq": (L, H, nh * qk),
                "wkv_a": (L, H, c.kv_lora_rank + c.qk_rope_head_dim),
                "kv_norm": (L, c.kv_lora_rank),
                "wkv_b": (L, c.kv_lora_rank,
                          nh * (c.qk_nope_head_dim + c.v_head_dim)),
                "wo": (L, nh * c.v_head_dim, H), "ln2": (L, H)}
    F = c.intermediate_size
    I = c.moe_intermediate_size or c.intermediate_size
    E, R, S = _held(c), c.n_routed_experts, I * c.n_shared_experts
    shapes = {"embed": (c.vocab_size, H), "final_norm": (H,),
              "lm_head": (H, c.vocab_size)}
    if Ld:
        shapes["dense"] = dict(attn(Ld), w_gate=(Ld, H, F), w_up=(Ld, H, F),
                               w_down=(Ld, F, H))
    if Lm:
        shapes["moe"] = dict(
            attn(Lm), router=(Lm, H, R), router_bias=(Lm, R),
            w_gate=(Lm, E, H, I), w_up=(Lm, E, H, I), w_down=(Lm, E, I, H),
            s_gate=(Lm, H, S), s_up=(Lm, H, S), s_down=(Lm, S, H))
    return shapes


def init_params(config: DeepSeekConfig, seed=0, dtype=jnp.float32,
                router_bias_range=0.0):
    """Seeded normal(0, initializer_range) weights, norms at 1, the
    router's correction bias float32 normal(0, `router_bias_range`)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(seed), len(paths))

    def leaf(k, name, shape):
        if name in NORMS:
            return jnp.ones(shape, dtype)
        x = jax.random.normal(k, shape, jnp.float32)
        if name in FROZEN:
            return x * router_bias_range
        return (x * config.initializer_range).astype(dtype)
    return jax.tree_util.tree_unflatten(treedef, [
        leaf(k, path[-1].key, shape) for k, (path, shape) in zip(keys, paths)])


def param_specs(config, mesh):
    """Every leaf whole on every chip: the family is trained data-parallel
    outside its experts, and the experts' exchange is not written."""
    return jax.tree_util.tree_map(lambda _: P(), param_shapes(config),
                                  is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------- forward
def _rope_table(c, s):
    rot = c.qk_rope_head_dim
    inv = 1.0 / c.rope_theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)
    return jnp.cos(ang), jnp.sin(ang)               # (S, rot / 2)


def _rotate(x, cos, sin):
    """Interleaved pairs: dims (2i, 2i + 1) of x (B, S, ..., rot) turn by
    the i-th angle of its position. Float32 inside, x's type out."""
    shape = x.shape
    xf = x.astype(jnp.float32).reshape(shape[:-1] + (shape[-1] // 2, 2))
    extra = (None,) * (xf.ndim - 4)
    cos, sin = cos[(None, slice(None)) + extra], sin[(None, slice(None)) + extra]
    a, b = xf[..., 0], xf[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1) \
        .reshape(shape).astype(x.dtype)


def latent_attention(lp, x, rope, sri, c: DeepSeekConfig):
    """x (B, S, H) normed -> (B, S, H): keys and values up-projected from
    the latent, keys `qk_nope + qk_rope` wide, values `v_head_dim`."""
    b, s, _ = x.shape
    nh, nope, rot, vd = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim, c.v_head_dim)
    rank = c.kv_lora_rank
    q = (x @ lp["wq"]).reshape(b, s, nh, nope + rot)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], *rope)], -1)
    down = x @ lp["wkv_a"]
    latent = _rms(down[..., :rank], lp["kv_norm"], c.rms_norm_eps)
    k_rope = _rotate(down[..., rank:], *rope)       # one for all heads
    up = (latent @ lp["wkv_b"]).reshape(b, s, nh, nope + vd)
    k = jnp.concatenate(
        [up[..., :nope],
         jnp.broadcast_to(k_rope[:, :, None], (b, s, nh, rot))], -1)
    v = up[..., nope:]
    sri_h = None if sri is None else \
        jnp.broadcast_to(sri, (b, nh, s, sri.shape[-1]))
    o = flashmask_attention_bhsd(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), sri_h,
        causal=True, sm_scale=1.0 / math.sqrt(nope + rot))
    return o.swapaxes(1, 2).reshape(b, s, nh * vd) @ lp["wo"]


def route(x, router, bias, c: DeepSeekConfig):
    """x (T, H) -> (expert (T, k) i32 over ALL the layer's experts, weight
    (T, k) f32). Scores in full float32 (which expert is sixth hangs on a
    score's fourth digit); the selection sees the bias and carries no
    gradient; the weights are the chosen scores without it."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(lax.stop_gradient(s + bias.astype(jnp.float32)),
                       c.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), c.routed_scaling_factor * w


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def expert_ffn(lp, x, c: DeepSeekConfig):
    """x (B, S, H) normed -> (out (B, S, H) in x's type, rows (E,) i32:
    the rows each held expert got)."""
    b, s, H = x.shape
    flat = x.reshape(b * s, H)
    with jax.named_scope("train.moe_route"):
        expert, weight = route(flat, lp["router"], lp["router_bias"], c)
    with jax.named_scope("train.moe_experts"):
        routed, rows = dropless_experts_blocked(
            flat, expert, weight, lp["w_gate"], lp["w_up"], lp["w_down"],
            first=c.first_expert, num_experts=c.n_routed_experts)
    with jax.named_scope("train.shared_experts"):
        shared = _swiglu(flat, lp["s_gate"], lp["s_up"], lp["s_down"])
    out = routed + shared.astype(jnp.float32)
    return out.astype(x.dtype).reshape(b, s, H), rows


def decoder_layer(lp, h, rope, sri, config: DeepSeekConfig):
    """One layer, pure: h (B, S, H) -> (h, rows). `lp` holds a router: an
    expert layer; else a dense SwiGLU one, whose rows are ()."""
    c = config
    with jax.named_scope("train.latent_attention"):
        h = h + latent_attention(lp, _rms(h, lp["ln1"], c.rms_norm_eps),
                                 rope, sri, c)
    x = _rms(h, lp["ln2"], c.rms_norm_eps)
    if "router" in lp:
        out, rows = expert_ffn(lp, x, c)
        return h + out, rows
    return h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), None


def forward(params, input_ids, config: DeepSeekConfig, doc_ids=None,
            return_hidden=False):
    """-> (logits (B, S, V), rows (expert layers, experts held) i32).
    `doc_ids` (B, S): attention stays inside a token's document (packed
    pre-training). `return_hidden`: the final-normed hidden states in the
    logits' place, for the fused loss."""
    c = config
    rope = _rope_table(c, input_ids.shape[1])
    sri = None if doc_ids is None else doc_end_indices(doc_ids)
    h = jnp.take(params["embed"], input_ids, axis=0)
    # a layer's activations are made again in the backward pass
    layer = jax.checkpoint(functools.partial(decoder_layer, config=c))
    rows = jnp.zeros((0, _held(c)), jnp.int32)
    for stack in ("dense", "moe"):
        if stack in params:
            h, got = lax.scan(lambda hh, lp: layer(lp, hh, rope, sri), h,
                              params[stack])
            rows = got if got is not None else rows
    h = _rms(h, params["final_norm"], c.rms_norm_eps)
    return (h if return_hidden else h @ params["lm_head"]), rows


def _ce_chunk(vocab):
    """The fused loss's chunk: the widest whole-lane divisor of the
    vocabulary up to CE_CHUNK, so that no padded column is multiplied
    (20,480 = 10 x 2,048); CE_CHUNK where there is none."""
    for chunk in range(min(CE_CHUNK, vocab) // 128 * 128, 0, -128):
        if vocab % chunk == 0:
            return chunk
    return CE_CHUNK


def loss_fn(params, batch, config):
    """batch: (input_ids, labels) or (input_ids, labels, doc_ids); labels
    < 0 are ignored. -> (masked mean next-token loss, rows). The head goes
    through the fused linear + cross-entropy: the logits never exist."""
    ids, labels, *doc_ids = batch
    h, rows = forward(params, ids, config, doc_ids=(doc_ids or [None])[0],
                      return_hidden=True)
    s, n = _fused_masked_nll(h, params["lm_head"], labels,
                             chunk=_ce_chunk(config.vocab_size))
    return s / jnp.maximum(n, 1.0), rows


# ---------------------------------------------------------------- training
def _frozen_back(new, old):
    """`new` with every FROZEN leaf (and its optimizer slots) as in `old`."""
    def pick(path, n, o):
        names = [getattr(k, "key", None) for k in path]
        return o if any(name in FROZEN for name in names) else n
    return jax.tree_util.tree_map_with_path(pick, new, old)


class TrainStep:
    """The compiled step as `(params, opt_state, step, batch) -> (params,
    opt_state, loss)`, with its books: the rows each held expert got, a
    layer and step, stay on the device until `snapshot()` is asked for, so
    a training loop waits for nothing it did not wait for before."""
    KEEP = 256          # steps' rows kept unread before the older are folded

    def __init__(self, step):
        self.jitted = step      # the compiled step itself, its rows returned
        self.registry = MetricsRegistry()
        r = self.registry
        self._steps = r.counter(
            "pt_train_steps", "Training steps dispatched and booked.")
        self._assignments = r.counter(
            "pt_train_moe_assignments",
            "Row-to-expert assignments that reached an expert held here, "
            "summed over expert layers and steps.")
        self._touched = r.counter(
            "pt_train_moe_experts_touched",
            "Held experts that got at least one row, an expert layer and "
            "step.")
        self._rows_max = r.counter(
            "pt_train_moe_rows_max",
            "Rows of the fullest held expert, summed over expert layers "
            "and steps.")
        self._pending = []

    def __call__(self, params, opt_state, step, batch):
        params, opt_state, loss, rows = self.jitted(params, opt_state, step,
                                                    batch)
        self._pending.append(rows)
        if len(self._pending) > self.KEEP:
            # steps long finished: reading them waits for nothing
            self._book(self._pending[:-2])
            del self._pending[:-2]
        return params, opt_state, loss

    def _book(self, pending):
        for rows in pending:
            rows = np.asarray(rows)
            self._steps.inc()
            self._assignments.inc(int(rows.sum()))
            self._touched.inc(int((rows > 0).sum()))
            self._rows_max.inc(int(rows.max(-1).sum()) if rows.size else 0)

    def snapshot(self):
        """The registry's snapshot with every dispatched step booked
        (waits for the steps in flight)."""
        self._book(self._pending)
        del self._pending[:]
        return self.registry.snapshot()


def make_train_step(config: DeepSeekConfig, mesh, lr=3e-4, clip_norm=1.0):
    """Build the jitted step on `mesh`, a mesh of ONE chip: attention, the
    shared experts and the router are whole on a chip and data-parallel in
    a deployment, and the held experts' exchange with the chips that hold
    the others is not written, so a larger mesh is refused, not imitated.

    -> `TrainStep`: `(params, opt_state, step, batch) -> (params,
    opt_state, loss)`; `opt_state` is `init_opt_state(params)`, and both
    are DONATED (a caller keeps what the step returns). AdamW as
    `llama_spmd.adamw_update` fixes it; the router's bias is handed back
    as it came."""
    ensure_compile_cache()
    if mesh.size != 1:
        raise NotImplementedError(
            f"deepseek_spmd.make_train_step: a mesh of {mesh.size} chips. "
            "The step runs one chip's share of a layer; the exchange of "
            "rows between the chips that hold a layer's experts is not "
            "written (ROADMAP C2)")
    repl = NamedSharding(mesh, P())

    def deepseek_train_step(params, opt_state, step, batch):
        (loss, rows), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, config)
        if clip_norm is not None:
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in jax.tree_util.tree_leaves(grads)))
            scale = clip_norm / jnp.maximum(gn, clip_norm)
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        new_p, new_s = adamw_update(params, grads, opt_state, lr, step)
        return (_frozen_back(new_p, params), _frozen_back(new_s, opt_state),
                loss, rows)

    return TrainStep(jax.jit(
        deepseek_train_step, out_shardings=repl, donate_argnums=(0, 1)))
