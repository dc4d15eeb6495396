"""Nemotron-H (nvidia, `model_type: nemotron_h`) on the serving path, as
one pipeline stage on one chip.

A decoder whose every layer is ONE mixer behind a pre-norm and a
residual, the mixer named by a letter of `hybrid_override_pattern`:

  M  a Mamba-2 mixer: one in-projection to a gate z, the convolution's
     channels xBC and a step dt a head; a causal depthwise convolution of
     `conv_kernel` over the slot's own rows, then SiLU; the selective-
     state recurrence S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,
     y_t = S_t C_t + D x_t over `mamba_num_heads` heads of
     `mamba_head_dim` and a state of `ssm_state_size`, B and C shared by a
     group of heads; the gate, an RMSNorm over each group's channels, the
     out-projection. d_inner is heads x head size, not `expand` x hidden.
  E  `n_routed_experts` experts that are NOT gated,
     `down(relu(up x)^2)`, the top `num_experts_per_tok` by sigmoid score
     plus correction bias, weights from the scores alone, renormalised,
     x `routed_scaling_factor`; beside them one shared expert of the same
     form.
  *  grouped-query attention, causal, no window and NO positional term
     (the report describes the attention layers without one; the
     recurrence carries the order): `rope_theta` stands in the config
     unread.

What an M layer keeps is a SLOT's and not a token's: the state S
(`ssm_state_size` x heads x head size, float32 unless
`ssm_state_dtype` says otherwise) and the last `conv_kernel - 1` rows
of xBC, the same bytes at any context length
(`serving/model_spec.SlotState`; `kernels/ragged_ssm.py`). Only the `*`
layers keep pages. `NemotronHConfig.serving_model()` hands
`ServingEngine` one cache group for the attention layers, two slot
states for the Mamba layers, and `nemotron_step`, which keeps
`llama_serving.unified_step`'s descriptor contract. Layers are
unrolled; pools and states are donated and written in place.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..kernels.ragged_paged_attention import (ragged_paged_attention,
                                              ragged_runs)
from ..kernels.ragged_ssm import (conv_tile, ragged_conv, ragged_scan,
                                  ssm_runs)
from ..observability.compile_telemetry import track_jit
from ..parallel.moe import dropless_experts
from ..serving.model_spec import CacheGroup, ServingModel, SlotState
from .llama_serving import _rms, _sample_flat, _scatter_kv

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
GROUP = "full"          # the cache group (`pool=` / `layer_type=` label)
# rows a step holds: every slot's decode row and a prompt's chunk beside
# them; the experts' 10 GB are read whatever the rows, so more rows a
# step cost the products little until about 256 (as LongCat's, PR 41)
ROWS_A_STEP = 256
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)  # hashable -> a static jit argument
class NemotronHConfig:
    """The published `config.json`'s own keys (defaults: NVIDIA-Nemotron-
    3-Nano-30B-A3B), and `ssm_state_dtype`, the type the recurrence's
    state is KEPT in (it is accumulated in float32 whatever this says;
    `bfloat16` is the benchmark's control). A cut in depth is the
    pattern's first `num_hidden_layers` letters."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    layer_norm_epsilon: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    initializer_range: float = 0.02
    ssm_state_dtype: str = "float32"

    def __post_init__(self):
        L, pattern = self.num_hidden_layers, self.hybrid_override_pattern
        if len(pattern) < L or set(pattern) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(
                f"NemotronHConfig: hybrid_override_pattern {pattern!r} for "
                f"num_hidden_layers={L} (letters M, E and *)")
        object.__setattr__(self, "hybrid_override_pattern", pattern[:L])
        if (self.n_group, self.topk_group) != (1, 1):
            raise ValueError(
                "NemotronHConfig: the router picks among all the experts "
                f"(n_group 1, topk_group 1), not {self.n_group} groups of "
                f"which {self.topk_group}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(
                f"NemotronHConfig: {self.mamba_num_heads} heads over "
                f"{self.n_groups} groups")

    @classmethod
    def from_dict(cls, d):
        """A config.json's dictionary; keys the program has no use for
        (`rope_theta`, `chunk_size`: the published kernel's block, `expand`,
        ...) are left out."""
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        """The convolution's channels: x, then B and C a group."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def count(self, letter):
        return self.hybrid_override_pattern.count(letter)

    def serving_model(self):
        return _serving_model(self)


# -- weights ------------------------------------------------------------------
NORMS = ("ln", "final_norm", "norm_w")
FLOAT32 = ("router_bias", "A_log", "dt_bias", "D")


def layer_shapes(c: NemotronHConfig, letter):
    H = c.hidden_size
    if letter == MAMBA:
        di, heads = c.d_inner, c.mamba_num_heads
        return {"ln": (H,), "w_in": (H, di + c.conv_dim + heads),
                "conv_w": (c.conv_kernel, c.conv_dim),
                "conv_b": (c.conv_dim,), "dt_bias": (heads,),
                "A_log": (heads,), "D": (heads,), "norm_w": (di,),
                "w_out": (di, H)}
    if letter == ATTENTION:
        nh, kv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        return {"ln": (H,), "wq": (H, nh * hd), "wk": (H, kv * hd),
                "wv": (H, kv * hd), "wo": (nh * hd, H)}
    E, F = c.n_routed_experts, c.moe_intermediate_size
    S = c.moe_shared_expert_intermediate_size * c.n_shared_experts
    return {"ln": (H,), "router": (H, E), "router_bias": (E,),
            # an expert's up matrix as a checkpoint keeps it, rows its
            # outputs: 1,856 is not whole lane tiles, and the device lays
            # such a matrix out with 2,688 last whatever its shape says
            "w_up": (E, F, H), "w_down": (E, F, H), "s_up": (H, S),
            "s_down": (S, H)}


def param_shapes(c: NemotronHConfig):
    H, V = c.hidden_size, c.vocab_size
    return {"embed": (V, H), "final_norm": (H,), "lm_head": (H, V),
            "layers": [layer_shapes(c, letter)
                       for letter in c.hybrid_override_pattern]}


def seeded_leaf(key, name, shape, c, dtype):
    """One seeded leaf: normal(0, initializer_range) in `dtype`, norms
    at 1, and Mamba-2's own: A_log = log U(1, 16); dt_bias the inverse
    softplus of a step drawn log-uniform in [time_step_min,
    time_step_max] and floored at time_step_floor; D = 1; the
    convolution as `nn.Conv1d` starts it, U(-1/sqrt(k), 1/sqrt(k)) for
    taps and bias. The three small vectors and the router's correction
    bias are float32."""
    if name in NORMS:
        return jnp.ones(shape, dtype)
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        lo, hi = jnp.log(c.time_step_min), jnp.log(c.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), c.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    if name in ("conv_w", "conv_b"):
        bound = c.conv_kernel ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    x = x * c.initializer_range
    return x if name == "router_bias" else x.astype(dtype)


def init_params(c: NemotronHConfig, seed=0, dtype=jnp.float32):
    """Seeded weights (`seeded_leaf`)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        seeded_leaf(k, getattr(path[-1], "key", ""), shape, c, dtype)
        for k, (path, shape) in zip(keys, leaves)])


# -- the step ---------------------------------------------------------------
def _dot(x, w):
    """-> float32: what feeds a norm, a state or the residual stream is
    not rounded on the way."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def route(x, router, bias, c: NemotronHConfig, row_on):
    """Sigmoid scores over all the experts in full float32 (which expert
    is sixth hangs on a score's fourth digit); the top k of score plus
    correction bias; weights from the scores without the bias,
    renormalised over the chosen (`norm_topk_prob`) and scaled. Slack
    rows route nowhere. -> (expert (T, k) i32, weight (T, k) f32)."""
    s = jax.nn.sigmoid(jnp.dot(x, router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32),
                           c.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, -1)
    if c.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    return jnp.where(row_on[:, None], idx.astype(jnp.int32), -1), \
        c.routed_scaling_factor * w


def _relu2(x, w_up, w_down):
    return _dot(jnp.square(jax.nn.relu(x @ w_up)), w_down)


@functools.partial(jax.jit,
                   static_argnames=("config", "page_size", "use_pallas",
                                    "interpret", "block_q", "block_pages"),
                   donate_argnames=("caches",))
def nemotron_step(params, caches, tables, tokens, tok_slot, tok_pos,
                  config: NemotronHConfig, page_size, *, sample, need_rows,
                  tok_buf, buf_write, use_pallas=False, interpret=False,
                  block_q=None, block_pages=None):
    """`unified_step`'s contract (flat `tok_slot` / `tok_pos` rows, -1 an
    inactive row; `need_rows` the epilogue's rows; the rows' tokens read
    from the device token ring `tok_buf`; `sample` the per-slot sampling
    arrays) over the pattern's layers, unrolled. `caches`: the attention
    layers' group (one `(k, v, None, None)` a layer with a leading 1),
    then the Mamba layers' two slot states, `ssm` (M layers, slots, state,
    heads x head size) and `conv` (M layers, slots, kernel - 1, channels
    in tiles of lanes);
    all DONATED, they come back in place. A slot's one run of rows a step
    advances its state, from zero where the run begins at position 0.
    Returns `(caches, logits, rec, tok_buf, aux)`; `aux["moe_rows"]` is
    (E layers, experts) i32, the rows each expert got; `aux["ssm_runs"]`,
    `["ssm_runs_fresh"]`, `["ssm_rows"]` are one M layer's runs, those
    that began from zero, and their rows."""
    c = config
    nh, kvh, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    heads, hp, n, g = (c.mamba_num_heads, c.mamba_head_dim,
                       c.ssm_state_size, c.n_groups)
    di, eps = c.d_inner, c.layer_norm_epsilon
    t = tok_slot.shape[0]
    row_on = tok_pos >= 0
    pos = jnp.maximum(tok_pos, 0)
    tokens = tok_buf[tok_slot, pos]
    # the residual stream is float32 (T x H: nothing beside the weights);
    # the products take it in the weights' type and add to it unrounded
    wdt = params["embed"].dtype
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    stacks, ssm, conv = caches
    stacks = list(stacks)
    (table,) = tables
    if stacks:
        trash = stacks[0][0].shape[2] - 1
        page_ids = jnp.where(row_on, table[tok_slot, pos // page_size], trash)
        off = pos % page_size
        attn_runs = ragged_runs(tok_slot, tok_pos, nh // kvh, block_q)
    runs, n_runs, n_fresh, n_rows = ssm_runs(tok_slot, tok_pos, ssm.shape[1])
    kernel = dict(tok_slot=tok_slot, tok_pos=tok_pos, runs=runs,
                  use_pallas=use_pallas, interpret=interpret)

    def mamba(lp, x, mi, ssm, conv):
        zxd = _dot(x, lp["w_in"])
        z, xbc, dt = (zxd[:, :di], zxd[:, di:di + c.conv_dim],
                      zxd[:, di + c.conv_dim:])
        with jax.named_scope("ssm_conv"):
            # rounded as the carried rows are kept, so that a row reads
            # the same inputs from this step's rows and from the state
            xbc, conv = ragged_conv(xbc.astype(conv.dtype), conv, mi,
                                    lp["conv_w"], lp["conv_b"], **kernel)
        xs = xbc[:, :di].reshape(t, heads, hp)
        b = xbc[:, di:di + g * n].reshape(t, g, n)
        cc = xbc[:, di + g * n:].reshape(t, g, n)
        dt = jax.nn.softplus(dt + lp["dt_bias"])
        with jax.named_scope("ssm_scan"):
            y, ssm = ragged_scan(xs, dt, -jnp.exp(lp["A_log"]), b, cc, ssm,
                                 mi, **kernel)
        y = (y + lp["D"][None, :, None] * xs).reshape(t, di) \
            * jax.nn.silu(z)
        # gate first, then the norm over each group's channels
        y = y.reshape(t, g, di // g)
        y = (y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
             ).reshape(t, di) * lp["norm_w"].astype(jnp.float32)
        return _dot(y.astype(wdt), lp["w_out"]), ssm, conv

    def attention(lp, x, ai):
        q = (x @ lp["wq"]).reshape(t, nh, hd)
        k = (x @ lp["wk"]).reshape(t, kvh, hd)
        v = (x @ lp["wv"]).reshape(t, kvh, hd)
        kp, vp, ksp, vsp = stacks[ai]
        kp, vp, ksp, vsp, kl, vl, _, _ = _scatter_kv(
            kp, vp, ksp, vsp, 0, page_ids, off, k.swapaxes(0, 1),
            v.swapaxes(0, 1), False, flat=True)
        stacks[ai] = (kp, vp, ksp, vsp)
        with jax.named_scope("full_attn"):
            o = ragged_paged_attention(
                q, kl, vl, table, tok_slot, tok_pos, use_pallas=use_pallas,
                interpret=interpret, block_q=block_q,
                block_pages=block_pages, runs=attn_runs)
        return _dot(o.reshape(t, nh * hd).astype(wdt), lp["wo"])

    def experts(lp, xf, x):
        with jax.named_scope("moe_route"):
            expert, weight = route(xf, lp["router"], lp["router_bias"], c,
                                   row_on)
        with jax.named_scope("moe_experts"):
            routed, got = dropless_experts(
                x, expert, weight, None, lp["w_up"], lp["w_down"],
                up_transposed=True, use_pallas=use_pallas,
                interpret=interpret)
        return routed + _relu2(x, lp["s_up"], lp["s_down"]), got

    nth = dict.fromkeys(c.hybrid_override_pattern, 0)   # a letter's next
    moe_rows = []
    # tpulint: disable-next-line=TPL002 -- unrolled on purpose: the layers are not alike (a mixer a letter), and each donated pool and state is written in place
    for letter, lp in zip(c.hybrid_override_pattern, params["layers"]):
        xf = _rms(h, lp["ln"], eps)
        x = xf.astype(wdt)
        if letter == MAMBA:
            out, ssm, conv = mamba(lp, x, nth[letter], ssm, conv)
        elif letter == ATTENTION:
            out = attention(lp, x, nth[letter])
        else:
            out, got = experts(lp, xf, x)
            moe_rows.append(got)
        nth[letter] += 1
        h = h + out
    h = _rms(h, params["final_norm"], eps).astype(wdt)
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
    aux = {"ssm_runs": n_runs, "ssm_runs_fresh": n_fresh, "ssm_rows": n_rows}
    if moe_rows:
        aux["moe_rows"] = jnp.stack(moe_rows)
    return (tuple(stacks), ssm, conv), logits, rec, tok_buf, aux


nemotron_step = track_jit("serving.nemotron_step")(nemotron_step)


_NOT_YET = {
    "prefix_cache": "a shared page says nothing of the recurrent state at "
                    "its end: reuse needs snapshots of the state, which "
                    "nothing keeps yet",
    "host_tier": "it spills the prefix cache's pages, which this model "
                 "cannot keep",
    "spec_decode": "a rejected draft would have to roll the recurrent state "
                   "back, and nothing keeps the state before a draft",
    "offload": "it stashes pages, not the state a slot keeps: use "
               "preempt_policy='recompute' (this model's default), which "
               "feeds a victim again from its first token",
    "tensor_parallel": "the step is written for one chip",
    "bucketed": "it has no bucketed prefill or decode entry points, only "
                "the ragged step (ragged=True)",
    "handoff": "a handoff ships pages; the recurrent state at their end "
               "is not among them",
    "int8_cache": "the state-space layers keep no pages to quantize, and "
                  "the one attention layer's are a twentieth of a slot's "
                  "state",
}


def _serving_model(c: NemotronHConfig):
    m = c.count(MAMBA)
    return ServingModel(
        groups=(CacheGroup(GROUP, (1,) * c.count(ATTENTION),
                           c.num_key_value_heads, c.head_dim),),
        slot_states=(
            SlotState("ssm", m, (c.ssm_state_size, c.d_inner),
                      c.ssm_state_dtype),
            SlotState("conv", m, (c.conv_kernel - 1,
                                  c.conv_dim // conv_tile(c.conv_dim),
                                  conv_tile(c.conv_dim)))),
        q_group=c.num_attention_heads // c.num_key_value_heads,
        step=nemotron_step, rows=ROWS_A_STEP,
        experts=(c.num_experts_per_tok, c.n_routed_experts)
        if c.count(EXPERTS) else None,
        unsupported={k: f"NemotronHConfig does not serve under {k}: {v}"
                     for k, v in _NOT_YET.items()})
