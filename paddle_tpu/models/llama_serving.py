"""Llama serving engine: continuous batching over a paged KV cache.

Reference parity: the reference's serving stack (PaddleNLP predictor with
block_multihead_attention + BlockManager) admits/evicts requests mid-
flight, storing KV in fixed-size blocks. TPU-native redesign:

  * one jitted `prefill` (dense causal flash attention, bucketed prompt
    lengths to bound recompiles) that also returns per-layer K/V to be
    scattered into the page pool;
  * one jitted `decode_step` for the WHOLE active batch: lax.scan over
    the stacked layer params, paged-attention pallas kernel per layer,
    functional scatter of the new token's K/V into the pool (inactive
    slots write to a reserved trash page);
  * host-side PagedKVCache free-list bookkeeping between steps — slots
    join/leave the batch without recompilation (page_table/lengths are
    plain inputs).

All shapes static: batch = max_seqs always; inactive slots are masked.
"""
from __future__ import annotations

import functools
import math
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from .. import _tuning_defaults as _tuning
from ..kernels.ragged_latent import ATTN_ROWS, latent_block_pages
from ..kernels.ragged_paged_attention import (ragged_paged_attention,
                                              ragged_runs, ragged_tile)
from ..observability import compile_telemetry as _compile
from ..observability.device_telemetry import device_generation
from ..observability import flight_recorder as _flight
from ..observability.compile_telemetry import track_jit
from ..parallel.moe import row_tile_visits, share_spills
from ..profiler import record_span
# host-side page bookkeeping only (numpy/stdlib — serving.kvcache,
# serving.kvtier and serving.faults never import model/engine code, so
# this direction stays cycle-free)
from ..serving.faults import FaultPlan
from ..serving.handoff import KVHandoff
from ..serving.kvcache import PagePool, PrefixCache
from ..serving.kvtier import HostTier, _dequantize_host, _quantize_host
from ..serving.model_spec import CacheGroup, ServingModel
from ..ops.rope import rope_cos_sin, apply_rotary_emb
from ..ops.flash_attention import flash_attention_bhsd
from ..ops.paged_attention import (LANES, paged_attention,
                                   paged_verify_attention, quantize_kv)
from ..ops.varlen_attention import (flash_attention_varlen,
                                    seg_ids_from_cu_seqlens)
from .llama import LlamaConfig


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def _flat_rows(heads, n_pages, page, page_ids, off):
    """Where (head, page_ids[i], off[i]) lies in a pool seen as rows
    (heads x pages x page, D): (len(heads), len(page_ids)) i32. `heads`
    are the head ordinals, a stack's layer folded in
    (`layer * KVH + head`)."""
    return ((heads * n_pages)[:, None] + page_ids[None, :]) * page \
        + off[None, :]


def _put_rows(pool, rows, new):
    """`new` (*rows.shape, D) into `rows` of `pool` seen as (rows, D): a
    scatter along the major dimension, which the TPU compiler performs
    where a donated or carried pool lies."""
    return pool.reshape(-1, pool.shape[-1]).at[rows.reshape(-1)].set(
        new.reshape(-1, new.shape[-1])).reshape(pool.shape)


def _scatter_kv(kp, vp, ksp, vsp, li, page_ids, off, kt, vt, quant,
                flat=False):
    """Write kt/vt (KVH, *idx, D) into layer li of the K/V pools at
    (page_ids, off) — *idx is page_ids/off's shape — quantizing on write
    when the pool is int8 (per-token scales ride in ksp/vsp). Single
    source for decode_step's one-token and verify_step's G-token
    scatters so the int8 path can never drift between them. Returns
    (kp, vp, ksp, vsp, kl, vl, ksl, vsl): the updated stacks plus this
    layer's views for the attention read.

    The layer is taken out of the stack, written and put back: in a
    scan over a stack of layers that is two copies of a layer's pool a
    layer, which the bucketed entry points pay and `unified_step` does
    not (`_scatter_kv_stacked`). A pool with one layer (`laguna_step`'s,
    li = 0) loses nothing by it.

    `flat` writes the same values as rows of the layer seen as
    (KVH x pages x page, D), a scatter along the major dimension: the
    TPU compiler then updates a donated pool where it lies, where the
    three-index form has it transpose the whole pool to put the heads
    inside the pages, and back (one-index `page_ids` only)."""
    kl = jax.lax.dynamic_index_in_dim(kp, li, 0, keepdims=False)
    vl = jax.lax.dynamic_index_in_dim(vp, li, 0, keepdims=False)
    if flat:
        kvh, n_pages, page = kl.shape[:3]
        rows = _flat_rows(jnp.arange(kvh, dtype=jnp.int32), n_pages, page,
                          page_ids, off)

        def put(pool, new):
            return _put_rows(pool, rows, new)
    else:
        def put(pool, new):
            return pool.at[:, page_ids, off].set(new)
    ksl = vsl = None
    if quant:
        kt, kts = quantize_kv(kt)
        vt, vts = quantize_kv(vt)
        ksl = jax.lax.dynamic_index_in_dim(ksp, li, 0, keepdims=False)
        vsl = jax.lax.dynamic_index_in_dim(vsp, li, 0, keepdims=False)
        ksl = put(ksl, kts)
        vsl = put(vsl, vts)
        ksp = jax.lax.dynamic_update_index_in_dim(ksp, ksl, li, 0)
        vsp = jax.lax.dynamic_update_index_in_dim(vsp, vsl, li, 0)
    kl = put(kl, kt.astype(kl.dtype))
    vl = put(vl, vt.astype(vl.dtype))
    kp = jax.lax.dynamic_update_index_in_dim(kp, kl, li, 0)
    vp = jax.lax.dynamic_update_index_in_dim(vp, vl, li, 0)
    return kp, vp, ksp, vsp, kl, vl, ksl, vsl


def _scatter_kv_stacked(kp, vp, ksp, vsp, li, page_ids, off, kt, vt, quant):
    """`_scatter_kv(flat=True)` for a stack of layers written where it
    lies: kt/vt (KVH, T, D) go into layer li (a traced i32) of the 5-D
    pools `(layers, KVH, pages, page, D)` as rows of the WHOLE stack
    seen as (layers x KVH x pages x page, D), the layer folded into the
    row. No layer is taken out of the stack or put back, so a scan
    that carries the pools copies none of them; the attention then
    reads the stack through the same layer index
    (`ragged_paged_attention(layer=)`). int8 pools quantize on write
    and their scales take the same path. Returns (kp, vp, ksp, vsp)."""
    kvh, n_pages, page = kp.shape[1:4]
    rows = _flat_rows(li * kvh + jnp.arange(kvh, dtype=jnp.int32), n_pages,
                      page, page_ids, off)
    if quant:
        kt, kts = quantize_kv(kt)
        vt, vts = quantize_kv(vt)
        ksp = _put_rows(ksp, rows, kts)
        vsp = _put_rows(vsp, rows, vts)
    return (_put_rows(kp, rows, kt.astype(kp.dtype)),
            _put_rows(vp, rows, vt.astype(vp.dtype)), ksp, vsp)


def _sample_record(logits, lengths, active, sample):
    """Device-side sampling + stop-condition evaluation, fused into the
    step program (ROADMAP item 4 / MPK direction: the host reads a few
    ints per slot instead of `[vocab]` rows, and the pipelined pump can
    consume them one step behind).

    Every sampling parameter is a TRACED per-slot array — temperature /
    top_k / top_p changing between requests can never retrace:
      temp (B,) f32      0 = greedy (device argmax);
      top_k (B,) i32     0 = off, clamped to vocab;
      top_p (B,) f32     1.0 = off (include-crossing-token convention,
                         same as generation._sample_logits);
      key (B, 2) u32     the request's base PRNG key; the step key is
                         fold_in(key, lengths) — a pure function of
                         (seed, position), so a preempted/restored
                         request continues the identical trajectory and
                         the sync and pipelined pumps are token-equal;
      eos (B,) i32       -1 = no eos;
      remaining (B,) i32 tokens of budget left including this one.

    Returns (next_token (B,) i32, done (B,) bool, logprob (B,) f32) —
    logprob is log p(token | context) under the RAW model distribution
    (the `logprobs=True` convention), computed here so even logprobs
    requests transfer one float, not a vocab row.
    """
    tok, lp = _filter_draw(logits.astype(jnp.float32), sample["temp"],
                           sample["top_k"], sample["top_p"],
                           sample["key"], lengths)
    done = active & ((sample["remaining"] <= 1) |
                     ((sample["eos"] >= 0) & (tok == sample["eos"])))
    return tok, done, lp


def _filter_draw(lg, temp, top_k, top_p, key, fold, lt=None):
    """Filtered categorical draw shared by the decode record and the
    verify grid: lg (N, V) f32 logits; temp/top_k/top_p/fold (N,)
    traced; key (N, 2) u32. Returns (token (N,) i32, raw-model logprob
    at that token (N,) f32). temp == 0 rows take the argmax.

    The draw does what the wave's rows ask for: the filter, the
    (seed, position) keys and the categorical draw (a Gumbel variate a
    vocabulary entry) run under a `lax.cond` on "some row has
    temp > 0", and an all-greedy wave, whose rows would each throw the
    draw away below, runs the argmax alone. The predicate is a traced
    scalar of the rows' own parameters: one program, and a request
    that samples never retraces. A drawing wave runs the draw for
    every row, so a row's token is the same whatever its neighbours.
    `lt` is the rows' `_filtered_logits` where the caller holds them
    already (a speculative step shares them with `_cand_probs`)."""
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    sampled_on = temp > 0.0

    def draw():
        flt = _filtered_logits(lg, temp, top_k, top_p) if lt is None else lt
        step_key = jax.vmap(jax.random.fold_in)(key, fold)
        return jax.vmap(jax.random.categorical)(step_key, flt) \
            .astype(jnp.int32)

    drawn = jax.lax.cond(jnp.any(sampled_on), draw, lambda: greedy)
    tok = jnp.where(sampled_on, drawn, greedy)
    lp = jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1),
                             tok[:, None], axis=-1)[:, 0]
    return tok, lp


def _filtered_logits(lg, temp, top_k, top_p):
    """The temperature/top_k/top_p filter HALF of `_filter_draw`:
    lg (N, V) f32 -> filtered temperature-scaled logits (kept tokens
    untouched, dropped ones -1e30). ONE definition shared by the
    device draw and the spec-decode candidate-probability path, so the
    distribution a rejection sampler accepts against is exactly the
    distribution the device sampler draws from.

    top_k/top_p are TRACED (a lax.top_k would need static k), so the
    cut is ONE descending value sort + threshold arithmetic, no
    argsort/unsort round trip. top_p keeps the include-crossing-token
    convention measured on the top-k-renormalized distribution (same
    as the host sampler's filter-then-renormalize order): with Z =
    cumulative prob mass of the top-k set, `cum - prob <= p * Z` over
    UNfiltered probs is exactly `cum_f - prob_f <= p` over the
    filtered ones.

    The sort is most of a sampler's time (a third of a step at a
    vocabulary of 100k), so it runs under a `lax.cond` on "some
    sampled row cuts" (top_k > 0 or top_p < 1). A wave that holds such
    a row runs the arithmetic for every row and cuts the rows that
    ask; a row that does not keeps every token there as in any other
    wave (its threshold would be its own minimum, give or take the
    cumulative sum's rounding), so a row's filtered logits follow from
    its own parameters alone, whatever its neighbours are."""
    V = lg.shape[-1]
    sampled_on = temp > 0.0
    row_cuts = sampled_on & ((top_k > 0) | (top_p < 1.0))
    # guard the divide so temp=0 rows cannot overflow
    lt = lg / jnp.where(sampled_on, jnp.maximum(temp, 1e-6), 1.0)[:, None]

    def cut(lt):
        k = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)
        sv = -jnp.sort(-lt, axis=-1)                 # descending values
        probs = jax.nn.softmax(sv, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        z = jnp.take_along_axis(cum, (k - 1)[:, None], axis=-1)
        keep = (jnp.arange(V)[None, :] < k[:, None]) & \
            (cum - probs <= top_p[:, None] * z)
        nkeep = jnp.maximum(keep.sum(-1), 1)         # crossing token stays
        thresh = jnp.take_along_axis(sv, (nkeep - 1)[:, None], axis=-1)
        return jnp.where(row_cuts[:, None] & (lt < thresh), -1e30, lt)

    return jax.lax.cond(jnp.any(row_cuts), cut, lambda lt: lt, lt)


@jax.jit
def _spec_dist_rows(lg, temp, top_k, top_p):
    """Filtered sampling DISTRIBUTION rows for the spec-decode
    rejection sampler: lg (N, V) f32 raw logits → (N, V) f32 softmax
    over `_filtered_logits`. Fixed caller shapes (one row at a time on
    the lazy rejection path) keep this at one compile."""
    return jax.nn.softmax(
        _filtered_logits(lg.astype(jnp.float32), temp, top_k, top_p),
        axis=-1)


def _sample_grid(logits, lengths, sample, lt=None):
    """Verify-chunk twin of `_sample_record`: logits (B, G, V), one
    draw per chunk position. The emission following chunk token g sits
    at cache position lengths+g+1 pre-advanced — exactly the fold the
    plain decode path uses for that emission index, so an un-drafted
    sampled request in a verify chunk draws the IDENTICAL token the
    plain engine would (cross-mode seeded parity). `lt` (B * G, V):
    `_filter_draw`'s. Returns (token (B, G) i32, logprob (B, G) f32)."""
    B, G, V = logits.shape
    lg = logits.astype(jnp.float32).reshape(B * G, V)
    pos = (lengths[:, None] + jnp.arange(G)[None, :] + 1).reshape(-1)

    def rep(a):
        return jnp.repeat(a, G, axis=0)
    tok, lp = _filter_draw(lg, rep(sample["temp"]), rep(sample["top_k"]),
                           rep(sample["top_p"]), rep(sample["key"]), pos, lt)
    return tok.reshape(B, G), lp.reshape(B, G)


def _sample_flat(logits, tok_slot, tok_pos, row_on, sample, lt=None):
    """Flat-row twin of `_sample_record`/`_sample_grid` for the unified
    ragged step: logits (T, V), one draw per buffer row. Per-slot
    sampling params gather through `tok_slot`; the PRNG fold is
    `tok_pos + 1` — exactly the (seed, position) key BOTH bucketed
    paths use (decode folds on pre-advanced lengths = fed-token
    position + 1; the verify grid folds on lengths + g + 1), so the
    ragged engine draws the identical token stream for identical
    logits, across sync and pipelined pumps. Spec engines evaluate
    stop conditions on host (their sample pytree carries no
    eos/remaining) — their rows return done=False. `lt` (T, V):
    `_filter_draw`'s. Returns
    (next_token (T,) i32, done (T,) bool, logprob (T,) f32)."""

    def g(a):
        return a[tok_slot]
    tok, lp = _filter_draw(logits.astype(jnp.float32), g(sample["temp"]),
                           g(sample["top_k"]), g(sample["top_p"]),
                           g(sample["key"]), tok_pos + 1, lt)
    if "remaining" in sample:
        done = row_on & ((g(sample["remaining"]) <= 1) |
                         ((g(sample["eos"]) >= 0) & (tok == g(sample["eos"]))))
    else:
        done = jnp.zeros_like(row_on)
    return tok, done, lp


def _cand_probs(logits, tok_slot, sample, cand):
    """Per-row filtered-distribution probability of a CANDIDATE token
    (the spec-decode draft that follows the row): logits (R, V), cand
    (R,) i32 → ((R,) f32, the filtered logits (R, V) f32). Shares
    `_filtered_logits` with the device draw, so the probability the
    rejection sampler accepts a draft with is computed under exactly
    the distribution the device would sample from — and the host
    fetches R floats instead of R vocab rows. The filtered logits go
    back to the caller, which hands them to the step's draw
    (`_sample_flat(lt=)`, `_sample_grid(lt=)`): the step filters once
    (two conditionals are not the compiler's to merge)."""
    def g(a):
        return a[tok_slot]
    lt = _filtered_logits(logits.astype(jnp.float32), g(sample["temp"]),
                          g(sample["top_k"]), g(sample["top_p"]))
    dist = jax.nn.softmax(lt, axis=-1)
    return jnp.take_along_axis(dist, cand[:, None], axis=-1)[:, 0], lt


def _attn_tp(fn, mesh, quant):
    """shard_map wrapper for the paged attention kernels under tensor
    parallelism: attention is embarrassingly parallel over heads, so
    each tp rank runs the unmodified kernel on its local Q heads
    (P(None, 'tp')) against its local KV heads (P('tp')) — GQA group
    ratios survive because the engine requires nh % tp == kvh % tp == 0.
    Everything around the kernel (matmuls, scatters, MLP) stays under
    GSPMD; only the pallas call needs the manual region (reference: the
    block_multi_head_attention kernel under fleet TP,
    paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu +
    distributed/fleet/meta_parallel/parallel_layers/mp_layers.py)."""
    from jax.sharding import PartitionSpec as P
    qs, kvs, rep = P(None, "tp"), P("tp"), P(None)
    in_specs = (qs, kvs, kvs, rep, rep) + ((kvs, kvs) if quant else ())
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=qs,
                         check_vma=False)


# ---------------------------------------------------------------------------
# jitted compute
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("config", "use_pallas"))
def prefill(params, input_ids, length, config: LlamaConfig, use_pallas=False):
    """input_ids: (1, S_padded); length: () actual prompt length.
    Returns (next_logits (V,), k_all, v_all: (L, KVH, S_padded, D))."""
    c = config
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    hd = c.hidden_size // nh
    b, s = input_ids.shape
    cos, sin = rope_cos_sin(s, hd, base=c.rope_theta, dtype=jnp.float32)
    h = jnp.take(params["embed"], input_ids, axis=0)

    def layer(h, lp):
        x = _rms(h, lp["ln1"], c.rms_norm_eps)
        q = (x @ lp["wq"]).reshape(b, s, nh, hd).swapaxes(1, 2)
        k = (x @ lp["wk"]).reshape(b, s, nkv, hd).swapaxes(1, 2)
        v = (x @ lp["wv"]).reshape(b, s, nkv, hd).swapaxes(1, 2)
        q, k = apply_rotary_emb(q, k, cos[None, None], sin[None, None])
        rep = nh // nkv
        kr = jnp.repeat(k, rep, axis=1) if rep > 1 else k
        vr = jnp.repeat(v, rep, axis=1) if rep > 1 else v
        o = flash_attention_bhsd(q, kr, vr, causal=True,
                                 use_pallas=use_pallas)
        h = h + o.swapaxes(1, 2).reshape(b, s, -1) @ lp["wo"]
        x = _rms(h, lp["ln2"], c.rms_norm_eps)
        mlp = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
        return h + mlp, (k[0], v[0])

    h, kv = jax.lax.scan(layer, h, params["layers"])
    h = _rms(h, params["final_norm"], c.rms_norm_eps)
    logits = h[0, length - 1] @ params["lm_head"]
    return logits, kv[0], kv[1]


@functools.partial(jax.jit, static_argnames=("config", "use_pallas",
                                             "interpret"))
def prefill_varlen(params, input_ids, cu_seqlens, config: LlamaConfig,
                   use_pallas=False, interpret=False):
    """Ragged-batch prefill in ONE call (reference parity:
    flash_attn_unpadded serving prefill).

    input_ids: (T_pad,) all admitted prompts packed back to back;
    cu_seqlens: (B+1,) prefix sums (fixed length → batch-size changes
    don't recompile; unused tail entries repeat the last offset).
    Returns (per-seq next-token logits (B, V),
             k_all, v_all: (L, KVH, T_pad, D))."""
    c = config
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    hd = c.hidden_size // nh
    t = input_ids.shape[0]
    seg = seg_ids_from_cu_seqlens(cu_seqlens, t)
    # in-segment position for RoPE (0 for padding; masked away anyway)
    starts = jnp.concatenate([cu_seqlens[:1] * 0, cu_seqlens])[seg + 1]
    pos = jnp.maximum(jnp.arange(t, dtype=jnp.int32) - starts, 0)
    cos, sin = rope_cos_sin(None, hd, base=c.rope_theta,
                            position_ids=pos)          # (T, hd)
    h = jnp.take(params["embed"], input_ids, axis=0)   # (T, H)

    def layer(h, lp):
        x = _rms(h, lp["ln1"], c.rms_norm_eps)
        q = (x @ lp["wq"]).reshape(t, nh, hd)
        k = (x @ lp["wk"]).reshape(t, nkv, hd)
        v = (x @ lp["wv"]).reshape(t, nkv, hd)
        q, k = apply_rotary_emb(q, k, cos[:, None], sin[:, None])
        o = flash_attention_varlen(q, k, v, seg, seg, causal=True,
                                   use_pallas=use_pallas,
                                   interpret=interpret,
                                   same_offsets=True)   # (T, nh, hd)
        h = h + o.reshape(t, -1) @ lp["wo"]
        x = _rms(h, lp["ln2"], c.rms_norm_eps)
        mlp = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
        return h + mlp, (k, v)

    h, kv = jax.lax.scan(layer, h, params["layers"])
    h = _rms(h, params["final_norm"], c.rms_norm_eps)
    last = jnp.maximum(cu_seqlens[1:] - 1, 0)          # (B,)
    logits = h[last] @ params["lm_head"]               # (B, V)
    # (L, T, KVH, D) → (L, KVH, T, D) to match the pool scatter layout
    k_all = jnp.swapaxes(kv[0], 1, 2)
    v_all = jnp.swapaxes(kv[1], 1, 2)
    return logits, k_all, v_all


@functools.partial(jax.jit,
                   static_argnames=("config", "use_pallas", "page_size",
                                    "interpret", "mesh"))
def decode_step(params, k_pool, v_pool, page_table, lengths, tokens,
                active, config: LlamaConfig, page_size, use_pallas=False,
                interpret=False, k_scale=None, v_scale=None, mesh=None,
                sample=None):
    """One token for every slot.

    k_pool/v_pool: (L, KVH, P, page, D); tokens: (B,) current input token;
    lengths: (B,) length INCLUDING the current token; active: (B,) bool.
    With an int8 cache, k_scale/v_scale (L, KVH, P, page, 1) fp32 ride
    along: the new token's K/V is quantized in-graph and the attention
    kernel dequantizes on read.
    Returns (k_pool, v_pool, k_scale, v_scale, logits (B, V)).

    `sample` (traced pytree, see `_sample_record`) moves sampling and
    stop-condition evaluation INTO this program: the return gains a
    compact (next_token, done, logprob) record and the host never
    needs a logits row.
    """
    c = config
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    hd = c.hidden_size // nh
    B = tokens.shape[0]
    P = k_pool.shape[2]
    quant = k_scale is not None

    pos = jnp.maximum(lengths - 1, 0)                       # (B,)
    cos, sin = rope_cos_sin(None, hd, base=c.rope_theta,
                            position_ids=pos[:, None])      # (B, 1, hd)
    h = jnp.take(params["embed"], tokens[:, None], axis=0)  # (B, 1, H)

    page_ids = page_table[jnp.arange(B), pos // page_size]
    page_ids = jnp.where(active, page_ids, P - 1)           # trash page
    off = pos % page_size

    def layer(carry, xs):
        h, kp, vp, ksp, vsp = carry
        lp, li = xs
        x = _rms(h, lp["ln1"], c.rms_norm_eps)
        q = (x @ lp["wq"]).reshape(B, 1, nh, hd).swapaxes(1, 2)
        k = (x @ lp["wk"]).reshape(B, 1, nkv, hd).swapaxes(1, 2)
        v = (x @ lp["wv"]).reshape(B, 1, nkv, hd).swapaxes(1, 2)
        q, k = apply_rotary_emb(q, k, cos[:, None], sin[:, None])
        # write this token's K/V: (B, KVH, D) → pool[li][:, page_ids, off]
        kt = k[:, :, 0].swapaxes(0, 1)                      # (KVH, B, D)
        vt = v[:, :, 0].swapaxes(0, 1)
        kp, vp, ksp, vsp, kl, vl, ksl, vsl = _scatter_kv(
            kp, vp, ksp, vsp, li, page_ids, off, kt, vt, quant)
        if mesh is not None:
            # scales arrive as explicit defaulted params (not a *sc
            # truthiness branch): the arity is fixed by `quant`, which
            # is static, so the trace has no value-dependent control flow
            def _attn(q_, kl_, vl_, pt_, ln_, ks_=None, vs_=None):
                return paged_attention(
                    q_, kl_, vl_, pt_, ln_, use_pallas=use_pallas,
                    interpret=interpret, k_scale=ks_, v_scale=vs_)
            args = (q[:, :, 0], kl, vl, page_table, lengths) \
                + ((ksl, vsl) if quant else ())
            o = _attn_tp(_attn, mesh, quant)(*args)         # (B, QH, D)
        else:
            o = paged_attention(q[:, :, 0], kl, vl, page_table, lengths,
                                use_pallas=use_pallas, interpret=interpret,
                                k_scale=ksl, v_scale=vsl)   # (B, QH, D)
        h = h + o.reshape(B, 1, -1).astype(h.dtype) @ lp["wo"]
        x = _rms(h, lp["ln2"], c.rms_norm_eps)
        mlp = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
        return (h + mlp, kp, vp, ksp, vsp), None

    L = k_pool.shape[0]
    (h, k_pool, v_pool, k_scale, v_scale), _ = jax.lax.scan(
        layer, (h, k_pool, v_pool, k_scale, v_scale),
        (params["layers"], jnp.arange(L)))
    h = _rms(h, params["final_norm"], c.rms_norm_eps)
    logits = h[:, 0] @ params["lm_head"]
    if sample is None:
        return k_pool, v_pool, k_scale, v_scale, logits
    rec = _sample_record(logits, lengths, active, sample)
    return k_pool, v_pool, k_scale, v_scale, logits, rec


@functools.partial(jax.jit,
                   static_argnames=("config", "page_size", "use_pallas",
                                    "interpret", "mesh"))
def verify_step(params, k_pool, v_pool, page_table, lengths, tokens,
                n_tok, active, config: LlamaConfig, page_size,
                use_pallas=False, interpret=False,
                k_scale=None, v_scale=None, mesh=None, sample=None,
                need_rows=None, cand_tok=None):
    """Speculative-decoding verify: G chunk tokens per slot in ONE
    forward — every matmul runs at (B, G, ...) so one weight read
    covers G tokens, which is where the speculative speedup comes from
    (reference parity: PaddleNLP speculative decoding / "inference with
    reference" draft-verify flow).

    tokens: (B, G) = [pending next_token, draft_1 .. draft_{G-1}],
    right-padded per slot; n_tok: (B,) real chunk length (1..G) — padded
    positions write their K/V to the trash page (their page-table slots
    may not exist, and a default 0 entry would corrupt another slot's
    page 0). lengths: (B,) cache length BEFORE this chunk (chunk token g
    lands at position lengths+g — NB different convention from
    decode_step, which takes lengths pre-advanced); active: (B,) bool.

    Real chunk tokens' K/V are written to the pool; entries past the
    host-side accepted prefix simply sit beyond the slot's length,
    masked from every future read and overwritten when those positions
    are legitimately reached. Returns (k_pool, v_pool, k_scale, v_scale,
    logits (B, G, V)) — logits[:, g] follows chunk token g.

    Attention runs the multi-query paged kernel
    (ops/paged_attention.paged_verify_attention): pages stream
    HBM→VMEM via scalar-prefetch index maps with a per-row causal
    limit — no contiguous gather of the cache. Off-TPU the XLA
    reference (gather + masked dense block) runs instead.
    """
    c = config
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    hd = c.hidden_size // nh
    B, G = tokens.shape
    Pn = k_pool.shape[2]
    quant = k_scale is not None

    pos = lengths[:, None] + jnp.arange(G)[None, :]          # (B, G)
    cos, sin = rope_cos_sin(None, hd, base=c.rope_theta,
                            position_ids=pos)                # (B, G, hd)
    h = jnp.take(params["embed"], tokens, axis=0)            # (B, G, H)

    page_ids = page_table[jnp.arange(B)[:, None], pos // page_size]
    real = active[:, None] & (jnp.arange(G)[None, :] < n_tok[:, None])
    page_ids = jnp.where(real, page_ids, Pn - 1)             # trash page
    off = pos % page_size                                    # (B, G)

    def layer(carry, xs):
        h, kp, vp, ksp, vsp = carry
        lp, li = xs
        x = _rms(h, lp["ln1"], c.rms_norm_eps)
        q = (x @ lp["wq"]).reshape(B, G, nh, hd).swapaxes(1, 2)
        k = (x @ lp["wk"]).reshape(B, G, nkv, hd).swapaxes(1, 2)
        v = (x @ lp["wv"]).reshape(B, G, nkv, hd).swapaxes(1, 2)
        q, k = apply_rotary_emb(q, k, cos[:, None], sin[:, None])
        kt = k.swapaxes(0, 1)                                # (KVH, B, G, D)
        vt = v.swapaxes(0, 1)
        kp, vp, ksp, vsp, kl, vl, ksl, vsl = _scatter_kv(
            kp, vp, ksp, vsp, li, page_ids, off, kt, vt, quant)
        # q: (B, QH, G, D); per-row causal limit base+g inside the op
        if mesh is not None:
            # see prefill `_attn`: fixed arity instead of *sc truthiness
            def _attn(q_, kl_, vl_, pt_, ln_, ks_=None, vs_=None):
                return paged_verify_attention(
                    q_, kl_, vl_, pt_, ln_, use_pallas=use_pallas,
                    interpret=interpret, k_scale=ks_, v_scale=vs_)
            args = (q, kl, vl, page_table, lengths) \
                + ((ksl, vsl) if quant else ())
            o = _attn_tp(_attn, mesh, quant)(*args)
        else:
            o = paged_verify_attention(q, kl, vl, page_table, lengths,
                                       use_pallas=use_pallas,
                                       interpret=interpret,
                                       k_scale=ksl, v_scale=vsl)
        o = o.swapaxes(1, 2).reshape(B, G, nh * hd)
        h = h + o.astype(h.dtype) @ lp["wo"]
        x = _rms(h, lp["ln2"], c.rms_norm_eps)
        mlp = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
        return (h + mlp, kp, vp, ksp, vsp), None

    L = k_pool.shape[0]
    (h, k_pool, v_pool, k_scale, v_scale), _ = jax.lax.scan(
        layer, (h, k_pool, v_pool, k_scale, v_scale),
        (params["layers"], jnp.arange(L)))
    h = _rms(h, params["final_norm"], c.rms_norm_eps)
    if need_rows is not None:
        # the suffix-prefill path: gather the needed flat (B*G)-space
        # rows before the unembed matmul — a bucket-G chunk pays
        # len(need_rows) rows of lm_head FLOPs, not B*G. Callers pass
        # sample=None here (the seed token is picked host-side at
        # finish, the PR 8 convention).
        hf = h.reshape(B * G, -1)[jnp.maximum(need_rows, 0)]
        logits = hf @ params["lm_head"]              # (M, V)
        return k_pool, v_pool, k_scale, v_scale, logits
    logits = h @ params["lm_head"]
    if sample is None:
        return k_pool, v_pool, k_scale, v_scale, logits
    # device-side verify record (`sample` = the same traced pytree as
    # decode_step's): per-position continuation tokens — argmax for
    # greedy slots, the position-keyed categorical draw for sampled
    # ones — and their raw-model logprobs. The host acceptance loop
    # consumes (B, G) ints/floats, never a vocab row; spec_sample's
    # rejection sampler rides `cand_tok` candidate probabilities
    # (computed under the device filter) and pulls a distribution row
    # only on divergence.
    if cand_tok is None:
        rec = _sample_grid(logits, lengths, sample)
    else:
        slot_of = jnp.repeat(jnp.arange(B, dtype=jnp.int32), G)
        cand_p, lt = _cand_probs(logits.reshape(B * G, -1), slot_of,
                                 sample, cand_tok.reshape(-1))
        rec = _sample_grid(logits, lengths, sample, lt) \
            + (cand_p.reshape(B, G),)
    return k_pool, v_pool, k_scale, v_scale, logits, rec


@functools.partial(jax.jit,
                   static_argnames=("config", "page_size", "use_pallas",
                                    "interpret", "block_q",
                                    "block_pages"),
                   donate_argnames=("k_pool", "v_pool", "k_scale",
                                    "v_scale", "tok_buf"))
def unified_step(params, k_pool, v_pool, page_table, tokens, tok_slot,
                 tok_pos, config: LlamaConfig, page_size, *, need_rows,
                 use_pallas=False, interpret=False, k_scale=None,
                 v_scale=None, sample=None, cand_tok=None, block_q=None,
                 block_pages=None, tok_buf=None, buf_write=None):
    """ONE device program for an arbitrary prefill/decode mix (ROADMAP
    item 1; "Ragged Paged Attention" + the MPK fewer-bigger-programs
    direction): a FLAT token buffer replaces the (batch, seq) grids of
    `prefill`/`prefill_varlen`/`decode_step`/`verify_step`, so prefill
    chunks, prefix-cache suffix tails, spec-verify grids and
    single-token decodes ride the same trace — the mix changing
    between steps can never retrace, because every shape here is fixed
    by the engine's static buffer size.

    tokens: (T,) flat token ids; tok_slot: (T,) i32 owning slot;
    tok_pos: (T,) i32 ABSOLUTE cache position per row, -1 for
    inactive slack rows (their K/V lands on the trash page and the
    ragged attention kernel early-exits every page for them).
    page_table: (B, pages_per_seq) i32 snapshot. Rows must be causally
    ordered per slot within the buffer only in the sense that their
    positions are distinct — every row's K/V is scattered before
    attention, and row i reads columns < tok_pos[i]+1 (exactly
    verify_step's chunk contract, generalized).

    `sample` (traced pytree, `_sample_flat`) keeps the PR 8 device-side
    sampling contract: per-slot params gathered per row, PRNG fold =
    tok_pos + 1. Attention runs the pallas ragged paged kernel on TPU
    and its bit-identical jnp reference on CPU
    (paddle_tpu/kernels/ragged_paged_attention.py);
    `block_q`/`block_pages` (static) pick its tile — the engine
    resolves them ONCE at construction, so a tuned tile never retraces
    the serving trace.

    `need_rows` ((N,) i32, -1 = inactive) is the epilogue (docs/
    serving.md § The epilogue): the final-norm hidden states gather
    down to exactly those buffer rows BEFORE the lm_head matmul, so a
    64-token prefill chunk pays one row of unembed FLOPs and no
    (T, vocab) buffer exists in this program; a caller that wants every
    row names every row. Sampling rides the gathered rows with the
    row's own (tok_slot, tok_pos), so the PRNG fold is the row's
    position whatever its place in `need_rows`; the returned logits
    and rec are N-row (the caller indexes them in need-row space).
    `cand_tok` ((N,)) appends per-row filtered-distribution
    probabilities of a candidate token to the record — the spec-decode
    rejection sampler's accept tests then ride the compact record
    instead of pulling vocab rows (docs/serving.md § Speculative
    decoding).

    Returns (k_pool, v_pool, k_scale, v_scale, logits (N, V)[, rec]
    [, tok_buf]). `tok_buf` ((B, max_seq_len+1) i32 device ring) makes
    token values device-resident: rows gather their embedding input
    from it and decode rows (`buf_write`, (N,) bool) scatter their
    sampled token back, which is how wave N+1 reads wave N's tokens
    before the host has.

    The pools are never copied (ROADMAP [donate-pools]): `k_pool`,
    `v_pool`, `k_scale`, `v_scale` and `tok_buf` are DONATED and come
    back where they lay; the layer scan carries the stacks, writes a
    layer's rows flat into them (`_scatter_kv_stacked`) and the kernel
    reads them through the layer index. So the arrays a caller passed
    are gone when the call returns: rebind from the result (the engine
    does), and copy first what must outlive the call (`jnp.copy(pool)`).
    `tok_buf` comes back only with `sample`; give none without.
    """
    c = config
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    hd = c.hidden_size // nh
    t = tokens.shape[0]
    Pn = k_pool.shape[2]
    quant = k_scale is not None
    row_on = tok_pos >= 0
    pos = jnp.maximum(tok_pos, 0)
    if tok_buf is not None:
        # in-jit token source (docs/serving.md § Device token buffer):
        # column p of a slot's ring row holds the token CONSUMED at
        # cache position p, so the host ships only (slot, pos)
        # descriptors — token values (and the embedding gather below)
        # never leave the device. Wave N's own scatter (bottom of this
        # program) is device-ordered before wave N+1's gather, so a
        # second wave launches unread. Inactive rows read column 0 of
        # slot 0 — their K/V lands on the trash page and sampling
        # masks them, so the garbage value is never observed.
        tokens = tok_buf[tok_slot, pos]
    cos, sin = rope_cos_sin(None, hd, base=c.rope_theta,
                            position_ids=pos)            # (T, hd)
    h = jnp.take(params["embed"], tokens, axis=0)        # (T, H)

    page_ids = page_table[tok_slot, pos // page_size]
    page_ids = jnp.where(row_on, page_ids, Pn - 1)       # trash page
    off = pos % page_size
    # the kernel's unit of work, from the descriptors the step already
    # has: once a step, not once a layer (dead code, and dropped, where
    # the jnp reference runs)
    runs = ragged_runs(tok_slot, tok_pos, nh // nkv, block_q)

    def layer(carry, xs):
        h, kp, vp, ksp, vsp = carry
        lp, li = xs
        x = _rms(h, lp["ln1"], c.rms_norm_eps)
        # the three products END at the barrier, so the compiler cannot
        # fold the reshapes to heads into them: folded, it wanted each
        # weight as [heads, head_dim, hidden] and transposed the whole
        # slice in fast memory, every layer of every step; now it reads
        # the weights from HBM as they lie (docs/serving.md § The three
        # products; held by tests/test_tpu_lowering.py)
        q, k, v = jax.lax.optimization_barrier(
            (x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]))
        q = q.reshape(t, nh, hd)
        k = k.reshape(t, nkv, hd)
        v = v.reshape(t, nkv, hd)
        q, k = apply_rotary_emb(q, k, cos[:, None], sin[:, None])
        kt = k.swapaxes(0, 1)                            # (KVH, T, D)
        vt = v.swapaxes(0, 1)
        # the carried stacks are written, and then read, where they lie
        kp, vp, ksp, vsp = _scatter_kv_stacked(
            kp, vp, ksp, vsp, li, page_ids, off, kt, vt, quant)
        o = ragged_paged_attention(q, kp, vp, page_table, tok_slot,
                                   tok_pos, use_pallas=use_pallas,
                                   interpret=interpret,
                                   k_scale=ksp, v_scale=vsp,
                                   block_q=block_q,
                                   block_pages=block_pages,
                                   runs=runs, layer=li)  # (T, QH, D)
        h = h + o.reshape(t, -1).astype(h.dtype) @ lp["wo"]
        x = _rms(h, lp["ln2"], c.rms_norm_eps)
        mlp = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
        return (h + mlp, kp, vp, ksp, vsp), None

    L = k_pool.shape[0]
    (h, k_pool, v_pool, k_scale, v_scale), _ = jax.lax.scan(
        layer, (h, k_pool, v_pool, k_scale, v_scale),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    h = _rms(h, params["final_norm"], c.rms_norm_eps)
    # the epilogue: gather the needed rows FIRST — the unembed matmul
    # and everything downstream run at (N, ...), and no (T, vocab)
    # buffer exists in this program
    idx = jnp.maximum(need_rows, 0)
    need_on = need_rows >= 0
    h = h[idx]
    tok_slot = tok_slot[idx]
    tok_pos = tok_pos[idx]
    row_on = need_on & (tok_pos >= 0)
    logits = h @ params["lm_head"]                       # (N, V)
    if sample is None:
        return k_pool, v_pool, k_scale, v_scale, logits
    if cand_tok is None:
        rec = _sample_flat(logits, tok_slot, tok_pos, row_on, sample)
    else:
        cand_p, lt = _cand_probs(logits, tok_slot, sample, cand_tok)
        rec = _sample_flat(logits, tok_slot, tok_pos, row_on, sample, lt) \
            + (cand_p,)
    if tok_buf is not None:
        # scatter this wave's sampled tokens back into the ring: the
        # token sampled at position p is the one position p+1 consumes.
        # `buf_write` marks the decode rows (seed rows stay host-picked,
        # the PR 8 convention — the host pokes them at finish); masked
        # rows park on an out-of-bounds slot and drop.
        B = tok_buf.shape[0]
        wslot = jnp.where(buf_write & row_on, tok_slot, B)
        # tok_pos/tok_slot are already in epilogue space here (the
        # gather above re-indexed them), matching rec's rows
        pos_w = jnp.maximum(tok_pos, 0)
        tok_buf = tok_buf.at[wslot, pos_w + 1].set(
            rec[0].astype(jnp.int32), mode="drop")
        return k_pool, v_pool, k_scale, v_scale, logits, rec, tok_buf
    return k_pool, v_pool, k_scale, v_scale, logits, rec


# compile telemetry: each entry point reports compiles/retraces (new
# arg-shape signature == a fresh XLA compile) to the observability
# registry — `pt_compile_*` on /metrics, compile events in the flight
# recorder, and a retrace-storm warning when a shape churns per call
prefill = track_jit("serving.prefill")(prefill)
prefill_varlen = track_jit("serving.prefill_varlen")(prefill_varlen)
decode_step = track_jit("serving.decode_step")(decode_step)
verify_step = track_jit("serving.verify_step")(verify_step)
unified_step = track_jit("serving.unified_step")(unified_step)


# device token-ring setters (satellite of ROADMAP item 1): the two
# host-side writers of the buffer `unified_step` gathers embeddings
# from. Fixed shapes — one compile each for the life of the engine.
@jax.jit
def _tokbuf_stage(tok_buf, row_vals, slot):
    """Replace one slot's whole consumed-token row (admission, restore,
    handoff import — anywhere the sequence's history (re)enters)."""
    return tok_buf.at[slot].set(row_vals)


@jax.jit
def _tokbuf_poke(tok_buf, slot, pos, tok):
    """Write one consumed-token cell — the host-picked first token
    (PR 8 seeding convention keeps that draw host-side)."""
    return tok_buf.at[slot, pos].set(tok)


def speculative_sample(prob_rows, drafts, rng, cand_probs=None):
    """Rejection-sampled acceptance for a deterministic draft sequence
    (reference parity: speculative sampling, Leviathan et al. / the
    reference's speculative-decoding sampling path).

    prob_rows: the request's filtered sampling distributions — row g
    applies AFTER consuming chunk token g. Either a sequence of (V,)
    arrays or a callable g -> (V,) array; rows are materialized
    LAZILY, so a first-draft rejection (the common case at low
    acceptance rates) computes one row, not all n — filtering is an
    O(V log V) host sort at vocab 32k+. drafts: (n-1,) proposed tokens
    d_1..d_{n-1} (chunk tokens 1..n-1); rng: the request's
    np.random.RandomState.

    cand_probs (optional, (n-1,) floats): precomputed p_g(d_{g+1}) —
    the engine ships these as part of the device step record
    (`_cand_probs`), so the accept tests consume a float per draft and
    a row is materialized ONLY on divergence or for the final draw.
    The rng consumption order is identical with or without them: one
    rand() per accept test, one choice() per divergence/final draw.

    Accept d_{g+1} with probability p_g(d_{g+1}) (the draft proposal is
    a point mass, so min(1, p/q) = p(d)); on rejection sample from the
    renormalized residual p_g with d removed. Either way every emitted
    token is marginally distributed EXACTLY as p_g — the output
    distribution equals plain (non-speculative) sampling, while
    accepted drafts advance several tokens per verify step.

    Returns (tokens, n_accepted): up to n emitted tokens (accepted
    drafts + one final sample)."""
    row = prob_rows if callable(prob_rows) else prob_rows.__getitem__
    out = []
    n = len(drafts) + 1
    for g in range(n - 1):
        d = int(drafts[g])
        p_d = float(cand_probs[g]) if cand_probs is not None \
            else None
        if p_d is None:
            p = row(g)
            p_d = p[d]
        else:
            p = None                # materialized only on rejection
        if rng.rand() < p_d:
            out.append(d)           # accepted: token IS the draft
            continue
        if p is None:
            p = row(g)
        resid = p.copy()
        resid[d] = 0.0
        tot = resid.sum()
        if tot <= 0.0:              # p was a point mass on d — forced
            out.append(d)
            continue
        out.append(int(rng.choice(len(resid), p=resid / tot)))
        return out, g               # divergence: stop consuming drafts
    p_last = row(n - 1)
    out.append(int(rng.choice(len(p_last), p=p_last)))
    return out, n - 1


def prompt_lookup_draft(ctx, G, ngram=2):
    """Draft continuation tokens by n-gram lookup in the request's own
    context (reference parity: PaddleNLP "inference with reference" —
    speculative decoding without a draft model). Finds the most recent
    earlier occurrence of the trailing `ngram` tokens and proposes the
    up-to-G tokens that followed it. Returns [] when no match."""
    L = len(ctx)
    if L < ngram + 1:
        return []
    key = list(ctx[-ngram:])
    for i in range(L - ngram - 1, -1, -1):
        if list(ctx[i:i + ngram]) == key:
            return [int(t) for t in ctx[i + ngram:i + ngram + G]]
    return []


# ---------------------------------------------------------------------------
# engine (host-side orchestration)
# ---------------------------------------------------------------------------
class PipelineStall(RuntimeError):
    """`step_launch(carry=...)` needed a preemption victim while a step
    was still in flight. The victim's pending next_token only exists on
    device, so the caller must consume the in-flight ticket first
    (`step_finish`), then relaunch with carry=None — the drained state
    preempts exactly like the synchronous loop."""


class StepTicket:
    """One launched-but-unconsumed bucketed decode step: the
    device-resident result record plus the host metadata needed to
    apply it. `reqs` maps slot -> the Request that occupied it at
    launch; `step_finish` applies a slot's result only while that
    identity still holds. A bucketed engine is driven synchronously, so
    the ticket is consumed before the next launch."""

    __slots__ = ("slots", "reqs", "next_tok", "done", "logprob")

    def __init__(self, slots, reqs, next_tok, done, logprob):
        self.slots = slots          # launched slot ids, ascending
        self.reqs = reqs            # slot -> Request at launch time
        self.next_tok = next_tok    # device (B,) i32
        self.done = done            # device (B,) bool
        self.logprob = logprob      # device (B,) f32


class RaggedTicket:
    """One launched-but-unconsumed `unified_step` wave, applied one
    step later under the deep pump: `step_finish` applies a slot's
    result only while `reqs[slot]` still occupies it (a slot released
    or reused in between makes the in-flight result a discarded
    zombie) and marks a finishing slot's entry None in the NEXT ticket,
    rolling its length back, so its overrun token is never emitted.
    The record is FLAT: `flat` maps a decode slot to its buffer row, `seeds`
    lists (slot, req) whose prefill completed this wave — their
    first-token logits rows ride `seed_rows` and are picked HOST-side
    at finish (the PR 8 seeding convention). `aux` is what the model's
    step adds to the record (`ServingModel.step`), read with it. `rows`
    is what the wave carried, (decode rows, prompt rows), and
    `t_fetched` the `time.monotonic()` stamp at which its record reached
    the host: the pump books its periods by the two
    (docs/observability.md § A turn of the pump)."""

    __slots__ = ("reqs", "flat", "next_tok", "done", "logprob",
                 "seeds", "seed_rows", "slots", "aux", "rows", "t_fetched")

    def __init__(self, reqs, flat, next_tok, done, logprob, seeds,
                 seed_rows, slots, aux=None, rows=(0, 0)):
        self.reqs = reqs            # slot -> Request (decode rows only)
        self.flat = flat            # slot -> flat buffer row index
        self.next_tok = next_tok    # device (T,) i32
        self.done = done            # device (T,) bool
        self.logprob = logprob      # device (T,) f32
        self.seeds = seeds          # [(slot, req)] completed prefills
        self.seed_rows = seed_rows  # device (len(seeds), V) or None
        self.slots = slots          # slots with any row this wave
        self.aux = aux or {}        # device arrays of the step's record
        self.rows = rows            # (decode rows, prompt rows) of the wave
        self.t_fetched = None       # set by `step_finish`


class Request:
    """One generation request. Per-request sampling params (reference:
    PaddleNLP predictor SamplingParams): temperature=0 → greedy;
    top_k/top_p restrict the candidate set before sampling."""

    def __init__(self, rid, prompt_ids, max_new_tokens=64, eos_id=None,
                 temperature=0.0, top_k=0, top_p=1.0, seed=None,
                 logprobs=False):
        self.rid = rid
        self.prompt = list(prompt_ids)
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.rng = np.random.RandomState(seed) if seed is not None or \
            temperature > 0 else None
        # device-side sampling key state: the raw threefry key for
        # jax.random.PRNGKey(seed) is [seed>>32, seed&0xffffffff] —
        # built host-side (no device op at construction). The step
        # program samples with fold_in(base_key, position), so the
        # trajectory is a pure function of (seed, position): identical
        # across sync/pipelined pumps and across preemption resume.
        if self.temperature > 0:
            sk = seed if seed is not None \
                else int(np.random.randint(0, 2 ** 31 - 1))
            self._base_key = np.array(
                [(sk >> 32) & 0xFFFFFFFF, sk & 0xFFFFFFFF], np.uint32)
        else:
            self._base_key = None
        self.output = []
        self.slot = None
        self.next_token = None
        # prompt tokens served from the prefix KV cache at admission
        # (0 for cold admissions; surfaced in the HTTP usage block)
        self.cached_tokens = 0
        # runtime accounting (paddle_tpu.serving): cancellation flag is
        # honored at step boundaries; timestamps feed TTFT/TPOT metrics
        self.cancelled = False
        self._t_submit = None
        self._t_first = None
        self._t_last = None
        # logprobs=True: record log p(token | context) under the RAW
        # model distribution for every emitted token (reference parity:
        # the predictor's return_full_hidden/logprob outputs; vLLM
        # convention — raw softmax, not the filtered sampling dist)
        self.want_logprobs = bool(logprobs)
        self.logprobs = [] if logprobs else None

    def pick(self, logits_row):
        """Select the next token from this request's logits row."""
        from .generation import sample_logits_np
        return sample_logits_np(logits_row, self.temperature, self.top_k,
                                self.top_p, self.rng)

    def note_logprob(self, tok, logits_row):
        """Record the raw-model logprob of an emitted token."""
        if not self.want_logprobs:
            return
        x = np.asarray(logits_row, np.float64)
        x = x - x.max()
        self.logprobs.append(
            float(x[tok] - np.log(np.exp(x).sum())))

    @property
    def done(self):
        return (len(self.output) >= self.max_new_tokens or
                (self.eos_id is not None and self.output and
                 self.output[-1] == self.eos_id))


def _tl_mark(req, name):
    """Stamp an exceptional transition (preempted/resumed, spill/
    restore, handoff_export/import) on the request's timeline ledger.
    The scheduler attaches `req._timeline` (serving/timeline.py); bare
    engines and PT_SERVE_TIMELINE=0 leave it absent and this is a
    no-op. Host clock only — the timeline plane must never add device
    traffic to the step loop."""
    tl = getattr(req, "_timeline", None)
    if tl is not None:
        tl.mark(name)


def _tl_count(req, phase, n=1):
    """Bump the request's per-phase step counter (same ledger)."""
    tl = getattr(req, "_timeline", None)
    if tl is not None:
        tl.count(phase, n)


def _llama_step(params, caches, tables, tokens, tok_slot, tok_pos, config,
                page_size, **kw):
    """`unified_step` behind `ServingModel.step`'s contract: one cache
    group, one stack of alike layers."""
    ((k, v, ks, vs),), = caches
    out = unified_step(params, k, v, tables[0], tokens, tok_slot, tok_pos,
                       config, page_size, k_scale=ks, v_scale=vs, **kw)
    return ((tuple(out[:4]),),), out[4], out[5], out[6], {}


def llama_serving_model(config: LlamaConfig):
    """What `LlamaConfig.serving_model()` answers: every layer alike, so
    one cache group scanned as one stack, no window, and every engine
    feature (the bucketed entry points above are this family's)."""
    c = config
    return ServingModel(
        groups=(CacheGroup("full", (c.num_hidden_layers,),
                           c.num_key_value_heads,
                           c.hidden_size // c.num_attention_heads),),
        q_group=c.num_attention_heads // c.num_key_value_heads,
        step=_llama_step)


class _GroupCache:
    """One cache group's share of the engine: its pool arrays on the
    device, one list (an array a stack) for each of the group's planes
    and one for each plane's int8 scales, and on the host its page
    table, its allocator and the pages each slot holds. `base[s]` is the
    ordinal of slot s's first HELD page: 0 unless a window has given
    pages back. `k`, `v`, `ks`, `vs` are the K/V case's names for the
    first two planes and their scales."""

    def __init__(self, spec, num_pages, slot_cap, max_seqs, pages_per_seq,
                 page_size, pool_dtype, quant, placement, prefix_cache=None):
        self.spec = spec
        self.num_pages = num_pages
        # most pages a slot can hold at once: its whole context, or what
        # a window plus one buffer of rows can still see
        self.slot_cap = slot_cap

        def pools(plane, dt, last):
            heads = spec.kv_heads if plane.per_head else 1
            return [jnp.zeros((n, heads, num_pages, page_size, last),
                              dt, device=placement) for n in spec.stacks]
        self.names = [p.name for p in spec.planes]
        # a row shared by all heads is fetched whole by DMA: whole lane
        # tiles of it (the TPU tiles a row so anyway)
        self.pools = [pools(p, jnp.dtype(p.dtype or pool_dtype),
                            p.width if p.per_head
                            else -(-p.width // LANES) * LANES)
                      for p in spec.planes]
        self.scales = [pools(p, jnp.float32, 1) if quant
                       else [None] * len(spec.stacks) for p in spec.planes]
        # unassigned entries point at the trash page (the last), never
        # page 0: a stale row must alias a page no live slot reads
        self.table = np.full((max_seqs, pages_per_seq), num_pages - 1,
                             np.int32)
        self.pool = PagePool(num_pages - 1, cache=prefix_cache)
        self.seq_pages = {s: [] for s in range(max_seqs)}
        self.base = np.zeros((max_seqs,), np.int64)
        self.released = 0       # pages a window gave back

    def _plane(kind, i):  # noqa: N805 - a property factory
        def get(self):
            return getattr(self, kind)[i]

        def put(self, value):
            getattr(self, kind)[i] = value
        return property(get, put)

    k, v = _plane("pools", 0), _plane("pools", 1)
    ks, vs = _plane("scales", 0), _plane("scales", 1)
    del _plane

    def device(self):
        """A tuple a stack: its planes' pools, then their scales."""
        return tuple(zip(*self.pools, *self.scales))

    def take(self, caches):
        cols = [list(x) for x in zip(*caches)]
        self.pools, self.scales = cols[:len(self.names)], \
            cols[len(self.names):]

    def end(self, s):
        """Ordinal one past slot s's last held page."""
        return int(self.base[s]) + len(self.seq_pages[s])

    def keys(self):
        """The names `gather` files a page's arrays under: a plane's own,
        and with an `s` its scales' (`k`, `v`, `ks`, `vs`)."""
        return self.names + [n + "s" for n in self.names]

    def gather(self, pg):
        """Pages `pg` of every layer, to the host: (layers, heads, n,
        page, row) arrays by `keys()`, scales None unless the pool is
        int8."""
        def cat(arrs):
            return None if arrs[0] is None else np.concatenate(
                [np.asarray(a[:, :, pg]) for a in arrs])
        return dict(zip(self.keys(),
                        map(cat, self.pools + self.scales)))

    def scatter(self, pg, host):
        """The inverse of `gather`: host arrays, by `keys()`, into pages
        `pg`."""
        for key, arrs in zip(self.keys(), self.pools + self.scales):
            if arrs[0] is None:
                continue
            at = 0
            for i, a in enumerate(arrs):
                n = a.shape[0]
                arrs[i] = a.at[:, :, pg].set(
                    jnp.asarray(host[key][at:at + n], a.dtype))
                at += n


def _latent_walk(on, cont, live, block):
    """How `kernels/ragged_latent.py`'s attention kernels walk a step, a
    layer, from the plan's own descriptors (`on`: rows of a run; `cont`:
    row i + 1 continues row i's stretch; `live`: a row's position + 1):
    runs by kind, then trips by kind, in `serving.metrics.LATENT_KINDS`'
    order. A run ends at a q block's edge (`ATTN_ROWS` rows). One that
    fills the q block (`whole`) walks its context once, one product a
    trip; a decode row (`row`) once by itself; the 2-15 rows of a chunk's
    `piece` at a q block's edge once EACH, a row's product a trip."""
    cont = cont & (np.arange(1, len(on)) % ATTN_ROWS != 0)
    first = np.nonzero(on & ~np.append(False, cont))[0]
    last = np.nonzero(on & ~np.append(cont, False))[0]
    rows = last - first + 1
    trips = -(-live[last] // block)
    kinds = (rows == ATTN_ROWS, rows == 1, (rows > 1) & (rows < ATTN_ROWS))
    return ([int(k.sum()) for k in kinds]
            + [int((trips * np.where(rows == ATTN_ROWS, 1, rows))[k].sum())
               for k in kinds])


class ServingEngine:
    """Continuous-batching decode loop over the paged cache.

    Admission control (reference: PaddleNLP predictor scheduling +
    vLLM-style paged serving): submit() rejects requests that can never
    fit max_seq_len with a clear error; requests that fit but exceed
    CURRENT capacity queue until slots/pages free up. `num_pages`
    (default: worst-case max_seqs*pages_per_seq) may oversubscribe the
    pool; if decode then runs out of pages, the most-recently admitted
    request is preempted — its pages return to the pool and it re-enters
    the head of the queue (no re-sampling of tokens it already emitted).

    `preempt_policy` selects how an evicted request resumes (reference
    parity: fleet BlockManager swap-out/swap-in in
    paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu's
    serving stack):
      * "offload" (the default, unless the model cannot be offloaded:
        `ServingModel.unsupported`): the victim's KV pages are copied to HOST
        memory on eviction and scattered back into fresh device pages on
        resume — zero recompute, one device<->host round trip of
        n_pages*page_size tokens of KV.
      * "recompute": pages are dropped; resume re-prefills
        prompt + generated-so-far (cheaper on host RAM, ~1 extra prefill
        of compute per eviction).

    `prefix_cache=True` (serving/kvcache.py; docs/serving.md § Prefix
    caching) indexes full KV pages by a chained block hash of their
    token ids: admissions sharing a prompt prefix map the same
    physical pages (ref-counted via the PagePool every page-lifetime
    path runs through) and prefill ONLY their suffix — lengths are
    pre-seeded to the cached token count and the suffix runs as one
    bucket-shaped verify_step chunk over the cached pages. Refcount-0
    pages that are still indexed park in an LRU that allocation
    reclaims before the pool is declared empty.

    Sampling and stop-condition evaluation run INSIDE the jitted step
    (docs/serving.md § Pipelined step loop): `decode_step` takes every
    sampling parameter as a traced per-slot array plus a per-slot PRNG
    key (fold_in(seed_key, position)) and returns a compact
    (next_token, done, logprob) record — the host transfer is a few
    ints per slot, never a `[vocab]` row. `step_launch`/`step_finish`
    split the step so a pipelined driver (the scheduler's pump over a
    ragged engine, or `run_pipelined`) can consume step N's record
    while step N+1 — fed step N's tokens from the device token ring —
    is already running.

    `host_tier_bytes>0` (serving/kvtier.py; docs/serving.md § KV-cache
    tiering) adds a bounded host-RAM tier under that LRU: evictions
    demote their pages (async device->host copy off the pump thread,
    int8-quantized with per-token fp32 scales unless
    tier_quantize=False) instead of discarding them, admission lookups
    fall through device -> host, and tier hits are restored into fresh
    device pages so a returning multi-turn conversation prefills only
    its genuinely new tokens. The preemption offload stash shares the
    tier's bytes ledger regardless of the budget."""

    def __init__(self, params, config: LlamaConfig, max_seqs=4,
                 max_seq_len=512, page_size=16, dtype=jnp.float32,
                 use_pallas=None, interpret=False, num_pages=None,
                 cache_dtype=None, preempt_policy=None,
                 spec_decode=0, spec_ngram=2, chunked_prefill=False,
                 spec_sample=False, mesh=None, prefix_cache=False,
                 host_tier_bytes=0, tier_quantize=True, faults=None,
                 ragged=None, ragged_tokens=None, block_q=None,
                 block_pages=None, device=None):
        c = config
        _compile.ensure_compile_cache()
        # the model seam (serving/model_spec.py): the configuration says
        # how it is served, a cache spec and a step. Everything below is
        # built from that answer, never from the configuration's type.
        model = self.model = c.serving_model()
        tp_ = mesh is not None and mesh.shape.get("tp", 1) > 1
        if ragged is None and "bucketed" in model.unsupported:
            ragged = True
        if preempt_policy is None:
            preempt_policy = "recompute" if "offload" in model.unsupported \
                else "offload"
        for feature, asked in (
                ("offload", preempt_policy == "offload"),
                ("tensor_parallel", tp_), ("prefix_cache", prefix_cache),
                ("host_tier", host_tier_bytes),
                ("spec_decode", int(spec_decode) > 1 or chunked_prefill),
                ("bucketed", ragged is not None and not ragged),
                ("int8_cache", cache_dtype in ("int8", jnp.int8))):
            if asked and feature in model.unsupported:
                raise ValueError(model.unsupported[feature])
        # mesh with a 'tp' axis: tensor-parallel serving — weights get
        # megatron NamedShardings (llama_spmd.param_specs), the KV pool
        # shards over its KV-head axis, the paged kernels run per-rank
        # under shard_map (_attn_tp) and everything else partitions via
        # GSPMD. Admission/eviction logic is untouched: page_table and
        # lengths stay replicated host-visible arrays. This is how a
        # model larger than one chip serves (reference: fleet TP under
        # the predictor, mp_layers.py + block_multihead_attention).
        self._mesh = None
        if tp_:
            tp = mesh.shape["tp"]
            if c.num_attention_heads % tp or c.num_key_value_heads % tp:
                raise ValueError(
                    f"tp={tp} must divide num_attention_heads="
                    f"{c.num_attention_heads} and num_key_value_heads="
                    f"{c.num_key_value_heads} (degenerate GQA shardings "
                    "are not supported)")
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            from . import llama_spmd as _spmd
            params = _spmd.place_params(params, c, mesh, pp=False)
            self._mesh = mesh
            self._pool_placement = NamedSharding(mesh, P(None, "tp"))
            self._repl_sharding = NamedSharding(mesh, P())
            self.device = None
        else:
            # the ONE device this engine lives on: `device`, else where
            # jax places new arrays in the constructing thread (so a
            # factory run under `jax.default_device(d)` — what
            # `build_replicas` does — lands on d). Weights, KV pools
            # and the token ring are COMMITTED to it; the per-step
            # descriptors are built from host arrays, uncommitted, and
            # follow them, so every step runs here whichever thread
            # pumps it. Already-resident weights are not copied.
            self.device = device if device is not None else \
                next(iter(jnp.zeros(()).devices()))
            params = jax.device_put(params, self.device)
            self._pool_placement = self.device
        self.params = params
        self.config = c
        self.page_size = page_size
        self.max_seqs = max_seqs
        self.max_seq_len = max_seq_len
        self.pages_per_seq = -(-max_seq_len // page_size)
        # +1 trash page for masked writes of inactive slots. One count
        # for the model's one group, or {group name: pages} where it has
        # several (each group's pool has its own trash page).
        if not isinstance(num_pages, dict):
            if num_pages is not None and len(model.groups) > 1:
                raise ValueError(
                    f"num_pages={num_pages}: this model keeps "
                    f"{[g.name for g in model.groups]} pages apart; give "
                    "a dict of pages by group name")
            num_pages = {model.groups[0].name: num_pages}
        if cache_dtype not in (None, "int8", jnp.int8):
            # a silently-wrong pool dtype (e.g. 'int4', or a typo)
            # would truncate K/V writes with no scales and decode
            # garbage — fail at construction, not mid-decode
            raise ValueError(
                f"cache_dtype={cache_dtype!r} unsupported: use 'int8' "
                "(quantized pool + per-token scales) or None (pool "
                "stores `dtype`)")
        if preempt_policy not in ("offload", "recompute"):
            raise ValueError(
                f"preempt_policy={preempt_policy!r}: use 'offload' "
                "(host-swap KV pages) or 'recompute' (re-prefill)")
        self.preempt_policy = preempt_policy
        self.preemptions = 0
        self.prefill_tokens = 0  # total tokens ever run through prefill
        # speculative decoding (reference: PaddleNLP speculative /
        # "inference with reference"): spec_decode = chunk width G —
        # each device step verifies 1 pending + up to G-1 prompt-lookup
        # drafted tokens for greedy requests. 0/1 = plain decode.
        self.spec_decode = int(spec_decode)
        self.spec_ngram = int(spec_ngram)
        if self.spec_decode < 0:
            raise ValueError(f"spec_decode={spec_decode}: want >= 0")
        # chunked prefill (reference parity: PaddleNLP/vLLM split-fuse):
        # admissions feed their prompt G tokens per verify step instead
        # of one monolithic prefill, so decoding requests never stall
        # behind a long prompt. Rides the spec verify chunk — needs
        # spec_decode >= 2 (G is the chunk width).
        self.chunked_prefill = bool(chunked_prefill)
        if self.chunked_prefill and self.spec_decode < 2:
            raise ValueError(
                "chunked_prefill rides the spec verify chunk: set "
                "spec_decode >= 2 (the chunk width)")
        # spec_sample: draft for SAMPLED requests too, accepted by
        # rejection sampling (speculative_sample) — the output
        # DISTRIBUTION equals plain sampling exactly, but the rng
        # consumption (hence the seeded trajectory) differs from the
        # non-speculative engine, so it is opt-in
        self.spec_sample = bool(spec_sample)
        if self.spec_sample and self.spec_decode < 2:
            raise ValueError("spec_sample needs spec_decode >= 2")
        self.spec_drafted = 0    # draft tokens fed to verify
        self.spec_accepted = 0   # draft tokens accepted
        self.device_steps = 0    # decode/verify device calls
        # steps whose wave held a row that samples with a top_k / top_p
        # that cuts, and a row that samples at all: how often the step's
        # `_filtered_logits` and `_filter_draw` conditionals engage
        self.sampler_filter_steps = 0
        self.sampler_draw_steps = 0
        # unified ragged step (docs/serving.md § Unified ragged step):
        # every device dispatch — admission prefills, prefix-cache
        # suffix tails, spec-verify grids, single-token decodes — rides
        # ONE jitted `unified_step` over a flat token buffer, so the
        # prefill/decode mix changing between steps can never retrace
        # and no token row is bucket padding. Default ON; the bucketed
        # entry points remain as the PT_SERVE_RAGGED=0 fallback for one
        # release. Tensor-parallel engines stay bucketed (the ragged
        # pallas kernel has no shard_map wrapper yet).
        if ragged is None:
            ragged = os.environ.get("PT_SERVE_RAGGED", "1") \
                not in ("", "0") and self._mesh is None
        self.ragged = bool(ragged)
        if self.ragged and self._mesh is not None:
            raise ValueError(
                "ragged=True does not run under tensor parallelism yet "
                "— build the engine with ragged=False (or "
                "PT_SERVE_RAGGED=0) to keep the bucketed entry points")
        G_ = max(self.spec_decode, 1)
        if ragged_tokens is None:
            # the model's own word (`ServingModel.rows`), else a power
            # of two over the slots
            ragged_tokens = model.rows or 1 << math.ceil(
                math.log2(max(max_seqs * G_, 16)))
        self.ragged_buf = int(ragged_tokens)
        if self.ragged and self.ragged_buf < max_seqs * G_:
            raise ValueError(
                f"ragged_tokens={self.ragged_buf} cannot hold one "
                f"row per slot ({max_seqs} slots x chunk width {G_}) — "
                "a full wave would not fit the flat buffer")
        # padding-waste telemetry (pt_pad_tokens_total /
        # pt_ragged_tokens_total via EngineMetrics.on_step): pad counts
        # power-of-two bucket padding rows dispatched by the bucketed
        # prefill sites (`_bucket_for`); ragged counts REAL rows served
        # through `unified_step` — buffer slack rows are skipped
        # capacity (the kernel's early exit), not dispatched padding
        self.pad_tokens = 0
        self.ragged_tokens = 0
        # what the ragged kernel has to do, from the same descriptors
        # (pt_ragged_attn_pairs / pt_ragged_kv_tokens): the query-key
        # pairs of one layer and head, and the tokens of K/V one layer
        # must read at least once; `last_rows` is the newest wave's
        # (decode, prefill) row mix for the pump's `serving.turn` span,
        # and rides the wave's ticket (`RaggedTicket.rows`) to its fetch
        self.ragged_attn_pairs = 0
        self.ragged_kv_tokens = 0
        # the same two by layer type (a windowed group's rows see at
        # most the window), and what the step's record says of its
        # expert layers: assignments made, experts that got a row, the
        # fullest expert's rows, each summed over sparse layers and steps
        self.ragged_by_type = {g.name: [0, 0] for g in model.groups}
        self.moe_assignments = 0
        self.moe_experts_touched = 0
        self.moe_rows_max_expert = 0
        # the (expert, row tile) visits of a layer's grouped product at
        # the tile its buffer runs at (`parallel/moe.row_tile_visits`):
        # over `moe_experts_touched`, how often an expert's run of sorted
        # rows spans a second tile and its weights are read again
        self.moe_row_tiles = 0
        # the (layer, step) pairs of a share whose held assignments the
        # few sorted rows could not hold, so that the layer's products
        # ran over every assignment (`parallel/moe.share_spills`)
        self.moe_share_spills = 0
        # a share of a layer's experts: the rows each held expert got,
        # and the assignments that went to experts held elsewhere; and
        # those that went to identity experts, which cost no product and
        # are nobody's to compute
        self.moe_rows_by_expert = None
        self.moe_rows_elsewhere = 0
        self.moe_assignments_zero = 0
        # groups whose layers select (`CacheGroup.select`): rows, columns
        # scored, positions kept, rows that kept every column, a layer
        self.dsa_by_type = {g.name: [0, 0, 0, 0] for g in model.groups
                            if g.select is not None}
        # ... and how the kernel goes about it (pt_ragged_runs /
        # pt_ragged_kv_blocks): the runs of rows it launches a program
        # for, and its loop trips over KV blocks
        self.ragged_runs = 0
        self.ragged_kv_blocks = 0
        # ... and the latent kernels about theirs, at their own tile
        # (pt_latent_runs / pt_latent_trips{kind=}): a group's runs and
        # the trips of their walks (`_latent_walk`)
        self.latent_walk = {g.name: [0] * 6 for g in model.groups
                            if g.latent}
        self.last_rows = (0, 0)
        # the row-sparse lm_head epilogue (docs/serving.md § The
        # epilogue): every unified dispatch and the suffix prefill pass a
        # `need_rows` descriptor and no (T, vocab) logits buffer exists
        # — only the rows a wave actually samples, seeds, or
        # rejection-tests pay unembed FLOPs. A wave needs at most one
        # sampled row per decoding slot (x chunk width G under spec)
        # plus one seed row per prefilling slot — and a slot is never
        # both, so max_seqs * G bounds it. Fixed shape => zero retrace
        # as the mix changes.
        self.need_buf = max_seqs * G_
        # pt_logit_rows_total / pt_logit_rows_skipped_total telemetry:
        # unembed rows actually computed vs dispatched rows the
        # epilogue never unembedded
        self.logit_rows = 0
        self.logit_rows_skipped = 0
        # ragged kernel tile, q rows a block x pages a KV block
        # (docs/tuning.md § Serving kernel autotune): constructor args
        # win, else the per-TPU-generation winner persisted by
        # tools/tune_ragged.py, else (None) derived from the shapes.
        # Resolved ONCE here — a static jit arg, so the tile never
        # retraces the serving trace mid-flight.
        tq, tpg = _tuning.load_ragged_tile(device_generation())
        if block_q is None:
            block_q = tq
        if block_pages is None:
            block_pages = tpg
        self._block_q = int(block_q) or None
        self._block_pages = int(block_pages) or None
        # the effective tile, for the plan's pt_ragged_kv_blocks
        q_rows, kv_pages = ragged_tile(
            self._block_q, self._block_pages, self.ragged_buf,
            model.q_group, page_size, self.pages_per_seq)
        self._ragged_q_rows = q_rows
        self._ragged_kv_block = kv_pages * page_size
        self._latent_block = page_size * latent_block_pages(
            page_size, self.pages_per_seq)
        # device-resident token ring (ROADMAP item-1 last follow-on):
        # (max_seqs, max_seq_len+1) i32 where column p holds the token
        # a slot CONSUMES at cache position p. `unified_step` gathers
        # its embedding input from it (host ships only slot/pos
        # descriptors) and scatters each wave's sampled tokens back
        # in-jit, which is what lets wave N+1 launch before wave N is
        # read. Host writes ride two fixed-shape jitted setters
        # (`_tokbuf_stage` at admission/restore/import, `_tokbuf_poke`
        # for host-picked seeds) — zero retrace. Ragged plain-decode
        # engines only: the spec verify chunk keeps host-fed token
        # values.
        self.tok_buf = jnp.zeros((max_seqs, max_seq_len + 1), jnp.int32,
                                 device=self.device) \
            if self.ragged and self.spec_decode <= 1 else None
        # optional telemetry sink (paddle_tpu.serving.metrics
        # EngineMetrics duck type): the step loop reports TTFT/TPOT,
        # occupancy, page stats, and preemptions into it. None = free.
        self.metrics = None
        self._order = 0
        # cache_dtype="int8": quantized KV pool with per-token fp32
        # scales (reference parity: cachekv-quant decode in
        # phi/kernels/fusion/gpu/block_attn.h) — 2x (bf16) / ~3.5x
        # (fp32, net of scales) the servable tokens per pool byte
        self.cache_quant = cache_dtype in ("int8", jnp.int8)
        pool_dtype = jnp.int8 if self.cache_quant else \
            (cache_dtype or dtype)
        # single ref-count-aware allocator a group for EVERY page-lifetime
        # path (admission, finish, cancel sweep, offload/restore). The trash
        # page (last id) is outside the pool: never allocated, shared,
        # indexed, or evicted. prefix_cache=True additionally indexes
        # full pages by chained block hash so admissions sharing a
        # prompt prefix map the same physical pages and prefill only
        # their suffix (serving/kvcache.py; docs/serving.md).
        self.prefix_cache = PrefixCache(page_size) if prefix_cache else None
        # pools by layer type (docs/serving.md § The cache spec): allocated
        # where they live, on the engine's device or laid out over the
        # tp mesh (KV heads sharded). The legacy names (`k_pool`,
        # `page_table`, `pool`, `_seq_pages`, `num_pages`) are group 0's.
        self._caches = []
        for g in model.groups:
            # a windowed slot holds what a row can still see plus the
            # chunk being written: the window, one buffer of rows, and
            # a page of slack at either end
            cap = self.pages_per_seq if g.window is None else min(
                self.pages_per_seq,
                -(-(g.window + self.ragged_buf) // page_size) + 1)
            n = num_pages.pop(g.name, None)
            if n is None:
                n = max_seqs * cap + 1
            n = int(n)
            if n < cap + 1:
                raise ValueError(
                    f"num_pages={n} cannot hold even one "
                    f"max_seq_len sequence ({cap} pages) "
                    "+ the trash page")
            self._caches.append(_GroupCache(
                g, n, cap, max_seqs, self.pages_per_seq, page_size,
                pool_dtype, self.cache_quant, self._pool_placement,
                self.prefix_cache))
        if num_pages:
            raise ValueError(
                f"num_pages names {sorted(num_pages)}; this model's cache "
                f"groups are {[g.name for g in model.groups]}")
        self._windowed = [gc for gc in self._caches
                          if gc.spec.window is not None]
        # what the model keeps a SLOT beside its pages (`SlotState`): one
        # zero array a spec, `(layers, max_seqs) + shape`, donated to the
        # step with the pools and rebound from its result. The engine
        # never writes them: the step starts a run at position 0 from
        # zero state, so admission, release and preemption by recompute
        # (which feeds a victim again from its first token) send the
        # device nothing for them
        self._slot_state = [
            jnp.zeros((st.layers, max_seqs) + tuple(st.shape),
                      jnp.dtype(st.dtype or dtype),
                      device=self._pool_placement)
            for st in model.slot_states]
        self.slot_state_bytes = sum(a.nbytes for a in self._slot_state)
        # from the step's record, one layer's: the slots whose state a
        # step read and wrote (a slot has ONE run of rows a step, so they
        # are the runs too), the runs that began from zero state, the rows
        self.ssm_state_slots = 0
        self.ssm_runs_fresh = 0
        self.ssm_rows = 0
        # page_table/lengths are HOST numpy state, transferred once per
        # device call: the admission/growth bookkeeping reads and writes
        # them element-wise every step, and each element access on a
        # device array is a blocking host<->device round trip (~31 eager
        # dispatches per step measured on CPU) — the whole tables are a
        # few hundred bytes, so one jnp.asarray per step is strictly
        # cheaper
        self.lengths = np.zeros((max_seqs,), np.int32)
        # host-RAM KV tier (serving/kvtier.py; docs/serving.md
        # § KV-cache tiering): one budgeted ledger for ALL
        # host-resident KV. The preemption offload stash always lives
        # here; with host_tier_bytes > 0 the prefix cache's LRU
        # evictions additionally DEMOTE their pages into it (async
        # device->host copy off the pump thread, int8-quantized with
        # per-token scales unless tier_quantize=False) and admission
        # lookups fall through device -> host, restoring hits into
        # fresh device pages. Disabled spill — the default — keeps
        # seed behavior exactly.
        if host_tier_bytes and not prefix_cache:
            raise ValueError(
                f"host_tier_bytes={host_tier_bytes} needs "
                "prefix_cache=True: only the prefix cache's evictions "
                "feed the spill tier")
        self.host_tier = HostTier(page_size, tier_bytes=host_tier_bytes,
                                  quantize=tier_quantize)
        # deterministic fault injection (serving/faults.py;
        # docs/reliability.md): a seeded plan armed at the stack's real
        # failure sites, via constructor or PT_FAULTS. None (the
        # default when the env var is unset) costs nothing and
        # preserves seed behavior exactly. `restarts` counts
        # crash_reset() warm restarts — the scheduler's recovery path.
        self.faults = faults if faults is not None else FaultPlan.from_env()
        self.host_tier.faults = self.faults
        self.restarts = 0
        # disaggregated prefill/decode handoff (serving/handoff.py;
        # docs/serving.md § Disaggregated prefill/decode): a request
        # submitted with `_handoff_export` set finishes with its KV
        # pages exported as a KVHandoff instead of decoding here.
        # Counters mirror to pt_handoff_* via EngineMetrics.on_step;
        # `_handoff_times` is drained into the pt_handoff_seconds
        # histogram there (both on the pump thread — single-writer).
        # `_handoff_pending` is a fast-path guard for the per-launch
        # harvest scan: 0 (the role="both" default) costs one int
        # compare per step and constructs nothing.
        self.handoff_exports = 0
        self.handoff_imports = 0
        self.handoff_bytes = 0
        self.handoff_failures = 0
        self._handoff_times = []
        self._handoff_pending = 0
        if self.prefix_cache is not None:
            self.prefix_cache.on_evict = self._note_prefix_evict
            if self.host_tier.enabled:
                self.prefix_cache.on_spill = self._spill_page
        self._index_suspend = False  # set while releasing failed slots
        self._slots = [None] * max_seqs          # slot -> Request
        # occupied-slot set maintained by admit/release: the per-step
        # page-growth and batch-building passes iterate THIS, not all
        # max_seqs slots (a 256-slot engine at occupancy 3 was paying
        # a 256-iteration host scan per step)
        self._live = set()
        self._waiting = []
        self.finished = []
        # step-loop launch telemetry: wall time between consecutive
        # decode/verify dispatches (pt_step_host_gap_seconds) and how
        # many launched steps the host has not yet consumed
        # (pt_pipeline_depth: 1 under the double-buffered pump)
        self._t_launch_end = None
        self.last_host_gap_s = 0.0
        self.pipeline_depth = 0
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self._use_pallas = use_pallas
        # prefill under tp runs the jnp attention (GSPMD partitions it
        # over heads automatically); only the paged decode/verify
        # kernels get the manual shard_map region. Prefill is
        # matmul-bound, so XLA's fused attention is near-parity there —
        # the pallas win is the decode path's page streaming.
        self._use_pallas_prefill = False if self._mesh is not None \
            else use_pallas
        self._interpret = interpret

    # group 0's share of the cache under the names the engine has always
    # used (every Llama-only path, the tests and the tools go by them)
    def _g0(name, stack=False):  # noqa: N805 - a property factory
        def get(self):
            v = getattr(self._caches[0], name)
            return v[0] if stack else v

        def put(self, value):
            if stack:
                getattr(self._caches[0], name)[0] = value
            else:
                setattr(self._caches[0], name, value)
        return property(get, put)

    k_pool, v_pool = _g0("k", True), _g0("v", True)
    k_scale, v_scale = _g0("ks", True), _g0("vs", True)
    page_table, pool = _g0("table"), _g0("pool")
    _seq_pages, num_pages = _g0("seq_pages"), _g0("num_pages")
    del _g0

    @property
    def _free(self):
        """The pool's free list (compatibility view — tests and tools
        poke it directly; engine code goes through `self.pool`)."""
        return self.pool.free

    @_free.setter
    def _free(self, pages):
        self.pool.free = list(pages)

    # -- request admission ------------------------------------------------
    def validate(self, req: Request):
        """Raise ValueError for a request that could NEVER run (clear
        engine-level error instead of a deep PagedKVCache failure
        mid-decode). Separated from submit() so frontends can
        admit-or-refuse before queueing."""
        S = len(req.prompt)
        if S == 0:
            raise ValueError("serving: empty prompt")
        if S + req.max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"serving: prompt ({S} tokens) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds max_seq_len="
                f"{self.max_seq_len}; truncate the prompt, lower "
                "max_new_tokens, or build the engine with a larger "
                "max_seq_len")

    def submit(self, req: Request):
        """Validate-or-reject now; queue what fits."""
        self.validate(req)
        if req._t_submit is None:
            req._t_submit = time.perf_counter()
        if getattr(req, "_handoff_export", False) or \
                getattr(req, "_kv_import", None) is not None:
            if "handoff" in self.model.unsupported:
                raise ValueError(self.model.unsupported["handoff"])
        if getattr(req, "_handoff_export", False):
            self._handoff_pending += 1
        self._waiting.append(req)
        m = self.metrics
        if m is not None:
            m.on_submit(self)

    def cancel(self, req: Request):
        """Cancel a queued or active request: queued requests leave
        the waiting queue immediately; an active slot is released (its
        pages return to the pool) at the next step() boundary. Either
        way the request lands in `finished` with req.cancelled=True
        and whatever output it already produced. NOT thread-safe —
        call from the thread driving step() (the scheduler's pump).
        Returns True if the request was queued or active."""
        req.cancelled = True
        if req in self._waiting:
            self._waiting.remove(req)
            self._drop_offload(req)
            self._clear_handoff_flag(req)
            self.finished.append(req)
            m = self.metrics
            if m is not None:
                m.on_cancel("queued")
            return True
        return req.slot is not None

    def _sweep_cancelled(self):
        """Release slots (and drop queued entries) whose requests were
        cancelled since the last step."""
        m = self.metrics
        for s in sorted(self._live):
            r = self._slots[s]
            if r is not None and r.cancelled:
                self.finished.append(r)
                self._release(s)
                r.slot = None
                if m is not None:
                    m.on_cancel("active")
        if any(r.cancelled for r in self._waiting):
            keep = []
            for r in self._waiting:
                if r.cancelled:
                    self._drop_offload(r)
                    self._clear_handoff_flag(r)
                    self.finished.append(r)
                    if m is not None:
                        m.on_cancel("queued")
                else:
                    keep.append(r)
            self._waiting = keep

    def _note_emit(self, req: Request, n: int):
        """Token-emission accounting: first emission closes the TTFT
        clock (from submit, queueing included), later ones feed the
        per-token latency histogram."""
        m = self.metrics
        if m is None or n <= 0:
            return
        _tl_count(req, "decode")
        now = time.perf_counter()
        if req._t_first is None:
            req._t_first = now
            if req._t_submit is not None:
                m.observe_ttft(now - req._t_submit)
        elif req._t_last is not None:
            m.observe_tpot((now - req._t_last) / n)
        req._t_last = now
        m.on_tokens(n)

    def _note_finish(self, req: Request):
        m = self.metrics
        if m is not None:
            dt = None if req._t_submit is None \
                else time.perf_counter() - req._t_submit
            m.on_finish(req, dt)

    def _note_step(self, n_active: int):
        m = self.metrics
        if m is not None:
            m.on_step(self, n_active)

    def _attach(self, slot, req):
        """Single site that occupies a slot — keeps the live-slot set
        in sync with `_slots` (release is the only other mutator)."""
        self._slots[slot] = req
        self._live.add(slot)

    def _stage_tokbuf(self, slot, req):
        """(Re)write one slot's device token-ring row: everything the
        sequence has consumed or holds pending — prompt + output (the
        pending next_token is always output's tail) — zero-padded to
        the fixed row shape. One call per (re)admission; no-op when
        the engine runs the host token path."""
        if self.tok_buf is None:
            return
        vals = np.zeros((self.max_seq_len + 1,), np.int32)
        toks = list(req.prompt) + [int(t) for t in req.output]
        n = min(len(toks), self.max_seq_len + 1)
        vals[:n] = toks[:n]
        self.tok_buf = _tokbuf_stage(self.tok_buf, vals, np.int32(slot))

    def _fetch_results(self, tree):
        """The ONE sanctioned device->host read in the serving step
        loop (tpulint config `sanctioned_sync`): everything the host
        needs from a device step — the per-slot (next_token, done,
        logprob) records, spec verify grids, sampling rows, admission
        seed rows — rides ONE batched transfer. Under the pipelined
        pump this read is issued one step behind the launch, so it
        overlaps the next device step instead of stalling it."""
        return jax.device_get(tree)

    def _spec_row_dist(self, logits, idx, req):
        """Materialize ONE filtered sampling distribution row for the
        spec rejection sampler's divergence/final draws (docs/serving.md
        § Speculative decoding). The filter (`_spec_dist_rows`) runs on
        device over a fixed (1, V) shape — one compile for the whole
        serve — and the row crosses via the sanctioned `_fetch_results`
        read. The common accepted-draft case never calls this: accept
        tests ride the step record's candidate probabilities.
        Renormalized in float64 so np.random.choice's sum-to-1 check
        passes on a float32 softmax row."""
        row = _spec_dist_rows(
            logits[jnp.asarray(idx, jnp.int32)][None],
            jnp.full((1,), req.temperature, jnp.float32),
            jnp.full((1,), req.top_k, jnp.int32),
            jnp.full((1,), req.top_p, jnp.float32))
        p = self._fetch_results(row)[0].astype(np.float64)
        return p / p.sum()

    def _fire(self, point, value=None, rids=None):
        """Fault-injection hook (serving/faults.py): no-op unless a
        FaultPlan is attached; an armed rule may raise, sleep, or
        corrupt `value` here — at the stack's real failure site."""
        f = self.faults
        if f is None:
            return value
        return f.fire(point, value, rids=rids)

    def crash_reset(self):
        """The engine half of a warm restart, after a step exception:
        release every slot exactly as a failure must (prefix indexing
        SUSPENDED — a failed step's K/V may be partial; slots that were
        mid-admission when the exception hit still hold pages but no
        Request, so the sweep keys on either), drop engine-queued
        work's host-stashed KV, and clear the launch telemetry clock.
        What happens to the REQUESTS (requeue / quarantine / fail) is
        the scheduler's decision — this only returns the engine to a
        cleanly-empty, immediately servable state. Returns the requests
        that were engine-queued at the crash."""
        self.restarts += 1
        self._t_launch_end = None
        self._index_suspend = True
        try:
            for s in range(self.max_seqs):
                if self._slots[s] is not None or self._seq_pages[s]:
                    self._release(s)
        finally:
            self._index_suspend = False
        for r in self._waiting:
            self._drop_offload(r)
        waiting, self._waiting = self._waiting, []
        # requeued requests keep their export flags; re-submission
        # re-counts them, so the pending counter restarts from zero
        self._handoff_pending = 0
        return waiting

    @staticmethod
    def _feed_ids(req):
        """Tokens to prefill: the original prompt, plus — after a
        preemption — everything already generated except the pending
        next_token (which was sampled but not yet fed to the cache)."""
        if getattr(req, "_resume", False):
            return list(req.prompt) + [int(t) for t in req.output[:-1]]
        return list(req.prompt)

    def _bucket_for(self, n):
        """The power-of-two padding bucket for an n-token bucketed
        dispatch — ONE definition for the monolithic prefill, the
        packed varlen prefill and the suffix-prefill chunk (they used
        to recompute it independently). Reports the choice to compile
        telemetry (`set_context(bucket=...)` rides the NEXT tracked
        call's flight "compile" record, so a retrace storm names the
        bucket that caused it) and counts the `b - n` padding rows into
        `pt_pad_tokens_total` — the waste the ragged step eliminates."""
        b = max(self.page_size, 1 << math.ceil(math.log2(max(n, 1))))
        self.pad_tokens += b - n
        _compile.set_context(bucket=b)
        return b

    def _admit(self):
        """Admit all waiting requests that fit — ONE varlen prefill call
        for the whole ragged batch (no per-sequence dense fallback)."""
        free_slots = [s for s in range(self.max_seqs)
                      if self._slots[s] is None]
        # admit only what both slots AND kv pages can hold — popping a
        # request we cannot scatter would silently drop it
        # reserve pages that active slots will need at this step —
        # otherwise an admission can fill the pool and become the
        # immediate preemption victim (full prefill wasted). Plain
        # decode grows one page exactly at a boundary; a spec verify
        # chunk can need pages for up to G new positions at once.
        if self.spec_decode > 1 or self.ragged:
            G = max(self.spec_decode, 1)
            def _reserve(s):
                r = self._slots[s]
                if self._prefilling(r):
                    # keep a mid-prefill slot's whole remaining prompt
                    # reserved (lazily allocated, but spoken for):
                    # admitting a second long prompt into pages the
                    # first will certainly need would just thrash
                    # admit -> evict cycles
                    horizon = len(r._pf_feed)
                else:
                    horizon = min(int(self.lengths[s]) + G,
                                  self.max_seq_len)
                # a windowed group never holds more than its cap
                return [
                    max(0, min(-(-horizon // self.page_size) - gc.end(s),
                               gc.slot_cap - len(gc.seq_pages[s])))
                    for gc in self._caches]
            growth_need = [sum(col) for col in zip(
                *(_reserve(s) for s in sorted(self._live)))] \
                or [0] * len(self._caches)
        else:
            growth_need = [sum(
                1 for s in self._live
                if int(self.lengths[s]) > 0
                and int(self.lengths[s]) % self.page_size == 0
                and len(self._seq_pages[s]) * self.page_size
                <= int(self.lengths[s]))]
        # pages spoken for, a cache group (the bucketed paths serve
        # one-group models only)
        reserve = growth_need
        take = 0
        for req in self._waiting[:len(free_slots)]:
            ofl = getattr(req, "_offload", None)
            hin = getattr(req, "_kv_import", None)
            if ofl is not None:
                need = list(ofl["pages"])
                if ofl["len"] % self.page_size == 0 and \
                        (ofl["base"][0] + need[0]) * self.page_size \
                        <= ofl["len"]:
                    # boundary growth this same step
                    need = [n + 1 for n in need]
            elif hin is not None:
                # a handoff import scatters its shipped pages like a
                # restore — no prefix probe (the payload IS the prefix)
                need = hin.pages
                if hin.length % self.page_size == 0 and \
                        need * self.page_size <= hin.length:
                    need += 1
            else:
                feed = self._feed_ids(req)
                feed_len = max(len(feed), 1)
                # acquire the cached prefix NOW (ref-counted) so a
                # later candidate's allocation cannot evict it out
                # from under this one; `need` then counts only the
                # UNCACHED pages — cache-aware admission accounting
                req._kv_match = self._cache_acquire(feed, req)
                need = -(-feed_len // self.page_size) \
                    - len(req._kv_match[0])
                if feed_len % self.page_size == 0:
                    need += 1  # its own first decode boundary, same step
            # every group has to hold its share: the same pages a group,
            # up to what a windowed slot can hold at once
            if isinstance(need, int):
                need = [need] * len(self._caches)
            need = [min(n, gc.slot_cap)
                    for n, gc in zip(need, self._caches)]
            # pool.available() counts free + reclaimable (rc==0 cached)
            # pages; reviving a matched page above already removed it
            # from the reclaimable side
            if any(n > gc.pool.available() - r
                   for n, gc, r in zip(need, self._caches, reserve)):
                self._cache_unacquire(req)
                break
            reserve = [r + n for r, n in zip(reserve, need)]
            take += 1
        if take == 0:
            return
        all_reqs = [self._waiting.pop(0) for _ in range(take)]
        all_slots = free_slots[:take]
        _flight.record(
            "engine.admit", rids=[str(r.rid) for r in all_reqs],
            resumed=sum(1 for r in all_reqs
                        if getattr(r, "_offload", None) is not None),
            free_pages=len(self._free))
        # host-offloaded victims resume by scattering their saved pages
        # back — no prefill compute; everything else joins one varlen
        # prefill batch (or, under chunked_prefill, starts feeding its
        # prompt G tokens per verify step so decoders never stall)
        reqs, slots = [], []
        for slot, req in zip(all_slots, all_reqs):
            if getattr(req, "_resume", False):
                # swap-in / recompute-resume / crash-recovery re-admit:
                # one timeline mark regardless of which path below runs
                _tl_mark(req, "resumed")
            match = getattr(req, "_kv_match", None) or ([], 0)
            req._kv_match = None
            if getattr(req, "_offload", None) is not None:
                self._restore_into(slot, req)
            elif getattr(req, "_kv_import", None) is not None and \
                    self._import_handoff(slot, req):
                pass  # scattered + attached; failure fell through below
            elif self.chunked_prefill or self.ragged:
                req._pf_feed = self._feed_ids(req)
                req._pf_cursor = 0
                # seed the first token iff it was never seeded: a
                # resumed DECODING request keeps its pending next_token
                # (output non-empty), while a fresh request or a victim
                # evicted mid-prefill (output still empty) needs one
                req._pf_sample = not req.output
                req._resume = False
                req.slot = slot
                req._admit_order = self._order
                self._order += 1
                self._attach(slot, req)
                self._stage_tokbuf(slot, req)
                if match[0]:
                    # cached prefix: map the shared pages in and start
                    # the chunk feed at the first uncached token
                    self._map_prefix(slot, match)
                    req._pf_cursor = match[1]
                self._note_prefix_admit(req, match)
            elif match[0]:
                self._prefill_suffix_into(slot, req, match)
            else:
                self._note_prefix_admit(req, match)
                reqs.append(req)
                slots.append(slot)
        take = len(reqs)
        if take == 0:
            return
        if take == 1:
            self._prefill_into(slots[0], reqs[0])
            return
        feeds = [self._feed_ids(r) for r in reqs]
        for r in reqs:
            _tl_count(r, "prefill")
        lens = [len(f) for f in feeds]
        total = sum(lens)
        self.prefill_tokens += total
        bucket = self._bucket_for(total)
        ids = np.zeros((bucket,), np.int64)
        cu = np.zeros((self.max_seqs + 1,), np.int32)
        off = 0
        for i, f in enumerate(feeds):
            ids[off:off + lens[i]] = f
            off += lens[i]
            cu[i + 1] = off
        cu[take + 1:] = off  # unused tail: zero-length segments
        # `prefill_varlen`'s epilogue is already row-sparse (one final
        # row per packed segment)
        self.logit_rows += self.max_seqs
        with record_span("serving.prefill"):
            logits, k_all, v_all = prefill_varlen(
                self.params, jnp.asarray(ids), jnp.asarray(cu),
                self.config, use_pallas=self._use_pallas_prefill,
                interpret=self._interpret)
        # ONE bucket-shaped scatter for the whole packed buffer: per-
        # request slices would give every distinct prompt length its own
        # scatter shape, and each shape is a fresh XLA compile (~100 ms
        # on CPU) — measured 96 compiles in
        # 65 steps before this, drowning steady-state decode
        pg, off = self._packed_indices(k_all.shape[2])
        # every admitted request's first-token logits row comes over in
        # one batched read through the engine's sanctioned reader —
        # np.asarray(logits[i]) inside the loop was a blocking round
        # trip per admission (tpulint TPL001)
        seed_idx = [i for i, req in enumerate(reqs)
                    if not getattr(req, "_resume", False)]
        seed_rows = dict(zip(seed_idx, self._fetch_results(
            logits[jnp.asarray(seed_idx, jnp.int32)]))) \
            if seed_idx else {}
        for i, (slot, req) in enumerate(zip(slots, reqs)):
            a = int(cu[i])
            self._fill_indices(pg, off, slot, a, lens[i])
            req.slot = slot
            req._admit_order = self._order
            self._order += 1
            self._attach(slot, req)
            # index BEFORE seeding: a max_new_tokens==1 request
            # finishes (and releases) inside _seed_first_token
            self._index_slot(slot, req)
            if getattr(req, "_resume", False):
                # resuming after preemption: next_token was already
                # sampled before eviction — do NOT re-sample it
                req._resume = False
            else:
                self._seed_first_token(slot, req, seed_rows[i])
        self._scatter_packed(k_all, v_all, pg, off)

    def _packed_indices(self, t):
        """Fresh (page, offset) index arrays of length t, pointing at
        the trash page — bucket-static shapes keep the scatter compile
        count at one per bucket."""
        pg = np.full((t,), self.num_pages - 1, np.int32)
        off = (np.arange(t) % self.page_size).astype(np.int32)
        return pg, off

    def _fill_indices(self, pg, off, slot, start, S):
        """Point positions start..start+S at slot's freshly-allocated
        pages and set its length."""
        n_pages = -(-S // self.page_size)
        self._seq_pages[slot] = []
        pages = self._alloc_pages(slot, n_pages)
        pos = np.arange(S)
        pg[start:start + S] = np.asarray(pages)[pos // self.page_size]
        off[start:start + S] = pos % self.page_size
        self.lengths[slot] = S

    def _scatter_packed(self, kq, vq, pg, off):
        """Scatter packed per-layer K/V (L, KVH, T, D) into the pools
        at (pg, off) — trash-page tail positions absorb the padding."""
        if self.cache_quant:
            kq, ks = quantize_kv(kq)
            vq, vs = quantize_kv(vq)
            self.k_scale = self.k_scale.at[:, :, pg, off].set(ks)
            self.v_scale = self.v_scale.at[:, :, pg, off].set(vs)
        self.k_pool = self.k_pool.at[:, :, pg, off].set(
            kq.astype(self.k_pool.dtype))
        self.v_pool = self.v_pool.at[:, :, pg, off].set(
            vq.astype(self.v_pool.dtype))

    def _scatter_prompt(self, slot, kq, vq, S):
        """Scatter one prompt's per-layer K/V (L, KVH, T>=S, D) into
        fresh pages for `slot`; positions past S land on the trash
        page (pass the PADDED buffer — slicing to S would recompile
        per prompt length)."""
        pg, off = self._packed_indices(kq.shape[2])
        self._fill_indices(pg, off, slot, 0, S)
        self._scatter_packed(kq, vq, pg, off)

    def _alloc_pages(self, slot, n, gc=None):
        """n more pages for `slot` in cache group `gc` (group 0 unless
        given), at the ordinals after its last held page."""
        gc = gc or self._caches[0]
        if not gc.pool.can_alloc(n):
            raise RuntimeError("serving: out of KV pages")
        start = gc.end(slot)
        if start + n > self.pages_per_seq:
            raise RuntimeError("serving: sequence exceeds max_seq_len")
        pages = gc.pool.alloc(n)
        gc.seq_pages[slot].extend(pages)
        for i, pg in enumerate(pages):
            gc.table[slot, start + i] = pg
        m = self.metrics
        if m is not None:
            m.on_page_alloc(n)
        return pages

    def _prefill_into(self, slot, req: Request):
        c = self.config
        feed = self._feed_ids(req)
        S = len(feed)
        self.prefill_tokens += S
        _tl_count(req, "prefill")
        bucket = self._bucket_for(S)
        ids = np.zeros((1, bucket), np.int64)
        ids[0, :S] = feed
        # `prefill`'s epilogue is already row-sparse (one final row)
        self.logit_rows += 1
        with record_span("serving.prefill"):
            logits, k_all, v_all = prefill(
                self.params, jnp.asarray(ids), jnp.asarray(S), c,
                use_pallas=self._use_pallas_prefill)
        self._scatter_prompt(slot, k_all, v_all, S)
        req.slot = slot
        req._admit_order = self._order
        self._order += 1
        self._attach(slot, req)
        self._index_slot(slot, req)
        if getattr(req, "_resume", False):
            req._resume = False  # next_token survives from before eviction
        else:
            self._seed_first_token(
                slot, req, self._fetch_results(logits).reshape(-1))

    def _preempt_one(self, exclude):
        """Evict the most-recently admitted active request (never
        `exclude`): pages return to the pool and the request re-enters
        the HEAD of the waiting queue. Under preempt_policy="offload"
        its KV pages are first copied to host memory (resume = scatter
        back, no recompute); under "recompute" resume re-prefills
        prompt + generated-so-far. Returns False when nothing can be
        evicted."""
        # mid-chunked-prefill slots ARE evictable: their chunk state
        # (_pf_feed/_pf_cursor) lives on the Request, so offload resumes
        # the feed exactly where it stopped (cursor == saved length) and
        # recompute re-feeds the prompt from the start
        victims = [s for s, r in enumerate(self._slots)
                   if r is not None and s != exclude]
        if not victims:
            return False
        s = max(victims, key=lambda v: self._slots[v]._admit_order)
        req = self._slots[s]
        if self.preempt_policy == "offload":
            payload = {}
            for i, gc in enumerate(self._caches):
                n_pg = len(gc.seq_pages[s])
                # gather at the FIXED pages_per_seq width (tail reads
                # the trash page, sliced off after the transfer): a
                # per-count gather shape would be a fresh XLA compile
                # per eviction size
                pg = np.full((self.pages_per_seq,), gc.num_pages - 1,
                             np.int32)
                pg[:n_pg] = gc.seq_pages[s]
                # group 0 under the bare names, as it always was
                payload.update({
                    k + (f".{i}" if i else ""):
                    None if a is None else a[:, :, :n_pg]
                    for k, a in gc.gather(pg).items()})
            # the KV itself parks in the host tier's PINNED stash —
            # one host-RAM ledger with the spilled prefix pages (no
            # second ad-hoc store); the request carries only shape
            # metadata. Stored verbatim: a resume must be exact.
            self.host_tier.stash_put(
                id(req), payload,
                sum(len(gc.seq_pages[s]) for gc in self._caches))
            _tl_mark(req, "spill")
            req._offload = {
                "len": int(self.lengths[s]),
                # actual page counts a group, NOT ceil(len/page_size):
                # a victim evicted right after its boundary growth
                # already holds the next (still-empty) page; and the
                # ordinal its first held page had (a window's is not 0)
                "pages": [len(gc.seq_pages[s]) for gc in self._caches],
                "base": [int(gc.base[s]) for gc in self._caches],
            }
        req._resume = True
        req.slot = None
        _tl_mark(req, "preempted")
        self._waiting.insert(0, req)
        _flight.record(
            "engine.preempt", rid=str(req.rid),
            policy=self.preempt_policy, slot=s,
            tokens=len(req.output), pages=len(self._seq_pages[s]))
        flagged = getattr(req, "_handoff_export", False)
        self._release(s)
        if flagged:
            # a preempted export candidate stays one: re-arm the flag
            # _release just consumed so the re-admission still hands off
            req._handoff_export = True
            self._handoff_pending += 1
        self.preemptions += 1
        m = self.metrics
        if m is not None:
            m.on_preempt(self.preempt_policy)
        return True

    def _restore_into(self, slot, req: Request):
        """Swap-in: scatter a host-offloaded request's KV pages into
        fresh device pages. No prefill compute; the pending next_token
        survived eviction on the Request itself."""
        o = req._offload
        S = o["len"]
        p = self.host_tier.stash_take(id(req))
        for i, (gc, n_pages, base) in enumerate(zip(
                self._caches, o["pages"], o["base"])):
            part = {k: p[k + (f".{i}" if i else "")] for k in gc.keys()}
            gc.seq_pages[slot] = []
            gc.base[slot] = base
            pages = self._alloc_pages(slot, n_pages, gc)
            self._scatter_host_pages(pages, part, gc)
        self.lengths[slot] = S
        req._offload = None
        req._resume = False
        req.slot = slot
        req._admit_order = self._order
        self._order += 1
        self._attach(slot, req)
        self._stage_tokbuf(slot, req)

    def _scatter_host_kv(self, pages, k, v, ks, vs, gc=None):
        """Scatter host-resident page KV (np, (L, KVH, n, page, D))
        into device `pages` of cache group `gc` (group 0 unless given)
        — the single swap-in path shared by preemption restore and
        host-tier restore. Scatters at the fixed pages_per_seq width
        (tail -> trash page), mirroring the offload gather: one compile
        total, not one per page count."""
        self._scatter_host_pages(
            pages, {"k": k, "v": v, "ks": ks, "vs": vs},
            gc or self._caches[0])

    def _scatter_host_pages(self, pages, host, gc):
        """`_scatter_host_kv` for any cache group: `host` holds the
        group's arrays by `gc.keys()`."""
        n = len(pages)
        ppseq = self.pages_per_seq
        pg = np.full((ppseq,), gc.num_pages - 1, np.int32)
        pg[:n] = pages

        def pad(a):
            if a is None:
                return None
            out = np.zeros(a.shape[:2] + (ppseq,) + a.shape[3:], a.dtype)
            out[:, :, :n] = a
            return out
        gc.scatter(pg, {k: pad(a) for k, a in host.items()})

    def _drop_offload(self, req):
        """Forget a waiting request's host-stashed KV (cancel/failure
        paths) — the tier ledger must not keep bytes for a request
        that will never resume."""
        if getattr(req, "_offload", None) is not None:
            self.host_tier.stash_discard(id(req))
        req._offload = None

    @staticmethod
    def _prefilling(req):
        """True while a chunked-prefill admission still has prompt
        tokens to feed."""
        feed = getattr(req, "_pf_feed", None)
        return feed is not None and req._pf_cursor < len(feed)

    def _seed_first_token(self, slot, req, row):
        """Sample/argmax the first generated token from the prefill's
        final-position logits `row` (np, (V,)) — single source for the
        monolithic, varlen-batch, and chunked prefill completions."""
        tok = req.pick(row) if req.temperature > 0.0 else int(np.argmax(row))
        req.next_token = tok
        req.output.append(tok)
        req.note_logprob(tok, row)
        self._note_emit(req, 1)
        if req.done:  # e.g. max_new_tokens == 1
            self.finished.append(req)
            self._note_finish(req)
            self._release(slot)
        elif self.tok_buf is not None:
            # the host-picked seed is the token position `lengths`
            # consumes next — poke it into the device token ring
            self.tok_buf = _tokbuf_poke(
                self.tok_buf, np.int32(slot),
                np.int32(int(self.lengths[slot])), np.int32(tok))

    # -- disaggregated prefill/decode handoff -----------------------------
    def _clear_handoff_flag(self, req):
        """Consume a request's export flag, keeping the fast-path
        pending counter honest. Safe to call on unflagged requests."""
        if getattr(req, "_handoff_export", False):
            req._handoff_export = False
            self._handoff_pending = max(0, self._handoff_pending - 1)

    def _harvest_handoffs(self):
        """Export-and-finish every live slot flagged for handoff whose
        prompt is fully prefilled and seeded. Runs at the top of each
        launch, BEFORE decode planning: a slot only becomes eligible
        the launch after its seeding finish, and that previous launch
        skipped it (next_token was still None), so no in-flight wave
        touches the slot — its KV is exactly prompt-complete and
        `lengths` was never advanced past the prompt."""
        if self._handoff_pending <= 0:
            return
        if self.host_tier is None:
            # no tier, no export path: flagged requests simply decode
            # locally to completion (flags clear at release)
            return
        for s in sorted(self._live):
            req = self._slots[s]
            if req is None or not getattr(req, "_handoff_export", False):
                continue
            if req.next_token is None or self._prefilling(req):
                continue  # prefill (or its seeding fetch) still pending
            self._export_handoff(s, req)
        # mirror immediately: if that was the last live slot the engine
        # idles, and no later on_step would carry the export deltas
        # (counters + duration) onto /metrics
        m = self.metrics
        if m is not None:
            m.on_handoff(self)

    def _export_handoff(self, s, req):
        """Ship slot `s`'s KV pages out as a KVHandoff and finish the
        request here with state "handoff" (the decode replica owns the
        rest of its life). The gather/fence/quantize runs on the tier's
        copy thread (`HostTier.export_pages`) — same explicit-fence
        discipline as a spill, nothing syncs the pump thread's device
        queue beyond the blocking wait itself. On ANY failure the slot
        is left exactly as it was and the request simply keeps decoding
        locally — degradation, never a drop."""
        t0 = time.perf_counter()
        self._clear_handoff_flag(req)
        n_pg = len(self._seq_pages[s])
        # fixed-width gather like _preempt_one: tail reads trash page,
        # sliced off host-side — one XLA gather shape for all exports
        pg = np.full((self.pages_per_seq,), self.num_pages - 1, np.int32)
        pg[:n_pg] = self._seq_pages[s]
        try:
            p = self.host_tier.export_pages(
                self.k_pool[:, :, pg], self.v_pool[:, :, pg],
                None if self.k_scale is None else self.k_scale[:, :, pg],
                None if self.v_scale is None else self.v_scale[:, :, pg],
                prequantized=self.cache_quant, rids=[str(req.rid)])
        except Exception as e:
            self.handoff_failures += 1
            _flight.record("handoff.fail", rid=str(req.rid),
                           trace_id=getattr(req, "_trace_id", None),
                           where="export", error=repr(e))
            return  # slot untouched -> local decode from here on
        _tl_mark(req, "handoff_export")
        tl = getattr(req, "_timeline", None)
        h = KVHandoff(
            rid=req.rid, prompt=req.prompt, output=req.output,
            next_token=int(req.next_token), length=int(self.lengths[s]),
            pages=n_pg,
            k=p["k"][:, :, :n_pg], v=p["v"][:, :, :n_pg],
            ks=None if p["ks"] is None else p["ks"][:, :, :n_pg],
            vs=None if p["vs"] is None else p["vs"][:, :, :n_pg],
            quantized=p["ks"] is not None,
            trace_id=getattr(req, "_trace_id", None),
            logprobs=req.logprobs, cached_tokens=req.cached_tokens,
            timeline=None if tl is None else tl.to_dict())
        req._handoff_done = h
        self.handoff_exports += 1
        self.handoff_bytes += h.nbytes
        self._handoff_times.append(time.perf_counter() - t0)
        _flight.record("handoff.export", rid=str(req.rid),
                       trace_id=h.trace_id, pages=n_pg, bytes=h.nbytes,
                       tokens=h.length)
        # finish WITHOUT _note_finish: the decode replica completes the
        # request; this replica's ledger records it as a handoff.
        self.finished.append(req)
        self._release(s)  # indexes the prefix first -> source keeps cache
        req.slot = None

    def _import_handoff(self, slot, req):
        """Decode-side scatter of a KVHandoff into fresh pages (the
        preemption swap-in path, `_scatter_host_kv`), adapting the wire
        encoding to this pool's dtype host-side. Returns True on
        success; on ANY failure the fresh pages are returned to the
        pool (crash_reset-grade release discipline) and the caller
        falls back to the recompute-resume prefill path — token-
        identical replay, never a dropped request."""
        h = req._kv_import
        t0 = time.perf_counter()
        self._seq_pages[slot] = []
        try:
            # fault point BEFORE the alloc: a raise here leaks nothing
            self._fire("handoff_import", rids=[str(req.rid)])
            pages = self._alloc_pages(slot, h.pages)
            try:
                k, v, ks, vs = h.k, h.v, h.ks, h.vs
                if ks is not None and not self.cache_quant:
                    k, v = _dequantize_host(k, ks), _dequantize_host(v, vs)
                    ks = vs = None
                elif ks is None and self.cache_quant:
                    k, ks = _quantize_host(k)
                    v, vs = _quantize_host(v)
                self._scatter_host_kv(pages, k, v, ks, vs)
            except BaseException:
                self.pool.decref(pages)
                self._seq_pages[slot] = []
                self.page_table[slot, :] = self.num_pages - 1
                raise
        except Exception as e:
            self.handoff_failures += 1
            _flight.record("handoff.fail", rid=str(req.rid),
                           trace_id=h.trace_id, where="import",
                           error=repr(e))
            req._kv_import = None
            req._resume = True  # recompute path: prompt + output[:-1]
            return False
        self.lengths[slot] = h.length
        _tl_mark(req, "handoff_import")
        req._kv_import = None
        req._resume = False
        req.slot = slot
        req._admit_order = self._order
        self._order += 1
        self._attach(slot, req)
        self._stage_tokbuf(slot, req)
        self._index_slot(slot, req)
        self.handoff_imports += 1
        self.handoff_bytes += h.nbytes
        self._handoff_times.append(time.perf_counter() - t0)
        _flight.record("handoff.import", rid=str(req.rid),
                       trace_id=h.trace_id, pages=h.pages, bytes=h.nbytes,
                       tokens=h.length)
        return True

    # -- decode loop ------------------------------------------------------
    def step(self):
        """One decode step for all active slots; returns #active.
        Synchronous driver: launch + consume in one call. The pipelined
        pump calls `step_launch`/`step_finish` itself so the consume of
        step N overlaps the device executing step N+1."""
        self._admit_turn()
        if self.spec_decode > 1:
            return self._spec_step()
        t = self.step_launch(_admitted=True)
        return 0 if t is None else self.step_finish(t)

    def _admit_turn(self):
        """The head of every turn: cancels, handoffs, admission (with
        `_stage_tokbuf`'s device call) — the engine's share of the
        turn's `admit` part."""
        with record_span("serving.admit", part="admit"):
            self._sweep_cancelled()
            self._harvest_handoffs()
            self._admit()

    def _note_launch_gap(self, depth):
        """Host-gap + pipeline-depth telemetry, taken at the instant a
        decode/verify program is about to dispatch: the wall time since
        the previous dispatch RETURNED is exactly how long the device
        had no step-loop program queued behind the running one."""
        now = time.perf_counter()
        m = self.metrics
        if self._t_launch_end is not None:
            self.last_host_gap_s = now - self._t_launch_end
            if m is not None:
                m.observe_host_gap(self.last_host_gap_s)
        self.pipeline_depth = depth
        if m is not None:
            m.set_pipeline_depth(depth)

    def step_launch(self, carry=None, _admitted=False):
        """Admission + page growth + ONE step dispatch, with NO device
        read: returns a ticket whose result record is still on device
        (None when nothing runs). `carry` is the previous, still-
        unconsumed ticket of a RAGGED engine — continuing slots take
        their input token from the device token ring, so the host
        launches step N+1 knowing nothing about step N. A bucketed
        engine's step returns new pools and is driven synchronously:
        launch, finish, launch.

        A carried slot that will exhaust max_new_tokens in the
        in-flight step is NOT launched (its finish is host-predictable);
        an eos finish is not, so such a slot runs one discarded zombie
        step and `step_finish` rolls its length back. Raises
        PipelineStall instead of preempting while carrying — the
        victim's pending token is still in flight."""
        if self.ragged:
            return self._ragged_launch(carry=carry, _admitted=_admitted)
        if carry is not None:
            raise ValueError("a bucketed engine is driven synchronously: "
                             "finish the step in flight before the next")
        if not _admitted:
            self._sweep_cancelled()
            self._harvest_handoffs()
            self._admit()
        # page-growth pass with preemption, over OCCUPIED slots only: a
        # slot about to cross a page boundary must get a page; when the
        # (oversubscribed) pool is dry, evict the most recent admission
        # rather than dying deep in the allocator
        for s in sorted(self._live):
            cur = int(self.lengths[s])
            if cur % self.page_size == 0 and cur > 0 and \
                    len(self._seq_pages[s]) * self.page_size <= cur:
                while not self.pool.can_alloc(1):
                    if not self._preempt_one(exclude=s):
                        raise RuntimeError(
                            "serving: KV page pool exhausted with a "
                            "single active sequence — num_pages is too "
                            "small for max_seq_len")
                self._alloc_pages(s, 1)
        if not self._live:
            self._t_launch_end = None
            return None
        B = self.max_seqs
        tokens = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        top_ps = np.ones((B,), np.float32)
        keys = np.zeros((B, 2), np.uint32)
        eos = np.full((B,), -1, np.int32)
        remaining = np.ones((B,), np.int32)
        launch, reqs = [], {}
        for s in sorted(self._live):
            req = self._slots[s]
            launch.append(s)
            reqs[s] = req
            tokens[s] = req.next_token
            temps[s] = req.temperature
            top_ks[s] = req.top_k
            top_ps[s] = req.top_p
            if req._base_key is not None:
                keys[s] = req._base_key
            if req.eos_id is not None:
                eos[s] = int(req.eos_id)
            remaining[s] = req.max_new_tokens - len(req.output)
        active = np.zeros((B,), bool)
        active[launch] = True
        self.lengths = np.where(active, self.lengths + 1, self.lengths)
        self._note_sampler(temps, top_ks, top_ps)
        sample = {"temp": jnp.asarray(temps),
                  "top_k": jnp.asarray(top_ks),
                  "top_p": jnp.asarray(top_ps),
                  "key": jnp.asarray(keys),
                  "eos": jnp.asarray(eos),
                  "remaining": jnp.asarray(remaining)}
        # fault point: one hit per decode dispatch, with the launched
        # request ids so rid-scoped rules can model a poison request
        self._fire("step_launch", rids=[str(reqs[s].rid) for s in launch])
        self._note_launch_gap(0)
        # bucketed decode is one row per slot already — no rows to skip
        self.logit_rows += B
        # page_table/lengths go to the device as SNAPSHOTS (.copy(), a
        # few hundred bytes): jnp.asarray may zero-copy a numpy buffer
        # on CPU, and the host mutates both tables in place (release /
        # admission) as soon as the results land — while the same
        # step's K/V scatter thunks can still be reading them under
        # XLA's async thunk runtime. Observed as a rare (<1%)
        # final-token corruption under concurrent serving load.
        with record_span("serving.decode_step"):
            (self.k_pool, self.v_pool, self.k_scale, self.v_scale,
             _logits, rec) = decode_step(
                self.params, self.k_pool, self.v_pool,
                jnp.asarray(self.page_table.copy()),
                jnp.asarray(self.lengths.copy()),
                jnp.asarray(tokens), jnp.asarray(active),
                self.config, self.page_size, use_pallas=self._use_pallas,
                interpret=self._interpret, k_scale=self.k_scale,
                v_scale=self.v_scale, mesh=self._mesh, sample=sample)
        self._t_launch_end = time.perf_counter()
        self.device_steps += 1
        return StepTicket(launch, reqs, rec[0], rec[1], rec[2])

    def step_finish(self, ticket, inflight=None):
        """Consume a launched step: ONE batched transfer of a few ints
        per slot (the device already sampled and evaluated the stop
        conditions), then the host bookkeeping. `inflight` is the
        ticket launched AFTER this one (a ragged engine under the deep
        pump): a slot that finishes here already ran one step past its
        end in `inflight`, so its entry there is zombied and its length
        rolled back — release/indexing then see exactly the synchronous
        loop's state."""
        if self.ragged:
            return self._ragged_finish(ticket, inflight=inflight)
        self._fire("step_finish",
                   rids=[str(r.rid) for r in ticket.reqs.values()
                         if r is not None])
        nxt, done, lp = self._fetch_results(
            (ticket.next_tok, ticket.done, ticket.logprob))
        for s in ticket.slots:
            req = ticket.reqs.get(s)
            if req is None or self._slots[s] is not req:
                continue  # zombie: slot released/reused since launch
            tok = int(nxt[s])
            req.output.append(tok)
            req.next_token = tok
            if req.want_logprobs:
                req.logprobs.append(float(lp[s]))
            self._note_emit(req, 1)
            if bool(done[s]):
                self.finished.append(req)
                self._note_finish(req)
                self._release(s)
        self._note_step(len(ticket.slots))
        return len(ticket.slots)

    def _ragged_launch(self, carry=None, _admitted=False):
        """Ragged twin of `step_launch`: ONE `unified_step` dispatch
        serving every live slot — single-token decode rows AND
        chunked-prefill feeds — as rows of a flat (slot, pos, token)
        descriptor buffer. No padding buckets: the buffer holds exactly
        the tokens fed (unused tail rows carry pos=-1 and the kernel
        skips them), so the mix changing between waves never changes
        the trace signature. State (lengths, prefill cursors) advances
        AT LAUNCH so a pipelined launch N+1 plans against consistent
        state; `step_finish`-side rollback (eos zombie) is identical to
        the bucketed path. A slot whose prefill completed in the
        in-flight wave is unseeded (next_token None) and sits out one
        wave — its first token is picked host-side at finish, the PR 8
        seeding convention, so outputs stay token-identical."""
        if not _admitted:
            self._admit_turn()
        with record_span("serving.plan", part="plan"):
            plan = self._ragged_plan(carry)
        if plan is None:
            return None
        (tokens, tok_slot, tok_pos, sampling, need, flat, reqs, seeds,
         n_decode, slots) = plan
        with record_span("serving.stage", part="dispatch"):
            # every host->device transfer of the wave, a dozen small
            # arrays, BEFORE the launch-gap stamp: the gap then ends
            # where the dispatch starts
            sample = {k: jnp.asarray(v) for k, v in sampling.items()}
            need_rows = jnp.asarray(need)
            # page_table goes to the device as a SNAPSHOT (.copy()):
            # see the bucketed `step_launch`
            tables = tuple(jnp.asarray(gc.table.copy())
                           for gc in self._caches)
            staged = (jnp.asarray(tokens), jnp.asarray(tok_slot),
                      jnp.asarray(tok_pos))
            # device token ring: decode rows write their sampled token
            # for the next wave to read
            bw = np.zeros((self.need_buf,), bool)
            bw[:n_decode] = True
            buf_write = jnp.asarray(bw)
        self._note_launch_gap(1 if carry is not None else 0)
        with record_span("serving.unified_step", part="dispatch",
                         ring=True):
            caches, logits, rec, self.tok_buf, aux = self.model.step(
                self.params, tuple(gc.device() for gc in self._caches)
                + tuple(self._slot_state),
                tables, *staged, self.config, self.page_size,
                use_pallas=self._use_pallas, interpret=self._interpret,
                sample=sample, need_rows=need_rows,
                block_q=self._block_q, block_pages=self._block_pages,
                tok_buf=self.tok_buf, buf_write=buf_write)
        for gc, got in zip(self._caches, caches):
            gc.take(got)
        self._slot_state = list(caches[len(self._caches):])
        seed_rows = None
        if seeds:
            with record_span("serving.seed_gather", part="dispatch"):
                # seed rows were gathered into need positions n_decode..
                seed_rows = logits[jnp.arange(
                    n_decode, n_decode + len(seeds), dtype=jnp.int32)]
        self._t_launch_end = time.perf_counter()
        self.device_steps += 1
        return RaggedTicket(reqs, flat, rec[0], rec[1], rec[2], seeds,
                            seed_rows, slots, aux, rows=self.last_rows)

    def _grow_to(self, s, end, carry, what):
        """Pages for slot s in every cache group up to ordinal `end`
        (exclusive), evicting the newest admission where a pool is dry;
        with a step in flight that is a PipelineStall, the victim's
        pending token being still on the device."""
        for gc in self._caches:
            while gc.end(s) < end:
                while not gc.pool.can_alloc(1):
                    if carry is not None:
                        raise PipelineStall(
                            f"{what} growth needs a preemption victim "
                            "with a step in flight")
                    if not self._preempt_one(exclude=s):
                        raise RuntimeError(
                            "serving: KV page pool exhausted with a "
                            "single active sequence — num_pages is too "
                            "small for max_seq_len")
                self._alloc_pages(s, 1, gc)

    def _window_release(self):
        """Give back, for every live slot and windowed group, the pages
        that lie wholly behind what the slot's next row can see: the row
        this turn feeds first sits at `lengths[s]` and sees columns from
        `lengths[s] - (window - 1)` on, and every later row sees less.
        The ragged kernel's walk starts at that column's block and its
        page fetch at that column's page, so a page given back here is
        never read again; the device runs the steps in order, so one
        re-issued in this same turn is written only after the step in
        flight (whose rows sat one position lower, and which snapshotted
        its own page table) has read it."""
        with record_span("serving.window_release", part="plan"):
            live = np.fromiter(self._live, np.int64, len(self._live))
            for gc in self._windowed:
                # the first page each live slot still needs; most turns
                # pass no page's edge, so only the slots that did loop
                first = (self.lengths[live].astype(np.int64)
                         - (gc.spec.window - 1)) // self.page_size
                due = first > gc.base[live]
                for s, f in zip(live[due].tolist(), first[due].tolist()):
                    n = f - int(gc.base[s])
                    pages = gc.seq_pages[s]
                    gc.pool.decref(pages[:n])
                    del pages[:n]
                    gc.table[s, gc.base[s]:f] = gc.num_pages - 1
                    gc.base[s] = f
                    gc.released += n

    def _note_sampler(self, temps, top_ks, top_ps):
        """Count a wave for `pt_sampler_filter_steps` /
        `pt_sampler_draw_steps` from the per-slot arrays the step is
        about to get: the predicates of `_filtered_logits` and
        `_filter_draw`, in the same float32."""
        sampled = temps > 0.0
        self.sampler_draw_steps += int(sampled.any())
        self.sampler_filter_steps += int(
            (sampled & ((top_ks > 0) | (top_ps < 1.0))).any())

    def _ragged_plan(self, carry):
        """The host half of `_ragged_launch` up to the transfers: page
        growth, the decode and prefill plans, and the wave's numpy
        descriptors. Advances the engine's state (lengths, cursors,
        row counters); returns None when nothing runs this wave."""
        if self._windowed:
            self._window_release()
        # decode-boundary page growth, bucketed logic verbatim (mid-
        # prefill slots grow against their own chunk below)
        for s in sorted(self._live):
            if self._prefilling(self._slots[s]):
                continue
            cur = int(self.lengths[s])
            if cur % self.page_size == 0 and cur > 0:
                self._grow_to(s, cur // self.page_size + 1, carry, "page")
        if not self._live:
            self._t_launch_end = None
            return None
        # plan decode rows (no state mutation yet — preemption during
        # the feed-growth pass below may still evict a planned slot)
        decode_plan = []
        for s in sorted(self._live):
            req = self._slots[s]
            if self._prefilling(req):
                continue
            if req.next_token is None:
                continue  # seeding rides the in-flight wave's finish
            carried = carry is not None and carry.reqs.get(s) is req
            left = req.max_new_tokens - len(req.output) \
                - (1 if carried else 0)
            if left <= 0:
                continue  # the in-flight step emits its last token
            decode_plan.append((s, req, left))
        # plan prefill feeds into the remaining buffer rows, growing
        # pages for every real chunk position now
        room = self.ragged_buf - len(decode_plan)
        prefill_plan = []
        for s in sorted(self._live):
            req = self._slots[s]
            if not self._prefilling(req):
                continue
            n = min(len(req._pf_feed) - req._pf_cursor, room)
            if n <= 0:
                continue  # buffer full this wave; slot feeds next wave
            self._grow_to(
                s, -(-(int(self.lengths[s]) + n) // self.page_size), carry,
                "prefill")
            prefill_plan.append((s, req, n))
            room -= n
        # a preemption above may have evicted a planned slot
        decode_plan = [p for p in decode_plan if self._slots[p[0]] is p[1]]
        prefill_plan = [p for p in prefill_plan
                        if self._slots[p[0]] is p[1]]
        if not decode_plan and not prefill_plan:
            return None  # every occupied slot is finishing/seeding
        T = self.ragged_buf
        B = self.max_seqs
        tokens = np.zeros((T,), np.int32)
        tok_slot = np.zeros((T,), np.int32)
        tok_pos = np.full((T,), -1, np.int32)
        temps = np.zeros((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        top_ps = np.ones((B,), np.float32)
        keys = np.zeros((B, 2), np.uint32)
        eos = np.full((B,), -1, np.int32)
        remaining = np.ones((B,), np.int32)
        flat, reqs = {}, {}
        row = 0
        for s, req, left in decode_plan:
            tok_slot[row] = s
            tok_pos[row] = int(self.lengths[s])
            # NO token values are shipped: the row's token is
            # device-resident in the ring (staged at admission,
            # scattered by the previous wave, or poked at seeding)
            temps[s] = req.temperature
            top_ks[s] = req.top_k
            top_ps[s] = req.top_p
            if req._base_key is not None:
                keys[s] = req._base_key
            if req.eos_id is not None:
                eos[s] = int(req.eos_id)
            remaining[s] = left
            self.lengths[s] += 1
            flat[s] = row
            reqs[s] = req
            row += 1
        seeds, seed_flat = [], []
        for s, req, n in prefill_plan:
            feed = req._pf_feed
            base = int(self.lengths[s])
            tok_slot[row:row + n] = s
            tok_pos[row:row + n] = base + np.arange(n, dtype=np.int32)
            req._pf_cursor += n
            _tl_count(req, "prefill")
            self.lengths[s] += n
            self.prefill_tokens += n
            if req._pf_cursor >= len(feed):
                # feed complete: index the slot's full pages NOW (the
                # bucketed prefill paths index right after dispatch),
                # so a live decoding slot's prefix is shareable by the
                # very next admission
                self._index_slot(s, req)
                if req._pf_sample:
                    # last chunk: its final row's logits seed the first
                    # generated token host-side at finish
                    seeds.append((s, req))
                    seed_flat.append(row + n - 1)
            row += n
        self.ragged_tokens += row
        # the kernel's work from the same descriptors: unused tail rows
        # carry pos=-1 and count for nothing
        live = (tok_pos + 1).astype(np.int64)
        pairs = int(live.sum())
        self.ragged_attn_pairs += pairs
        kv = np.zeros((B,), np.int64)
        np.maximum.at(kv, tok_slot, live)
        kv_tokens = int(kv.sum())
        self.ragged_kv_tokens += kv_tokens
        on = tok_pos >= 0
        for gc in self._caches:
            # by layer type: a windowed row pairs with at most the
            # window, and a slot's rows read from the first column its
            # first row sees
            w, by = gc.spec.window, self.ragged_by_type[gc.spec.name]
            if gc.spec.select is not None:
                seen = live[on]
                dsa = self.dsa_by_type[gc.spec.name]
                dsa[0] += int(seen.size)
                dsa[1] += int(seen.sum())
                dsa[2] += int(np.minimum(seen, gc.spec.select).sum())
                dsa[3] += int((seen <= gc.spec.select).sum())
            if w is None:
                by[0] += kv_tokens
                by[1] += pairs
                continue
            lo = np.full((B,), np.iinfo(np.int64).max)
            np.minimum.at(lo, tok_slot[on], live[on] - 1)
            by[0] += int(np.sum(np.where(
                kv > 0, kv - np.maximum(lo - (w - 1), 0), 0)))
            by[1] += int(np.minimum(live, w).sum())
        # how the kernel's mechanism engages: its programs a KV head
        # (maximal runs of one slot's consecutive positions) and its
        # loop trips a layer (KV blocks over the kernel's own runs,
        # which also end at a q block's edge)
        cont = (on[1:] & on[:-1] & (tok_slot[1:] == tok_slot[:-1])
                & (tok_pos[1:] == tok_pos[:-1] + 1))
        self.ragged_runs += int(on.sum() - cont.sum())
        edge = np.arange(1, len(tok_pos)) % self._ragged_q_rows == 0
        ends = on & ~np.append(cont & ~edge, False)   # a run's last row
        self.ragged_kv_blocks += int(
            (-(-live[ends] // self._ragged_kv_block)).sum())
        if self.latent_walk:
            walk = _latent_walk(on, cont, live, self._latent_block)
            for booked in self.latent_walk.values():
                for i, n in enumerate(walk):
                    booked[i] += n
        n_decode = len(decode_plan)
        self.last_rows = (n_decode, row - n_decode)
        self._note_sampler(temps, top_ks, top_ps)
        sampling = {"temp": temps, "top_k": top_ks, "top_p": top_ps,
                    "key": keys, "eos": eos, "remaining": remaining}
        # need-row descriptor: decode rows sit at buffer rows
        # 0..n_decode-1 (so flat[s] doubles as the need index) and
        # completed-prefill seed rows follow; -1 pads the fixed shape,
        # so the mix changing never retraces
        need = np.full((self.need_buf,), -1, np.int32)
        need[:n_decode] = np.arange(n_decode, dtype=np.int32)
        need[n_decode:n_decode + len(seed_flat)] = seed_flat
        self.logit_rows += self.need_buf
        self.logit_rows_skipped += T - self.need_buf
        self._fire("step_launch",
                   rids=[str(p[1].rid) for p in decode_plan] +
                        [str(p[1].rid) for p in prefill_plan])
        return (tokens, tok_slot, tok_pos, sampling, need, flat, reqs,
                seeds, n_decode,
                sorted([p[0] for p in decode_plan] +
                       [p[0] for p in prefill_plan]))

    def _ragged_finish(self, ticket, inflight=None):
        """Ragged twin of `step_finish`: ONE batched transfer (decode
        records + completed-prefill logits rows), then host bookkeeping.
        Seeds land first — the bucketed path seeds at admission, before
        any decode consume — then decode rows in slot order with the
        identical zombie / eos-rollback contract."""
        self._fire("step_finish",
                   rids=[str(r.rid) for r in ticket.reqs.values()
                         if r is not None] +
                        [str(r.rid) for _, r in ticket.seeds])
        with record_span("serving.fetch", part="fetch") as fetch:
            nxt, done, lp, seed_rows, aux = self._fetch_results(
                (ticket.next_tok, ticket.done, ticket.logprob,
                 ticket.seed_rows, ticket.aux))
        ticket.t_fetched = fetch.t_end
        with record_span("serving.consume", part="consume"):
            if "moe_rows" in aux:
                # (sparse layers, experts): the rows each expert got
                rows = aux["moe_rows"]
                self.moe_assignments += int(rows.sum())
                self.moe_experts_touched += int((rows > 0).sum())
                self.moe_rows_max_expert += int(rows.max(axis=1).sum())
                picks, among = self.model.experts
                launched = rows, self.ragged_buf * picks, among
                self.moe_row_tiles += row_tile_visits(*launched)
                self.moe_share_spills += share_spills(*launched)
                if "moe_elsewhere" in aux:
                    # the model holds a share of each layer's experts
                    self.moe_rows_elsewhere += int(aux["moe_elsewhere"].sum())
                    held = rows.sum(axis=0).astype(np.int64)
                    self.moe_rows_by_expert = held if \
                        self.moe_rows_by_expert is None else \
                        self.moe_rows_by_expert + held
                if "moe_zero" in aux:
                    self.moe_assignments_zero += int(aux["moe_zero"].sum())
            if "ssm_runs" in aux:
                # a slot has one run a step: as many states moved
                self.ssm_state_slots += int(aux["ssm_runs"])
                self.ssm_runs_fresh += int(aux["ssm_runs_fresh"])
                self.ssm_rows += int(aux["ssm_rows"])
            self._ragged_consume(ticket, inflight, nxt, done, lp,
                                 seed_rows)
        return len(ticket.slots)

    def _ragged_consume(self, ticket, inflight, nxt, done, lp, seed_rows):
        """The host bookkeeping of one fetched wave: seeding, per-slot
        token accounting, releases."""
        if seed_rows is not None:
            for (s, req), rowv in zip(ticket.seeds, seed_rows):
                if self._slots[s] is not req:
                    continue  # zombie: slot released/reused since launch
                self._seed_first_token(s, req, rowv)
        for s in sorted(ticket.flat):
            req = ticket.reqs.get(s)
            if req is None or self._slots[s] is not req:
                continue  # zombie: slot released/reused since launch
            i = ticket.flat[s]
            tok = int(nxt[i])
            req.output.append(tok)
            req.next_token = tok
            if req.want_logprobs:
                req.logprobs.append(float(lp[i]))
            self._note_emit(req, 1)
            if bool(done[i]):
                self.finished.append(req)
                self._note_finish(req)
                if inflight is not None and inflight.reqs.get(s) is req:
                    inflight.reqs[s] = None
                    self.lengths[s] -= 1
                self._release(s)
        self._note_step(len(ticket.slots))

    def _spec_step(self):
        """One speculative verify step: drafts up to G-1 tokens per
        greedy slot by prompt lookup, verifies the whole chunk in one
        forward, emits the accepted prefix + one model token. Exactly
        reproduces plain greedy decode (the model token at the first
        draft divergence is the token plain decode would have picked)."""
        G = self.spec_decode
        active_slots = sorted(self._live)
        if not active_slots:
            self._t_launch_end = None
            return 0
        tokens = np.zeros((self.max_seqs, G), np.int64)
        n_tok = np.ones((self.max_seqs,), np.int32)
        active = np.zeros((self.max_seqs,), bool)
        for s in active_slots:
            req = self._slots[s]
            active[s] = True
            if self._prefilling(req):
                # chunked prefill: the chunk is the next G prompt tokens
                feed, cur = req._pf_feed, req._pf_cursor
                n = min(G, len(feed) - cur)
                tokens[s, :n] = feed[cur:cur + n]
                n_tok[s] = n
                self.prefill_tokens += n
                continue
            tokens[s, 0] = req.next_token
            cur = int(self.lengths[s])
            room = self.max_seq_len - cur - 1
            budget = min(G - 1, room,
                         req.max_new_tokens - len(req.output))
            if budget > 0 and (req.temperature == 0.0 or self.spec_sample):
                # context = everything decided so far incl. the pending
                # next_token (it's the tail the n-gram keys off)
                ctx = req.prompt + req.output
                draft = prompt_lookup_draft(ctx, budget, self.spec_ngram)
                for j, t in enumerate(draft):
                    tokens[s, 1 + j] = t
                n_tok[s] = 1 + len(draft)
                self.spec_drafted += len(draft)
        # page growth: every REAL chunk position needs its page now
        for s in active_slots:
            if self._slots[s] is None:
                continue   # evicted by a preemption for an earlier slot
            need = -(-(int(self.lengths[s]) + int(n_tok[s]))
                     // self.page_size)
            while len(self._seq_pages[s]) < need:
                while not self.pool.can_alloc(1):
                    if not self._preempt_one(exclude=s):
                        raise RuntimeError(
                            "serving: KV page pool exhausted with a "
                            "single active sequence — num_pages is too "
                            "small for max_seq_len")
                self._alloc_pages(s, 1)
        active_slots = sorted(self._live)
        for s in range(self.max_seqs):
            if s not in active_slots:
                active[s] = False
        if not active_slots:
            return 0
        temps = np.zeros((self.max_seqs,), np.float32)
        top_ks = np.zeros((self.max_seqs,), np.int32)
        top_ps = np.ones((self.max_seqs,), np.float32)
        keys = np.zeros((self.max_seqs, 2), np.uint32)
        for s in active_slots:
            req = self._slots[s]
            if self._prefilling(req):
                continue  # chunk feed: nothing sampled on device
            temps[s] = req.temperature
            top_ks[s] = req.top_k
            top_ps[s] = req.top_p
            if req._base_key is not None:
                keys[s] = req._base_key
        self._note_sampler(temps, top_ks, top_ps)
        sample = {"temp": jnp.asarray(temps),
                  "top_k": jnp.asarray(top_ks),
                  "top_p": jnp.asarray(top_ps),
                  "key": jnp.asarray(keys)}
        # one rows dict for the SAMPLING requests only: rejection
        # sampling (speculative_sample) needs the full filtered
        # distribution, so those rows still come to host. Greedy slots
        # — logprobs included — ride the device verify record: the
        # argmax grid and its raw-model logprobs are (B, G) ints and
        # floats, never a vocab row. Everything the host needs this
        # step — grids, sampling rows, and the final-chunk row that
        # seeds a finishing prefill — rides the engine's ONE sanctioned
        # batched read (`_fetch_results`).
        need_rows = [s for s in active_slots
                     if self._slots[s].temperature > 0.0
                     and int(n_tok[s]) > 1
                     and not self._prefilling(self._slots[s])]
        seed_slots = [s for s in active_slots
                      if self._prefilling(self._slots[s])
                      and self._slots[s]._pf_cursor + int(n_tok[s])
                      >= len(self._slots[s]._pf_feed)
                      and self._slots[s]._pf_sample]
        # same fault point as step_launch: one hit per device step,
        # whichever dispatch the engine mode uses
        self._fire("step_launch",
                   rids=[str(self._slots[s].rid) for s in active_slots])
        self._note_launch_gap(0)
        if self.ragged:
            # ragged dispatch: each slot's verify chunk occupies
            # n_tok[s] consecutive rows of the flat buffer; row
            # base[s]+g is sampled with fold lengths+g+1 — the bucketed
            # grid's exact (seed, position) key — so the shared
            # acceptance loop below sees token-identical grids
            base = {}
            row = 0
            for s in active_slots:
                base[s] = row
                row += int(n_tok[s])
            T = self.ragged_buf
            ftok = np.zeros((T,), np.int32)
            fslot = np.zeros((T,), np.int32)
            fpos = np.full((T,), -1, np.int32)
            for s in active_slots:
                n = int(n_tok[s])
                b = base[s]
                ftok[b:b + n] = tokens[s, :n]
                fslot[b:b + n] = s
                fpos[b:b + n] = int(self.lengths[s]) + \
                    np.arange(n, dtype=np.int32)
            self.ragged_tokens += row
            # the wave's rows ARE the needed rows (every chunk position
            # feeds the verify record), so the descriptor is the
            # identity over the packed rows — the unembed runs at
            # need_buf rows, never T. cand carries each row's FOLLOWING
            # draft token so the rejection sampler's accept tests ride
            # the record.
            need = np.full((self.need_buf,), -1, np.int32)
            need[:row] = np.arange(row, dtype=np.int32)
            cand_np = np.zeros((self.need_buf,), np.int32)
            for s in active_slots:
                n = int(n_tok[s])
                if n > 1:
                    cand_np[base[s]:base[s] + n - 1] = tokens[s, 1:n]
            self.logit_rows += self.need_buf
            self.logit_rows_skipped += T - self.need_buf
            with record_span("serving.unified_step"):
                (self.k_pool, self.v_pool, self.k_scale, self.v_scale,
                 logits, rec) = unified_step(
                    self.params, self.k_pool, self.v_pool,
                    jnp.asarray(self.page_table.copy()),
                    jnp.asarray(ftok), jnp.asarray(fslot),
                    jnp.asarray(fpos), self.config, self.page_size,
                    use_pallas=self._use_pallas,
                    interpret=self._interpret, k_scale=self.k_scale,
                    v_scale=self.v_scale, sample=sample,
                    need_rows=jnp.asarray(need),
                    cand_tok=jnp.asarray(cand_np),
                    block_q=self._block_q,
                    block_pages=self._block_pages)
            self._t_launch_end = time.perf_counter()
            self.device_steps += 1
            self._fire("step_finish",
                       rids=[str(self._slots[s].rid)
                             for s in active_slots])
            seed_idx = [base[s] + int(n_tok[s]) - 1 for s in seed_slots]
            # the sampling slots' pull is rec[3]'s candidate
            # probabilities (a float per draft), never vocab rows;
            # divergence/final rows come lazily through `_spec_row_dist`
            tok_f, lp_f, cand_f, seed_vals = self._fetch_results(
                (rec[0], rec[2], rec[3],                  # (N,) each
                 logits[jnp.asarray(seed_idx, jnp.int32)]
                 if seed_slots else None))
            grid = np.zeros((self.max_seqs, G), np.int64)
            lp_grid = np.zeros((self.max_seqs, G), np.float32)
            for s in active_slots:
                n = int(n_tok[s])
                grid[s, :n] = tok_f[base[s]:base[s] + n]
                lp_grid[s, :n] = lp_f[base[s]:base[s] + n]
            cand_by_slot = {
                s: cand_f[base[s]:base[s] + int(n_tok[s]) - 1]
                for s in need_rows}
            flat_logits = logits
            row_of = {s: base[s] for s in active_slots}
            seed_rows = {} if seed_vals is None else \
                dict(zip(seed_slots, seed_vals))
        else:
            cand = None
            if need_rows:
                # the verify grid's record carries candidate
                # probabilities, so sampling slots pull (B, G) floats
                # instead of (n, V) vocab rows
                cand_np = np.zeros((self.max_seqs, G), np.int32)
                for s in need_rows:
                    n = int(n_tok[s])
                    cand_np[s, :n - 1] = tokens[s, 1:n]
                cand = jnp.asarray(cand_np)
            self.logit_rows += self.max_seqs * G
            with record_span("serving.verify_step"):
                (self.k_pool, self.v_pool, self.k_scale, self.v_scale,
                 logits, rec) = verify_step(
                    self.params, self.k_pool, self.v_pool,
                    jnp.asarray(self.page_table.copy()),
                    jnp.asarray(self.lengths.copy()),
                    jnp.asarray(tokens), jnp.asarray(n_tok),
                    jnp.asarray(active), self.config, self.page_size,
                    use_pallas=self._use_pallas,
                    interpret=self._interpret,
                    k_scale=self.k_scale, v_scale=self.v_scale,
                    mesh=self._mesh, sample=sample, cand_tok=cand)
            grid_dev, lp_dev = rec[0], rec[1]
            self._t_launch_end = time.perf_counter()
            self.device_steps += 1
            self._fire("step_finish",
                       rids=[str(self._slots[s].rid)
                             for s in active_slots])
            grid, lp_grid, cand_vals, seed_vals = \
                self._fetch_results(
                    (grid_dev, lp_dev,                    # (B, G) each
                     rec[2] if cand is not None else None,
                     logits[jnp.asarray(seed_slots, jnp.int32),
                            jnp.asarray([int(n_tok[s]) - 1
                                         for s in seed_slots], jnp.int32)]
                     if seed_slots else None))
            cand_by_slot = {} if cand_vals is None else \
                {s: cand_vals[s, :int(n_tok[s]) - 1] for s in need_rows}
            V = logits.shape[-1]
            flat_logits = logits.reshape(-1, V)
            row_of = {s: s * G for s in active_slots}
            seed_rows = {} if seed_vals is None else \
                dict(zip(seed_slots, seed_vals))
        for s in active_slots:
            req = self._slots[s]
            n = int(n_tok[s])
            if self._prefilling(req):
                # chunk fed; emit nothing until the prompt is complete,
                # then the final position's logits seed generation
                req._pf_cursor += n
                _tl_count(req, "prefill")
                self.lengths[s] += n
                if req._pf_cursor >= len(req._pf_feed) and req._pf_sample:
                    self._seed_first_token(s, req, seed_rows[s])
                continue
            if req.temperature > 0.0 and n > 1:
                # speculative sampling: distributionally exact. The
                # accept tests ride the record's candidate
                # probabilities; a distribution row is materialized
                # (device-filtered, `_spec_row_dist`) only on
                # divergence or the final draw.
                outs, a = speculative_sample(
                    lambda g: self._spec_row_dist(
                        flat_logits, row_of[s] + g, req),
                    tokens[s, 1:n], req.rng,
                    cand_probs=cand_by_slot[s])
            elif req.temperature > 0.0:
                # un-drafted sampled slot: the device already drew the
                # token with the SAME (seed, position) key the plain
                # decode path uses — cross-mode seeded parity for free
                outs, a = [int(grid[s, 0])], 0
            else:
                outs = [int(t) for t in grid[s, :n]]
                # accept drafts while they match the model's own choices
                a = 0
                while a < n - 1 and tokens[s, a + 1] == outs[a]:
                    a += 1
                outs = outs[:a + 1]
            self.spec_accepted += a
            emitted = 0
            for j, tok in enumerate(outs):
                req.output.append(tok)
                req.next_token = tok
                if req.want_logprobs:
                    if s in cand_by_slot:
                        # sampled slot: pull THIS emission's raw row
                        # (logprobs opt-in pays per-token, the default
                        # path stays narrow)
                        req.note_logprob(tok, self._fetch_results(
                            flat_logits[row_of[s] + j]))
                    else:
                        # greedy: emitted token j IS the grid token at
                        # j, whose raw-model logprob came on device
                        req.logprobs.append(float(lp_grid[s, j]))
                emitted += 1
                if req.done:
                    break
            # cache retains chunk tokens 0..emitted-1 (the pending token
            # + the drafts CONSUMED to produce the emissions)
            self.lengths[s] += emitted
            self._note_emit(req, emitted)
            if req.done:
                self.finished.append(req)
                self._note_finish(req)
                self._release(s)
        self._note_step(len(active_slots))
        return len(active_slots)

    def _release(self, slot):
        req = self._slots[slot]
        if req is not None:
            self._clear_handoff_flag(req)
            # a finished/cancelled/preempted slot's KV is valid up to
            # `lengths` — index its full pages so later admissions
            # sharing the prefix skip their prefill
            self._index_slot(slot, req)
        for gc in self._caches:
            # decref tail-first: deepest blocks park least-recently-
            # used, so eviction reclaims children before the prefixes
            # they need
            gc.pool.decref(reversed(gc.seq_pages[slot]))
            gc.seq_pages[slot] = []
            gc.base[slot] = 0
            # re-point the freed row at the trash page: stale entries
            # keep aliasing pages the pool may re-hand to other slots
            gc.table[slot, :] = gc.num_pages - 1
        self.lengths[slot] = 0
        self._slots[slot] = None
        self._live.discard(slot)

    # -- prefix KV cache (serving/kvcache.py + serving/kvtier.py) ---------
    def _cache_acquire(self, feed, req=None):
        """Longest-prefix match for an admission candidate; matched
        pages are ref-counted immediately, so nothing later in this
        admission wave can evict them. Lookup falls through device ->
        host: where the device match ends, the host tier's index takes
        over and hits are restored into fresh device pages. Returns
        (pages, cached_tokens)."""
        pc = self.prefix_cache
        if pc is None:
            return [], 0
        pages, cached = pc.match(feed)
        if pages:
            self.pool.incref(pages)
        if self.host_tier.enabled:
            try:
                pages, cached = self._tier_restore(feed, pages, cached,
                                                   req)
            except BaseException:
                # a failed restore must give back the device-matched
                # refs NOW: the caller never sees them (req._kv_match
                # is only assigned on return), so crash recovery could
                # not find the leak
                if pages:
                    self.pool.decref(pages)
                raise
        return pages, cached

    def _tier_restore(self, feed, pages, cached, req):
        """Second lookup level: continue the prefix walk into the host
        tier and swap hits back in through the preemption restore
        machinery (`_scatter_host_kv`), re-indexing them in the device
        cache so this request — and every later one — maps them like
        ordinary cached pages. Restored pages arrive refcount-1 from
        alloc, matching the incref the device match took on its own
        pages, so `_cache_unacquire` treats both uniformly."""
        tier = self.host_tier
        blocks = tier.match(feed, cached)
        room = min(self.pages_per_seq - len(pages),
                   self.pool.available())
        n = min(len(blocks), max(room, 0))
        if n == 0:
            tier.note_lookup(0)
            return pages, cached
        blocks = blocks[:n]
        # fault point BEFORE the alloc: a raise here leaks nothing (the
        # device-matched incref is dropped by recovery's unacquire)
        self._fire("tier_restore",
                   rids=None if req is None else [str(req.rid)])
        # alloc may evict — and spill — OTHER parked pages; this
        # candidate's device-matched prefix is already increfed, so
        # the restore can never cannibalize its own chain
        new_pages = self.pool.alloc(n)
        try:
            k = np.stack([b["k"] for b in blocks], axis=2)
            v = np.stack([b["v"] for b in blocks], axis=2)
            ks = vs = None
            if blocks[0]["ks"] is not None:
                ks = np.stack([b["ks"] for b in blocks], axis=2)
                vs = np.stack([b["vs"] for b in blocks], axis=2)
            if ks is not None and not self.cache_quant:
                # int8-quantized tier over an fp pool: dequantize on
                # host (same absmax/127 scheme as the engine's int8
                # cache) and scatter full-precision values
                from ..serving.kvtier import _dequantize_host
                k = _dequantize_host(k, ks)
                v = _dequantize_host(v, vs)
                ks = vs = None
            self._scatter_host_kv(new_pages, k, v, ks, vs)
        except BaseException:
            # scatter failed mid-restore: the fresh pages were never
            # mapped or indexed — return them or they leak
            self.pool.decref(new_pages)
            raise
        all_pages = pages + new_pages
        new_cached = cached + n * self.page_size
        self.prefix_cache.insert(feed, all_pages, new_cached)
        tier.note_lookup(n)
        if req is not None:
            _tl_mark(req, "restore")
        _flight.record(
            "kvtier.hit", rid=None if req is None else str(req.rid),
            trace_id=None if req is None
            else getattr(req, "_trace_id", None),
            pages=n, tokens=n * self.page_size,
            device_cached=cached)
        return all_pages, new_cached

    def _spill_page(self, page, parent, block, depth):
        """Prefix-cache eviction hook: demote the page's KV to the
        host tier instead of discarding it. Slicing the pools HERE
        (pump thread) pins the page's current contents — jax arrays
        are functional, so the slices stay valid while the allocator
        re-issues the page and later steps overwrite it; the blocking
        device->host fence runs on the tier's copy thread."""
        self.host_tier.spill_async(
            parent, block, depth,
            self.k_pool[:, :, page], self.v_pool[:, :, page],
            None if self.k_scale is None else self.k_scale[:, :, page],
            None if self.v_scale is None else self.v_scale[:, :, page],
            prequantized=self.cache_quant)

    def _cache_unacquire(self, req):
        """Admission did not take the candidate after all: drop its
        acquired prefix (rc==0 pages fall back into the cache LRU)."""
        match = getattr(req, "_kv_match", None)
        if match and match[0]:
            self.pool.decref(match[0])
        req._kv_match = None

    def _map_prefix(self, slot, match):
        """Map already-acquired shared prefix pages into the slot's
        page-table row and pre-seed its length to the cached token
        count — the device only ever sees the suffix."""
        pages, cached = match
        self._seq_pages[slot] = list(pages)
        for i, pg in enumerate(pages):
            self.page_table[slot, i] = pg
        self.lengths[slot] = cached

    def _index_slot(self, slot, req):
        """Index the slot's full pages under the chained block hash of
        the tokens they hold (cache position i holds the KV of token
        (prompt+output)[i]) so later admissions can share them."""
        pc = self.prefix_cache
        if pc is None or self._index_suspend:
            return
        L = int(self.lengths[slot])
        toks = (list(req.prompt) + [int(t) for t in req.output])[:L]
        pc.insert(toks, self._seq_pages[slot], L)

    def _note_prefix_admit(self, req, match):
        """Admission-time cache accounting. Only admitted requests
        count — a queued candidate re-probed every step is not a
        stream of lookups."""
        pc = self.prefix_cache
        if pc is None:
            return
        cached = match[1]
        req.cached_tokens = cached
        pc.lookups += 1
        if cached > 0:
            pc.hits += 1
            pc.tokens_reused += cached
            _flight.record("kvcache.hit", rid=str(req.rid),
                           trace_id=getattr(req, "_trace_id", None),
                           cached_tokens=cached, pages=len(match[0]))
        m = self.metrics
        if m is not None:
            m.on_prefix_lookup(cached)

    def _note_prefix_evict(self, page):
        m = self.metrics
        if m is not None:
            m.on_prefix_evict()

    def _prefill_suffix_into(self, slot, req, match):
        """Suffix-only prefill for a prefix-cache hit: the matched
        pages are mapped in shared (ref-counted) and ONLY the
        remaining tokens run through the device — one bucket-shaped
        verify_step whose chunk attends to the cached pages through
        the slot's page table. The chunk/cache split is exactly the
        verify kernel's contract, so no new jitted entry point (and
        no new compile telemetry surface) is needed; partial-page
        prompt tails are part of the suffix and recomputed."""
        pages, cached = match
        feed = self._feed_ids(req)
        suffix = feed[cached:]
        n = len(suffix)
        self.prefill_tokens += n
        _tl_count(req, "prefill")
        self._map_prefix(slot, match)
        total = -(-len(feed) // self.page_size)
        if total > len(pages):
            self._alloc_pages(slot, total - len(pages))
        # bucketed chunk width: one compile per bucket, not one per
        # distinct suffix length (same reasoning as the packed
        # prefill scatter above)
        G = self._bucket_for(n)
        tokens = np.zeros((self.max_seqs, G), np.int64)
        tokens[slot, :n] = suffix
        n_tok = np.zeros((self.max_seqs,), np.int32)
        n_tok[slot] = n
        active = np.zeros((self.max_seqs,), bool)
        active[slot] = True
        self._fire("suffix_prefill", rids=[str(req.rid)])
        # only the chunk's final row seeds the first generated token —
        # one row of unembed FLOPs, not B*G
        need = jnp.asarray([slot * G + n - 1], jnp.int32)
        self.logit_rows += 1
        self.logit_rows_skipped += self.max_seqs * G - 1
        with record_span("serving.prefill"):
            (self.k_pool, self.v_pool, self.k_scale, self.v_scale,
             logits) = verify_step(
                self.params, self.k_pool, self.v_pool,
                jnp.asarray(self.page_table.copy()),
                jnp.asarray(self.lengths.copy()),
                jnp.asarray(tokens), jnp.asarray(n_tok),
                jnp.asarray(active), self.config, self.page_size,
                use_pallas=self._use_pallas, interpret=self._interpret,
                k_scale=self.k_scale, v_scale=self.v_scale,
                mesh=self._mesh, need_rows=need)
        self.lengths[slot] = cached + n
        req.slot = slot
        req._admit_order = self._order
        self._order += 1
        self._attach(slot, req)
        self._note_prefix_admit(req, match)
        self._index_slot(slot, req)
        if getattr(req, "_resume", False):
            req._resume = False  # next_token survives from before eviction
        else:
            self._seed_first_token(slot, req,
                                   self._fetch_results(logits[0]))

    def run(self, max_steps=10000):
        steps = 0
        while (self._live or self._waiting) and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    def run_pipelined(self, max_steps=10000):
        """Drive the engine with the depth-1 double-buffered loop (the
        scheduler's pipelined pump uses the same step_launch /
        step_finish pair): launch step N+1 before consuming step N, so
        the host bookkeeping overlaps the in-flight device program.
        Token-identical to `run()` — greedy and seeded sampling both,
        because sampling happens inside the step keyed by (seed,
        position). Bucketed engines (their step returns new pools) and
        spec-decode engines (drafting needs host-current context) fall
        back to the synchronous loop. Cancellation must only be applied
        between consumed steps — drive cancels through the scheduler,
        which drains the pipeline first."""
        if not self.ragged or self.spec_decode > 1:
            return self.run(max_steps=max_steps)
        pending = None
        steps = 0
        while steps < max_steps and (self._live or self._waiting
                                     or pending is not None):
            try:
                ticket = self.step_launch(carry=pending)
            except PipelineStall:
                self.step_finish(pending)
                pending = None
                ticket = self.step_launch()
            if pending is not None:
                self.step_finish(pending, inflight=ticket)
            pending = ticket
            steps += 1
        if pending is not None:
            self.step_finish(pending)
        return self.finished
