"""Llama 4D-parallel pretrain step — the fleet-equivalent SPMD path.

Replaces the reference's fleet hybrid-parallel Llama pretrain
(python/paddle/distributed/fleet/meta_parallel/* + PaddleNLP llm/
modeling_pp.py) with a single pure train-step program:

  * layer params stacked (L, ...) → lax.scan over layers (pp=1) or
    grouped (pp, L/pp, ...) and pipelined via shard_map+ppermute (pp>1).
  * tp: megatron specs on weight axes (GSPMD inserts collectives).
  * dp: batch sharding (grad psum from GSPMD).
  * sp: optional ring attention over an 'sp' axis for long context.
  * remat: jax.checkpoint around each decoder layer.
  * AdamW with fp32 master weights; params bf16 on TPU.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability.compile_telemetry import ensure_compile_cache
from ..ops.rope import rope_cos_sin, apply_rotary_emb
from ..ops.flash_attention import flash_attention_bhsd
from ..ops.flashmask_attention import flashmask_attention_bhsd
from ..parallel.pp import (pipeline_apply, pipeline_train_1f1b,
                           pipeline_train_interleaved, group_stages,
                           group_virtual_stages, ungroup_virtual_stages)
from ..parallel.ring import ring_attention
from ..parallel.ulysses import ulysses_attention
from .llama import LlamaConfig


# ---------------------------------------------------------------- params
def init_params(config: LlamaConfig, seed=0, dtype=jnp.float32):
    c = config
    key = jax.random.key(seed)
    ks = jax.random.split(key, 12)
    H, F_, V, L = c.hidden_size, c.intermediate_size, c.vocab_size, \
        c.num_hidden_layers
    KV = c.num_key_value_heads * (H // c.num_attention_heads)
    std = c.initializer_range

    def w(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    params = {
        "embed": w(ks[0], (V, H)),
        "final_norm": jnp.ones((H,), dtype),
        "lm_head": w(ks[1], (H, V)),
        "layers": {
            "ln1": jnp.ones((L, H), dtype),
            "wq": w(ks[2], (L, H, H)),
            "wk": w(ks[3], (L, H, KV)),
            "wv": w(ks[4], (L, H, KV)),
            "wo": w(ks[5], (L, H, H)),
            "ln2": jnp.ones((L, H), dtype),
            "w_gate": w(ks[6], (L, H, F_)),
            "w_up": w(ks[7], (L, H, F_)),
            "w_down": w(ks[8], (L, F_, H)),
        },
    }
    return params


def param_specs(config, mesh, pp=False, fsdp_axis=None):
    """PartitionSpecs: megatron TP on weight axes; stacked layer axis over
    'pp' when pipelining; optional fsdp sharding of the embed/lm_head."""
    tp = "tp" if "tp" in mesh.shape else None
    ppax = "pp" if (pp and "pp" in mesh.shape) else None
    specs = {
        "embed": P(tp, None),
        "final_norm": P(),
        "lm_head": P(None, tp),
        "layers": {
            "ln1": P(ppax, None),
            "wq": P(ppax, None, tp),
            "wk": P(ppax, None, tp),
            "wv": P(ppax, None, tp),
            "wo": P(ppax, tp, None),
            "ln2": P(ppax, None),
            "w_gate": P(ppax, None, tp),
            "w_up": P(ppax, None, tp),
            "w_down": P(ppax, tp, None),
        },
    }
    return specs


# ---------------------------------------------------------------- forward
def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    out = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * g.astype(jnp.float32)).astype(x.dtype)


def doc_end_indices(doc_ids):
    """(B, S) contiguous per-token document ids → (B, 1, S, 1) FlashMask
    startend_row_indices: for key column j, the first row that must NOT
    attend to it (= its document's end boundary). jit-safe."""
    B, S = doc_ids.shape
    idx = jnp.arange(S)
    is_last = jnp.concatenate(
        [doc_ids[:, 1:] != doc_ids[:, :-1], jnp.ones((B, 1), bool)], axis=1)
    cand = jnp.where(is_last, idx + 1, S + 1)
    end = lax.cummin(cand[:, ::-1], axis=1)[:, ::-1]
    return end.astype(jnp.int32)[:, None, :, None]


def _sharded_attn(fn, mesh, attn_spec):
    """Run an attention kernel over (B, H, S, D) operands under its own
    shard_map (every mesh axis manual): GSPMD cannot partition a Mosaic
    kernel — lowering one under a mesh of more than one device raises —
    and attention is independent per (batch row, head), so each device
    runs the unmodified kernel on its `attn_spec` block."""
    if attn_spec is None:
        return fn
    return lambda *ops: jax.shard_map(
        fn, mesh=mesh, in_specs=(attn_spec,) * len(ops),
        out_specs=attn_spec, check_vma=False)(*ops)


def decoder_layer(lp, h, rope, config: LlamaConfig, sp_axis=None,
                  sp_impl="ring", mesh=None, attn_spec=None):
    """One decoder layer, pure. h: (B, S, H). rope: (cos, sin) or
    (cos, sin, sri) where sri is a FlashMask startend_row_indices
    tensor (B, 1, S_k, n) for packed-document attention.

    sp_impl: context-parallel scheme when sp_axis is set — "ring"
    (K/V rotation, scales past head count) or "ulysses" (all-to-all
    head<->sequence re-shard, full local flash kernel; needs
    heads % sp == 0). See parallel/ulysses.py for the trade. The
    attention is wrapped in its own shard_map over `mesh` (required
    with sp_axis): plain jit/GSPMD never binds named axes, so the
    _local collectives cannot be called bare from here.

    attn_spec: PartitionSpec of the (B, H, S, D) attention operands on
    `mesh` (make_train_step derives it: batch over the batch axes,
    heads over tp); the flash / flashmask kernel then runs under
    `_sharded_attn`."""
    c = config
    cos, sin = rope[0], rope[1]
    sri = rope[2] if len(rope) > 2 else None
    nh = c.num_attention_heads
    nkv = c.num_key_value_heads
    hd = c.hidden_size // nh
    b, s, H = h.shape

    x = _rms(h, lp["ln1"], c.rms_norm_eps)
    q = (x @ lp["wq"]).reshape(b, s, nh, hd).swapaxes(1, 2)
    k = (x @ lp["wk"]).reshape(b, s, nkv, hd).swapaxes(1, 2)
    v = (x @ lp["wv"]).reshape(b, s, nkv, hd).swapaxes(1, 2)
    q, k = apply_rotary_emb(q, k, cos[None, None], sin[None, None])
    rep = nh // nkv
    if rep > 1 and not (sp_axis is not None and sp_impl == "ulysses"):
        # ulysses takes GQA K/V unrepeated: it moves them over ICI at
        # kv width and repeats after the re-shard (rep× fewer wire
        # bytes); every other path wants full-head K/V here
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if sp_axis is not None:
        if mesh is None:
            raise ValueError(
                "decoder_layer(sp_axis=...) needs the mesh: the "
                "context-parallel attention runs under its own "
                "shard_map; without it the named axis is unbound")
        attn = ulysses_attention if sp_impl == "ulysses" else ring_attention
        o = attn(q, k, v, mesh, sp_axis, causal=True)
    elif sri is not None:
        # packed-document pretraining: causal within each document,
        # blocked across documents — flashmask kernel, no dense mask
        sri_h = jnp.broadcast_to(sri, (b, nh, s, sri.shape[-1]))
        o = _sharded_attn(
            functools.partial(flashmask_attention_bhsd, causal=True),
            mesh, attn_spec)(q, k, v, sri_h)
    else:
        o = _sharded_attn(
            functools.partial(flash_attention_bhsd, causal=True),
            mesh, attn_spec)(q, k, v)
    attn_out = o.swapaxes(1, 2).reshape(b, s, H) @ lp["wo"]
    h = h + attn_out

    x = _rms(h, lp["ln2"], c.rms_norm_eps)
    mlp = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
    return h + mlp


def forward(params, input_ids, config: LlamaConfig, mesh=None, n_micro=None,
            remat=True, sp_axis=None, doc_ids=None, return_hidden=False,
            sp_impl="ring", attn_spec=None):
    """→ logits (B, S, V). Uses pipeline when mesh has pp>1, else scan.

    doc_ids: optional (B, S) contiguous document ids for packed-sequence
    pretraining — attention stays causal within a document and is
    blocked across documents via the FlashMask kernel (no dense mask).

    return_hidden: return the final-norm'd hidden states (B, S, H)
    WITHOUT the lm_head projection — the fused linear+cross-entropy
    loss path consumes these directly so the (B, S, V) logits are never
    materialized.
    """
    c = config
    s = input_ids.shape[1]
    cos, sin = rope_cos_sin(s, c.hidden_size // c.num_attention_heads,
                            c.rope_theta, jnp.float32)
    extra = (cos, sin)
    if doc_ids is not None:
        if mesh is not None and mesh.shape.get("pp", 1) > 1:
            raise NotImplementedError(
                "packed-document flashmask + pipeline parallelism: the "
                "per-row mask cannot ride the replicated pipeline extra "
                "yet — use doc_ids without pp, or pp without doc_ids")
        if sp_axis is not None:
            raise NotImplementedError(
                "packed-document flashmask + sequence parallelism is "
                "not supported: neither the ring nor the ulysses "
                "context-parallel attention carries a document mask — "
                "drop sp_axis or doc_ids")
        extra = (cos, sin, doc_end_indices(doc_ids))
    h = jnp.take(params["embed"], input_ids, axis=0)

    use_pp_ = mesh is not None and mesh.shape.get("pp", 1) > 1
    if sp_axis is not None and use_pp_:
        raise NotImplementedError(
            "sequence parallelism inside the pp pipeline is not "
            "supported: the attention's shard_map cannot nest inside "
            "the pipeline's — shard sequence on a pp=1 mesh, or drop "
            "sp_axis")
    layer = functools.partial(decoder_layer, config=c, sp_axis=sp_axis,
                              sp_impl=sp_impl, mesh=mesh,
                              attn_spec=attn_spec)
    if remat == "dots":
        # save matmul outputs, recompute only elementwise — ~MFU win over
        # full remat when activations still fit in HBM
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif remat:
        layer = jax.checkpoint(layer)

    use_pp = mesh is not None and mesh.shape.get("pp", 1) > 1
    if use_pp:
        n_stages = mesh.shape["pp"]
        staged = group_stages(params["layers"], n_stages)
        h = pipeline_apply(staged, h,
                           lambda lp, hh, extra_: layer(lp, hh, extra_),
                           mesh, pp_axis="pp", n_micro=n_micro,
                           extra=extra)
    else:
        def body(hh, lp):
            return layer(lp, hh, extra), None
        h, _ = lax.scan(body, h, params["layers"])

    h = _rms(h, params["final_norm"], c.rms_norm_eps)
    if return_hidden:
        return h
    return h @ params["lm_head"]


def _masked_nll(logits, labels):
    """→ (nll_sum, valid_count): summed next-token NLL over labels >= 0
    (labels < 0 are the ignore sentinel, e.g. document boundaries).
    Single source for every loss path so semantics cannot drift."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None].astype(jnp.int32),
        axis=-1)[..., 0]
    valid = (labels >= 0).astype(jnp.float32)
    return -jnp.sum(picked * valid), jnp.sum(valid)


# default vocab-chunk for the fused linear+CE path; 8192 keeps the live
# (N, chunk) logits slab ~64 MB at N=32k tokens vs 4 GB for full fp32
# (B, S, V) logits at V=32000
FUSED_CE_CHUNK = 8192


def _fused_masked_nll(h, lm_head, labels, chunk=FUSED_CE_CHUNK):
    """(nll_sum, valid_count) via ops.fused.fused_linear_cross_entropy:
    the (B, S, V) logits are never materialized — vocab is streamed in
    chunks with an online logsumexp (reference parity:
    paddle/phi/kernels/gpu/cross_entropy_kernel.cu softmax+CE fusion).
    Same semantics as _masked_nll(h @ lm_head, labels)."""
    from ..ops.fused import fused_linear_cross_entropy
    B, S, H = h.shape
    x = h.reshape(B * S, H)
    lab = labels.reshape(B * S).astype(jnp.int32)
    valid = lab >= 0
    per_tok = fused_linear_cross_entropy(
        x, lm_head, jnp.where(valid, lab, 0), chunk_size=chunk,
        reduction="none")
    per_tok = jnp.where(valid, per_tok, 0.0)
    return jnp.sum(per_tok), jnp.sum(valid.astype(jnp.float32))


def _resolve_fused_ce(fused_ce):
    """None → the PT_FUSED_CE env knob (bench/autotune sweep surface)."""
    if fused_ce is None:
        import os
        return os.environ.get("PT_FUSED_CE", "0") == "1"
    return bool(fused_ce)


def loss_fn(params, batch, config, mesh=None, n_micro=None, remat=True,
            sp_axis=None, fused_ce=False, sp_impl="ring", attn_spec=None):
    """batch: (input_ids, labels) or (input_ids, labels, doc_ids) for
    packed-document pretraining. Labels < 0 are ignored (masked mean)."""
    s, n = loss_sum_fn(params, batch, config, mesh, n_micro, remat, sp_axis,
                       fused_ce=fused_ce, sp_impl=sp_impl,
                       attn_spec=attn_spec)
    return s / jnp.maximum(n, 1.0)


def loss_sum_fn(params, batch, config, mesh=None, n_micro=None, remat=True,
                sp_axis=None, fused_ce=False, sp_impl="ring",
                attn_spec=None):
    """(nll_sum, valid_count) variant — the grad-accumulation path
    accumulates these so microbatches are weighted by their VALID token
    counts, keeping n_micro=k exactly equal to the one-shot step even
    with unevenly distributed ignore-labels.

    fused_ce=True routes the head through the fused linear+CE op (no
    logits materialization) — numerically equivalent, big activation-
    memory/HBM win at large vocab."""
    input_ids, labels = batch[0], batch[1]
    doc_ids = batch[2] if len(batch) > 2 else None
    if fused_ce:
        h = forward(params, input_ids, config, mesh, n_micro, remat, sp_axis,
                    doc_ids=doc_ids, return_hidden=True, sp_impl=sp_impl,
                    attn_spec=attn_spec)
        return _fused_masked_nll(h, params["lm_head"], labels)
    logits = forward(params, input_ids, config, mesh, n_micro, remat, sp_axis,
                     doc_ids=doc_ids, sp_impl=sp_impl, attn_spec=attn_spec)
    return _masked_nll(logits, labels)


# ---------------------------------------------------------------- training
def init_opt_state(params):
    return jax.tree_util.tree_map(
        lambda p: {"m": jnp.zeros_like(p, dtype=jnp.float32),
                   "v": jnp.zeros_like(p, dtype=jnp.float32),
                   # copy=True: master must not alias the param buffer
                   # (both pytrees are donated to the train step)
                   "master": jnp.array(p, dtype=jnp.float32, copy=True)}, params)


def adamw_update(params, grads, state, lr, step, b1=0.9, b2=0.95, eps=1e-8,
                 wd=0.1):
    t = step.astype(jnp.float32) + 1.0

    def upd(p, g, s):
        g = g.astype(jnp.float32)
        m = b1 * s["m"] + (1 - b1) * g
        v = b2 * s["v"] + (1 - b2) * g * g
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        master = s["master"] * (1 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
        return master.astype(p.dtype), {"m": m, "v": v, "master": master}

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_flatten(grads)[0]
    flat_s = treedef.flatten_up_to(state)
    outs = [upd(p, g, s) for p, g, s in zip(flat_p, flat_g, flat_s)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    new_s = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    return new_p, new_s


def make_train_step(config, mesh, batch_spec=P("dp"), n_micro=None, remat=True,
                    clip_norm=1.0, lr=3e-4, sp_axis=None, donate=True,
                    schedule=None, fused_ce=None, vpp=2, sp_impl="ring"):
    """Build the jitted 4D-parallel train step.

    (params, opt_state, step, batch) → (params, opt_state, loss)

    schedule: with pp>1, "gpipe" runs the differentiable scan pipeline
    (AD backward, O(n_micro) stashed activations), "1f1b" runs the
    hand-seeded one-forward-one-backward schedule (O(pp) stashed stage
    inputs — reference pipeline_parallel.py:958 parity), and
    "interleave" runs interleaved virtual-stage 1F1B with `vpp` layer
    chunks per stage — fill/drain bubble divided by vpp (reference
    pipeline_parallel.py:1309). None (default) consults fleet's
    strategy.pipeline_configs['schedule_mode'] when fleet.init ran,
    else "gpipe". NB interleave keeps the contiguous (L, ...) param
    layout at rest; the step regroups to the chunked layout under jit,
    so GSPMD inserts a per-step layer-param reshuffle over the pp axis
    — store-interleaved layouts are a future optimization.

    fused_ce: route every loss path through the fused linear+CE op so
    the (B, S, V) logits never materialize (reference:
    phi/kernels/gpu/cross_entropy_kernel.cu fusion). None consults the
    PT_FUSED_CE env knob so bench.py/autotune can sweep it.
    """
    ensure_compile_cache()
    fused_ce = _resolve_fused_ce(fused_ce)
    if schedule is None:
        schedule = "gpipe"
        try:
            from ..distributed.fleet import fleet as _fleet
            if getattr(_fleet, "_is_initialized", False):
                schedule = _fleet.pipeline_schedule()
                if schedule == "interleave":
                    fleet_vpp = _fleet.virtual_pp_degree()
                    if fleet_vpp <= 1:
                        # never silently pick a virtual degree the user
                        # didn't configure (fleet policy: no silent
                        # downgrades/upgrades of the memory profile)
                        raise ValueError(
                            "schedule_mode 'interleave' needs "
                            "hybrid_configs pp_configs virtual_pp_degree "
                            ">= 2 (got "
                            f"{fleet_vpp}); set it, or pass vpp= "
                            "explicitly with schedule='interleave'")
                    vpp = fleet_vpp
        except ImportError:  # pragma: no cover
            pass
    use_pp = mesh.shape.get("pp", 1) > 1
    # on more than one device the attention kernel gets its own
    # shard_map region (`_sharded_attn`): batch rows over the batch
    # axes, heads over tp. The pp pipeline and the sp attentions are
    # shard_maps already and keep their own arrangement.
    attn_spec = None
    if mesh.size > 1 and not use_pp and sp_axis is None:
        batch_axes = batch_spec[0] \
            if isinstance(batch_spec, P) and len(batch_spec) else None
        attn_spec = P(batch_axes, "tp" if "tp" in mesh.shape else None)
    # forward() gets the mesh only when something under it needs one
    # (pipeline, sp attention, sharded attention kernel); None keeps
    # the plain scan-over-layers path
    fwd_mesh = mesh if (use_pp or sp_axis or attn_spec is not None) \
        else None
    specs = param_specs(config, mesh, pp=use_pp)
    pshard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                    is_leaf=lambda x: isinstance(x, P))
    sshard = jax.tree_util.tree_map(
        lambda sh: {"m": sh, "v": sh, "master": sh}, pshard,
        is_leaf=lambda x: isinstance(x, NamedSharding))
    repl = NamedSharding(mesh, P())
    bshard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), batch_spec,
                                    is_leaf=lambda x: isinstance(x, P))

    def grads_pipelined(params, batch):
        """Loss + grads via the hand-seeded pipeline (1F1B or
        interleaved vpp): embed lookup and its scatter-grad run
        replicated outside the pipeline; final-norm + lm_head + loss
        fold into head_fn on the last stage."""
        c = config
        if len(batch) > 2:
            raise NotImplementedError(
                "packed-document flashmask + 1F1B pipeline is not "
                "supported yet (see forward()'s doc_ids + pp note)")
        if sp_axis is not None:
            raise NotImplementedError(
                "sequence parallelism inside the 1F1B/interleave "
                "pipeline is not supported (see forward()'s sp + pp "
                "note)")
        input_ids, labels = batch[0], batch[1]
        s = input_ids.shape[1]
        cos, sin = rope_cos_sin(s, c.hidden_size // c.num_attention_heads,
                                c.rope_theta, jnp.float32)
        layer = functools.partial(decoder_layer, config=c)
        if remat == "dots":
            layer = jax.checkpoint(
                layer,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        elif remat:
            layer = jax.checkpoint(layer)

        h0, pull_embed = jax.vjp(
            lambda e: jnp.take(e, input_ids, axis=0), params["embed"])

        def head_fn(hp, h, tgt):
            # returns (nll_sum, valid_count): pipeline_train_1f1b
            # normalizes by the GLOBAL valid count, so microbatches are
            # weighted by their valid tokens — identical loss/grad
            # semantics to the no-pp and grad-accum paths even with
            # uneven ignore-label masking.
            hh = _rms(h, hp["final_norm"], c.rms_norm_eps)
            if fused_ce:
                return _fused_masked_nll(hh, hp["lm_head"], tgt)
            logits = hh @ hp["lm_head"]
            return _masked_nll(logits, tgt)

        n_stages = mesh.shape["pp"]
        head_p = {"final_norm": params["final_norm"],
                  "lm_head": params["lm_head"]}
        layer_fn = lambda lp, hh, extra: layer(lp, hh, extra)
        if schedule == "interleave":
            staged = group_virtual_stages(params["layers"], n_stages, vpp)
            loss, gstage, ghead, dh0 = pipeline_train_interleaved(
                staged, h0, labels, layer_fn, head_fn, head_p, mesh,
                pp_axis="pp", n_micro=n_micro, vpp=vpp, extra=(cos, sin))
            g_layers = ungroup_virtual_stages(gstage, n_stages, vpp)
        else:
            staged = group_stages(params["layers"], n_stages)
            loss, gstage, ghead, dh0 = pipeline_train_1f1b(
                staged, h0, labels, layer_fn, head_fn, head_p, mesh,
                pp_axis="pp", n_micro=n_micro, extra=(cos, sin))
            L = c.num_hidden_layers
            g_layers = jax.tree_util.tree_map(
                lambda a: a.reshape(L, *a.shape[2:]), gstage)
        (g_embed,) = pull_embed(dh0.astype(h0.dtype))
        grads = {"embed": g_embed, "final_norm": ghead["final_norm"],
                 "lm_head": ghead["lm_head"], "layers": g_layers}
        return loss, grads

    def step_fn(params, opt_state, step, batch):
        if use_pp and schedule in ("1f1b", "interleave"):
            loss, grads = grads_pipelined(params, batch)
        elif n_micro and n_micro > 1 and not use_pp:
            # true gradient accumulation: scan over n_micro microbatches,
            # summing fp32 grads. Peak activation memory drops ~n_micro×
            # (one microbatch's activations live at a time) at the cost
            # of a serial loop — can unlock a bigger global batch or a
            # lighter remat policy. With pp, n_micro instead feeds the
            # pipeline schedule (forward() above).
            B = batch[0].shape[0]
            assert B % n_micro == 0, (
                f"batch {B} not divisible by n_micro={n_micro}")
            mb = B // n_micro
            parts = tuple(p.reshape(n_micro, mb, *p.shape[1:])
                          for p in batch)

            # accumulate SUMMED NLL + valid counts so microbatches are
            # weighted by their valid-token counts — exactly equal to
            # the one-shot step even with uneven ignore-labels
            def micro(acc, mb_batch):
                acc_s, acc_n, acc_g = acc

                def sum_only(p):
                    s, n = loss_sum_fn(p, mb_batch, config, fwd_mesh,
                                       None, remat, sp_axis,
                                       fused_ce=fused_ce, sp_impl=sp_impl,
                                       attn_spec=attn_spec)
                    return s, n
                (s, n), g = jax.value_and_grad(sum_only, has_aux=True)(params)
                acc_g = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), acc_g, g)
                return (acc_s + s, acc_n + n, acc_g), None

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss_s, loss_n, grads), _ = lax.scan(
                micro, (jnp.float32(0.0), jnp.float32(0.0), zero_g), parts)
            denom = jnp.maximum(loss_n, 1.0)
            loss = loss_s / denom
            grads = jax.tree_util.tree_map(lambda g: g / denom, grads)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(
                params, batch, config, fwd_mesh, n_micro,
                remat, sp_axis, fused_ce, sp_impl, attn_spec)
        if clip_norm is not None:
            leaves = jax.tree_util.tree_leaves(grads)
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in leaves))
            scale = clip_norm / jnp.maximum(gn, clip_norm)
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        params, opt_state = adamw_update(params, grads, opt_state, lr, step)
        return params, opt_state, loss

    return jax.jit(
        step_fn,
        # batch may be (ids, labels) or (ids, labels, doc_ids): shard
        # every element the same way without pinning the arity
        in_shardings=(pshard, sshard, None, bshard),
        out_shardings=(pshard, sshard, repl),
        donate_argnums=(0, 1) if donate else ())


def place_params(params, config, mesh, pp=None):
    if pp is None:
        pp = mesh.shape.get("pp", 1) > 1
    specs = param_specs(config, mesh, pp=pp)
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_s = treedef.flatten_up_to(specs)
    placed = [jax.device_put(p, NamedSharding(mesh, s))
              for p, s in zip(flat_p, flat_s)]
    return jax.tree_util.tree_unflatten(treedef, placed)
