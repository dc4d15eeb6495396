"""Ragged paged attention: one kernel for an arbitrary prefill/decode mix.

The serving engine's `unified_step` feeds a FLAT token buffer — every
row is one token of some sequence, described by `(tok_slot, tok_pos)`
instead of a (batch, seq) grid — so a single device program serves any
mix of prefill chunks, prefix-cache suffix tails, spec-verify grids and
single-token decodes ("Ragged Paged Attention", PAPERS.md; the
split-fuse / fixed-token-budget direction). Row i attends over slot
`tok_slot[i]`'s paged KV through the page table, causally limited to
columns `< tok_pos[i] + 1` (its own position included — the row's K/V
was scattered into the pages beforehand). Inactive buffer slack rows
carry `tok_pos = -1`: every page is skipped for them, which is the
attention early-exit that makes the fixed buffer cheap.

Two implementations with ONE arithmetic contract, asserted BIT-identical
on CPU in tests. Bit-exactness across two separately-compiled XLA
programs does not come for free — three things make it hold:

  * both run the SAME traced op sequence: `_page_update` below is the
    single online-softmax page step, called from the pallas kernel body
    and from the reference's page scan;
  * the reference replays the kernel's exact operand SHAPES (q group
    padded to the sublane minimum, m/l stats lane-replicated to
    (group_pad, LANES) with `_fit_lanes` slicing) — XLA CPU picks
    different vectorizations for different shapes and e.g. `exp` then
    rounds differently;
  * `lax.optimization_barrier` pins the contraction-sensitive spots
    (the dots, the exps, each mul feeding an add) so neither compiled
    loop body can FMA/fuse them into differently-rounded forms. The
    barrier has no vmap batching rule, so the reference fans out over
    (token, head) with `lax.map` rather than vmap.

The bit-identity contract is a CPU one (interpreted kernel vs
reference). The Mosaic TPU lowering has no rule for
`optimization_barrier`, so the kernel body compiled for the chip
(`interpret=False`) carries none; there the check against the reference
is by tolerance (chip_smoke.py, tools/validate_tpu_kernels.py).

GQA: q is viewed (tokens, kv_heads, group, head_dim). int8 pools ride
per-token fp32 scales dequantized inside `_page_update`.

Tile shape is a STATIC parameter (`block_q` q-rows per block x
`block_pages` KV pages per grid step, both sublane-legal), defaulting
to the seed shape (GQA group padded to the sublane minimum x 1 page).
Every legal config runs the identical `_page_update` call sequence over
the same page ordinals with the same operand shapes, so the jnp
reference stays the bit-identity oracle for all of them — what changes
is only how the pallas grid batches DMA and compute. The per-TPU-
generation winner is found offline by tools/tune_ragged.py and loaded
through paddle_tpu/_tuning_defaults.load_ragged_tile.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.flash_attention import _fit_lanes
from ..ops.paged_attention import (F0, F1, LANES, MIN_GROUP, NEG_INF, Z,
                                   _on_tpu)

__all__ = ["ragged_paged_attention", "ragged_paged_attention_reference"]

_bar = jax.lax.optimization_barrier


def _no_bar(x):
    return x


def _page_update(q, k, v, acc, m_prev, l_prev, limit, pi, scale,
                 page_size, ks=None, vs=None, bar=_bar):
    """One online-softmax step over one KV page — THE arithmetic
    contract shared by the pallas kernel and the jnp reference.

    q/acc: (group_pad, d) f32; m_prev/l_prev: (group_pad, LANES) f32;
    k/v: (page_size, d) f32; ks/vs: (page_size, 1) dequant scales when
    the pool is int8; limit/pi: i32 scalars. Returns the updated
    (acc, m, l). The optimization barriers keep XLA from contracting
    the muls into the adds (or re-fusing the dots/exps) differently in
    the two compiled programs — without them the kernel and reference
    drift by 1 ULP on CPU. `bar=_no_bar` drops them for the Mosaic
    build, which cannot lower the primitive.
    """
    if ks is not None:
        k = k * ks
        v = v * vs
    s = bar(jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)) * scale
    cols = pi * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < limit, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = bar(jnp.exp(s - _fit_lanes(m_new, s.shape[-1])))
    alpha = bar(jnp.exp(m_prev - m_new))
    al, sp = bar((alpha * l_prev, jnp.sum(p, axis=1, keepdims=True)))
    l_new = al + sp
    aa, pv = bar((acc * _fit_lanes(alpha, acc.shape[-1]),
                  jax.lax.dot_general(
                      p, v, (((1,), (0,)), ((), ())),
                      preferred_element_type=jnp.float32)))
    return aa + pv, m_new, l_new


# ---------------------------------------------------------------------------
# Reference (pure jnp, CPU production path)
# ---------------------------------------------------------------------------
def ragged_paged_attention_reference(q, k_pages, v_pages, page_table,
                                     tok_slot, tok_pos, sm_scale=None,
                                     k_scale=None, v_scale=None,
                                     block_q=None):
    """q: (T, QH, D); pages: (KVH, P, page, D); page_table:
    (S, pages_per_seq); tok_slot/tok_pos: (T,) i32 (pos -1 = inactive
    row → zeros out). Returns (T, QH, D).

    This is NOT a dense-softmax shortcut: it replays `_page_update`
    over page ordinals with the kernel's exact shapes (group padded,
    lane-replicated stats), skipped pages carrying the previous stats
    through unchanged, so CPU tests can assert the pallas kernel
    bit-identical against it. `block_q` is the kernel's q-row block
    (the q group's sublane padding) — the reference must replay the
    same padded shape to stay the bit-identity oracle for a non-default
    tile. `block_pages` has no reference twin: it only re-batches the
    grid, the `_page_update` ordinal sequence is unchanged."""
    t, qh, d = q.shape
    kvh, _, page_size, _ = k_pages.shape
    group = qh // kvh
    gp = _resolve_block_q(block_q, group)
    scale = np.float32(sm_scale if sm_scale is not None else d ** -0.5)
    n_pages = page_table.shape[1]
    quant = k_scale is not None

    pages = page_table[tok_slot].astype(jnp.int32)       # (T, n_pages)
    limit = (tok_pos + 1).astype(jnp.int32)              # (T,)
    qg = q.reshape(t, kvh, group, d).astype(jnp.float32)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))

    def token_head(args):
        qg_th, pages_t, limit_t, hi = args
        k_h = k_pages[hi]
        v_h = v_pages[hi]
        sc = (k_scale[hi], v_scale[hi]) if quant else None

        def body(carry, xs):
            acc, m, l = carry
            pg, pi = xs
            k = k_h[pg].astype(jnp.float32)              # (page, d)
            v = v_h[pg].astype(jnp.float32)
            acc_new, m_new, l_new = _page_update(
                qg_th, k, v, acc, m, l, limit_t, pi, scale, page_size,
                *( (sc[0][pg], sc[1][pg]) if quant else () ))
            # page skip: the kernel's @pl.when leaves the scratch
            # UNTOUCHED on a masked page — carry the old bits through
            take = pi * page_size < limit_t
            return (jnp.where(take, acc_new, acc),
                    jnp.where(take, m_new, m),
                    jnp.where(take, l_new, l)), None

        init = (jnp.zeros((gp, d), jnp.float32),
                jnp.full((gp, LANES), NEG_INF, jnp.float32),
                jnp.zeros((gp, LANES), jnp.float32))
        (acc, m, l), _ = jax.lax.scan(
            body, init, (pages_t, jnp.arange(n_pages, dtype=jnp.int32)))
        l_safe = jnp.where(l == F0, F1, l)
        return acc / _fit_lanes(l_safe, acc.shape[-1])

    ti_idx = jnp.repeat(jnp.arange(t), kvh)
    hi_idx = jnp.tile(jnp.arange(kvh), t)
    o = jax.lax.map(token_head, (qg.reshape(t * kvh, gp, d),
                                 pages[ti_idx], limit[ti_idx], hi_idx))
    o = o.reshape(t, kvh, gp, d)[:, :, :group]
    return o.reshape(t, qh, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------
def _resolve_block_q(block_q, group):
    """Validated q-row block: None/0 derive the seed shape (group
    padded to the sublane minimum); an explicit value must cover the
    group and stay sublane-aligned or the block is not DMA-legal."""
    gp_min = group + (-group) % MIN_GROUP
    if not block_q:
        return gp_min
    block_q = int(block_q)
    if block_q % MIN_GROUP or block_q < group:
        raise ValueError(
            f"block_q={block_q}: must be a multiple of the sublane "
            f"tile ({MIN_GROUP}) and >= the GQA group ({group})")
    return block_q


def _ragged_kernel(slot_ref, pos_ref, ptab_ref, *refs, scale, page_size,
                   n_pages, block_pages, quant, bar):
    """Grid (T, KVH, ceil(pages_per_seq / block_pages));
    tok_slot/tok_pos/page_table ride scalar prefetch — each of the
    `block_pages` per-step page operands has its own BlockSpec index
    map resolving `ptab[slot[ti], pi*block_pages + j]`, so one grid
    step DMAs a strip of `block_pages` pages and the unrolled body
    consumes them in ordinal order (the exact `_page_update` sequence
    of the one-page kernel — bit-identity is tile-invariant). Scale
    refs ride interleaved per page when the pool is int8, dequantized
    inside `_page_update` so int8 is what rides HBM→VMEM."""
    del slot_ref, ptab_ref  # consumed by the index maps
    per = 4 if quant else 2
    q_ref = refs[0]
    page_refs = refs[1:1 + per * block_pages]
    o_ref = refs[1 + per * block_pages]
    acc_ref, m_ref, l_ref = refs[2 + per * block_pages:]
    ti = pl.program_id(0)
    pi = pl.program_id(2)
    grid_pages = -(-n_pages // block_pages)

    @pl.when(pi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    limit = pos_ref[ti] + 1  # -1 (inactive row) → 0: every page skips

    for j in range(block_pages):
        # ordinal*page_size < limit also masks the clamped
        # past-the-end ordinals of the last grid step: limit <=
        # n_pages*page_size always, so ordinal >= n_pages fails it —
        # the same predicate the reference's `take` carry uses.
        ordinal = pi * block_pages + j
        k_ref = page_refs[per * j]
        v_ref = page_refs[per * j + 1]
        sc_refs = page_refs[per * j + 2:per * j + 4] if quant else None

        @pl.when(ordinal * page_size < limit)
        def _body(k_ref=k_ref, v_ref=v_ref, sc_refs=sc_refs,
                  ordinal=ordinal):
            sc = () if sc_refs is None else (sc_refs[0][0, 0],
                                             sc_refs[1][0, 0])
            acc_new, m_new, l_new = _page_update(
                q_ref[0, 0].astype(jnp.float32),
                k_ref[0, 0].astype(jnp.float32),
                v_ref[0, 0].astype(jnp.float32),
                acc_ref[:], m_ref[:], l_ref[:], limit, ordinal, scale,
                page_size, *sc, bar=bar)
            acc_ref[:] = acc_new
            m_ref[:] = m_new
            l_ref[:] = l_new

    @pl.when(pi == grid_pages - 1)
    def _fin():
        l = l_ref[:]
        l_safe = jnp.where(l == F0, F1, l)
        o_ref[0, 0] = (acc_ref[:] /
                       _fit_lanes(l_safe, o_ref.shape[-1])).astype(o_ref.dtype)


def _ragged_pallas(q4, k_pages, v_pages, page_table, tok_slot, tok_pos,
                   scale, interpret, k_scale=None, v_scale=None,
                   block_pages=1):
    t, kvh, group_pad, d = q4.shape
    _, _, page_size, _ = k_pages.shape
    n_pages = page_table.shape[1]
    quant = k_scale is not None
    grid_pages = -(-n_pages // block_pages)

    # index maps receive grid indices first, then scalar-prefetch refs.
    # Per-j maps pick page ordinal pi*block_pages + j, clamped on the
    # ragged last strip (the kernel body masks those ordinals out).
    def _page_map(j):
        def m(ti, hi, pi, slot, pos, ptab):
            o = jnp.minimum(pi * block_pages + j, n_pages - 1)
            return (hi, ptab[slot[ti], o], Z, Z)
        return m

    in_specs = [
        pl.BlockSpec((1, 1, group_pad, d),
                     lambda ti, hi, pi, slot, pos, ptab: (ti, hi, Z, Z)),
    ]
    operands = [tok_slot, tok_pos, page_table, q4]
    for j in range(block_pages):
        page_spec = pl.BlockSpec((1, 1, page_size, d), _page_map(j))
        in_specs += [page_spec, page_spec]
        operands += [k_pages, v_pages]
        if quant:
            scale_spec = pl.BlockSpec((1, 1, page_size, 1), _page_map(j))
            in_specs += [scale_spec, scale_spec]
            operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t, kvh, grid_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, group_pad, d),
                               lambda ti, hi, pi, slot, pos, ptab:
                               (ti, hi, Z, Z)),
        scratch_shapes=[
            pltpu.VMEM((group_pad, d), jnp.float32),
            pltpu.VMEM((group_pad, LANES), jnp.float32),
            pltpu.VMEM((group_pad, LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, scale=np.float32(scale), page_size=page_size,
        n_pages=n_pages, block_pages=block_pages, quant=quant,
        bar=_bar if interpret else _no_bar)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, kvh, group_pad, d), q4.dtype),
        interpret=interpret,
    )(*operands)


def ragged_paged_attention(q, k_pages, v_pages, page_table, tok_slot,
                           tok_pos, sm_scale=None, use_pallas=None,
                           interpret=None, k_scale=None, v_scale=None,
                           block_q=None, block_pages=None):
    """Ragged mixed prefill/decode attention over a paged KV cache.

    q: (T, QH, D) — T flat token rows; k_pages/v_pages:
    (KVH, num_pages, page_size, D); page_table: (S, pages_per_seq)
    i32; tok_slot: (T,) i32 owning slot per row; tok_pos: (T,) i32
    absolute position per row (-1 = inactive slack row → zero output).
    Row i attends to slot tok_slot[i]'s cache columns < tok_pos[i]+1.

    int8 cache: pass int8 pages plus k_scale/v_scale fp32 per-token
    scales (KVH, num_pages, page_size, 1), dequantized inside the
    kernel. Off-TPU (and not under interpret) the jnp reference runs —
    same arithmetic, bit-identical.

    `block_q`/`block_pages` pick the STATIC kernel tile (q rows per
    block x KV pages per grid step); None/0 keep the seed defaults
    (sublane-padded group x 1). Any legal tile computes the same
    `_page_update` sequence — outputs stay bit-identical to the
    reference at the matching `block_q` — so the choice is purely a
    DMA/occupancy trade tuned per TPU generation (tools/tune_ragged.py,
    docs/tuning.md § Kernel autotune).
    """
    t, qh, d = q.shape
    kvh = k_pages.shape[0]
    group = qh // kvh
    scale = sm_scale if sm_scale is not None else d ** -0.5
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    gp = _resolve_block_q(block_q, group)
    n_pages = page_table.shape[1]
    bp = int(block_pages or 1)
    if bp < 1:
        raise ValueError(f"block_pages={block_pages}: want >= 1")
    bp = min(bp, n_pages)
    if use_pallas is None:
        use_pallas = _on_tpu()
    if interpret is None:
        interpret = False
    if not use_pallas and not interpret:
        return ragged_paged_attention_reference(
            q, k_pages, v_pages, page_table, tok_slot, tok_pos, scale,
            k_scale, v_scale, block_q=gp)
    q4 = q.reshape(t, kvh, group, d)
    pad = gp - group
    if pad:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, pad), (0, 0)))
    o = _ragged_pallas(q4, k_pages, v_pages,
                       page_table.astype(jnp.int32),
                       tok_slot.astype(jnp.int32),
                       tok_pos.astype(jnp.int32), scale, interpret,
                       k_scale=k_scale, v_scale=v_scale, block_pages=bp)
    if pad:
        o = o[:, :, :group]
    return o.reshape(t, qh, d)
