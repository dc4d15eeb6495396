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
carry `tok_pos = -1` and come back exactly zero.

The Pallas kernel walks the WORK, not the page table (ISSUE 26). Its
unit is a run: a stretch of buffer rows of one slot with consecutive
positions (`ragged_runs`, derived inside the jitted step from the
descriptors it already has). What bounds its iteration space:

  * one program per q block of the buffer (`block_q` rows x the GQA
    group, all KV heads; one block at the engine's 32 rows), which
    walks the runs that lie in it — a prefill chunk's rows meet a KV
    block together, causal by `column < position + 1` inside the block;
  * per run, a loop over KV blocks of `block_pages` pages whose trip
    count is the run's KV length (scalar prefetch). The pool stays in
    HBM in its `(KVH, P, page, D)` layout; a page comes in for all KV
    heads by one strided DMA through the page table, double-buffered,
    the prefetch crossing from a run's last block to the next run's
    first. No grid step, loop trip or DMA exists for a page a run does
    not own. Given `layer=`, the pool is a whole stack `(layers, KVH, P,
    page, D)` and the layer one more prefetched scalar in the DMA's
    source, so a scan over layers hands the kernel its carry as it lies
    and slices nothing out of it (`unified_step`, ROADMAP [donate-pools]);
  * QK^T on the stored operands (bf16 products are exact in the f32
    accumulator), running max, sum and accumulator in f32, P in f32
    into the PV product; int8 pages are widened and scaled in VMEM, a
    KV block at a time.

The jnp reference below is the CPU path every engine test runs: an
online softmax one page at a time (`_page_update`). Kernel and
reference differ in the order of summation (a block of pages against a
page), so they are held together by tolerance: 1e-5 on float32 inputs
in TPU interpret mode (tests/test_ragged_step.py), a bf16 ulp or two
on the chip (chip_smoke.py, tools/validate_tpu_kernels.py). The
reference keeps the `optimization_barrier`s that once made it
bit-identical to an interpreted grid kernel; nothing depends on them
now, and the kernel body has none (Mosaic has no rule for them).

GQA: q is viewed (kv_heads, tokens x group, head_dim). The tile
(`block_q` q rows a block x `block_pages` pages a KV block) is STATIC
and derived from the shapes unless given (`ragged_tile`); a tile
changes the order of summation and nothing else. The per-TPU-
generation winner, where one beats the derived tile, is found offline
by tools/tune_ragged.py and loaded through
paddle_tpu/_tuning_defaults.load_ragged_tile.
"""
from __future__ import annotations

import functools
import math

import jax
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.flash_attention import _fit_lanes
from ..ops.paged_attention import (F0, F1, LANES, MIN_GROUP, NEG_INF, Z,
                                   _on_tpu)

__all__ = ["ragged_paged_attention", "ragged_paged_attention_reference",
           "ragged_runs", "ragged_tile"]

_bar = jax.lax.optimization_barrier


def _page_update(q, k, v, acc, m_prev, l_prev, limit, pi, scale,
                 page_size, ks=None, vs=None, bar=_bar, lo=None):
    """One online-softmax step over one KV page: the jnp reference's
    arithmetic.

    q/acc: (group_pad, d) f32; m_prev/l_prev: (group_pad, LANES) f32;
    k/v: (page_size, d) f32; ks/vs: (page_size, 1) dequant scales when
    the pool is int8; limit/pi: i32 scalars; lo: the first visible
    column under a window, None without one. Returns the updated
    (acc, m, l). The optimization barriers keep XLA from contracting
    the muls into the adds (or re-fusing the dots/exps): they date from
    the grid kernel this reference was bit-identical to on CPU, and
    stay because the engine tests' token streams are this function's.
    """
    if ks is not None:
        k = k * ks
        v = v * vs
    s = bar(jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)) * scale
    cols = pi * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = cols < limit
    if lo is not None:
        seen &= cols >= lo
    s = jnp.where(seen, s, NEG_INF)
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
                                     block_q=None, window=None, layer=None):
    """q: (T, QH, D); pages: (KVH, P, page, D); page_table:
    (S, pages_per_seq); tok_slot/tok_pos: (T,) i32 (pos -1 = inactive
    row → zeros out). `window` (static): a row at position p sees
    columns j with 0 <= p - j < window, and no page wholly behind them
    is read. `layer` (i32 scalar): pages and scales are stacks with a
    leading layer dimension, and this layer's are read. Returns
    (T, QH, D).

    This is NOT a dense-softmax shortcut: it replays `_page_update`
    over page ordinals (group padded, lane-replicated stats), skipped
    pages carrying the previous stats through unchanged. `block_q` here
    is the q group's sublane padding, the reference's own; the pallas
    kernel's tile has no twin in it."""
    if layer is not None:
        k_pages, v_pages, k_scale, v_scale = (
            None if a is None else a[layer]
            for a in (k_pages, v_pages, k_scale, v_scale))
    t, qh, d = q.shape
    kvh, _, page_size, _ = k_pages.shape
    group = qh // kvh
    gp = _resolve_block_q(block_q, group)
    scale = np.float32(sm_scale if sm_scale is not None else d ** -0.5)
    n_pages = page_table.shape[1]
    quant = k_scale is not None

    pages = page_table[tok_slot].astype(jnp.int32)       # (T, n_pages)
    limit = (tok_pos + 1).astype(jnp.int32)              # (T,)
    first_col = None if window is None else \
        jnp.maximum(limit - np.int32(window), 0)
    qg = q.reshape(t, kvh, group, d).astype(jnp.float32)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))

    def token_head(args):
        qg_th, pages_t, limit_t, hi = args[:4]
        lo_t = args[4] if window is not None else None
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
                *( (sc[0][pg], sc[1][pg]) if quant else () ), lo=lo_t)
            # page skip: the kernel's @pl.when leaves the scratch
            # UNTOUCHED on a masked page — carry the old bits through
            take = pi * page_size < limit_t
            if window is not None:
                take &= (pi + 1) * page_size > lo_t
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
                                 pages[ti_idx], limit[ti_idx], hi_idx)
                    + (() if window is None else (first_col[ti_idx],)))
    o = o.reshape(t, kvh, gp, d)[:, :, :group]
    return o.reshape(t, qh, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------
def _resolve_block_q(block_q, group):
    """The reference's q-row block: None/0 derive the GQA group padded
    to the sublane minimum; an explicit value must cover the group and
    stay sublane-aligned."""
    gp_min = group + (-group) % MIN_GROUP
    if not block_q:
        return gp_min
    block_q = int(block_q)
    if block_q % MIN_GROUP or block_q < group:
        raise ValueError(
            f"block_q={block_q}: must be a multiple of the sublane "
            f"tile ({MIN_GROUP}) and >= the GQA group ({group})")
    return block_q


def _q_rows(block_q, t, group):
    """Validated q rows a block. None/0 derive them: rows x the GQA
    group fill the MXU's 128 rows, never more than the buffer, always
    a whole number of sublane tiles."""
    unit = MIN_GROUP // math.gcd(MIN_GROUP, group)  # rows*group % 8 == 0
    if not block_q:
        return min(-(-t // unit) * unit,
                   max(unit, LANES // group // unit * unit))
    block_q = int(block_q)
    if block_q < 1 or block_q % unit:
        raise ValueError(
            f"block_q={block_q}: q rows a block times the GQA group "
            f"({group}) must be a positive multiple of the sublane tile "
            f"({MIN_GROUP})")
    return block_q


def _kv_pages(block_pages, page_size, n_pages):
    """Validated pages a KV block. None/0 derive one lane width of
    tokens, never more pages than a sequence owns."""
    if not block_pages:
        block_pages = max(1, LANES // page_size)
    block_pages = int(block_pages)
    if block_pages < 1:
        raise ValueError(f"block_pages={block_pages}: want >= 1")
    block_pages = min(block_pages, n_pages)
    blk = block_pages * page_size
    if blk > LANES and blk % LANES:
        raise ValueError(
            f"block_pages={block_pages}: a KV block of {blk} tokens must "
            f"be at most or a multiple of the lane width ({LANES})")
    return block_pages


def ragged_tile(block_q, block_pages, t, group, page_size, n_pages):
    """The kernel's effective static tile `(q rows a block, pages a KV
    block)` for a buffer of `t` rows: the given values validated,
    None/0 derived from the shapes."""
    return (_q_rows(block_q, t, group),
            _kv_pages(block_pages, page_size, n_pages))


def ragged_runs(tok_slot, tok_pos, group, block_q=None):
    """The step's row descriptors as RUNS, the kernel's unit of work.

    A run is a maximal stretch of buffer rows of one slot with
    consecutive positions that lies inside one q block (`block_q` rows,
    the kernel's tile for a GQA group of `group`): a decode row, a
    prefill chunk, a suffix tail, a verify grid, or a q block's piece
    of one. Rows with `tok_pos = -1` belong to no run. A buffer that
    breaks the engine's layout (a slot's rows apart, positions not
    consecutive) only yields more, shorter runs.

    Returns `(runs, qb_first)`, both i32: `runs` is (4, T) with rows
    first-row / row-count / slot / KV length (= last position + 1),
    valid in columns `< qb_first[-1]`, ordered by first row;
    `qb_first` is (T_blocks + 1,) where q block j owns runs
    `qb_first[j] .. qb_first[j + 1]`. A few integer ops on T elements:
    derive it once a step, outside the layer scan.
    """
    t = tok_pos.shape[0]
    block_q = _q_rows(block_q, t, group)
    slot = tok_slot.astype(jnp.int32)
    pos = tok_pos.astype(jnp.int32)
    i = jnp.arange(t, dtype=jnp.int32)
    on = pos >= 0
    cont = (on[1:] & on[:-1] & (slot[1:] == slot[:-1])
            & (pos[1:] == pos[:-1] + 1) & (i[1:] % block_q != 0))
    start = on & ~jnp.concatenate([jnp.zeros((1,), bool), cont])
    rid = jnp.cumsum(start, dtype=jnp.int32) - 1        # a row's run
    member = on[None, :] & (rid[None, :] == i[:, None])  # (run, row)
    first = member & start[None, :]
    runs = jnp.stack([
        jnp.sum(jnp.where(first, i[None, :], 0), axis=1),
        jnp.sum(member, axis=1),
        jnp.sum(jnp.where(first, slot[None, :], 0), axis=1),
        jnp.max(jnp.where(member, pos[None, :] + 1, 0), axis=1),
    ]).astype(jnp.int32)
    edges = jnp.arange(-(-t // block_q) + 1, dtype=jnp.int32) * block_q
    qb_first = jnp.sum(start[None, :] & (i[None, :] < edges[:, None]),
                       axis=1).astype(jnp.int32)
    return runs, qb_first


def _ragged_kernel(runs_ref, qb_ref, ptab_ref, *refs, scale, page_size,
                   block_pages, group, quant, window=None, stacked=False):
    """Grid (q blocks,). Program j holds q block j (all KV heads, its
    rows x the GQA group) in VMEM and walks the runs that lie in it;
    per run, a loop over KV blocks of `block_pages` pages whose trip
    count is the run's KV length. The pool stays in HBM: a page comes
    in for all KV heads by one strided DMA through the page table,
    into the buffer the next trip reads while this trip computes (the
    prefetch crosses from a run's last block to the next run's first).
    A page the run does not own is neither fetched nor waited for.
    With a `window` a run's walk begins at the KV block that holds the
    first column its first row sees, pages wholly behind that column
    are not fetched either, and the mask cuts inside the block.
    `stacked`: one more prefetched scalar, the layer, comes before the
    q block, and the K and V pools are whole stacks `(layers, KVH, P,
    page, D)` of which a page's DMA reads that layer."""
    layer_ref = None
    if stacked:
        layer_ref, *refs = refs
    q_ref, *refs = refs
    n_pool = 3 if quant else 2
    pools, o_ref = refs[:n_pool], refs[n_pool]
    bufs = refs[n_pool + 1:2 * n_pool + 1]
    sem, m_ref, l_ref, acc_ref = refs[2 * n_pool + 1:]
    kvh, rows, d = q_ref.shape
    block_q = rows // group
    blk = block_pages * page_size
    j = pl.program_id(0)
    r_lo, r_hi = qb_ref[j], qb_ref[j + 1]

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j == 0)
    def _finite_buffers():
        # the tail of a run's last block is never fetched: what P = 0
        # multiplies there must be finite, and stale pages are
        for buf in bufs[1:]:
            buf[...] = jnp.zeros_like(buf)

    def first_col(r):
        """The first column run r's first row sees (window only)."""
        return jnp.maximum(
            runs_ref[3, r] - runs_ref[1, r] - np.int32(window - 1), Z)

    def first_block(r):
        return Z if window is None else first_col(r) // np.int32(blk)

    def for_pages(r, b, slot_, op):
        """`start` or `wait` the DMA of every pool page of block b of
        run r into buffer slot_."""
        seq = runs_ref[2, r]
        owned = pl.cdiv(runs_ref[3, r], np.int32(page_size))
        for p in range(block_pages):
            ordinal = b * np.int32(block_pages) + np.int32(p)
            held = ordinal < owned
            if window is not None:
                held &= ordinal >= first_col(r) // np.int32(page_size)

            @pl.when(held)
            def _(p=p, ordinal=ordinal):
                page = ptab_ref[seq, ordinal]
                for n, (pool, buf) in enumerate(zip(pools, bufs)):
                    if n == 2:      # the scales: a page of one layer
                        src, dst = pool.at[page], buf.at[slot_, np.int32(p)]
                    else:
                        src = pool.at[layer_ref[0], :, page] if stacked \
                            else pool.at[:, page]
                        dst = buf.at[slot_, :, np.int32(p)]
                    getattr(pltpu.make_async_copy(
                        src, dst, sem.at[np.int32(n), slot_]), op)()

    def block(r, b, slot_):
        first, n_rows, kv_len = runs_ref[0, r], runs_ref[1, r], runs_ref[3, r]
        row = j * np.int32(block_q) + jax.lax.broadcasted_iota(
            jnp.int32, (rows, blk), 0) // np.int32(group)
        col = b * np.int32(blk) + jax.lax.broadcasted_iota(
            jnp.int32, (rows, blk), 1)
        # a row's causal limit is its position + 1; positions are
        # consecutive in a run and the last row's is kv_len - 1
        live = ((row >= first) & (row < first + n_rows)
                & (col < kv_len - (first + n_rows) + row + np.int32(1)))
        if window is not None:
            live &= col > kv_len - (first + n_rows) + row - np.int32(window)
        if quant:
            sc = bufs[2][slot_].reshape(blk, bufs[2].shape[-1])
        for h in range(kvh):
            q = q_ref[h]
            k = bufs[0][slot_, h]
            v = bufs[1][slot_, h].astype(jnp.float32).reshape(blk, d)
            if quant:
                # lanes of the scale page: K's KV heads, then V's
                k = k.astype(jnp.float32).reshape(blk, d) * sc[:, h:h + 1]
                v = v * sc[:, kvh + h:kvh + h + 1]
                q = q.astype(jnp.float32)
            else:
                k = k.reshape(blk, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(live, s, NEG_INF)
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(live, jnp.exp(s - _fit_lanes(m_new, blk)),
                          jnp.zeros_like(s))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * _fit_lanes(alpha, d) + \
                jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(r_lo < r_hi)
    def _first_fetch():
        for_pages(r_lo, first_block(r_lo), Z, "start")

    def run(r, slot_):
        n_blocks = pl.cdiv(runs_ref[3, r], np.int32(blk))

        def trip(b, slot_):
            last = b + np.int32(1) >= n_blocks
            r_next = jnp.where(last, r + np.int32(1), r)
            if window is None:
                b_next = jnp.where(last, Z, b + np.int32(1))
            else:
                # the run after the last has no descriptor to read
                b_next = jnp.where(
                    last, first_block(jnp.minimum(r_next, r_hi - np.int32(1))),
                    b + np.int32(1))

            @pl.when(r_next < r_hi)
            def _prefetch():
                for_pages(r_next, b_next, np.int32(1) - slot_, "start")

            for_pages(r, b, slot_, "wait")
            block(r, b, slot_)
            return np.int32(1) - slot_

        return jax.lax.fori_loop(first_block(r), n_blocks, trip, slot_)

    jax.lax.fori_loop(r_lo, r_hi, run, Z)

    for h in range(kvh):
        l = l_ref[h]
        l_safe = jnp.where(l == F0, F1, l)      # rows of no run: 0 / 1
        o_ref[h] = (acc_ref[h] / _fit_lanes(l_safe, d)).astype(o_ref.dtype)


def _scale_pages(k_scale, v_scale):
    """(KVH, P, page, 1) x 2 -> (P, page, lanes): a page's scales for
    every head of K then of V side by side on the lanes, padded to a
    whole number of lane tiles. A manual DMA wants a minor dimension of
    whole tiles, which the pools' trailing 1 is not; one page then
    comes in by ONE copy and head h's column is a static lane."""
    sc = jnp.concatenate([k_scale[..., 0], v_scale[..., 0]])
    sc = sc.transpose(1, 2, 0)
    return jnp.pad(sc, ((0, 0), (0, 0), (0, (-sc.shape[-1]) % LANES)))


def _ragged_pallas(qg, pools, page_table, runs, qb_first, scale, interpret,
                   block_pages, group, window=None, layer=None):
    kvh, rows_all, d = qg.shape
    n_qb = qb_first.shape[0] - 1
    rows = rows_all // n_qb
    page_size = pools[0].shape[-2]
    quant = len(pools) == 3
    # the scalars every index map is handed after the grid index: the
    # runs, the q blocks' first runs, the page table and, for stacked
    # pools, the layer
    scalars = (runs, qb_first, page_table) + (
        () if layer is None else (jnp.reshape(layer, (1,)).astype(jnp.int32),))
    q_spec = pl.BlockSpec((kvh, rows, d), lambda j, *_: (Z, j, Z))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(n_qb,),
        in_specs=[q_spec] + [hbm] * len(pools),
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, kvh, block_pages) + pool.shape[-2:], pool.dtype)
            for pool in pools[:2]
        ] + [
            pltpu.VMEM((2, block_pages) + pool.shape[1:], pool.dtype)
            for pool in pools[2:]
        ] + [
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.VMEM((kvh, rows, LANES), jnp.float32),
            pltpu.VMEM((kvh, rows, LANES), jnp.float32),
            pltpu.VMEM((kvh, rows, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, scale=np.float32(scale), page_size=page_size,
        block_pages=block_pages, group=group, quant=quant,
        **({} if window is None else {"window": int(window)}),
        **({} if layer is None else {"stacked": True}))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="ragged_paged_attention",
    )(*scalars, qg, *pools)


def ragged_paged_attention(q, k_pages, v_pages, page_table, tok_slot,
                           tok_pos, sm_scale=None, use_pallas=None,
                           interpret=None, k_scale=None, v_scale=None,
                           block_q=None, block_pages=None, runs=None,
                           window=None, layer=None):
    """Ragged mixed prefill/decode attention over a paged KV cache.

    q: (T, QH, D) — T flat token rows; k_pages/v_pages:
    (KVH, num_pages, page_size, D); page_table: (S, pages_per_seq)
    i32; tok_slot: (T,) i32 owning slot per row; tok_pos: (T,) i32
    absolute position per row (-1 = inactive slack row → zero output).
    Row i attends to slot tok_slot[i]'s cache columns < tok_pos[i]+1.

    int8 cache: pass int8 pages plus k_scale/v_scale fp32 per-token
    scales (KVH, num_pages, page_size, 1), applied to a KV block
    inside the kernel. Off-TPU (and not under interpret) the jnp
    reference runs; the two agree by tolerance, not bit for bit.

    `block_q`/`block_pages` are the kernel's STATIC tile: q rows a
    block and pages a KV block; None/0 derive them from the shapes
    (`ragged_tile`). A tile changes the order of summation, nothing
    else (tools/tune_ragged.py, docs/tuning.md § Serving
    kernel autotune). `runs` takes `ragged_runs(tok_slot, tok_pos,
    group, block_q)` from a caller that derives it once for many calls
    (the layer scan); None derives it here.

    `window` (STATIC; None = every column up to the row's own): a row
    at position p sees columns j with 0 <= p - j < window. A run's
    walk then starts at the KV block holding the first column its
    first row sees, so page-table entries wholly behind the window
    (the engine has released those pages) are never read.

    `layer` (traced i32 scalar; None = the pools are one layer's): the
    pools, and the scales, are whole stacks `(layers, KVH, num_pages,
    page_size, D)` and this layer's pages are read. The kernel takes
    the stack as it lies in HBM and the layer as one more prefetched
    scalar, so a caller that scans over layers slices nothing out of
    its carry (`unified_step`); the reference indexes the layer.
    """
    t, qh, d = q.shape
    kvh, _, page_size, _ = k_pages.shape[-4:]
    group = qh // kvh
    scale = sm_scale if sm_scale is not None else d ** -0.5
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    bq, bp = ragged_tile(block_q, block_pages, t, group, page_size,
                         page_table.shape[1])
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas and not interpret:
        return ragged_paged_attention_reference(
            q, k_pages, v_pages, page_table, tok_slot, tok_pos, scale,
            k_scale, v_scale, window=window, layer=layer)
    if runs is None:
        runs = ragged_runs(tok_slot, tok_pos, group, bq)
    runs, qb_first = runs
    t_pad = (qb_first.shape[0] - 1) * bq
    assert 0 <= t_pad - t < bq, "runs derived for another block_q"
    # (T, QH, D) -> (KVH, T x group, D): a q block is contiguous rows
    qg = jnp.pad(q, ((0, t_pad - t), (0, 0), (0, 0))).reshape(
        t_pad, kvh, group, d).swapaxes(0, 1).reshape(kvh, t_pad * group, d)
    pools = (k_pages, v_pages)
    if k_scale is not None:
        if layer is not None:   # the scales are re-laid a layer anyway
            k_scale, v_scale = k_scale[layer], v_scale[layer]
        pools += (_scale_pages(k_scale, v_scale),)
    o = _ragged_pallas(qg, pools, page_table.astype(jnp.int32), runs,
                       qb_first, scale, bool(interpret), bp, group, window,
                       layer)
    return o.reshape(kvh, t_pad, group, d).swapaxes(0, 1).reshape(
        t_pad, qh, d)[:t]
