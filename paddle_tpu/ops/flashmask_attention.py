"""FlashMask attention for TPU — pallas kernels (fwd + bwd).

Reference: python/paddle/nn/functional/flash_attention.py:1299
(flashmask_attention) and its CUDA kernel
paddle/phi/kernels/gpu/flash_attn_kernel.cu — sparse causal masks
expressed as per-key-column start/end row indices, applied WITHOUT ever
materializing the dense (S, S) mask.

TPU-native design (VERDICT r2 item 4): the dense flash kernel's
blockwise online-softmax structure, plus

  * the column index vector `startend_row_indices` (B, Hk, S_k, n) is
    transposed to (n, S_k) per head and streamed block-by-block next to
    K/V — O(S) memory, never (S, S);
  * per (q-block, k-block), block-level aggregates (max of starts, min
    of ends over the k-block's columns) decide SKIP: a block whose every
    (row, col) pair is masked is skipped via @pl.when before any MXU
    work, mirroring the reference kernel's block-skip. A ragged tail
    is grown to a whole block with its last column again, so its
    aggregates are its real columns';
  * the same aggregates, taken once outside the kernels, give every
    line of a grid its live range: the first and last inner block that
    may hold an unmasked pair (`_live_ranges`), prefetched scalars.
    The grids are (bh, outer) and hold no inner dimension: a step walks
    its line's range in a loop of `last - first + 1` trips (`_walk`),
    with the inner side's operands left in HBM and copied in by hand
    into two buffers, the next block's copy (across the end of a line,
    the next line's first block) in flight behind the current block's
    products. A block outside a line's range costs nothing: no step,
    no copy, no test;
  * surviving blocks apply the exact per-pair mask built from row iota
    vs the streamed start/end columns;
  * blocks come from the shapes (`derived_blocks`) unless the caller
    or PT_FLASH_BLOCK_Q / _K name them.

Mask semantics (n = trailing dim of startend_row_indices), matching the
reference docstring:
  causal,  n=1: masked  <=>  r >= start_j
  causal,  n=2: masked  <=>  start_j <= r < end_j
  ~causal, n=2: masked  <=>  (r >= start_j) | (r < end_j)
  ~causal, n=4: masked  <=>  (s0_j <= r < e0_j) | (s1_j <= r < e1_j)
plus the base causal triangle / sliding window when requested.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu._env import env_int
from .flash_attention import (F0, F1, NEG_INF, Z, LANES, _fit_lanes,
                              _on_tpu, pallas_disabled)


def dropout_keep_mask(rows, cols, bh, seed, dropout):
    """Deterministic counter-based dropout keep-mask (True = keep).

    A murmur3-finalizer hash of the ABSOLUTE (row, col, batch*head,
    seed) coordinates, in plain uint32 jnp ops — no PRNG primitive, so
    the exact same mask is regenerated inside the pallas forward and
    both backward kernels (and by the dense reference) from coordinates
    alone. Reference parity: the CUDA kernel's philox dropout
    (flash_attn_kernel.cu) is likewise counter-based per position.

    rows/cols/bh: broadcastable int arrays; seed: int32 scalar;
    dropout: static python float in [0, 1).
    """
    x = (rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) ^ \
        (cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)) ^ \
        (jnp.asarray(bh).astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)) ^ \
        jnp.asarray(seed).astype(jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    thresh = np.uint32(min(int(float(dropout) * 4294967296.0), 4294967295))
    return x >= thresh


def _sri_masked(rows, srib, causal, n):
    """(block_q, block_k) bool: pairs masked by the start/end indices.
    rows: (block_q, block_k) absolute row ids; srib: (n, block_k)."""
    def col(i):
        return srib[i:i + 1, :]  # (1, block_k) broadcasts over rows
    if causal and n == 1:
        return rows >= col(0)
    if causal and n == 2:
        return (rows >= col(0)) & (rows < col(1))
    if not causal and n == 2:
        return (rows >= col(0)) | (rows < col(1))
    if not causal and n == 4:
        return ((rows >= col(0)) & (rows < col(1))) | \
               ((rows >= col(2)) & (rows < col(3)))
    raise ValueError(f"startend_row_indices last dim {n} invalid for "
                     f"causal={causal}")


def _sri_all_masked(r_first, r_last, mx, mn, causal, n):
    """Every (row, col) pair of a block is masked by the start/end
    indices — safe to skip. mx(i) / mn(i): max / min of index column i
    over the block's key columns. Conservative under ragged-tail
    padding garbage (max only grows, min only shrinks)."""
    if causal and n == 1:
        return r_first >= mx(0)
    if causal and n == 2:
        return (r_first >= mx(0)) & (r_last < mn(1))
    if not causal and n == 2:
        return (r_first >= mx(0)) | (r_last < mn(1))
    if not causal and n == 4:
        return ((r_first >= mx(0)) & (r_last < mn(1))) | \
               ((r_first >= mx(2)) & (r_last < mn(3)))
    raise ValueError(f"n={n} invalid for causal={causal}")


def _base_live(r_first, r_last, c_first, c_last, causal, window):
    """A block may hold a pair the causal triangle / window keeps."""
    live = True
    if causal:
        live = live & (r_last >= c_first)
    if window is not None:
        live = live & (c_last >= r_first - window[0])
        if not causal:
            live = live & (c_first <= r_last + window[1])
    return live


def _block_keep(qi, ki, block_q, block_k, sq, sk, causal, window, srib, n):
    """(compute_predicate, per-pair keep mask builder) for one block."""
    r_first = qi * block_q
    r_last = qi * block_q + block_q - 1
    c_first = ki * block_k
    c_last = ki * block_k + block_k - 1
    compute = jnp.bool_(True) & _base_live(r_first, r_last, c_first, c_last,
                                           causal, window)
    if srib is not None:
        compute = compute & ~_sri_all_masked(
            r_first, r_last, lambda i: jnp.max(srib[i:i + 1, :]),
            lambda i: jnp.min(srib[i:i + 1, :]), causal, n)

    def keep_mask():
        rows = r_first + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = c_first + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        keep = (cols < sk) & (rows < sq)
        if causal:
            keep = keep & (rows >= cols)
        if window is not None:
            keep = keep & (cols >= rows - window[0])
            if not causal:
                keep = keep & (cols <= rows + window[1])
        if srib is not None:
            keep = keep & ~_sri_masked(rows, srib, causal, n)
        return keep
    return compute, keep_mask


# ---------------------------------------------------------------------------
# The live ranges: which blocks the kernels walk
# ---------------------------------------------------------------------------
def _pad_to(x, n, axis, mode="constant"):
    """x with `axis` grown to n at its end (zeros, or mode="edge": the
    last entry again); x itself where it is n long already."""
    if x.shape[axis] == n:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, widths, mode=mode)


def _live_blocks(srir, bh, causal, window, block_q, block_k, sq, sk):
    """-> (bh, n_q, n_k) bool: block (q, k) may hold an unmasked pair.
    srir: (bh, n, S_k) int32 start/end indices or None. The predicate is
    `_block_keep`'s, on the max / min of each index column over a k
    block's real columns (the ragged tail repeats its last one)."""
    n_q, n_k = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)
    r_first = jnp.arange(n_q, dtype=jnp.int32)[:, None] * block_q
    r_last = jnp.minimum(r_first + block_q, sq) - 1
    c_first = jnp.arange(n_k, dtype=jnp.int32)[None, :] * block_k
    c_last = jnp.minimum(c_first + block_k, sk) - 1
    live = jnp.ones((n_q, n_k), bool) & _base_live(
        r_first, r_last, c_first, c_last, causal, window)
    live = live[None]
    if srir is not None:
        def agg(op):
            return lambda i: op(_pad_to(srir[:, i], n_k * block_k, 1, "edge")
                                .reshape(bh, 1, n_k, block_k), axis=-1)
        live = live & ~_sri_all_masked(
            r_first[None], r_last[None], agg(jnp.max), agg(jnp.min), causal,
            srir.shape[1])
    return jnp.broadcast_to(live, (bh, n_q, n_k))


def _ranges(live):
    """(bh, n_q, n_k) live blocks -> ((k_first, k_last), (q_first,
    q_last)): the first and last live k block of every (bh, q block),
    flat (bh * n_q,) int32, and the first and last live q block of
    every (bh, k block), (bh * n_k,). An empty range reads (0, -1)."""
    def first_last(axis):
        n = live.shape[axis]
        idx = jnp.arange(n, dtype=jnp.int32).reshape(
            (1, n, 1) if axis == 1 else (1, 1, n))
        last = jnp.max(jnp.where(live, idx, -1), axis=axis)
        first = jnp.min(jnp.where(live, idx, n), axis=axis)
        first = jnp.where(last < 0, 0, first)
        return first.reshape(-1), last.reshape(-1)
    return first_last(2), first_last(1)


def _live_ranges(srir, bh, causal, window, block_q, block_k, sq, sk):
    """The ranges the kernels walk (`_ranges` of `_live_blocks`). A
    block outside a range holds no unmasked pair; inside, a general
    mask may still have dead blocks (the range is an envelope: the
    kernels keep the exact predicate). For causal document masks
    (n = 1) the range is exact."""
    return _ranges(_live_blocks(srir, bh, causal, window, block_q, block_k,
                                sq, sk))


def _sri_rows(sri):
    """(B, H, S_k, n) -> (bh, n, S_k) int32: the kernels read (n,
    block_k) tiles whose LANE dim is the 128-aligned key axis."""
    b, h, sk, n = sri.shape
    return jnp.swapaxes(sri, -1, -2).reshape(b * h, n, sk).astype(jnp.int32)


def _window_pair(window):
    """None, an int (symmetric) or (left, right) -> None or (int, int)."""
    if window is None:
        return None
    return (int(window), int(window)) if np.isscalar(window) \
        else (int(window[0]), int(window[1]))


def flashmask_live_blocks(startend_row_indices, causal=True, window=None,
                          block_q=None, block_k=None):
    """-> (live, grid): how many (q block, k block) pairs may hold an
    unmasked pair under this mask, and how many the flashmask kernels
    launch for it (a line's grid step times its loop's trips: the
    blocks inside the ranges `_live_ranges` gives, which is all a
    kernel visits), each summed over batch and heads and counted for
    the forward's grid. `grid - live` is the launched blocks that do
    nothing: 0 for causal document masks, whose ranges are exact, and
    the holes inside the envelope for a general mask.
    startend_row_indices: (B, H, S, n), queries and keys both S long;
    block_q / block_k default to what the kernel entry derives for S at
    head 128 in bfloat16."""
    b, h, s, _ = startend_row_indices.shape
    dq, dk = derived_blocks(s, s, LANES, jnp.bfloat16)
    block_q = min(block_q or dq, s)
    block_k = min(block_k or dk, s)
    live = _live_blocks(_sri_rows(jnp.asarray(startend_row_indices)), b * h,
                        causal, _window_pair(window), block_q, block_k, s, s)
    (first, last), _ = _ranges(live)
    return int(jnp.sum(live)), int(jnp.sum(last - first + 1))


# ---------------------------------------------------------------------------
# Reference (dense XLA) — correctness baseline + off-TPU fallback.
# ---------------------------------------------------------------------------
def flashmask_reference(q, k, v, sri=None, causal=True, window=None,
                        sm_scale=None, dropout=0.0, dropout_seed=None):
    """q,k,v (B,H,S,D); sri (B,H,S_k,n) already at q heads. Returns
    (out, lse). Materializes the dense mask — baseline only. window may
    be an int (symmetric) or (left, right). dropout drops attention
    probabilities (reference kernel semantics) using the SAME
    counter-based mask the pallas kernels regenerate in-kernel
    (dropout_keep_mask) — exact fwd/bwd agreement with the kernel
    path."""
    *_, sq, d = q.shape
    sk = k.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    window = _window_pair(window)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    rows = jnp.arange(sq)[:, None]
    cols = jnp.arange(sk)[None, :]
    keep = jnp.ones((sq, sk), bool)
    if causal:
        keep = keep & (cols <= rows)
    if window is not None:
        keep = keep & (cols >= rows - window[0])
        if not causal:
            keep = keep & (cols <= rows + window[1])
    keep = jnp.broadcast_to(keep[None, None], s.shape)
    if sri is not None:
        n = sri.shape[-1]
        r = rows[None, None]
        sc = jnp.swapaxes(sri, -1, -2)[:, :, :, None, :]  # (B,H,n,1,S_k)

        def col(i):
            return sc[:, :, i]
        if causal and n == 1:
            masked = r >= col(0)
        elif causal and n == 2:
            masked = (r >= col(0)) & (r < col(1))
        elif not causal and n == 2:
            masked = (r >= col(0)) | (r < col(1))
        elif not causal and n == 4:
            masked = ((r >= col(0)) & (r < col(1))) | \
                     ((r >= col(2)) & (r < col(3)))
        else:
            raise ValueError(f"n={n} invalid for causal={causal}")
        keep = keep & ~masked
    s = jnp.where(keep, s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    p = jnp.where(keep, p, jnp.zeros_like(p))
    if dropout > 0.0:
        assert dropout_seed is not None, "dropout requires dropout_seed"
        B, H = p.shape[0], p.shape[1]
        bh = (jnp.arange(B)[:, None] * H
              + jnp.arange(H)[None, :])[..., None, None]
        keep_p = dropout_keep_mask(
            jnp.broadcast_to(rows[None, None], p.shape),
            jnp.broadcast_to(cols[None, None], p.shape),
            bh, jnp.asarray(dropout_seed, jnp.int32).reshape(()),
            dropout)
        p = jnp.where(keep_p, p / (1.0 - dropout), F0)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------
def _drop_keep(seed_ref, bh, qi, ki, block_q, block_k, dropout):
    """(block_q, block_k) keep-mask + inverse-keep-prob scale for this
    block, from absolute coordinates — fwd and both bwd kernels call
    this with the same (bh, qi, ki) and regenerate the identical mask.
    bh must be read via pl.program_id at kernel top level (it does not
    lower inside a pl.when body under interpret mode)."""
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = dropout_keep_mask(rows, cols, bh, seed_ref[0], dropout)
    return keep, np.float32(1.0 / (1.0 - dropout))


def _walk(first_ref, last_ref, slot_ref, sem, streamed, block, tile):
    """One grid step (b, outer) of a kernel: run tile(inner, slot) for
    every inner block of the line's live range [first, last], in order,
    and for no other. The inner side's operands stay in HBM and come in
    by hand, double-buffered: `streamed` pairs each with its (2, ...)
    VMEM buffer and says whether its blocks run along the lanes (the
    start/end rows, axis 2) or the rows (axis 1). A trip starts the copy
    of the block the next trip reads, which after a line's last block is
    the first block of the next line of the same (batch, head), then
    waits for its own; an empty line starts the next line's. The copies
    of one (batch, head) are so one stream across its lines, and
    `slot_ref` carries which buffer the next block lands in from step
    to step (the outer grid dimension is "arbitrary": in order)."""
    b, outer, n_outer = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    line = b * n_outer + outer
    first, last = first_ref[line], last_ref[line]
    nxt = jnp.minimum(line + 1, (b + 1) * n_outer - 1)
    next_first = first_ref[nxt]
    feeds_next = (outer + 1 < n_outer) & (last_ref[nxt] >= next_first)
    empty = last < first

    def copy(inner, slot, op):
        at = pl.ds(pl.multiple_of(inner * block, block), block)
        for n, (src, buf, on_lanes) in enumerate(streamed):
            src = src.at[b, :, at] if on_lanes else src.at[b, at]
            getattr(pltpu.make_async_copy(
                src, buf.at[slot], sem.at[np.int32(n), slot]), op)()

    @pl.when(outer == 0)
    def _open():
        slot_ref[0] = Z
        pl.when(~empty)(lambda: copy(first, Z, "start"))

    @pl.when(empty & feeds_next)
    def _pass_on():
        copy(next_first, slot_ref[0], "start")

    def trip(inner, slot):
        at_end = inner == last

        @pl.when(~at_end | feeds_next)
        def _prefetch():
            copy(jnp.where(at_end, next_first, inner + 1), 1 - slot, "start")

        copy(inner, slot, "wait")
        tile(inner, slot)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(first, last + 1, trip, slot_ref[0])


def _split(refs, n_streamed, n_out):
    """A kernel's refs after its resident inputs -> the streamed
    operands (in HBM), the outputs, the streamed operands' buffers, and
    the rest of the scratch (DMA semaphores, the slot, accumulators)."""
    cuts = [0, n_streamed, n_streamed + n_out, 2 * n_streamed + n_out]
    return [refs[a:b] for a, b in zip(cuts, cuts[1:])] + [refs[cuts[-1]:]]


def _rows(buf, slot, width):
    """Block `slot` of a streamed operand's buffer at the operand's own
    width: the buffer's lanes are whole tiles (`_call`)."""
    return buf[slot, :, :width]


def _keys_streamed(hbm, bufs, n_sri):
    """`_walk`'s `streamed` for the kernels whose inner side is the
    keys': k, v and, where there is a mask, its start/end rows."""
    return [(hbm[0], bufs[0], False), (hbm[1], bufs[1], False)] + (
        [(hbm[2], bufs[2], True)] if n_sri else [])


def _fwd_kernel(first_ref, last_ref, seed_ref, q_ref, *refs, scale, causal,
                window, n_sri, block_q, block_k, sq, sk, dropout):
    hbm, (o_ref, lse_ref), bufs, (sem, slot_ref, acc_ref, m_ref, l_ref) = \
        _split(refs, 2 + bool(n_sri), 2)
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    def tile(ki, slot):
        compute, keep_mask = _block_keep(
            qi, ki, block_q, block_k, sq, sk, causal, window,
            bufs[2][slot] if n_sri else None, n_sri)

        @pl.when(compute)
        def _body():
            d = o_ref.shape[-1]         # the accumulator's width: the values'
            q, k, v = q_ref[0], _rows(bufs[0], slot, q_ref.shape[-1]), \
                _rows(bufs[1], slot, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            keep = keep_mask()
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[:]
            l_prev = l_ref[:]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - _fit_lanes(m_new, s.shape[-1]))
            p = jnp.where(keep, p, jnp.zeros_like(p))
            alpha = jnp.exp(m_prev - m_new)
            # l (→ lse) accumulates the UNdropped p: dropout applies to
            # the normalized probabilities (reference kernel semantics),
            # which post-normalization equals dropping unnormalized p
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pd = p
            if dropout > 0.0:
                dkeep, inv = _drop_keep(seed_ref, bh, qi, ki, block_q,
                                        block_k, dropout)
                pd = jnp.where(dkeep, p * inv, F0)
            acc_ref[:] = acc_ref[:] * _fit_lanes(alpha, d) + \
                jax.lax.dot_general(pd.astype(v.dtype), v,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            m_ref[:] = m_new
            l_ref[:] = l_new

    _walk(first_ref, last_ref, slot_ref, sem,
          _keys_streamed(hbm, bufs, n_sri), block_k, tile)

    # a line with an empty range lands here with l = 0: zeros, finite lse
    l = l_ref[:]
    l_safe = jnp.where(l == F0, F1, l)
    o_ref[0] = (acc_ref[:] / _fit_lanes(l_safe, o_ref.shape[-1])
                ).astype(o_ref.dtype)
    lse_ref[0] = m_ref[:] + jnp.log(l_safe)


def _p_and_ds(q, k, v, do, lse, delta, keep, dkeep_inv, scale):
    """The backward's two tiles for one block, float32: p (undropped
    probabilities) and ds = p ∘ (D∘dp − delta) · scale. Operands go to
    the MXU in their own type with float32 accumulation: a product of
    two bfloat16 values is exact in float32, so `do · vᵀ` is the
    number an up-cast would give."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(keep, s, NEG_INF)
    p = jnp.exp(s - _fit_lanes(lse, s.shape[-1]))
    p = jnp.where(keep, p, jnp.zeros_like(p))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if dkeep_inv is not None:
        # delta already equals Σ_k p̃ dp (= do·o), so only dp gets the
        # dropout mask
        dkeep, inv = dkeep_inv
        dp = jnp.where(dkeep, dp * inv, F0)
    ds = jnp.where(keep, p * (dp - _fit_lanes(delta, dp.shape[-1])) * scale,
                   F0)
    return p, ds


def _bwd_dq_kernel(first_ref, last_ref, seed_ref, q_ref, do_ref, lse_ref,
                   delta_ref, *refs, scale, causal, window, n_sri, block_q,
                   block_k, sq, sk, dropout):
    hbm, (dq_ref,), bufs, (sem, slot_ref, dq_acc) = \
        _split(refs, 2 + bool(n_sri), 1)
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    dq_acc[:] = jnp.zeros_like(dq_acc)

    def tile(ki, slot):
        compute, keep_mask = _block_keep(
            qi, ki, block_q, block_k, sq, sk, causal, window,
            bufs[2][slot] if n_sri else None, n_sri)

        @pl.when(compute)
        def _body():
            k = _rows(bufs[0], slot, q_ref.shape[-1])
            v = _rows(bufs[1], slot, do_ref.shape[-1])
            drop = _drop_keep(seed_ref, bh, qi, ki, block_q, block_k,
                              dropout) if dropout > 0.0 else None
            _, ds = _p_and_ds(q_ref[0], k, v, do_ref[0], lse_ref[0],
                              delta_ref[0], keep_mask(), drop, scale)
            dq_acc[:] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _walk(first_ref, last_ref, slot_ref, sem,
          _keys_streamed(hbm, bufs, n_sri), block_k, tile)
    dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(first_ref, last_ref, seed_ref, k_ref, v_ref, *refs, scale,
                    causal, window, n_sri, block_q, block_k, sq, sk, dropout):
    sri_ref = None
    if n_sri:
        sri_ref, *refs = refs
    hbm, (dk_ref, dv_ref), bufs, (sem, slot_ref, dk_acc, dv_acc) = \
        _split(refs, 4, 2)
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile(qi, slot):
        compute, keep_mask = _block_keep(
            qi, ki, block_q, block_k, sq, sk, causal, window,
            sri_ref[0] if n_sri else None, n_sri)

        @pl.when(compute)
        def _body():
            q = _rows(bufs[0], slot, k_ref.shape[-1])
            do = _rows(bufs[1], slot, v_ref.shape[-1])
            lse, delta = bufs[2][slot], bufs[3][slot]
            drop = _drop_keep(seed_ref, bh, qi, ki, block_q, block_k,
                              dropout) if dropout > 0.0 else None
            p, ds = _p_and_ds(q, k_ref[0], v_ref[0], do, lse, delta,
                              keep_mask(), drop, scale)
            pd = p if drop is None else jnp.where(drop[0], p * drop[1], F0)
            dv_acc[:] += jax.lax.dot_general(
                pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[:] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _walk(first_ref, last_ref, slot_ref, sem,
          [(ref, buf, False) for ref, buf in zip(hbm, bufs)], block_q, tile)
    dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------
_BLOCKS = (512, 256, 128)
# what one kernel may hold of VMEM: v5e's scoped default is 16 MiB, the
# smallest of the generations this runs on
_VMEM_BUDGET = 16 * 2 ** 20


def _vmem_bytes(block_q, block_k, d, itemsize, n_sri=1, d_v=None):
    """VMEM the backward dK/dV kernel, the largest of the three, holds
    at one block: its operands and outputs twice (the pipeline's double
    buffer), its float32 accumulators, and four (block_q, block_k)
    float32 tiles (scores, p, dp, ds). q, k and dk are `d` wide; v, do
    and dv `d_v` (None: `d`), each in whole lanes."""
    sub = lambda n: -(-n // 8) * 8
    d_v = d if d_v is None else d_v
    if d_v != d:        # a width between lane multiples lies in whole lanes
        d, d_v = (-(-w // LANES) * LANES for w in (d, d_v))
    operands = ((block_q + block_k) * (d + d_v) * itemsize  # q, do; k, v
                + 2 * block_q * LANES * 4             # lse, delta
                + sub(n_sri) * block_k * 4)           # start/end rows
    outputs = block_k * (d + d_v) * itemsize          # dk, dv
    accs = block_k * (d + d_v) * 4
    tiles = 4 * block_q * block_k * 4
    return 2 * (operands + outputs) + accs + tiles


def derived_blocks(sq, sk, d, dtype, d_v=None):
    """(block_q, block_k) from the shapes: the candidates (`_BLOCKS`)
    that fit the sequence and the VMEM budget, of those the one that
    pads the sequence least, of those the largest. A static function of
    shapes; nothing is timed. The table is one sweep on a v5e at
    (4, 16, 4096, 128) bfloat16 with packed documents (PERF.md, PR 38),
    made while a block outside a line's range still cost a grid step,
    which by itself favoured large blocks. No such step is launched
    now; what a large block still saves is a trip's fixed work (its
    copies' starts and waits, the mask's iotas, the row statistics),
    and what it costs is the pairs it covers and the mask kills (30% of
    `packed_8k`'s at 512 x 512, 45% of `packed_4k`'s).
    `tools/flashmask_bench.py` read 512 / 256 a side again at both
    training shapes on a v5e: 512 x 512 stays faster by 10-37%
    (PERF.md, PR 49), so the table stands; it moves only where another
    choice is faster by 3% at both. `d_v`: the values' width where it
    is not the keys' (latent attention: 192 and 128)."""
    itemsize = jnp.dtype(dtype).itemsize

    def pick(s, fits):
        ok = [c for c in _BLOCKS if c <= max(s, _BLOCKS[-1]) and fits(c)]
        ok = ok or [_BLOCKS[-1]]
        return min(ok, key=lambda c: (pl.cdiv(s, c) * c, -c))
    block_q = pick(sq, lambda c: _vmem_bytes(c, c, d, itemsize, d_v=d_v)
                   <= _VMEM_BUDGET)
    block_k = pick(sk, lambda c: _vmem_bytes(block_q, c, d, itemsize, d_v=d_v)
                   <= _VMEM_BUDGET)
    return block_q, block_k


def _vmem_limit(block_q, block_k, d, dtype, n_sri, d_v=None):
    """What the kernels ask of VMEM: the budget the derived blocks were
    held to, and the count itself where explicit blocks pass it."""
    return max(_VMEM_BUDGET, 2 * _vmem_bytes(
        block_q, block_k, d, jnp.dtype(dtype).itemsize, max(n_sri, 1), d_v))


def _blocks(block_q, block_k, sq, sk, d, dtype, d_v=None):
    """The blocks a call asks for: the caller's, else PT_FLASH_BLOCK_Q/K
    where the environment sets them, else derived from the shapes;
    never past the sequence (`_prep` rounds them up to whole lane
    tiles)."""
    dq, dk = derived_blocks(sq, sk, d, dtype, d_v)
    if block_q is None:
        block_q = env_int("PT_FLASH_BLOCK_Q", dq)
    if block_k is None:
        block_k = env_int("PT_FLASH_BLOCK_K", dk)
    return min(block_q, sq), min(block_k, sk)


def _prep(q, k, v, sri, block_q, block_k):
    """Operands flat over (batch, head), each sequence grown with zeros
    to a whole number of its blocks (the start/end rows with their last
    column again, which leaves a block's max and min what they are): a
    kernel copies and multiplies whole blocks, and the per-pair mask
    keeps rows and columns past the real lengths out. -> the operands
    and the blocks the call runs at."""
    b, h, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[-1]
    bh = b * h
    # whole lane tiles (and so whole sublane tiles of every type): what
    # a copy by hand can slice out of an array on either axis
    block_q, block_k = (pl.cdiv(c, LANES) * LANES for c in _blocks(
        block_q, block_k, sq, sk, d, q.dtype, d_v))
    n_q, n_k = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)
    qr = _pad_to(q.reshape(bh, sq, d), n_q * block_q, 1)
    kr = _pad_to(k.reshape(bh, sk, d), n_k * block_k, 1)
    vr = _pad_to(v.reshape(bh, sk, d_v), n_k * block_k, 1)
    srir = None if sri is None else _pad_to(_sri_rows(sri), n_k * block_k, 2,
                                            "edge")
    return qr, kr, vr, srir, block_q, block_k


def _seed_arr(seed):
    if seed is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(seed, jnp.int32).reshape((1,))


def _call(kernel, scalars, resident, streamed, outs, accs, outer_block,
          inner_block, vmem_limit, interpret):
    """One kernel over the grid (bh, outer blocks). `resident` and
    `outs`: (array or its shape, on_lanes), blocked by the grid's step
    along the rows (axis 1) or, the start/end rows, the lanes (axis 2);
    `streamed`: (array, on_lanes), left in HBM for `_walk`, each with a
    two-block buffer, its rows grown to whole lane tiles where they are
    not (a copy by hand takes no narrower slice; the kernels read their
    own width of the buffer, so no product runs over the padding);
    `scalars`: (first, last, seed), prefetched; `accs`: the
    accumulators' shapes, float32."""
    streamed = [(x if on_lanes else
                 _pad_to(x, pl.cdiv(x.shape[2], LANES) * LANES, 2), on_lanes)
                for x, on_lanes in streamed]

    def blocked(x, on_lanes):
        if on_lanes:
            return pl.BlockSpec((1, x.shape[1], outer_block),
                                lambda b, i, *_: (b, Z, i))
        return pl.BlockSpec((1, outer_block, x.shape[2]),
                            lambda b, i, *_: (b, i, Z))

    def buffer(x, on_lanes):
        return pltpu.VMEM((2, x.shape[1], inner_block) if on_lanes
                          else (2, inner_block, x.shape[2]), x.dtype)
    bh, rows = resident[0][0].shape[:2]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(bh, rows // outer_block),
            in_specs=[blocked(*r) for r in resident]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(streamed),
            out_specs=[blocked(*o) for o in outs],
            scratch_shapes=[buffer(*s) for s in streamed]
            + [pltpu.SemaphoreType.DMA((len(streamed), 2)),
               pltpu.SMEM((1,), jnp.int32)]
            + [pltpu.VMEM(shape, jnp.float32) for shape in accs]),
        out_shape=[o for o, _ in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(*scalars, *(r for r, _ in resident), *(s for s, _ in streamed))


def _fwd_pallas(q, k, v, sri, causal, window, scale, block_q, block_k,
                interpret, dropout=0.0, seed=None):
    b, h, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[-1]
    qr, kr, vr, srir, block_q, block_k = _prep(q, k, v, sri, block_q, block_k)
    bh, n_sri = b * h, 0 if srir is None else srir.shape[1]
    (k_first, k_last), _ = _live_ranges(srir, bh, causal, window, block_q,
                                        block_k, sq, sk)
    kernel = functools.partial(
        _fwd_kernel, scale=np.float32(scale), causal=causal, window=window,
        n_sri=n_sri, block_q=block_q, block_k=block_k, sq=sq, sk=sk,
        dropout=dropout)
    o, lse = _call(
        kernel, (k_first, k_last, _seed_arr(seed)), [(qr, False)],
        [(kr, False), (vr, False)] + ([(srir, True)] if n_sri else []),
        [(jax.ShapeDtypeStruct((bh, qr.shape[1], d_v), q.dtype), False),
         (jax.ShapeDtypeStruct((bh, qr.shape[1], LANES), jnp.float32), False)],
        [(block_q, d_v), (block_q, LANES), (block_q, LANES)],
        block_q, block_k, _vmem_limit(block_q, block_k, d, q.dtype, n_sri, d_v),
        interpret)
    return (o[:, :sq].reshape(b, h, sq, d_v),
            lse[:, :sq].reshape(b, h, sq, LANES))


def _bwd_pallas(q, k, v, sri, o, lse, do, causal, window, scale,
                block_q, block_k, interpret, dropout=0.0, seed=None):
    b, h, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[-1]
    qr, kr, vr, srir, block_q, block_k = _prep(q, k, v, sri, block_q, block_k)
    bh, n_sri = b * h, 0 if srir is None else srir.shape[1]
    k_range, q_range = _live_ranges(srir, bh, causal, window, block_q,
                                    block_k, sq, sk)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    rows = qr.shape[1]
    dor = _pad_to(do.reshape(bh, sq, d_v), rows, 1)
    lser = _pad_to(lse.reshape(bh, sq, LANES), rows, 1)
    deltar = _pad_to(jnp.broadcast_to(delta.reshape(bh, sq)[..., None],
                                      (bh, sq, LANES)), rows, 1)

    statics = dict(scale=np.float32(scale), causal=causal, window=window,
                   n_sri=n_sri, block_q=block_q, block_k=block_k, sq=sq, sk=sk,
                   dropout=dropout)
    q_side = [(qr, False), (dor, False), (lser, False), (deltar, False)]
    k_side = [(kr, False), (vr, False)] + ([(srir, True)] if n_sri else [])
    vmem_limit = _vmem_limit(block_q, block_k, d, q.dtype, n_sri, d_v)
    like = lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype), False)

    dq, = _call(
        functools.partial(_bwd_dq_kernel, **statics),
        (*k_range, _seed_arr(seed)), q_side, k_side, [like(qr)],
        [(block_q, d)], block_q, block_k, vmem_limit, interpret)
    dk, dv = _call(
        functools.partial(_bwd_dkv_kernel, **statics),
        (*q_range, _seed_arr(seed)), k_side, q_side, [like(kr), like(vr)],
        [(block_k, d), (block_k, d_v)], block_k, block_q, vmem_limit,
        interpret)
    return (dq[:, :sq].reshape(b, h, sq, d), dk[:, :sk].reshape(b, h, sk, d),
            dv[:, :sk].reshape(b, h, sk, d_v))


# ---------------------------------------------------------------------------
# Public op with custom VJP
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flashmask(q, k, v, sri, seed, causal, window, scale, block_q, block_k,
               interpret, dropout):
    o, _ = _fwd_pallas(q, k, v, sri, causal, window, scale, block_q,
                       block_k, interpret, dropout, seed)
    return o


def _flashmask_fwd(q, k, v, sri, seed, causal, window, scale, block_q,
                   block_k, interpret, dropout):
    o, lse = _fwd_pallas(q, k, v, sri, causal, window, scale, block_q,
                         block_k, interpret, dropout, seed)
    return o, (q, k, v, sri, seed, o, lse)


def _flashmask_bwd(causal, window, scale, block_q, block_k, interpret,
                   dropout, res, do):
    q, k, v, sri, seed, o, lse = res
    dq, dk, dv = _bwd_pallas(q, k, v, sri, o, lse, do, causal, window,
                             scale, block_q, block_k, interpret, dropout,
                             seed)
    dsri = (None if sri is None
            else np.zeros(sri.shape, jax.dtypes.float0))
    dseed = (None if seed is None
             else np.zeros(np.shape(seed), jax.dtypes.float0))
    return dq, dk, dv, dsri, dseed


_flashmask.defvjp(_flashmask_fwd, _flashmask_bwd)


def flashmask_attention_bhsd(q, k, v, startend_row_indices=None, causal=True,
                             window=None, sm_scale=None,
                             block_q=None, block_k=None,
                             use_pallas=None, interpret=None,
                             dropout=0.0, dropout_seed=None):
    """Core entry: q,k (B,H,S,D), v (B,H,S_k,D_v), startend_row_indices
    (B,H,S_k,n) already broadcast to the q heads -> (B,H,S,D_v).
    O(S·block) memory on the kernel path; dense reference off-TPU unless
    interpret is forced.

    D_v may differ from D (latent attention up-projected: keys of 192,
    values of 128): the three kernels carry v, o, dO and dV at the
    values' width and q, k, dQ and dK at the keys', so no product runs
    over padding; `sm_scale` defaults to 1/sqrt(D). With D_v == D the
    kernels are the ones they were.

    block_q / block_k: None (the default) takes PT_FLASH_BLOCK_Q / _K
    where the environment sets them and else derives the blocks from
    the shapes (`derived_blocks`). The kernels walk only the blocks
    inside each line's live range (`_live_ranges`, computed here from
    the mask, prefetched as scalars); `flashmask_live_blocks` counts
    them for a mask.

    dropout: attention-probability dropout applied IN-KERNEL from a
    deterministic counter-based mask keyed by (dropout_seed, coords) —
    the kernel path stays O(S·block) for every config, dropout
    included (VERDICT r4 item 5). The dense off-TPU reference applies
    the identical mask when given dropout_seed, so the two paths agree
    exactly.
    """
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    window = _window_pair(window)
    if dropout > 0.0 and dropout_seed is None:
        raise ValueError("flashmask dropout requires dropout_seed")
    if use_pallas is None:
        use_pallas = _on_tpu() and not pallas_disabled()
    if interpret is None:
        interpret = not _on_tpu()
    if not use_pallas:
        o, _ = flashmask_reference(q, k, v, startend_row_indices, causal,
                                   window, scale, dropout=dropout,
                                   dropout_seed=dropout_seed)
        return o
    return _flashmask(q, k, v, startend_row_indices,
                      _seed_arr(dropout_seed) if dropout > 0.0 else None,
                      causal, window, scale, block_q, block_k, interpret,
                      float(dropout))
