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
    work, mirroring the reference kernel's block-skip. Aggregates over
    the ragged tail's padding lanes only weaken the skip predicate
    (max grows / min shrinks), never falsify it;
  * the same aggregates, taken once outside the kernels, give every
    line of the grid its live range: the first and last inner block
    that may hold an unmasked pair (`_live_ranges`). The ranges are
    prefetched scalars; the index maps clamp into them, so a step
    outside its range names the block already resident (no copy) and
    its body is skipped on a comparison of two scalars. The grid stays
    (bh, outer, inner): a dead step still costs a pipeline step, about
    0.3 us on a v5e, which is why the blocks are large;
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
from .flash_attention import (F0, F1, NEG_INF, Z, LANES,
                              _col_mask, _fit_lanes, _on_tpu,
                              pallas_disabled)


def _zero_oob(qi, ki, q, k, v, do=None, *, block_q, block_k, sq, sk):
    """Zero out ragged-tail garbage: OOB lanes of a padded block read
    undefined values, and 0 * NaN would poison the accumulators even
    where the keep-mask already zeroes p/ds. q / k are `d` wide, v / do
    `d_v` (the same mask where the two are one width)."""
    d, d_v = q.shape[-1], v.shape[-1]
    if sk % block_k != 0:
        km = _col_mask(ki * block_k, block_k, sk, d)
        k = jnp.where(km, k, jnp.zeros_like(k))
        vm = km if d_v == d else _col_mask(ki * block_k, block_k, sk, d_v)
        v = jnp.where(vm, v, jnp.zeros_like(v))
    if sq % block_q != 0:
        qm = _col_mask(qi * block_q, block_q, sq, d)
        q = jnp.where(qm, q, jnp.zeros_like(q))
        if do is not None:
            dm = qm if d_v == d else _col_mask(qi * block_q, block_q, sq, d_v)
            do = jnp.where(dm, do, jnp.zeros_like(do))
    return (q, k, v) if do is None else (q, k, v, do)


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
def _live_ranges(srir, bh, causal, window, block_q, block_k, sq, sk):
    """-> ((k_first, k_last), (q_first, q_last)): per (bh, q block) the
    first and last k block that may hold an unmasked pair, flat
    (bh * n_q,) int32, and per (bh, k block) the first and last q
    block, (bh * n_k,). An empty range reads (0, -1).

    srir: (bh, n, S_k) int32 start/end indices or None. The block
    predicate is `_block_keep`'s, on the max / min of each index column
    over a k block's real columns, so a block outside a range holds no
    unmasked pair; inside, a general mask may still have dead blocks
    (the range is an envelope: the body keeps the exact predicate).
    For causal document masks (n = 1) the range is exact."""
    n_q, n_k = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)
    r_first = jnp.arange(n_q, dtype=jnp.int32)[:, None] * block_q
    r_last = jnp.minimum(r_first + block_q, sq) - 1
    c_first = jnp.arange(n_k, dtype=jnp.int32)[None, :] * block_k
    c_last = jnp.minimum(c_first + block_k, sk) - 1
    live = jnp.ones((n_q, n_k), bool) & _base_live(
        r_first, r_last, c_first, c_last, causal, window)
    live = live[None]
    if srir is not None:
        pad = n_k * block_k - sk

        def agg(op, fill):
            def of(i):
                col = jnp.pad(srir[:, i], ((0, 0), (0, pad)),
                              constant_values=fill)
                return op(col.reshape(bh, 1, n_k, block_k), axis=-1)
            return of
        info = jnp.iinfo(jnp.int32)
        live = live & ~_sri_all_masked(
            r_first[None], r_last[None], agg(jnp.max, info.min),
            agg(jnp.min, info.max), causal, srir.shape[1])
    live = jnp.broadcast_to(live, (bh, n_q, n_k))

    def first_last(axis, n):
        idx = jnp.arange(n, dtype=jnp.int32).reshape(
            (1, n, 1) if axis == 1 else (1, 1, n))
        last = jnp.max(jnp.where(live, idx, -1), axis=axis)
        first = jnp.min(jnp.where(live, idx, n), axis=axis)
        first = jnp.where(last < 0, 0, first)
        return first.reshape(-1), last.reshape(-1)
    return first_last(2, n_k), first_last(1, n_q)


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
    """-> (live, grid): how many (q block, k block) steps of the
    flashmask kernels' grids do work for this mask, and how many the
    grid has, summed over batch and heads. `live` counts the blocks
    inside the ranges the kernels walk (`_live_ranges`): for causal
    document masks exactly the blocks that hold an unmasked pair, for
    a general mask an envelope of them. startend_row_indices:
    (B, H, S, n), queries and keys both S long; block_q / block_k
    default to what the kernel entry derives for S at head 128 in
    bfloat16."""
    b, h, s, _ = startend_row_indices.shape
    dq, dk = derived_blocks(s, s, LANES, jnp.bfloat16)
    block_q = min(block_q or dq, s)
    block_k = min(block_k or dk, s)
    (first, last), _ = _live_ranges(
        _sri_rows(jnp.asarray(startend_row_indices)), b * h, causal,
        _window_pair(window), block_q, block_k, s, s)
    grid = b * h * pl.cdiv(s, block_q) * pl.cdiv(s, block_k)
    return int(jnp.sum(last - first + 1)), grid


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


def _when_live(first_ref, last_ref, line, inner, sri_ref, qi, ki, *,
               block_q, block_k, sq, sk, causal, window, n_sri):
    """Decorator: run body(keep_mask) when block (qi, ki) holds an
    unmasked pair. First a comparison of prefetched scalars: `inner`
    (the grid's inner block index) lies in line `line`'s live range;
    outside it the index maps name the block already resident
    (`_index_maps`), so the step moves no bytes and reads nothing. Then
    `_block_keep`'s exact predicate on the start/end block, since a
    general mask's range is an envelope."""
    def deco(body):
        @pl.when((inner >= first_ref[line]) & (inner <= last_ref[line]))
        def _in_range():
            srib = sri_ref[0] if sri_ref is not None else None
            compute, keep_mask = _block_keep(qi, ki, block_q, block_k, sq,
                                             sk, causal, window, srib, n_sri)
            pl.when(compute)(lambda: body(keep_mask))
    return deco


def _fwd_kernel(first_ref, last_ref, seed_ref, q_ref, k_ref, v_ref, sri_ref,
                o_ref, lse_ref, acc_ref, m_ref, l_ref, *, scale, causal,
                window, n_sri, block_q, block_k, n_q, n_k, sq, sk, dropout):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @_when_live(first_ref, last_ref, bh * n_q + qi, ki, sri_ref, qi, ki,
                block_q=block_q, block_k=block_k, sq=sq, sk=sk, causal=causal,
                window=window, n_sri=n_sri)
    def body(keep_mask):
        q, k, v = _zero_oob(qi, ki, q_ref[0], k_ref[0], v_ref[0],
                            block_q=block_q, block_k=block_k, sq=sq, sk=sk)
        d = v.shape[-1]                 # the accumulator's width: the values'
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
        # l (→ lse) accumulates the UNdropped p: dropout applies to the
        # normalized probabilities (reference kernel semantics), which
        # post-normalization equals dropping unnormalized p
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pd = p
        if dropout > 0.0:
            dkeep, inv = _drop_keep(seed_ref, bh, qi, ki, block_q, block_k,
                                    dropout)
            pd = jnp.where(dkeep, p * inv, F0)
        acc_ref[:] = acc_ref[:] * _fit_lanes(alpha, d) + jax.lax.dot_general(
            pd.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        l_ref[:] = l_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == F0, F1, l)
        d = o_ref.shape[-1]
        o_ref[0] = (acc_ref[:] / _fit_lanes(l_safe, d)).astype(o_ref.dtype)
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


def _bwd_dq_kernel(first_ref, last_ref, seed_ref, q_ref, k_ref, v_ref,
                   sri_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc, *,
                   scale, causal, window, n_sri, block_q, block_k, n_q, n_k,
                   sq, sk, dropout):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @_when_live(first_ref, last_ref, bh * n_q + qi, ki, sri_ref, qi, ki,
                block_q=block_q, block_k=block_k, sq=sq, sk=sk, causal=causal,
                window=window, n_sri=n_sri)
    def body(keep_mask):
        q, k, v, do = _zero_oob(qi, ki, q_ref[0], k_ref[0], v_ref[0],
                                do_ref[0], block_q=block_q, block_k=block_k,
                                sq=sq, sk=sk)
        drop = _drop_keep(seed_ref, bh, qi, ki, block_q, block_k,
                          dropout) if dropout > 0.0 else None
        _, ds = _p_and_ds(q, k, v, do, lse_ref[0], delta_ref[0], keep_mask(),
                          drop, scale)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _fin():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(first_ref, last_ref, seed_ref, q_ref, k_ref, v_ref,
                    sri_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_acc, dv_acc, *, scale, causal, window, n_sri, block_q,
                    block_k, n_q, n_k, sq, sk, dropout):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @_when_live(first_ref, last_ref, bh * n_k + ki, qi, sri_ref, qi, ki,
                block_q=block_q, block_k=block_k, sq=sq, sk=sk, causal=causal,
                window=window, n_sri=n_sri)
    def body(keep_mask):
        q, k, v, do = _zero_oob(qi, ki, q_ref[0], k_ref[0], v_ref[0],
                                do_ref[0], block_q=block_q, block_k=block_k,
                                sq=sq, sk=sk)
        drop = _drop_keep(seed_ref, bh, qi, ki, block_q, block_k,
                          dropout) if dropout > 0.0 else None
        p, ds = _p_and_ds(q, k, v, do, lse_ref[0], delta_ref[0], keep_mask(),
                          drop, scale)
        pd = p if drop is None else jnp.where(drop[0], p * drop[1], F0)
        dv_acc[:] += jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _fin():
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
    (4, 16, 4096, 128) bfloat16 with packed documents (PERF.md, PR 38):
    large blocks win until the tiles leave VMEM, because a grid step
    costs the same whatever it holds. `d_v`: the values' width where it
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
    """The blocks a call runs at: the caller's, else PT_FLASH_BLOCK_Q/K
    where the environment sets them, else derived from the shapes;
    never past the sequence."""
    dq, dk = derived_blocks(sq, sk, d, dtype, d_v)
    if block_q is None:
        block_q = env_int("PT_FLASH_BLOCK_Q", dq)
    if block_k is None:
        block_k = env_int("PT_FLASH_BLOCK_K", dk)
    return min(block_q, sq), min(block_k, sk)


def _prep(q, k, v, sri):
    """Operands flat over (batch, head). `d`: the width of q and k;
    `d_v`: of v (and so of o and do), the same or its own."""
    b, h, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[-1]
    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, d_v)
    srir = None if sri is None else _sri_rows(sri)
    return qr, kr, vr, srir, b, h, sq, sk, d, d_v, bh


def _mem_spec():
    return functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)


def _mk_kernel(fn, have_sri, **kw):
    """Bind statics; when sri is absent, shim a None into the kernel's
    sri_ref slot so one kernel body serves both signatures."""
    if have_sri:
        return functools.partial(fn, **kw)
    return functools.partial(
        lambda first_, last_, seed_, q_, k_, v_, *rest, **kw2:
        fn(first_, last_, seed_, q_, k_, v_, None, *rest, **kw2),
        **kw)


def _seed_arr(seed):
    if seed is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(seed, jnp.int32).reshape((1,))


def _index_maps(n_outer, inner_is_k):
    """Index maps of a grid (bh, outer, inner) whose prefetched scalars
    are (first, last, seed): the inner block index is clamped into the
    line's live range, so a step outside it names the block the step
    before it held and the pipeline issues no copy. -> maps for the
    q-side, the k-side and the start/end blocks."""
    def clamped(b, outer, inner, first, last, _seed):
        line = b * n_outer + outer
        return jnp.maximum(jnp.minimum(inner, last[line]), first[line])

    def q_map(b, outer, inner, *s):
        return (b, outer if inner_is_k else clamped(b, outer, inner, *s), Z)

    def k_map(b, outer, inner, *s):
        return (b, clamped(b, outer, inner, *s) if inner_is_k else outer, Z)

    def sri_map(b, outer, inner, *s):
        return (b, Z, k_map(b, outer, inner, *s)[1])
    return q_map, k_map, sri_map


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch_shapes,
          vmem_limit, interpret):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )


def _fwd_pallas(q, k, v, sri, causal, window, scale, block_q, block_k,
                interpret, dropout=0.0, seed=None):
    scale = np.float32(scale)
    qr, kr, vr, srir, b, h, sq, sk, d, d_v, bh = _prep(q, k, v, sri)
    block_q, block_k = _blocks(block_q, block_k, sq, sk, d, q.dtype, d_v)
    n_q = pl.cdiv(sq, block_q)
    n_k = pl.cdiv(sk, block_k)
    n_sri = srir.shape[1] if srir is not None else 0
    spec = _mem_spec()
    (k_first, k_last), _ = _live_ranges(srir, bh, causal, window, block_q,
                                        block_k, sq, sk)
    q_map, k_map, sri_map = _index_maps(n_q, inner_is_k=True)

    in_specs = [spec((1, block_q, d), q_map), spec((1, block_k, d), k_map),
                spec((1, block_k, d_v), k_map)]
    args = [k_first, k_last, _seed_arr(seed), qr, kr, vr]
    if srir is not None:
        in_specs.append(spec((1, n_sri, block_k), sri_map))
        args.append(srir)
    kernel = _mk_kernel(_fwd_kernel, srir is not None, scale=scale,
                        causal=causal, window=window, n_sri=n_sri,
                        block_q=block_q, block_k=block_k, n_q=n_q, n_k=n_k,
                        sq=sq, sk=sk, dropout=dropout)

    o, lse = _call(
        kernel, (bh, n_q, n_k), in_specs,
        out_specs=[spec((1, block_q, d_v), q_map),
                   spec((1, block_q, LANES), q_map)],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d_v), q.dtype),
                   jax.ShapeDtypeStruct((bh, sq, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, d_v), jnp.float32),
                        pltpu.VMEM((block_q, LANES), jnp.float32),
                        pltpu.VMEM((block_q, LANES), jnp.float32)],
        vmem_limit=_vmem_limit(block_q, block_k, d, q.dtype, n_sri, d_v),
        interpret=interpret,
    )(*args)
    return o.reshape(b, h, sq, d_v), lse.reshape(b, h, sq, LANES)


def _bwd_pallas(q, k, v, sri, o, lse, do, causal, window, scale,
                block_q, block_k, interpret, dropout=0.0, seed=None):
    scale = np.float32(scale)
    qr, kr, vr, srir, b, h, sq, sk, d, d_v, bh = _prep(q, k, v, sri)
    block_q, block_k = _blocks(block_q, block_k, sq, sk, d, q.dtype, d_v)
    n_q = pl.cdiv(sq, block_q)
    n_k = pl.cdiv(sk, block_k)
    n_sri = srir.shape[1] if srir is not None else 0
    spec = _mem_spec()
    k_range, q_range = _live_ranges(srir, bh, causal, window, block_q,
                                    block_k, sq, sk)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dor = do.reshape(bh, sq, d_v)
    lser = lse.reshape(bh, sq, LANES)
    deltar = jnp.broadcast_to(delta.reshape(bh, sq)[..., None],
                              (bh, sq, LANES))

    def specs(q_map, k_map, sri_map):
        return ([spec((1, block_q, d), q_map), spec((1, block_k, d), k_map),
                 spec((1, block_k, d_v), k_map)]
                + ([spec((1, n_sri, block_k), sri_map)]
                   if srir is not None else [])
                + [spec((1, block_q, d_v), q_map),
                   spec((1, block_q, LANES), q_map),
                   spec((1, block_q, LANES), q_map)])

    statics = dict(scale=scale, causal=causal, window=window, n_sri=n_sri,
                   block_q=block_q, block_k=block_k, n_q=n_q, n_k=n_k,
                   sq=sq, sk=sk, dropout=dropout)
    operands = [qr, kr, vr] + ([srir] if srir is not None else []) + \
        [dor, lser, deltar]
    vmem_limit = _vmem_limit(block_q, block_k, d, q.dtype, n_sri, d_v)

    dq_maps = _index_maps(n_q, inner_is_k=True)
    dq = _call(
        _mk_kernel(_bwd_dq_kernel, srir is not None, **statics),
        (bh, n_q, n_k), specs(*dq_maps),
        out_specs=[spec((1, block_q, d), dq_maps[0])],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        vmem_limit=vmem_limit, interpret=interpret,
    )(*k_range, _seed_arr(seed), *operands)[0]

    dkv_maps = _index_maps(n_k, inner_is_k=False)
    dk, dv = _call(
        _mk_kernel(_bwd_dkv_kernel, srir is not None, **statics),
        (bh, n_k, n_q), specs(*dkv_maps),
        out_specs=[spec((1, block_k, d), dkv_maps[1]),
                   spec((1, block_k, d_v), dkv_maps[1])],
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d_v), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32)],
        vmem_limit=vmem_limit, interpret=interpret,
    )(*q_range, _seed_arr(seed), *operands)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d_v))


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
