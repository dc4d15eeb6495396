"""Varlen (unpadded / packed) flash attention — TPU pallas kernel.

Reference parity: python/paddle/nn/functional/flash_attention.py:756
(`flash_attn_unpadded`: packed (total, H, D) tensors + cu_seqlens prefix
sums — the serving-prefill workhorse for ragged batches).

TPU-native redesign: instead of the CUDA kernel's per-sequence pointer
arithmetic, sequences are packed along one token axis and masked by
*segment ids* — the layout XLA/Mosaic likes (static shapes, no gathers):

  * seg ids are derived from cu_seqlens (prefix sums) host/trace side;
  * q seg ids ride lane-replicated  (T_q, LANES)  blocks,
    k seg ids ride sublane-replicated (8, T_k)     blocks — both satisfy
    the TPU (8, 128) min-tile rule (same trick as the dense kernel's lse);
  * a position pair is attendable iff seg_q == seg_k (and, for causal,
    k_pos <= q_pos — packed positions are monotone inside a segment so
    global-position causality is exact within a segment);
  * padding tokens (beyond cu_seqlens[-1]) get sentinel segments that
    never match (q-pad = -1, k-pad = -2), so they attend nothing and
    contribute nothing; fully-masked rows resolve to output 0 via the
    safe-l trick and are masked out of the backward by `valid`.

The backward follows the dense kernel's two-pass structure (dq pass over
q blocks, dk/dv pass over k blocks) with the same segment masks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, F0, F1,
                              LANES, NEG_INF, Z, _fit_lanes, _on_tpu)

SUBLANES = 8


# ---------------------------------------------------------------------------
# Reference (pure XLA) implementation over packed layout
# ---------------------------------------------------------------------------
def rev_pos(seg):
    """Per-token distance from its segment's end (monotone seg ids):
    r[i] = (index one past the segment end) - i. Bottom-right-aligned
    causality (flash-attention semantics for unequal q/k lengths) is then
    simply r_k >= r_q — independent of where the segment sits in the pack.

    Negative ids mark padding (always trailing); they are remapped to a
    large value before the binary search so the array stays monotone —
    searchsorted on a non-monotone array would corrupt the segment ends
    of REAL tokens, not just the pads."""
    seg = seg.astype(jnp.int32)
    n = seg.shape[0]
    mono = jnp.where(seg < 0, jnp.int32(2**31 - 1), seg)
    ends = jnp.searchsorted(mono, mono, side="right").astype(jnp.int32)
    return ends - jnp.arange(n, dtype=jnp.int32)


def varlen_reference(q, k, v, seg_q, seg_k, causal, scale):
    """q: (H, Tq, D), k/v: (H, Tk, D), seg ids (Tq,)/(Tk,) int32.
    Returns (out (H, Tq, D), lse (H, Tq))."""
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    valid = seg_q[:, None] == seg_k[None, :]
    if causal:
        rq, rk = rev_pos(seg_q), rev_pos(seg_k)
        valid = valid & (rk[None, :] >= rq[:, None])
    s = jnp.where(valid[None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    e = jnp.where(valid[None], e, jnp.zeros_like(e))
    l = jnp.sum(e, axis=-1, keepdims=True)
    l_safe = jnp.where(l == F0, F1, l)
    o = jnp.einsum("hqk,hkd->hqd", e / l_safe, v.astype(jnp.float32))
    lse = (m + jnp.log(l_safe))[..., 0]
    return o.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------
def _vfwd_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, pq_ref, pk_ref,
                 o_ref, lse_ref, acc_ref, m_ref, l_ref, *, scale, causal,
                 same_offsets, block_q, block_k, n_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def body():
        q = q_ref[0]                      # (block_q, d)
        k = k_ref[0]                      # (block_k, d)
        v = v_ref[0]
        d = q.shape[-1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # q seg (block_q, LANES) tiled out to block_k lanes; k seg compared
        # as a (1, block_k) row — only a sublane broadcast, which Mosaic
        # handles (mirrors jax's tpu flash kernel segment-mask layout)
        valid = _fit_lanes(sq_ref[:], s.shape[-1]) == sk_ref[:1, :]
        if causal:
            # bottom-right alignment: k attendable iff its distance from
            # segment end >= q's (equal-length segments reduce to the
            # standard row>=col mask)
            valid = valid & (pk_ref[:1, :] >= _fit_lanes(pq_ref[:],
                                                         s.shape[-1]))
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:]
        l_prev = l_ref[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - _fit_lanes(m_new, s.shape[-1]))
        # rows with no valid col stay 0
        p = jnp.where(valid, p, jnp.zeros_like(p))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * _fit_lanes(alpha, d) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    if causal and same_offsets:
        # diagonal skip is only sound when q and k tokens share offsets
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            body()
    else:
        body()

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == F0, F1, l)
        d = o_ref.shape[-1]
        o_ref[0] = (acc_ref[:] / _fit_lanes(l_safe, d)).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l_safe)


def _pad_to(x, n, axis, value=0):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg, constant_values=value)


def _vfwd_pallas(q, k, v, seg_q, seg_k, pos_q, pos_k, causal, same_offsets,
                 scale, block_q, block_k, interpret):
    """q: (H, Tq, D) padded to block multiples; seg/pos (Tq,)/(Tk,)."""
    scale = np.float32(scale)
    h, tq, d = q.shape
    tk = k.shape[1]
    n_q = tq // block_q
    n_k = tk // block_k
    sq2 = jnp.broadcast_to(seg_q[:, None], (tq, LANES))
    sk2 = jnp.broadcast_to(seg_k[None, :], (SUBLANES, tk))
    pq2 = jnp.broadcast_to(pos_q[:, None], (tq, LANES))
    pk2 = jnp.broadcast_to(pos_k[None, :], (SUBLANES, tk))

    spec = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    kernel = functools.partial(_vfwd_kernel, scale=scale, causal=causal,
                               same_offsets=same_offsets,
                               block_q=block_q, block_k=block_k, n_k=n_k)
    o, lse = pl.pallas_call(
        kernel,
        grid=(h, n_q, n_k),
        in_specs=[
            spec((1, block_q, d), lambda hi, qi, ki: (hi, qi, Z)),
            spec((1, block_k, d), lambda hi, qi, ki: (hi, ki, Z)),
            spec((1, block_k, d), lambda hi, qi, ki: (hi, ki, Z)),
            spec((block_q, LANES), lambda hi, qi, ki: (qi, Z)),
            spec((SUBLANES, block_k), lambda hi, qi, ki: (Z, ki)),
            spec((block_q, LANES), lambda hi, qi, ki: (qi, Z)),
            spec((SUBLANES, block_k), lambda hi, qi, ki: (Z, ki)),
        ],
        out_specs=[
            spec((1, block_q, d), lambda hi, qi, ki: (hi, qi, Z)),
            spec((1, block_q, LANES), lambda hi, qi, ki: (hi, qi, Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((h, tq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, sq2, sk2, pq2, pk2)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------
def _vbwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    sq_ref, sk_ref, pq_ref, pk_ref, dq_ref, dq_acc, *,
                    scale, causal, same_offsets, block_q, block_k, n_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = _fit_lanes(sq_ref[:], s.shape[-1]) == sk_ref[:1, :]
        if causal:
            valid = valid & (pk_ref[:1, :] >= _fit_lanes(pq_ref[:],
                                                         s.shape[-1]))
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - _fit_lanes(lse_ref[0], s.shape[-1]))
        p = jnp.where(valid, p, jnp.zeros_like(p))
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = jnp.where(valid,
                       p * (dp - _fit_lanes(delta_ref[0], dp.shape[-1]))
                       * scale, F0)
        dq_acc[:] += jax.lax.dot_general(ds, k.astype(jnp.float32),
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if causal and same_offsets:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            body()
    else:
        body()

    @pl.when(ki == n_k - 1)
    def _fin():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _vbwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     sq_ref, sk_ref, pq_ref, pk_ref, dk_ref, dv_ref,
                     dk_acc, dv_acc, *, scale, causal, same_offsets,
                     block_q, block_k, n_q):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = _fit_lanes(sq_ref[:], s.shape[-1]) == sk_ref[:1, :]
        if causal:
            valid = valid & (pk_ref[:1, :] >= _fit_lanes(pq_ref[:],
                                                         s.shape[-1]))
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - _fit_lanes(lse_ref[0], s.shape[-1]))
        p = jnp.where(valid, p, jnp.zeros_like(p))
        do = do_ref[0].astype(jnp.float32)
        dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = jnp.where(valid,
                       p * (dp - _fit_lanes(delta_ref[0], dp.shape[-1]))
                       * scale, F0)
        dk_acc[:] += jax.lax.dot_general(ds, q.astype(jnp.float32),
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if causal and same_offsets:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            body()
    else:
        body()

    @pl.when(qi == n_q - 1)
    def _fin():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _vbwd_pallas(q, k, v, o, lse, do, seg_q, seg_k, pos_q, pos_k, causal,
                 same_offsets, scale, block_q, block_k, interpret):
    scale = np.float32(scale)
    h, tq, d = q.shape
    tk = k.shape[1]
    n_q = tq // block_q
    n_k = tk // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lser = jnp.broadcast_to(lse[..., None], (h, tq, LANES))
    deltar = jnp.broadcast_to(delta[..., None], (h, tq, LANES))
    sq2 = jnp.broadcast_to(seg_q[:, None], (tq, LANES))
    sk2 = jnp.broadcast_to(seg_k[None, :], (SUBLANES, tk))
    pq2 = jnp.broadcast_to(pos_q[:, None], (tq, LANES))
    pk2 = jnp.broadcast_to(pos_k[None, :], (SUBLANES, tk))

    spec = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_vbwd_dq_kernel, scale=scale, causal=causal,
                          same_offsets=same_offsets,
                          block_q=block_q, block_k=block_k, n_k=n_k),
        grid=(h, n_q, n_k),
        in_specs=[
            spec((1, block_q, d), lambda hi, qi, ki: (hi, qi, Z)),
            spec((1, block_k, d), lambda hi, qi, ki: (hi, ki, Z)),
            spec((1, block_k, d), lambda hi, qi, ki: (hi, ki, Z)),
            spec((1, block_q, d), lambda hi, qi, ki: (hi, qi, Z)),
            spec((1, block_q, LANES), lambda hi, qi, ki: (hi, qi, Z)),
            spec((1, block_q, LANES), lambda hi, qi, ki: (hi, qi, Z)),
            spec((block_q, LANES), lambda hi, qi, ki: (qi, Z)),
            spec((SUBLANES, block_k), lambda hi, qi, ki: (Z, ki)),
            spec((block_q, LANES), lambda hi, qi, ki: (qi, Z)),
            spec((SUBLANES, block_k), lambda hi, qi, ki: (Z, ki)),
        ],
        out_specs=[spec((1, block_q, d), lambda hi, qi, ki: (hi, qi, Z))],
        out_shape=[jax.ShapeDtypeStruct((h, tq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lser, deltar, sq2, sk2, pq2, pk2)[0]

    dk, dv = pl.pallas_call(
        functools.partial(_vbwd_dkv_kernel, scale=scale, causal=causal,
                          same_offsets=same_offsets,
                          block_q=block_q, block_k=block_k, n_q=n_q),
        grid=(h, n_k, n_q),
        in_specs=[
            spec((1, block_q, d), lambda hi, ki, qi: (hi, qi, Z)),
            spec((1, block_k, d), lambda hi, ki, qi: (hi, ki, Z)),
            spec((1, block_k, d), lambda hi, ki, qi: (hi, ki, Z)),
            spec((1, block_q, d), lambda hi, ki, qi: (hi, qi, Z)),
            spec((1, block_q, LANES), lambda hi, ki, qi: (hi, qi, Z)),
            spec((1, block_q, LANES), lambda hi, ki, qi: (hi, qi, Z)),
            spec((block_q, LANES), lambda hi, ki, qi: (qi, Z)),
            spec((SUBLANES, block_k), lambda hi, ki, qi: (Z, ki)),
            spec((block_q, LANES), lambda hi, ki, qi: (qi, Z)),
            spec((SUBLANES, block_k), lambda hi, ki, qi: (Z, ki)),
        ],
        out_specs=[
            spec((1, block_k, d), lambda hi, ki, qi: (hi, ki, Z)),
            spec((1, block_k, d), lambda hi, ki, qi: (hi, ki, Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((h, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lser, deltar, sq2, sk2, pq2, pk2)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp op over padded packed layout
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _varlen_mha(q, k, v, seg_q, seg_k, pos_q, pos_k, causal, same_offsets,
                scale, block_q, block_k, interpret):
    o, _ = _vfwd_pallas(q, k, v, seg_q, seg_k, pos_q, pos_k, causal,
                        same_offsets, scale, block_q, block_k, interpret)
    return o


def _varlen_mha_fwd(q, k, v, seg_q, seg_k, pos_q, pos_k, causal,
                    same_offsets, scale, block_q, block_k, interpret):
    o, lse = _vfwd_pallas(q, k, v, seg_q, seg_k, pos_q, pos_k, causal,
                          same_offsets, scale, block_q, block_k, interpret)
    return o, (q, k, v, seg_q, seg_k, pos_q, pos_k, o, lse)


def _varlen_mha_bwd(causal, same_offsets, scale, block_q, block_k, interpret,
                    res, do):
    q, k, v, seg_q, seg_k, pos_q, pos_k, o, lse = res
    dq, dk, dv = _vbwd_pallas(q, k, v, o, lse, do, seg_q, seg_k, pos_q,
                              pos_k, causal, same_offsets, scale, block_q,
                              block_k, interpret)
    return dq, dk, dv, None, None, None, None


_varlen_mha.defvjp(_varlen_mha_fwd, _varlen_mha_bwd)


# ---------------------------------------------------------------------------
# Public surfaces
# ---------------------------------------------------------------------------
def flash_attention_varlen(q, k, v, seg_q, seg_k, causal=False, sm_scale=None,
                           block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                           use_pallas=None, interpret=None,
                           same_offsets=None):
    """Packed-layout attention with segment-id masking.

    q: (Tq, H, D); k/v: (Tk, H_kv, D); seg ids (Tq,)/(Tk,) int32 where
    tokens of the same sequence share an id (monotone non-decreasing for
    causal). Causal masking is bottom-right aligned per segment (flash-
    attention semantics when a segment has more k than q tokens).
    `same_offsets=True` (auto when seg_q is seg_k) additionally enables
    the above-diagonal block skip. Returns (Tq, H, D).
    """
    tq, hq, d = q.shape
    tk, hk, _ = k.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if same_offsets is None:
        same_offsets = seg_q is seg_k
    if hk != hq:  # GQA
        k = jnp.repeat(k, hq // hk, axis=1)
        v = jnp.repeat(v, hq // hk, axis=1)
    qh = jnp.swapaxes(q, 0, 1)  # (H, Tq, D)
    kh = jnp.swapaxes(k, 0, 1)
    vh = jnp.swapaxes(v, 0, 1)
    # distinct pad sentinels per side: ANY negative seg id is padding, and
    # q-pads (-1) must never match k-pads (-2) — otherwise pad rows attend
    # pad keys and contaminate outputs/grads at pad positions
    seg_q = jnp.where(seg_q < 0, -1, seg_q).astype(jnp.int32)
    seg_k = jnp.where(seg_k < 0, -2, seg_k).astype(jnp.int32)
    if use_pallas is None:
        use_pallas = _on_tpu()
    if interpret is None:
        interpret = not _on_tpu()
    if not use_pallas and not interpret:
        o, _ = varlen_reference(qh, kh, vh, seg_q, seg_k, causal, scale)
        return jnp.swapaxes(o, 0, 1)
    pos_q = rev_pos(seg_q)
    pos_k = rev_pos(seg_k)
    # blocks must honor the (8, 128) min tile; round small inputs up
    block_q = min(block_q, -(-max(tq, 1) // SUBLANES) * SUBLANES)
    block_k = min(block_k, -(-max(tk, 1) // LANES) * LANES)
    tq_p = -(-tq // block_q) * block_q
    tk_p = -(-tk // block_k) * block_k
    o = _varlen_mha(
        _pad_to(qh, tq_p, 1), _pad_to(kh, tk_p, 1), _pad_to(vh, tk_p, 1),
        _pad_to(seg_q, tq_p, 0, value=-1), _pad_to(seg_k, tk_p, 0, value=-2),
        _pad_to(pos_q, tq_p, 0), _pad_to(pos_k, tk_p, 0),
        causal, same_offsets, scale, block_q, block_k, interpret)
    return jnp.swapaxes(o[:, :tq], 0, 1)


def seg_ids_from_cu_seqlens(cu_seqlens, total):
    """cu_seqlens: (B+1,) int32 prefix sums → (total,) segment ids; tokens
    past cu_seqlens[-1] get -1 (never matched against k's -2 padding)."""
    pos = jnp.arange(total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu_seqlens.astype(jnp.int32)[1:], pos,
                           side="right").astype(jnp.int32)
    return jnp.where(pos < cu_seqlens[-1], seg, -1)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q=None, max_seqlen_k=None, scale=None,
                        dropout=0.0, causal=False, return_softmax=False,
                        training=True, name=None, use_pallas=None,
                        interpret=None):
    """Paddle-compatible varlen attention
    (python/paddle/nn/functional/flash_attention.py:756).

    query: (total_q, H, D) packed across the batch; cu_seqlens_q/k:
    (B+1,) token-offset prefix sums. Returns (out, softmax) with
    softmax None (kernel never materializes it).
    """
    tq = query.shape[0]
    tk = key.shape[0]
    same = cu_seqlens_q is cu_seqlens_k
    if not same:
        try:  # static equality also enables the diagonal skip
            same = bool(np.array_equal(np.asarray(cu_seqlens_q),
                                       np.asarray(cu_seqlens_k)))
        except Exception:
            same = False
    seg_q = seg_ids_from_cu_seqlens(jnp.asarray(cu_seqlens_q), tq)
    seg_k = seg_ids_from_cu_seqlens(jnp.asarray(cu_seqlens_k), tk)
    if dropout > 0.0 and training:
        # reference-kernel semantics drop attention *probabilities*, not
        # outputs; the pallas kernel has no in-kernel PRNG, so take the
        # XLA path that materializes P and drops its entries.
        # NB: this materializes the (H, Tq, Tk) probability matrix — fine
        # for training-time dropout at moderate lengths, O(T^2) memory at
        # long context (attention dropout is off in llama-class training)
        from .._core.state import prng
        d = query.shape[-1]
        sc = scale if scale is not None else 1.0 / math.sqrt(d)
        hq, hk = query.shape[1], key.shape[1]
        kk, vv = key, value
        if hk != hq:
            kk = jnp.repeat(key, hq // hk, axis=1)
            vv = jnp.repeat(value, hq // hk, axis=1)
        qh = jnp.swapaxes(query, 0, 1)
        kh = jnp.swapaxes(kk, 0, 1)
        s_ = jnp.einsum("hqd,hkd->hqk", qh.astype(jnp.float32),
                        kh.astype(jnp.float32)) * sc
        # same distinct pad sentinels as the kernel path: q-pads must not
        # match k-pads
        seg_q = jnp.where(seg_q < 0, -1, seg_q)
        seg_k = jnp.where(seg_k < 0, -2, seg_k)
        valid = seg_q[:, None] == seg_k[None, :]
        if causal:
            valid = valid & (rev_pos(seg_k)[None, :] >=
                             rev_pos(seg_q)[:, None])
        s_ = jnp.where(valid[None], s_, NEG_INF)
        pmat = jax.nn.softmax(s_, axis=-1)
        pmat = jnp.where(valid[None], pmat, jnp.zeros_like(pmat))
        keep = jax.random.bernoulli(prng.next_key(), 1.0 - dropout,
                                    pmat.shape)
        pmat = jnp.where(keep, pmat / (1.0 - dropout), F0)
        oh = jnp.einsum("hqk,khd->hqd", pmat, vv.astype(jnp.float32))
        return (jnp.swapaxes(oh, 0, 1).astype(query.dtype), None)
    out = flash_attention_varlen(query, key, value, seg_q, seg_k,
                                 causal=causal, sm_scale=scale,
                                 use_pallas=use_pallas, interpret=interpret,
                                 same_offsets=same)
    return (out, None)
