"""Flash attention for TPU — pallas kernels (fwd + bwd).

Replaces the reference's CUDA flash-attn integration
(paddle/phi/kernels/gpu/flash_attn_kernel.cu,
python/paddle/nn/functional/flash_attention.py) with a TPU-native
blockwise online-softmax kernel:

  * forward: grid (batch*heads, q_blocks, k_blocks); fp32 running
    (m, l, acc) scratch in VMEM persists across the sequential k grid
    dimension; saves per-row logsumexp L for the backward.
  * backward: one pass for dQ (grid over q), one for dK/dV (grid over
    k), both recomputing P = exp(QKᵀ·scale − L) block-wise — O(S) memory.
  * causal masking skips fully-masked k blocks via @pl.when predication.

Falls back to a pure-XLA reference implementation off-TPU (and for
features the kernel doesn't cover: arbitrary masks, dropout).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (8,128)-aligned tile sizes; overridable for on-chip tuning sweeps.
# Canonical defaults live in _tuning_defaults (shared with autotune +
# perf guard so dedup/grouping stay in sync with the kernel).
from paddle_tpu._tuning_defaults import flash_block_q, flash_block_k
DEFAULT_BLOCK_Q = flash_block_q()
DEFAULT_BLOCK_K = flash_block_k()
# np.float32: a bare Python float lowers as an f64 constant inside Mosaic,
# and v5e libtpu rejects 'tpu.truncf f64->f32' — keep all kernel consts f32.
NEG_INF = np.float32(-1e30)
F0 = np.float32(0.0)
F1 = np.float32(1.0)
# index-map constants likewise must be i32: under jax_enable_x64 a literal 0
# traces as i64 and Mosaic fails to legalize the index-map func.return.
Z = np.int32(0)
LANES = 128  # TPU lane width: per-row stats are stored replicated over lanes
             # so every ref block keeps last-two dims (÷8, ÷128)-aligned


def _fit_lanes(x128, n):
    """(rows, 128) lane-replicated stat → (rows, n) for math against an
    (rows, n) tile. Values are equal across lanes, so slice or tile."""
    if n == LANES:
        return x128
    if n < LANES:
        return x128[:, :n]
    assert n % LANES == 0, f"block dim {n} must be a multiple of {LANES}"
    return jnp.tile(x128, (1, n // LANES))


def _on_tpu():
    # a backend that fails to initialise raises here: answering False
    # would silently select the jnp reference on a machine with a chip
    return jax.default_backend() == "tpu"


def pallas_disabled() -> bool:
    """Escape hatch: PT_DISABLE_PALLAS=1 forces the XLA reference path
    (e.g. when a new TPU generation rejects the kernel's block shapes)."""
    return os.environ.get("PT_DISABLE_PALLAS", "0") == "1"


# ---------------------------------------------------------------------------
# Reference (pure XLA) implementation — correctness baseline + fallback.
# ---------------------------------------------------------------------------
def mha_reference(q, k, v, bias=None, causal=False, sm_scale=None):
    """q,k,v: (B, H, S, D). Returns (out, logsumexp)."""
    *_, sq, d = q.shape
    sk = k.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _col_mask(start, block, total, d):
    """(block, d) bool mask: rows of this block that are inside `total`."""
    idx = start + jax.lax.broadcasted_iota(jnp.int32, (block, d), 0)
    return idx < total


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale, causal, block_q, block_k, n_k, sq, sk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def body():
        q = q_ref[0]  # (block_q, d)
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]
        d = q.shape[-1]
        if sk % block_k != 0:
            km = _col_mask(ki * block_k, block_k, sk, d)
            k = jnp.where(km, k, jnp.zeros_like(k))
            v = jnp.where(km, v, jnp.zeros_like(v))
        if sq % block_q != 0:
            q = jnp.where(_col_mask(qi * block_q, block_q, sq, d), q,
                          jnp.zeros_like(q))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = cols < sk
        if causal:
            valid = valid & (rows >= cols)
        if causal or sk % block_k != 0:
            s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:]                       # (block_q, LANES) replicated
        l_prev = l_ref[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)          # (block_q, 1)
        m_new = jnp.maximum(m_prev, m_cur)                 # (block_q, LANES)
        p = jnp.exp(s - _fit_lanes(m_new, s.shape[-1]))
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * _fit_lanes(alpha, d) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        l_ref[:] = l_new

    if causal:
        # skip blocks fully above the diagonal
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


def _fwd_pallas(q, k, v, causal, scale, block_q, block_k, interpret):
    scale = np.float32(scale)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    n_q = pl.cdiv(sq, block_q)
    n_k = pl.cdiv(sk, block_k)
    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, d)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=n_k,
                               sq=sq, sk=sk)
    spec = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            spec((1, block_q, d), lambda bh_, qi, ki: (bh_, qi, Z)),
            spec((1, block_k, d), lambda bh_, qi, ki: (bh_, ki, Z)),
            spec((1, block_k, d), lambda bh_, qi, ki: (bh_, ki, Z)),
        ],
        out_specs=[
            spec((1, block_q, d), lambda bh_, qi, ki: (bh_, qi, Z)),
            spec((1, block_q, LANES), lambda bh_, qi, ki: (bh_, qi, Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            # per-row logsumexp replicated over the lane dim (TPU block rule:
            # last two dims of a block must be ÷8 / ÷128 or whole-array)
            jax.ShapeDtypeStruct((bh, sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return o.reshape(b, h, sq, d), lse[..., 0].reshape(b, h, sq)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k, n_k, sq, sk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        d = q.shape[-1]
        if sk % block_k != 0:
            km = _col_mask(ki * block_k, block_k, sk, d)
            k = jnp.where(km, k, jnp.zeros_like(k))
            v = jnp.where(km, v, jnp.zeros_like(v))
        if sq % block_q != 0:
            q = jnp.where(_col_mask(qi * block_q, block_q, sq, d), q,
                          jnp.zeros_like(q))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = cols < sk
        if causal:
            valid = valid & (rows >= cols)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - _fit_lanes(lse_ref[0], s.shape[-1]))
        p = jnp.where(valid, p, jnp.zeros_like(p))
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # mask after the product: OOB rows of the ragged final q block read
        # undefined lse/delta, and 0 * inf would poison the accumulator
        ds = jnp.where(valid,
                       p * (dp - _fit_lanes(delta_ref[0], dp.shape[-1])) * scale,
                       F0)
        dq_acc[:] += jax.lax.dot_general(ds, k.astype(jnp.float32),
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            body()
    else:
        body()

    @pl.when(ki == n_k - 1)
    def _fin():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, n_q, sq, sk):
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
        d = q.shape[-1]
        if sk % block_k != 0:
            km = _col_mask(ki * block_k, block_k, sk, d)
            k = jnp.where(km, k, jnp.zeros_like(k))
            v = jnp.where(km, v, jnp.zeros_like(v))
        qm = None
        if sq % block_q != 0:
            qm = _col_mask(qi * block_q, block_q, sq, d)
            q = jnp.where(qm, q, jnp.zeros_like(q))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = (cols < sk) & (rows < sq)
        if causal:
            valid = valid & (rows >= cols)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - _fit_lanes(lse_ref[0], s.shape[-1]))  # (bq, bk)
        p = jnp.where(valid, p, jnp.zeros_like(p))
        do = do_ref[0].astype(jnp.float32)
        if qm is not None:
            do = jnp.where(qm, do, jnp.zeros_like(do))
        dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = jnp.where(valid,
                       p * (dp - _fit_lanes(delta_ref[0], dp.shape[-1])) * scale,
                       F0)
        dk_acc[:] += jax.lax.dot_general(ds, q.astype(jnp.float32),
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            body()
    else:
        body()

    @pl.when(qi == n_q - 1)
    def _fin():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, o, lse, do, causal, scale, block_q, block_k, interpret):
    scale = np.float32(scale)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    n_q = pl.cdiv(sq, block_q)
    n_k = pl.cdiv(sk, block_k)
    bh = b * h
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    qr, kr, vr = (t.reshape(bh, -1, d) for t in (q, k, v))
    dor = do.reshape(bh, sq, d)
    # lane-replicate per-row stats so their blocks obey the TPU (÷8, ÷128) rule
    lser = jnp.broadcast_to(lse.reshape(bh, sq)[..., None], (bh, sq, LANES))
    deltar = jnp.broadcast_to(delta.reshape(bh, sq)[..., None], (bh, sq, LANES))

    spec = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_k=n_k,
                          sq=sq, sk=sk),
        grid=(bh, n_q, n_k),
        in_specs=[
            spec((1, block_q, d), lambda b_, qi, ki: (b_, qi, Z)),
            spec((1, block_k, d), lambda b_, qi, ki: (b_, ki, Z)),
            spec((1, block_k, d), lambda b_, qi, ki: (b_, ki, Z)),
            spec((1, block_q, d), lambda b_, qi, ki: (b_, qi, Z)),
            spec((1, block_q, LANES), lambda b_, qi, ki: (b_, qi, Z)),
            spec((1, block_q, LANES), lambda b_, qi, ki: (b_, qi, Z)),
        ],
        out_specs=[spec((1, block_q, d), lambda b_, qi, ki: (b_, qi, Z))],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, deltar)[0]

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q=n_q,
                          sq=sq, sk=sk),
        grid=(bh, n_k, n_q),
        in_specs=[
            spec((1, block_q, d), lambda b_, ki, qi: (b_, qi, Z)),
            spec((1, block_k, d), lambda b_, ki, qi: (b_, ki, Z)),
            spec((1, block_k, d), lambda b_, ki, qi: (b_, ki, Z)),
            spec((1, block_q, d), lambda b_, ki, qi: (b_, qi, Z)),
            spec((1, block_q, LANES), lambda b_, ki, qi: (b_, qi, Z)),
            spec((1, block_q, LANES), lambda b_, ki, qi: (b_, qi, Z)),
        ],
        out_specs=[
            spec((1, block_k, d), lambda b_, ki, qi: (b_, ki, Z)),
            spec((1, block_k, d), lambda b_, ki, qi: (b_, ki, Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, deltar)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# ---------------------------------------------------------------------------
# Public op with custom VJP
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_mha(q, k, v, causal, scale, block_q, block_k, interpret):
    o, _ = _fwd_pallas(q, k, v, causal, scale, block_q, block_k, interpret)
    return o


def _flash_mha_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _fwd_pallas(q, k, v, causal, scale, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_mha_bwd(causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd_pallas(q, k, v, o, lse, do, causal, scale, block_q,
                             block_k, interpret)
    return dq, dk, dv


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


def flash_attention_bhsd(q, k, v, causal=False, sm_scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         use_pallas=None, interpret=None):
    """Core entry: q,k,v (B,H,S,D) → (B,H,S,D).

    use_pallas defaults to True on TPU; off-TPU uses the XLA reference
    (pallas interpret mode is available for kernel tests via interpret=True).
    """
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if use_pallas is None:
        use_pallas = _on_tpu() and not pallas_disabled()
    if interpret is None:
        interpret = not _on_tpu()
    if not use_pallas:
        o, _ = mha_reference(q, k, v, None, causal, scale)
        return o
    return _flash_mha(q, k, v, causal, scale, block_q, block_k, interpret)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, sm_scale=None, training=True,
                    use_pallas=None, **kwargs):
    """Paddle-compatible surface: q,k,v (B, S, H, D) like
    python/paddle/nn/functional/flash_attention.py. Returns (out, None).
    """
    q = jnp.swapaxes(query, 1, 2)
    k = jnp.swapaxes(key, 1, 2)
    v = jnp.swapaxes(value, 1, 2)
    # GQA: repeat kv heads if fewer than q heads
    hq, hk = q.shape[1], k.shape[1]
    if hk != hq:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if dropout > 0.0 and training:
        # reference kernel drops attention *probabilities* (each output is
        # a partial sum over surviving keys), not whole outputs; no
        # in-kernel PRNG, so materialize P on the XLA path
        from .._core.state import prng
        *_, sq, d = q.shape
        sk = k.shape[-2]
        scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if causal:
            cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
            s = jnp.where(cm, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        keep = jax.random.bernoulli(prng.next_key(), 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), F0)
        o = jnp.einsum("bhqk,bhkd->bhqd", p,
                       v.astype(jnp.float32)).astype(q.dtype)
    else:
        o = flash_attention_bhsd(q, k, v, causal=causal, sm_scale=sm_scale,
                                 use_pallas=use_pallas)
    out = jnp.swapaxes(o, 1, 2)
    return (out, None) if not return_softmax else (out, None, None)
