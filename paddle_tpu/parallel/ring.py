"""Ring attention — context/sequence parallelism for long sequences.

Replaces the reference's segment-parallel path (python/paddle/distributed/
fleet/meta_parallel/segment_parallel.py) with the TPU-native ring:
sequence sharded over the 'sp' mesh axis, K/V blocks rotate around the
ICI ring via lax.ppermute, online-softmax merging keeps O(S_local) memory.
Differentiable end-to-end (AD through ppermute), so the backward is a
reverse ring — no hand-written comm schedule.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask=None):
    """Block scores + unnormalized accumulation pieces.
    q: (B,H,Sq,D), k/v: (B,H,Sk,D) → (m, l, acc) partials."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m, l, acc


def ring_attention_local(q, k, v, axis_name, causal=False, sm_scale=None,
                         q_chunk=None):
    """Runs INSIDE shard_map: q,k,v (B,H,S_local,D) sequence-sharded over
    `axis_name`. Returns (B,H,S_local,D).

    q_chunk bounds the materialized score tile to (chunk, S_local)
    instead of (S_local, S_local) — the long-context memory knob (defaults
    to 512 when S_local exceeds it). The chunk body is jax.checkpoint'd so
    the bound holds under AD too: backward recomputes each chunk's scores
    instead of stacking per-chunk softmax residuals."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    d = q.shape[-1]
    s_local = q.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if q_chunk is None:
        q_chunk = 512
    q_chunk = min(q_chunk, s_local)

    m0 = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # chunk q ONCE, outside the ring loop (it never changes per step)
    chunked = q_chunk < s_local
    if chunked:
        n_ch = -(-s_local // q_chunk)
        qp = q
        if n_ch * q_chunk != s_local:
            qp = jnp.pad(q, ((0, 0),) * (q.ndim - 2) +
                         ((0, n_ch * q_chunk - s_local), (0, 0)))
        qs = jnp.moveaxis(qp.reshape(*q.shape[:-2], n_ch, q_chunk, d),
                          -3, 0)                     # (n_ch, B, H, C, D)
        row0s = jnp.arange(n_ch) * q_chunk

    def one_chunk(qc, row0, k_rot, v_rot, src):
        if causal:
            rows = row0 + jnp.arange(qc.shape[-2])[:, None]
            cols = jnp.arange(s_local)[None, :]
            diag_mask = rows >= cols
            mask = jnp.where(src == idx, diag_mask, src < idx)
            mask = jnp.broadcast_to(
                mask, qc.shape[:-2] + (qc.shape[-2], s_local))
            return _block_attn(qc, k_rot, v_rot, scale, mask)
        return _block_attn(qc, k_rot, v_rot, scale)

    # checkpoint: backward recomputes the chunk's scores — without this
    # the scan would stack per-chunk softmax residuals and the memory
    # bound would not survive differentiation
    one_chunk_ckpt = jax.checkpoint(one_chunk)

    def block(k_rot, v_rot, t):
        """(m, l, acc) partials of this K/V block, q chunked."""
        src = (idx - t) % n  # which shard's K/V we currently hold
        if not chunked:
            return one_chunk(q, 0, k_rot, v_rot, src)

        def scan_chunk(_, xs):
            qc, r0 = xs
            return None, one_chunk_ckpt(qc, r0, k_rot, v_rot, src)
        _, (ms, ls, accs) = lax.scan(scan_chunk, None, (qs, row0s))
        m = jnp.moveaxis(ms, 0, -2).reshape(*q.shape[:-2], -1)
        l = jnp.moveaxis(ls, 0, -2).reshape(*q.shape[:-2], -1)
        acc = jnp.moveaxis(accs, 0, -3).reshape(*q.shape[:-2], -1, d)
        return m[..., :s_local], l[..., :s_local], acc[..., :s_local, :]

    def step(carry, t):
        k_rot, v_rot, m_acc, l_acc, acc = carry
        m_b, l_b, acc_b = block(k_rot, v_rot, t)
        m_new = jnp.maximum(m_acc, m_b)
        a1 = jnp.exp(m_acc - m_new)
        a2 = jnp.exp(m_b - m_new)
        l_new = l_acc * a1 + l_b * a2
        acc_new = acc * a1[..., None] + acc_b * a2[..., None]
        k_next = lax.ppermute(k_rot, axis_name, perm)
        v_next = lax.ppermute(v_rot, axis_name, perm)
        return (k_next, v_next, m_new, l_new, acc_new), None

    (kf, vf, m_f, l_f, acc_f), _ = lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(n))
    l_safe = jnp.where(l_f == 0.0, 1.0, l_f)
    return (acc_f / l_safe[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh, sp_axis="sp", causal=False, sm_scale=None,
                   q_chunk=None):
    """q,k,v: (B, H, S, D) with S sharded over sp_axis; returns same."""
    fn = functools.partial(ring_attention_local, axis_name=sp_axis,
                           causal=causal, sm_scale=sm_scale,
                           q_chunk=q_chunk)
    spec = P(None, None, sp_axis, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=frozenset({sp_axis}),
                         check_vma=False)(q, k, v)


def sequence_shard(x, mesh, sp_axis="sp", seq_dim=1):
    """Annotate activations sequence-sharded (Megatron-SP style)."""
    spec = [None] * x.ndim
    spec[seq_dim] = sp_axis
    return lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, P(*spec)))
