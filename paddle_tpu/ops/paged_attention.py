"""Paged attention for TPU decode (serving path).

Reference parity: the reference serves LLMs through paged/block KV caches
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention — block_tables,
per-seq lengths). TPU-native redesign:

  * KV lives in a page pool `(kv_heads, num_pages, page_size, head_dim)`.
  * Each sequence owns a row of `page_table` (page indices) + a length.
  * The decode kernel runs grid `(batch, kv_heads, pages_per_seq)`; the
    page table and lengths ride scalar-prefetch (SMEM) so the BlockSpec
    index_map DMAs exactly the page each step needs — no gather of the
    whole cache. Online softmax (m/l lane-replicated scratch) accumulates
    across the page grid dimension; fully-masked pages are skipped with
    @pl.when (ragged batches don't pay for their padding).
  * GQA: q is viewed (batch, kv_heads, group, head_dim); group is padded
    to the sublane minimum (8) in the wrapper.

Off-TPU the XLA reference path (gather pages → dense softmax) is used;
the kernel also runs under pallas interpret mode for tests.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (F0, F1, LANES, NEG_INF, Z, _fit_lanes,
                              _on_tpu)

MIN_GROUP = 8  # TPU sublane minimum for the q-rows dim


# ---------------------------------------------------------------------------
# int8 cache quantization (reference parity: the cachekv-quant decode in
# paddle/phi/kernels/fusion/gpu/block_attn.h — int8 KV pages with scales,
# dequantized inside the attention kernel). Per-token-per-head absmax:
# one fp32 scale per stored (head, token) vector.
# ---------------------------------------------------------------------------
def quantize_kv(x, axis=-1):
    """x: (..., D) → (int8 values, fp32 scale with D→1 kept).

    scale = absmax/127 (floored to avoid div-by-zero on all-zero
    vectors, e.g. untouched pool pages)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=axis, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale):
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# Reference (XLA) implementation
# ---------------------------------------------------------------------------
def _gather_pages(k_pages, v_pages, page_table, k_scale, v_scale):
    """(B, KVH, pages_per_seq*page, D) contiguous dequantized views of
    each sequence's pages — shared by both XLA reference paths."""
    b = page_table.shape[0]
    kvh, _, _, d = k_pages.shape
    k = jnp.swapaxes(k_pages[:, page_table], 0, 1).reshape(b, kvh, -1, d)
    v = jnp.swapaxes(v_pages[:, page_table], 0, 1).reshape(b, kvh, -1, d)
    if k_scale is not None:  # dequantize the gathered slices only
        ks = jnp.swapaxes(k_scale[:, page_table], 0, 1).reshape(b, kvh, -1, 1)
        vs = jnp.swapaxes(v_scale[:, page_table], 0, 1).reshape(b, kvh, -1, 1)
        k = dequantize_kv(k, ks)
        v = dequantize_kv(v, vs)
    return k, v


def paged_attention_reference(q, k_pages, v_pages, page_table, lengths,
                              sm_scale=None, k_scale=None, v_scale=None):
    """q: (B, QH, D); pages: (KVH, P, page, D); page_table: (B, pages_per_seq);
    lengths: (B,). k_scale/v_scale: (KVH, P, page, 1) fp32 when the
    pages are int8-quantized. Returns (B, QH, D)."""
    b, qh, d = q.shape
    kvh = k_pages.shape[0]
    group = qh // kvh
    scale = sm_scale if sm_scale is not None else d ** -0.5
    k, v = _gather_pages(k_pages, v_pages, page_table, k_scale, v_scale)
    qg = q.reshape(b, kvh, group, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k.astype(jnp.float32)) * scale
    mask = jnp.arange(s.shape[-1])[None, None, None] < lengths[:, None, None,
                                                               None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(b, qh, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------
def _decode_kernel(ptab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, page_size, n_pages,
                   ks_ref=None, vs_ref=None):
    """ks_ref/vs_ref: per-token fp32 scale blocks (1, 1, page, 1) when
    the K/V pages are int8 — dequantized HERE, so the int8 pool is what
    rides HBM→VMEM (the whole point of cache quantization)."""
    del ptab_ref  # consumed by the index maps
    bi = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    seq_len = len_ref[bi]

    @pl.when(pi * page_size < seq_len)  # skip fully-masked pages
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)   # (group, d)
        k = k_ref[0, 0].astype(jnp.float32)   # (page, d)
        v = v_ref[0, 0].astype(jnp.float32)
        if ks_ref is not None:
            k = k * ks_ref[0, 0]              # (page, 1) broadcast over d
            v = v * vs_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols < seq_len, s, NEG_INF)
        m_prev = m_ref[:]
        l_prev = l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _fit_lanes(m_new, s.shape[-1]))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * _fit_lanes(alpha, acc_ref.shape[-1]) + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(pi == n_pages - 1)
    def _fin():
        l = l_ref[:]
        l_safe = jnp.where(l == F0, F1, l)
        o_ref[0, 0] = (acc_ref[:] /
                       _fit_lanes(l_safe, o_ref.shape[-1])).astype(o_ref.dtype)


def _quant_kernel(ptab_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, acc_ref, m_ref, l_ref, **kw):
    """Positional adapter: pallas passes the two scale inputs between
    v and the output ref."""
    _decode_kernel(ptab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, ks_ref=ks_ref, vs_ref=vs_ref,
                   **kw)


def _decode_pallas(q4, k_pages, v_pages, page_table, lengths, scale,
                   interpret, k_scale=None, v_scale=None):
    b, kvh, group, d = q4.shape
    _, _, page_size, _ = k_pages.shape
    n_pages = page_table.shape[1]
    quant = k_scale is not None

    # index maps receive grid indices first, then scalar-prefetch refs
    page_spec = pl.BlockSpec((1, 1, page_size, d),
                             lambda bi, hi, pi, ptab, lens:
                             (hi, ptab[bi, pi], Z, Z))
    in_specs = [
        pl.BlockSpec((1, 1, group, d),
                     lambda bi, hi, pi, ptab, lens: (bi, hi, Z, Z)),
        page_spec,
        page_spec,
    ]
    operands = [page_table, lengths, q4, k_pages, v_pages]
    if quant:
        scale_spec = pl.BlockSpec((1, 1, page_size, 1),
                                  lambda bi, hi, pi, ptab, lens:
                                  (hi, ptab[bi, pi], Z, Z))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda bi, hi, pi, ptab, lens: (bi, hi, Z, Z)),
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(_quant_kernel if quant else _decode_kernel,
                               scale=np.float32(scale),
                               page_size=page_size, n_pages=n_pages)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, group, d), q4.dtype),
        interpret=interpret,
    )(*operands)


def paged_attention(q, k_pages, v_pages, page_table, lengths, sm_scale=None,
                    use_pallas=None, interpret=None, k_scale=None,
                    v_scale=None):
    """Single-token decode attention over a paged KV cache.

    q: (B, QH, D); k_pages/v_pages: (KVH, num_pages, page_size, D);
    page_table: (B, pages_per_seq) int32; lengths: (B,) int32.

    int8 cache: pass int8 pages plus k_scale/v_scale fp32 per-token
    scales (KVH, num_pages, page_size, 1) — see quantize_kv. The pages
    are dequantized inside the kernel (reference parity: cachekv-quant
    in phi/kernels/fusion/gpu/block_attn.h), halving/quartering the
    HBM traffic and pool footprint vs bf16/fp32.
    """
    b, qh, d = q.shape
    kvh = k_pages.shape[0]
    group = qh // kvh
    scale = sm_scale if sm_scale is not None else d ** -0.5
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if use_pallas is None:
        use_pallas = _on_tpu()
    if interpret is None:
        interpret = False
    if not use_pallas and not interpret:
        return paged_attention_reference(q, k_pages, v_pages, page_table,
                                         lengths, scale, k_scale, v_scale)
    q4 = q.reshape(b, kvh, group, d)
    # q-rows block dim must be a multiple of the sublane tile (8)
    pad = (-group) % MIN_GROUP
    if pad:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, pad), (0, 0)))
    o = _decode_pallas(q4, k_pages, v_pages,
                       page_table.astype(jnp.int32),
                       lengths.astype(jnp.int32), scale, interpret,
                       k_scale=k_scale, v_scale=v_scale)
    if pad:
        o = o[:, :, :group]
    return o.reshape(b, qh, d)


# ---------------------------------------------------------------------------
# Multi-query (verify-chunk) paged attention — speculative decoding /
# chunked prefill: G chunk tokens per sequence attend against the paged
# cache in one kernel, token g seeing keys 0 .. base+g (its own position
# included; the chunk's K/V were scattered into the pages beforehand).
# Same page-streaming structure as the decode kernel, with a per-ROW
# column limit instead of a single per-sequence one.
# ---------------------------------------------------------------------------
def paged_verify_reference(q, k_pages, v_pages, page_table, base_lengths,
                           sm_scale=None, k_scale=None, v_scale=None):
    """q: (B, QH, G, D); pages as in paged_attention; base_lengths: (B,)
    cache length BEFORE the chunk. Returns (B, QH, G, D)."""
    b, qh, g, d = q.shape
    kvh = k_pages.shape[0]
    group = qh // kvh
    scale = sm_scale if sm_scale is not None else d ** -0.5
    k, v = _gather_pages(k_pages, v_pages, page_table, k_scale, v_scale)
    qg = q.reshape(b, kvh, group, g, d).astype(jnp.float32)
    s = jnp.einsum("bhxgd,bhkd->bhxgk", qg, k.astype(jnp.float32)) * scale
    cols = jnp.arange(s.shape[-1])[None, None, None, None]
    limit = (base_lengths[:, None, None, None, None]
             + jnp.arange(g)[None, None, None, :, None] + 1)
    s = jnp.where(cols < limit, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhxgk,bhkd->bhxgd", p, v.astype(jnp.float32))
    return o.reshape(b, qh, g, d).astype(q.dtype)


def _verify_kernel(ptab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, page_size, n_pages,
                   n_tok, ks_ref=None, vs_ref=None):
    """q rows are (group_pad * n_tok): r = gg * n_tok + g — token
    g = r % n_tok sees columns < base + g + 1."""
    del ptab_ref
    bi = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    base = len_ref[bi]

    @pl.when(pi * page_size < base + n_tok)  # skip fully-masked pages
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)   # (group_pad*n_tok, d)
        k = k_ref[0, 0].astype(jnp.float32)   # (page, d)
        v = v_ref[0, 0].astype(jnp.float32)
        if ks_ref is not None:
            k = k * ks_ref[0, 0]
            v = v * vs_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        # np.int32 divisor, NOT the bare python int: `% n_tok` binds the
        # int as a strong i64 const under x64, and Mosaic's int64->int32
        # convert recurses forever (chip-observed RecursionError).
        g_row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
            % np.int32(n_tok)
        s = jnp.where(cols < base + g_row + 1, s, NEG_INF)
        m_prev = m_ref[:]
        l_prev = l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _fit_lanes(m_new, s.shape[-1]))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * _fit_lanes(alpha, acc_ref.shape[-1]) + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(pi == n_pages - 1)
    def _fin():
        l = l_ref[:]
        l_safe = jnp.where(l == F0, F1, l)
        o_ref[0, 0] = (acc_ref[:] /
                       _fit_lanes(l_safe, o_ref.shape[-1])).astype(o_ref.dtype)


def _verify_quant_kernel(ptab_ref, len_ref, q_ref, k_ref, v_ref, ks_ref,
                         vs_ref, o_ref, acc_ref, m_ref, l_ref, **kw):
    _verify_kernel(ptab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, ks_ref=ks_ref, vs_ref=vs_ref,
                   **kw)


def paged_verify_attention(q, k_pages, v_pages, page_table, base_lengths,
                           sm_scale=None, use_pallas=None, interpret=None,
                           k_scale=None, v_scale=None):
    """Verify-chunk attention over a paged KV cache.

    q: (B, QH, G, D); pages/page_table as paged_attention;
    base_lengths: (B,) cache length BEFORE the chunk (token g of the
    chunk sits at absolute position base+g and may attend through
    itself). int8 pages take k_scale/v_scale exactly like the decode
    kernel. Returns (B, QH, G, D).

    This is the pallas replacement for the gather-based dense verify
    block: pages stream HBM→VMEM via scalar-prefetch index maps (no
    materialized contiguous copy), masked pages are skipped, and every
    q row of the (group × G) block shares the one page read.
    """
    b, qh, g, d = q.shape
    kvh = k_pages.shape[0]
    group = qh // kvh
    scale = sm_scale if sm_scale is not None else d ** -0.5
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if use_pallas is None:
        use_pallas = _on_tpu()
    if interpret is None:
        interpret = False
    if not use_pallas and not interpret:
        return paged_verify_reference(q, k_pages, v_pages, page_table,
                                      base_lengths, scale, k_scale, v_scale)
    # rows: r = gg * G + g (head-major) so r % G recovers the token.
    # Pad whole head-groups until (group_pad * G) hits the sublane tile
    # (8): the smallest e with (group+e)*G % 8 == 0 is e = (-group) mod
    # (8 / gcd(G, 8)) — padding a partial group would break the r % G
    # token mapping, and an unaligned row block is a Mosaic rejection.
    import math as _math
    r_mod = MIN_GROUP // _math.gcd(g, MIN_GROUP)
    extra_groups = (-group) % r_mod
    group_pad = group + extra_groups
    q5 = q.reshape(b, kvh, group, g, d)
    if extra_groups:
        q5 = jnp.pad(q5, ((0, 0), (0, 0), (0, extra_groups), (0, 0), (0, 0)))
    q4 = q5.reshape(b, kvh, group_pad * g, d)

    page_size = k_pages.shape[2]
    n_pages = page_table.shape[1]
    quant = k_scale is not None
    page_spec = pl.BlockSpec((1, 1, page_size, d),
                             lambda bi, hi, pi, ptab, lens:
                             (hi, ptab[bi, pi], Z, Z))
    in_specs = [
        pl.BlockSpec((1, 1, group_pad * g, d),
                     lambda bi, hi, pi, ptab, lens: (bi, hi, Z, Z)),
        page_spec,
        page_spec,
    ]
    operands = [page_table.astype(jnp.int32),
                base_lengths.astype(jnp.int32), q4, k_pages, v_pages]
    if quant:
        scale_spec = pl.BlockSpec((1, 1, page_size, 1),
                                  lambda bi, hi, pi, ptab, lens:
                                  (hi, ptab[bi, pi], Z, Z))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, group_pad * g, d),
                               lambda bi, hi, pi, ptab, lens: (bi, hi, Z, Z)),
        scratch_shapes=[
            pltpu.VMEM((group_pad * g, d), jnp.float32),
            pltpu.VMEM((group_pad * g, LANES), jnp.float32),
            pltpu.VMEM((group_pad * g, LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _verify_quant_kernel if quant else _verify_kernel,
        scale=np.float32(scale), page_size=page_size, n_pages=n_pages,
        n_tok=g)
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, group_pad * g, d), q.dtype),
        interpret=interpret,
    )(*operands)
    o = o.reshape(b, kvh, group_pad, g, d)[:, :, :group]
    return o.reshape(b, qh, g, d)


# ---------------------------------------------------------------------------
# Page pool / cache manager (host-side bookkeeping, device-side pool)
# ---------------------------------------------------------------------------
class PagedKVCache:
    """Per-layer paged KV pool with host-side free-list allocation.

    The pool tensors are device arrays updated functionally (scatter into
    pages); the page table / lengths / free list are host state — the
    serving loop mutates them between jitted decode steps, mirroring how
    the reference's BlockManager hands block_tables to the kernel.
    """

    def __init__(self, num_layers, kv_heads, head_dim, num_pages, page_size,
                 max_seqs, pages_per_seq, dtype=jnp.bfloat16):
        shape = (num_layers, kv_heads, num_pages, page_size, head_dim)
        # dtype "int8": quantized pool + per-token fp32 scales — 2x
        # (vs bf16) / 4x (vs fp32) the servable tokens per pool byte
        self.quantized = dtype in ("int8", jnp.int8)
        if self.quantized:
            self.k = jnp.zeros(shape, jnp.int8)
            self.v = jnp.zeros(shape, jnp.int8)
            sshape = shape[:-1] + (1,)
            self.k_scale = jnp.zeros(sshape, jnp.float32)
            self.v_scale = jnp.zeros(sshape, jnp.float32)
        else:
            self.k = jnp.zeros(shape, dtype)
            self.v = jnp.zeros(shape, dtype)
            self.k_scale = self.v_scale = None
        self.page_size = page_size
        self.page_table = jnp.zeros((max_seqs, pages_per_seq), jnp.int32)
        self.lengths = jnp.zeros((max_seqs,), jnp.int32)
        self._free = list(range(num_pages - 1, -1, -1))
        self._seq_pages = {}  # seq slot -> [page ids]

    def alloc_seq(self, slot, prompt_len):
        n = -(-max(prompt_len, 1) // self.page_size)
        if len(self._free) < n:
            raise RuntimeError("PagedKVCache: out of pages")
        pages = [self._free.pop() for _ in range(n)]
        self._seq_pages[slot] = pages
        tbl = self.page_table.at[slot, :n].set(jnp.asarray(pages, jnp.int32))
        self.page_table = tbl
        self.lengths = self.lengths.at[slot].set(prompt_len)
        return pages

    def extend_seq(self, slot):
        """Called before writing one more token; grabs a page on boundary."""
        cur = int(self.lengths[slot])
        if cur % self.page_size == 0 and cur > 0:
            if not self._free:
                raise RuntimeError("PagedKVCache: out of pages")
            pg = self._free.pop()
            idx = len(self._seq_pages[slot])
            self._seq_pages[slot].append(pg)
            self.page_table = self.page_table.at[slot, idx].set(pg)
        self.lengths = self.lengths.at[slot].add(1)

    def free_seq(self, slot):
        self._free.extend(reversed(self._seq_pages.pop(slot, [])))
        self.lengths = self.lengths.at[slot].set(0)

    def write_token(self, layer, slot, k_tok, v_tok):
        """k_tok/v_tok: (KVH, D) for the token at position lengths[slot]-1."""
        pos = int(self.lengths[slot]) - 1
        pg = self._seq_pages[slot][pos // self.page_size]
        off = pos % self.page_size
        if self.quantized:
            kq, ks = quantize_kv(k_tok)
            vq, vs = quantize_kv(v_tok)
            self.k = self.k.at[layer, :, pg, off].set(kq)
            self.v = self.v.at[layer, :, pg, off].set(vq)
            self.k_scale = self.k_scale.at[layer, :, pg, off].set(ks)
            self.v_scale = self.v_scale.at[layer, :, pg, off].set(vs)
            return
        self.k = self.k.at[layer, :, pg, off].set(k_tok.astype(self.k.dtype))
        self.v = self.v.at[layer, :, pg, off].set(v_tok.astype(self.v.dtype))
