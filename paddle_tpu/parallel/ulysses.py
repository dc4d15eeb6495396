"""Ulysses-style all-to-all sequence parallelism.

Reference parity: the fork's context-parallel attention utilities
(python/paddle/distributed/fleet/layers/mpu + ring attention in
PaddleNLP) ship ring P2P context parallelism; DeepSpeed-Ulysses-style
all-to-all is its standard alternative. TPU-native design: the two
lax.all_to_all re-shards ride ICI as XLA collectives — no NCCL, no
hand-written P2P.

Scheme (inside shard_map over the `sp` mesh axis, n devices):

    (B, H, S/n, D)  --all_to_all-->  (B, H/n, S, D)
    full flash attention per device (exact causal — every device holds
    the ENTIRE sequence for its head slice, so no cross-device masking
    logic at all, and the pallas kernel's causal block-skip applies)
    (B, H/n, S, D)  --all_to_all-->  (B, H, S/n, D)

vs ring attention (parallel/ring.py): ring keeps K/V moving n-1 hops
and masks per-block; Ulysses moves q/k/v/o once each and runs the
plain kernel at full context. Ulysses wins while heads >= n (wire
bytes comparable, far better kernel efficiency); ring is the fallback
when sequence must scale past the head count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.flash_attention import flash_attention_bhsd


def ulysses_attention_local(q, k, v, axis_name, causal=False, sm_scale=None):
    """Runs INSIDE shard_map: q (B, H, S_local, D) sequence-sharded over
    `axis_name`, H divisible by the axis size. k/v may carry FEWER
    (GQA) heads: when kv_heads is also divisible by the axis size they
    ride the all-to-all at kv width and are repeated to full head count
    only AFTER the re-shard — nh/nkv times fewer K/V wire bytes than
    repeating up front. Returns (B, H, S_local, D), same sharding."""
    n = lax.axis_size(axis_name)
    H, Hkv = q.shape[1], k.shape[1]
    if H % n:
        raise ValueError(
            f"ulysses attention needs heads ({H}) divisible by the sp "
            f"axis size ({n}); use ring attention to scale sequence "
            "past the head count")
    if v.shape[1] != Hkv or H % Hkv:
        raise ValueError(
            f"k/v head counts ({Hkv}, {v.shape[1]}) must match and "
            f"divide q heads ({H})")
    if Hkv != H and Hkv % n:
        # kv heads cannot shard over the axis — repeat up front and pay
        # the wire cost rather than refuse
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
    a2a = functools.partial(lax.all_to_all, axis_name=axis_name, tiled=True)
    # heads scatter, sequence gathers: received seq chunks concatenate
    # in device order = global token order
    qh = a2a(q, split_axis=1, concat_axis=2)
    kh = a2a(k, split_axis=1, concat_axis=2)
    vh = a2a(v, split_axis=1, concat_axis=2)
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    o = flash_attention_bhsd(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return a2a(o, split_axis=2, concat_axis=1)


def ulysses_attention(q, k, v, mesh, sp_axis="sp", causal=False,
                      sm_scale=None):
    """q, k, v: (B, H, S, D) with S sharded over sp_axis; returns same."""
    fn = functools.partial(ulysses_attention_local, axis_name=sp_axis,
                           causal=causal, sm_scale=sm_scale)
    spec = P(None, None, sp_axis, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=frozenset({sp_axis}),
                         check_vma=False)(q, k, v)
