"""A grouped matrix product that reads a group's whole matrix once.

`parallel/moe.grouped_product` is `lax.ragged_dot`: rows sorted by group
times their group's matrix. The TPU compiler's kernel for it walks
(group, row tile) visits over weight BLOCKS whose sides are the largest
power of two up to 512 that divides the matrix's (`ragged_dot_tiling`):
512 x 512 at Laguna's and GLM-5's widths, but 128 x 128 where a side is a
multiple of 128 and of nothing larger (Nemotron-3-Nano: 2,688 x 1,856 is
305 blocks of 32 KB an expert, 39,000 grid steps a product, and the steps
and not the bytes set the time: PERF.md, Findings PR 48).

`grouped_matmul` is the same product with the MATRIX as the block: one
program a (row tile, group) visit, the visits in the rows' order, the
group's matrix (K, N) whole in fast memory. A group whose rows span two
tiles is two visits on the same block, so its matrix is fetched once; a
group with no row is no visit and no fetch. A tile's visits each write the
rows their group owns (the first of them clears the rest), in one block
that stays in fast memory until the tile changes. Products in the
operands' type into float32, as `lax.ragged_dot` with
`preferred_element_type=float32`.

A matrix whose last dimension is not whole lane tiles (1,856) is kept by
the device with its OTHER dimension last, and a kernel that asks for it
row-major makes the compiler copy it every step (1.28 GB a layer:
PERF.md, Findings PR 48): such a matrix is handed over `transposed`, its
rows its outputs, which is also how a checkpoint keeps it.

Rows past the groups' sum belong to nobody: whatever lies there comes
back as it was (callers mask, as they do `grouped_product`'s).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "fits", "visits"]

Z = np.int32(0)
# a matrix is double-buffered whole: 2 x 16 MiB beside the row tiles
MATRIX_BYTES = 16 * 1024 * 1024
VMEM_LIMIT = 64 * 1024 * 1024


def fits(rhs):
    """Whether one group's matrix of `rhs` (G, K, N) can be a block."""
    return rhs.shape[1] * rhs.shape[2] * rhs.dtype.itemsize <= MATRIX_BYTES


def visits(group_sizes, num_tiles, tile):
    """The (row tile, group) visits of rows sorted by group, in the rows'
    order -> (5, V) i32 with V = num_tiles + groups: tile / group / the
    first and one past the last of the tile's rows the group owns / whether
    the visit is its tile's first; columns past the visits repeat the last
    visit's tile and group and own no row."""
    sizes = group_sizes.astype(jnp.int32)
    end = jnp.cumsum(sizes)
    start = end - sizes
    first_tile = start // tile
    spans = jnp.where(sizes > 0, (end - 1) // tile - first_tile + 1, 0)
    upto = jnp.cumsum(spans)                    # visits through group g
    cap = num_tiles + sizes.shape[0]
    v = jnp.arange(cap, dtype=jnp.int32)
    n = upto[-1]
    at = jnp.minimum(v, jnp.maximum(n - 1, 0))  # past the last: the last
    g = jnp.minimum(jnp.searchsorted(upto, at, side="right"),
                    sizes.shape[0] - 1).astype(jnp.int32)
    t = first_tile[g] + at - (upto[g] - spans[g])
    lo = jnp.maximum(start[g], t * tile) - t * tile
    hi = jnp.minimum(end[g], (t + 1) * tile) - t * tile
    live = v < n
    first = jnp.concatenate([jnp.ones((1,), bool), t[1:] != t[:-1]])
    return jnp.stack([t, g, jnp.where(live, lo, 0), jnp.where(live, hi, 0),
                      first.astype(jnp.int32)]).astype(jnp.int32)


def _kernel(visit_ref, lhs_ref, rhs_ref, out_ref, *, transposed):
    v = pl.program_id(0)
    lo, hi, first = visit_ref[2, v], visit_ref[3, v], visit_ref[4, v]

    @pl.when(hi > lo)
    def _():
        y = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...],
            (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        mine = (row >= lo) & (row < hi)
        rest = jnp.where(first > Z, jnp.zeros_like(y), out_ref[...])
        out_ref[...] = jnp.where(mine, y, rest)


def grouped_matmul(lhs, rhs, group_sizes, tile, transposed=False,
                   interpret=False):
    """lhs (M, K) rows sorted by group, M a whole number of `tile` rows;
    rhs (G, K, N), or (G, N, K) where `transposed` (a matrix's rows its
    OUTPUTS, as a checkpoint keeps a linear layer: the product then
    contracts both operands' last dimension, which the MXU takes as it
    takes q k^T); group_sizes (G,) i32 -> (M, N) float32, row i times the
    matrix of the group that owns it."""
    m, k = lhs.shape
    groups, n = rhs.shape[0], rhs.shape[1 if transposed else 2]
    if m % tile:
        raise ValueError(f"grouped_matmul: {m} rows are not whole tiles of "
                         f"{tile}")
    plan = visits(group_sizes, m // tile, tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(plan.shape[1],),
        in_specs=[pl.BlockSpec((tile, k), lambda v, p: (p[0, v], Z)),
                  pl.BlockSpec((None,) + rhs.shape[1:],
                               lambda v, p: (p[1, v], Z, Z))],
        out_specs=pl.BlockSpec((tile, n), lambda v, p: (p[0, v], Z)))
    return pl.pallas_call(
        functools.partial(_kernel, transposed=transposed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="ragged_dot_experts")(plan, lhs, rhs)
