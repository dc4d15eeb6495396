"""Ragged selective-state recurrence: the Mamba-2 mixer's two stateful
ops over the serving step's flat rows.

A state-space layer keeps a SLOT a state that does not grow with the
context (`serving/model_spec.SlotState`): the recurrence's state, (N, C)
float32 with N the state size and C = heads x head_dim channels, and the
last K - 1 rows of the causal convolution's input. The step's flat rows
hold, a slot, ONE run of consecutive positions (one row for a decoding
slot, a chunk for a prompt), several slots' runs side by side
(`tok_slot` / `tok_pos`, -1 an inactive row). Both ops walk the RUNS
(`ssm_runs`): a run starts from its slot's stored state, or from zero
where its first row sits at position 0 (a slot taken again sends the
device nothing: the step reads it from `tok_pos`), and leaves the state
after its last row where the slot's state lay.

  conv   y_t = silu(b + sum_k w_k u_{t-K+1+k}), u the slot's own rows:
         those of the run, before them the K - 1 carried ones (kept in
         tiles of 128 lanes: whole vector registers, and a layout the
         compiler and the kernel agree on).
  scan   S_t = a_t S_{t-1} + B_t (dt_t x_t)^T,  y_t = C_t S_t, per
         channel c of head h, group g(h): S (N, C); a_t = exp(dt_t A_h)
         a channel; B_t, C_t (N,) a group. Row by row, no term cut: a
         block is the kernel's business and no part of the value.

The Pallas kernels run one program a run (scalar prefetch: the run's
slot picks the state block, aliased in and out, so a state is read once
and written once a layer and step where it lies; runs past the step's
last keep the last block resident and do nothing). The rows' operands
stay whole in fast memory. The state's layout puts the STATE dimension
on sublanes and the channels on lanes: decay, dt x and the output are a
row's lane vectors, the sum over N is an add of vector registers, and
what needs a column (B_t, C_t along sublanes) is taken from a
transposed copy (`(N, rows)` a group, made once a layer outside) by a
masked lane sum, one column serving a group's 512 channels.

The `jax.numpy` path is the CPU's and the tests' yardstick: a scan over
the rows, each row the recurrence as written above.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["conv_tile", "ragged_conv", "ragged_scan", "ssm_runs"]

LANES = 128
# typed constants for the kernels' bodies and index maps: the package
# runs with x64 on, and a bare Python number there is a 64-bit one that
# Mosaic cannot take
Z, ONE, F0 = np.int32(0), np.int32(1), np.float32(0.0)
# what a program may hold in fast memory: two state blocks in and out
# (8 MB at Nemotron-3's 2 MB a slot) beside the rows' operands, twice
# (the pipeline's buffers); a v5e core has 128 MiB
VMEM_LIMIT = 100 * 1024 * 1024


def ssm_runs(tok_slot, tok_pos, num_slots):
    """The step's row descriptors as the RUNS a slot's state advances
    over: maximal stretches of buffer rows of one slot with consecutive
    positions. The engine gives a slot one run a step, so there are at
    most `min(rows, num_slots)`; a buffer that broke that would have its
    surplus runs dropped.

    -> (runs (4, R) i32: slot / first row / rows / begins at position 0,
    valid in columns < n, the columns past them holding the last run's
    slot and no rows; n () i32; fresh () i32: the runs that begin from
    zero; rows () i32: the rows in any run)."""
    t = tok_pos.shape[0]
    cap = min(t, num_slots)
    slot = tok_slot.astype(jnp.int32)
    pos = tok_pos.astype(jnp.int32)
    on = pos >= 0
    cont = (on[1:] & on[:-1] & (slot[1:] == slot[:-1])
            & (pos[1:] == pos[:-1] + 1))
    start = on & ~jnp.concatenate([jnp.zeros((1,), bool), cont])
    rid = jnp.cumsum(start, dtype=jnp.int32) - 1         # a row's run
    n = jnp.minimum(jnp.sum(start, dtype=jnp.int32), cap)
    first = jnp.nonzero(start, size=cap, fill_value=0)[0].astype(jnp.int32)
    length = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(on, rid, cap)].add(1, mode="drop")
    valid = jnp.arange(cap, dtype=jnp.int32) < n
    last = jnp.maximum(n - 1, 0)
    fresh = valid & (pos[first] == 0)
    runs = jnp.stack([jnp.where(valid, slot[first], slot[first[last]]),
                      jnp.where(valid, first, 0),
                      jnp.where(valid, length, 0),
                      fresh.astype(jnp.int32)])
    return (runs, n, jnp.sum(fresh, dtype=jnp.int32),
            jnp.sum(on, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# The jax.numpy path: row by row
# ---------------------------------------------------------------------------
def _conv_rows(u, state, weight, bias, tok_slot, tok_pos):
    """u (T, C) f32, state (S, K-1, C) -> (y (T, C) f32, state)."""
    def row(st, xs):
        u_t, slot, pos = xs
        on = pos >= 0
        slot = jnp.where(on, slot, 0)
        old = st[slot]
        prev = jnp.where(pos == 0, 0.0, old.astype(jnp.float32))
        win = jnp.concatenate([prev, u_t[None]])            # (K, C)
        y = jax.nn.silu(jnp.sum(win * weight, 0) + bias)
        new = jnp.where(on, win[1:].astype(st.dtype), old)
        return st.at[slot].set(new), jnp.where(on, y, 0.0)
    return jax.lax.scan(row, state, (u, tok_slot, tok_pos))[::-1]


def _scan_rows(xdt, decay, b, c, state, tok_slot, tok_pos):
    """xdt, decay (T, C) f32; b, c (T, G, N) f32; state (S, N, C)
    -> (y (T, C) f32, state)."""
    groups = b.shape[1]

    def wide(v):    # (G, N) a group -> (N, C): a group's channels alike
        return jnp.repeat(v.T, xdt.shape[1] // groups, axis=1)

    def row(st, xs):
        u_t, a_t, b_t, c_t, slot, pos = xs
        on = pos >= 0
        slot = jnp.where(on, slot, 0)
        old = st[slot]
        s = jnp.where(pos == 0, 0.0, old.astype(jnp.float32))
        s = s * a_t[None, :] + wide(b_t) * u_t[None, :]
        y = jnp.sum(s * wide(c_t), 0)
        new = jnp.where(on, s.astype(st.dtype), old)
        return st.at[slot].set(new), jnp.where(on, y, 0.0)
    return jax.lax.scan(row, state,
                        (xdt, decay, b, c, tok_slot, tok_pos))[::-1]


# ---------------------------------------------------------------------------
# Pallas kernels: one program a run
# ---------------------------------------------------------------------------
def _as(v, dtype):
    """`astype`, but nothing at all where the type already is (Mosaic's
    rule for a conversion to the same type never ends)."""
    return v if v.dtype == dtype else v.astype(dtype)


def _tiles(v, width):
    """(rows, C) -> (rows, C / width, width): a row is then one index
    of the LEADING dimension, which a kernel may take at any start
    (Mosaic loads no single row of a 2-D array at a start it cannot
    prove aligned), and a whole number of vector registers."""
    return v.reshape(v.shape[0], -1, width)


def _state_spec(shape, layer):
    """One slot's block of a `(layers, slots, ...)` state: the run's."""
    zeros = (Z,) * (len(shape) - 2)
    return pl.BlockSpec((None, None) + tuple(shape[2:]),
                        lambda r, runs: (np.int32(layer), runs[0, r]) + zeros)


def _whole(shape):
    return pl.BlockSpec(tuple(shape), lambda r, runs: (Z,) * len(shape))


def _conv_kernel(runs_ref, u_ref, w_ref, b_ref, st_ref, y_ref, out_ref):
    """u, y (rows, C / W, W); w (K, C / W, W); b (C / W, W); the state's
    block (K - 1, C / W, W)."""
    r = pl.program_id(0)
    start, rows, fresh = runs_ref[1, r], runs_ref[2, r], runs_ref[3, r]
    taps = w_ref.shape[0]
    keep = taps - 1

    def history(j):
        """The slot's row at offset j from the run's first (j < 0: a
        carried one, zero where the run begins the context)."""
        cur = u_ref[jnp.maximum(start + j, Z)]
        old = _as(st_ref[jnp.clip(np.int32(keep) + j, Z, np.int32(keep - 1))],
                  jnp.float32)
        return jnp.where(j >= Z, cur, jnp.where(fresh > Z, F0, old))

    @pl.when((rows == Z) & (r == Z))
    def _():                    # a step with no run: the block as it was
        out_ref[...] = st_ref[...]

    @pl.when(rows > Z)
    def _():
        def row(i, carry):
            acc = b_ref[...]
            for k in range(taps):
                acc = acc + history(i + np.int32(k - keep)) * w_ref[k]
            y_ref[start + i] = acc * jax.nn.sigmoid(acc)
            return carry
        jax.lax.fori_loop(Z, rows, row, Z)
        # every carried row is read before the first is written: the
        # block is aliased in and out, but the two are buffers apart
        last = [history(rows + np.int32(k - keep)) for k in range(keep)]
        for k in range(keep):
            out_ref[k] = _as(last[k], out_ref.dtype)


def _scan_kernel(runs_ref, u_ref, a_ref, bt_ref, ct_ref, st_ref, y_ref,
                 out_ref, *, groups):
    """u, a, y (rows, C / W, W), W lanes a tile; bt, ct (rows / 128,
    G N, 128); the state's block (N, C)."""
    r = pl.program_id(0)
    start, rows, fresh = runs_ref[1, r], runs_ref[2, r], runs_ref[3, r]
    n, chans = st_ref.shape
    tile = u_ref.shape[-1]
    per_group = chans // groups // tile         # tiles a group
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, bt_ref.shape[-1]), 1)

    def row(i, src, first):
        """One row through the state: read from `src`, written to
        `out_ref`."""
        t = start + i
        # lax, not `%` and `//`: their weak-typed operands send Mosaic's
        # conversion rule round in circles
        at = lane == jax.lax.rem(t, np.int32(LANES))
        block_of = jax.lax.div(t, np.int32(LANES))
        a_t, u_t = a_ref[t], u_ref[t]           # (C / W, W)

        def column(ref, g):     # (N, 1): row t's vector of group g
            block = ref[block_of, g * n:(g + 1) * n, :]
            return jnp.sum(jnp.where(at, block, F0), 1, keepdims=True)

        y = []
        for g in range(groups):
            b_col, c_col = column(bt_ref, g), column(ct_ref, g)
            for j in range(g * per_group, (g + 1) * per_group):
                cols = slice(j * tile, (j + 1) * tile)
                s = _as(src[:, cols], jnp.float32)
                if first:
                    s = jnp.where(fresh > Z, F0, s)
                s = s * a_t[j:j + 1] + b_col * u_t[j:j + 1]
                out_ref[:, cols] = _as(s, out_ref.dtype)
                y.append(jnp.sum(s * c_col, 0, keepdims=True))
        y_ref[t] = jnp.concatenate(y)

    @pl.when((rows == Z) & (r == Z))
    def _():                    # a step with no run: the block as it was
        out_ref[...] = st_ref[...]

    @pl.when(rows > Z)
    def _():
        row(Z, st_ref, True)

        def later(i, carry):
            row(i, out_ref, False)
            return carry
        jax.lax.fori_loop(ONE, rows, later, Z)


def _call(kernel, name, runs, operands, state, layer, out_shape, interpret):
    """One program a run: `operands` whole in fast memory, the run's
    slot's block of `state` aliased in and out. -> (y `out_shape` f32,
    state)."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(runs.shape[1],),
        in_specs=[_whole(a.shape) for a in operands]
        + [_state_spec(state.shape, layer)],
        out_specs=[_whole(out_shape), _state_spec(state.shape, layer)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(out_shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the prefetched scalars: the state comes after
        # them and the rows' operands
        input_output_aliases={1 + len(operands): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=name)(runs, *operands, state)


def ragged_conv(u, state, layer, weight, bias, tok_slot, tok_pos, runs=None,
                use_pallas=False, interpret=False):
    """The causal depthwise convolution with its carried rows, then SiLU.

    u (T, C): the rows' inputs (taken in float32); state (layers, S,
    K - 1, C / W, W): every layer's carried rows a slot in tiles of W =
    `conv_tile(C)` lanes, of which `layer`'s (static) are read and
    written; weight (K, C) f32, tap k on the row K - 1 - k back; bias
    (C,). `runs`: `ssm_runs(...)[0]`, derived here when None.
    -> (y (T, C) f32, zero in inactive rows; state)."""
    t, chans = u.shape
    width = state.shape[-1]
    u = u.astype(jnp.float32)
    weight = weight.astype(jnp.float32)
    bias = bias.astype(jnp.float32)
    if not use_pallas and not interpret:
        flat = state[layer].reshape(state.shape[1], -1, chans)
        y, new = _conv_rows(u, flat, weight, bias, tok_slot, tok_pos)
        return y, state.at[layer].set(new.reshape(state.shape[1:]))
    if runs is None:
        runs = ssm_runs(tok_slot, tok_pos, state.shape[1])[0]
    u = _tiles(u, width)
    y, state = _call(_conv_kernel, "ragged_ssm_conv", runs,
                     (u, _tiles(weight, width), _tiles(bias[None], width)[0]),
                     state, layer, u.shape, interpret)
    return jnp.where((tok_pos >= 0)[:, None], y.reshape(t, chans), 0.0), state


def conv_tile(chans):
    """The lanes a tile of the carried rows holds: the TPU's 128 where
    they divide the channels (they do at any published width)."""
    return LANES if chans % LANES == 0 else chans


def ragged_scan(x, dt, a, b, c, state, layer, tok_slot, tok_pos, runs=None,
                use_pallas=False, interpret=False):
    """The selective-state recurrence over the step's rows.

    x (T, heads, P); dt (T, heads) f32, after its softplus; a (heads,)
    f32, negative; b, c (T, G, N), a group serving heads / G heads;
    state (layers, S, N, heads x P): every layer's state a slot, of
    which `layer`'s (static) is read and written, accumulated in float32
    whatever it is kept in. -> (y (T, heads, P) f32 WITHOUT the skip
    term D x, zero in inactive rows; state)."""
    t, heads, p = x.shape
    groups = b.shape[1]
    f32 = jnp.float32
    dt = dt.astype(f32)
    xdt = (x.astype(f32) * dt[..., None]).reshape(t, heads * p)
    decay = jnp.repeat(jnp.exp(dt * a.astype(f32)), p, axis=1)
    b, c = b.astype(f32), c.astype(f32)
    if not use_pallas and not interpret:
        y, new = _scan_rows(xdt, decay, b, c, state[layer], tok_slot, tok_pos)
        return y.reshape(t, heads, p), state.at[layer].set(new)
    if runs is None:
        runs = ssm_runs(tok_slot, tok_pos, state.shape[1])[0]
    wide = heads * p // groups                  # a group's channels
    width = min(wide, LANES)
    if wide % width:
        raise ValueError(f"ragged_scan: a group's {wide} channels are not "
                         f"whole tiles of {width} lanes")

    def columns(v):     # (T, G, N) -> (T / 128, G N, 128): rows on lanes
        v = jnp.pad(v.reshape(t, -1), ((0, -t % LANES), (0, 0)))
        return v.reshape(-1, LANES, v.shape[1]).swapaxes(1, 2)

    xdt = _tiles(xdt, width)
    y, state = _call(
        functools.partial(_scan_kernel, groups=groups), "ragged_ssm_scan",
        runs, (xdt, _tiles(decay, width), columns(b), columns(c)), state,
        layer, xdt.shape, interpret)
    return jnp.where((tok_pos >= 0)[:, None], y.reshape(t, -1), 0.0).reshape(
        t, heads, p), state
