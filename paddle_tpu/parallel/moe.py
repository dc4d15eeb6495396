"""Expert parallelism / MoE (reference: python/paddle/incubate/nn/layer/
fused_moe + fleet EP groups over NCCL alltoall).

TPU-native GShard-style dense dispatch: top-k gating → capacity-bounded
one-hot dispatch tensors → two einsums. With the expert axis sharded
over 'ep' on the mesh, GSPMD lowers the dispatch einsums to all_to_all
over ICI — the NCCL alltoall of the reference, derived not hand-written.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .._core.tensor import Tensor, apply
from ..kernels import grouped_matmul
from ..nn.layer.layers import Layer
from ..nn.initializer import XavierUniform


def expert_slot_positions(topk_idx, tot_expert):
    """(T, k) expert ids (negatives = dropped) → (T, k) arrival rank of
    each assignment within its expert's queue, slot-major (slot 0 of
    every token first). THE shared rank computation for every
    capacity-bounded dispatch in the tree (this module's fused gating,
    incubate MoELayer's dispatch, the gshard gate's capacity limiter) —
    the `-1` must apply after reducing the hot column, a pitfall that
    has produced slot-collision bugs when re-derived by hand."""
    T, k = topk_idx.shape
    flat = jnp.where(topk_idx >= 0, topk_idx, tot_expert
                     ).transpose(1, 0).reshape(-1)
    onehot = jax.nn.one_hot(flat, tot_expert + 1, dtype=jnp.int32)
    rank = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
    return rank.reshape(k, T).transpose(1, 0)


def top_k_gating(logits, k, capacity, expert_axis_size=1):
    """logits (T, E) → dispatch (T, E, C) bool, combine (T, E, C) float,
    aux_loss (load-balance, Switch-style)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (T, k)
    # renormalize chosen gates
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position of each token within its expert queue (per chosen slot)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (T, k, E)
    # flatten slots in priority order: slot 0 of all tokens first
    flat = onehot.transpose(1, 0, 2).reshape(k * T, E)
    pos_in_expert = jnp.cumsum(flat, axis=0) * flat - 1  # (k*T, E)
    pos = pos_in_expert.reshape(k, T, E).transpose(1, 0, 2)  # (T, k, E)
    pos_tok = jnp.sum(pos * onehot, axis=-1)  # (T, k)
    keep = (pos_tok < capacity) & (pos_tok >= 0)

    # (T, k, E, C): expert one-hot × capacity-slot one-hot per chosen slot
    disp = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)[..., None] * \
        jax.nn.one_hot(jnp.clip(pos_tok, 0, capacity - 1), capacity,
                       dtype=jnp.float32)[..., None, :]
    disp = disp * keep[..., None, None].astype(jnp.float32)
    dispatch = jnp.sum(disp, axis=1)  # (T, E, C) 0/1
    combine = jnp.sum(disp * gate_vals[..., None, None], axis=1)  # (T, E, C)

    # load-balance aux loss (Switch): E * sum(me * ce)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return dispatch, combine, aux


def moe_ffn_apply(x_tokens, gate_w, expert_ws, k=2, capacity_factor=1.25,
                  ep_axis="ep", mesh=None, activation=jax.nn.silu):
    """Pure MoE forward over raw arrays.

    x_tokens: (T, M); gate_w: (M, E);
    expert_ws: dict(w_gate (E,M,F), w_up (E,M,F) [optional], w_down (E,F,M))
    Returns (T, M), aux_loss.
    """
    T, M = x_tokens.shape
    E = gate_w.shape[1]
    capacity = max(1, int(capacity_factor * T * k / E))
    logits = x_tokens.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    dispatch, combine, aux = top_k_gating(logits, k, capacity)
    # dispatch: (T,E,C) → expert inputs (E, C, M); GSPMD all_to_all if E sharded
    expert_in = jnp.einsum("tec,tm->ecm", dispatch.astype(x_tokens.dtype),
                           x_tokens)
    if mesh is not None and ep_axis in mesh.shape:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, jax.sharding.NamedSharding(mesh, P(ep_axis, None, None)))

    wg = expert_ws["w_gate"]
    wd = expert_ws["w_down"]
    wu = expert_ws.get("w_up")
    h = jnp.einsum("ecm,emf->ecf", expert_in, wg)
    if wu is not None:
        u = jnp.einsum("ecm,emf->ecf", expert_in, wu)
        h = activation(h) * u
    else:
        h = activation(h)
    expert_out = jnp.einsum("ecf,efm->ecm", h, wd)
    if mesh is not None and ep_axis in mesh.shape:
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, jax.sharding.NamedSharding(mesh, P(ep_axis, None, None)))
    out = jnp.einsum("tec,ecm->tm", combine.astype(x_tokens.dtype), expert_out)
    return out, aux


class MoELayer(Layer):
    """Mixture-of-experts FFN (SwiGLU experts + optional shared experts —
    DeepSeekMoE/Qwen2-MoE shape; reference: incubate FusedMoE)."""

    def __init__(self, d_model, d_ff, num_experts, top_k=2, capacity_factor=1.25,
                 num_shared_experts=0, ep_axis="ep", gate_attr=None, name=None):
        super().__init__()
        self.d_model = d_model
        self.d_ff = d_ff
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.ep_axis = ep_axis
        init = XavierUniform()
        self.gate_weight = self.create_parameter([d_model, num_experts],
                                                 attr=gate_attr,
                                                 default_initializer=init)
        self.w_gate = self.create_parameter([num_experts, d_model, d_ff],
                                            default_initializer=init)
        self.w_up = self.create_parameter([num_experts, d_model, d_ff],
                                          default_initializer=init)
        self.w_down = self.create_parameter([num_experts, d_ff, d_model],
                                            default_initializer=init)
        for p in (self.w_gate, self.w_up, self.w_down):
            p.dist_spec = P(ep_axis)
            p.is_distributed = True
        if num_shared_experts > 0:
            self.shared_gate = self.create_parameter(
                [d_model, d_ff * num_shared_experts], default_initializer=init)
            self.shared_up = self.create_parameter(
                [d_model, d_ff * num_shared_experts], default_initializer=init)
            self.shared_down = self.create_parameter(
                [d_ff * num_shared_experts, d_model], default_initializer=init)
        else:
            self.shared_gate = None
        self.aux_loss = None

    def forward(self, x):
        from .mesh import get_mesh
        mesh = get_mesh()
        shape = x.shape

        def fn(xr, gw, wg, wu, wd, *shared):
            tokens = xr.reshape(-1, shape[-1])
            out, aux = moe_ffn_apply(
                tokens, gw, {"w_gate": wg, "w_up": wu, "w_down": wd},
                k=self.top_k, capacity_factor=self.capacity_factor,
                ep_axis=self.ep_axis, mesh=mesh)
            if shared:
                sg, su, sd = shared
                s = (jax.nn.silu(tokens @ sg) * (tokens @ su)) @ sd
                out = out + s
            return out.reshape(xr.shape), aux

        args = [x, self.gate_weight, self.w_gate, self.w_up, self.w_down]
        if self.shared_gate is not None:
            args += [self.shared_gate, self.shared_up, self.shared_down]
        out, aux = apply(fn, *args, name="moe", multi=True)
        self.aux_loss = aux
        return out


# ---------------------------------------------------------------------------
# Dropless expert layer over flat rows (the serving step's; the
# capacity-bounded dispatch above stays for the eager models)
# ---------------------------------------------------------------------------
ROW_TILE_MIN, ROW_TILE_MAX = 32, 512


def row_tile(rows, groups):
    """The row tile a grouped product of `rows` sorted rows over `groups`
    groups should run at: the power of two that holds the rows a group
    gets when the buffer is full (`rows / groups`), from 32 up to the
    compiler's own 512. Every (group, row tile) visit of the compiler's
    kernel reads the group's matrix AND multiplies a whole tile of rows
    by it, the masked ones too: at decode's three to five rows an expert
    a tile of 512 spends 1.7 ms on products of rows nobody owns where
    the weights' bytes take 0.64, and a tile smaller than a group's run
    of rows reads its matrix once a tile (PERF.md, Findings PR 44:
    `tools/grouped_product_bench.py`)."""
    tile = ROW_TILE_MIN
    while tile * groups < rows and tile < ROW_TILE_MAX:
        tile *= 2
    return tile


def tiled_rows(rows, groups):
    """The shortest row buffer of at least `rows` rows that the TPU
    compiler gives `row_tile(rows, groups)`: it takes the largest power
    of two (at most 512) that DIVIDES the buffer's length for its row
    tile (`ragged_dot_tiling="tm,tk,tn"` on the compiled custom call;
    `tests/test_tpu_lowering.py` holds the steps to it), so the length
    is the next odd multiple of the tile: 1,056 for 1,024 rows over 256
    groups (tile 32), 1,088 over 16 (tile 64), 416 for 384 over 16."""
    tile = row_tile(rows, groups)
    return (-(-rows // tile) | 1) * tile


def row_tile_visits(rows, assignments, num_experts=None):
    """The (expert, row tile) visits of one grouped product of
    `dropless_experts`, summed over the layers of a step's `rows` record
    ((..., E) counts, numpy) out of `assignments` = T k a layer: the
    tiles each expert's run of sorted rows spans, from the cumulative
    sums of `rows`. Runs are not tile-aligned, so an expert of 4 rows
    can span two tiles; each visit past an expert's first reads its
    matrices again. The buffer, and so the tile, are the ones
    `dropless_experts` runs a layer at that step."""
    rows = np.asarray(rows, np.int64)
    held = rows.shape[-1]
    end = np.cumsum(rows, -1)
    start = end - rows

    def visits(n):
        tile = row_tile(n, held)
        return np.where(rows > 0, (end - 1) // tile - start // tile + 1,
                        0).sum(-1)

    few = _product_rows(assignments, held, num_experts)
    got = visits(few)
    if few < assignments:
        got = np.where(end[..., -1] <= few, got, visits(assignments))
    return int(got.sum())


def share_spills(rows, assignments, num_experts=None):
    """The layers of a step's `rows` record ((..., E) counts, numpy)
    whose held assignments exceed the sorted rows a share's products run
    over (`_product_rows` of `assignments` = T k), so that
    `dropless_experts` took its branch over all T k there: slow, not
    wrong. A whole layer's products run over all T k, and it has none."""
    rows = np.asarray(rows, np.int64)
    few = _product_rows(assignments, rows.shape[-1], num_experts)
    return int((rows.sum(-1) > few).sum())


def _product_rows(assignments, held, num_experts):
    """The sorted rows `dropless_experts`' products run over: all T k for
    a whole layer; for a share four times its mean, in whole 128s."""
    if num_experts in (None, held):
        return assignments
    return min(assignments,
               -(-4 * assignments * held // num_experts // 128) * 128)


def grouped_product(lhs, rhs, group_sizes):
    """Rows of `lhs` (M, K), sorted by group, times their group's matrix
    of `rhs` (G, K, N) -> (M, N) float32. `group_sizes` (G,) i32 says how
    many consecutive rows each group owns; rows past their sum belong to
    nobody (callers mask them). `jax.lax.ragged_dot`: the TPU compiler
    lowers it to a grouped matmul (device operations `ragged-dot-*`)
    that reads only the matrices of groups that own a row, a (group, row
    tile) visit at a time. M DECIDES THE ROW TILE: the compiler takes
    the largest power of two up to 512 that divides it, and multiplies a
    whole tile of rows a visit. A caller that knows its rows are few a
    group hands a buffer `tiled_rows(M, G)` long (`dropless_experts`
    pads the gather that builds it, not a copy) and cuts the result."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)


def _sorted_by_expert(expert, held, first, num_experts):
    """expert (T, k) over ALL the layer's experts -> (routed (T, k) bool:
    the assignment goes to one of the `held` experts from `first`; order
    (T k,): sorted row -> assignment, the held experts' runs first and in
    order; rows (held,) i32: the rows each held expert got)."""
    if num_experts in (None, held):
        routed = expert >= 0
    else:
        expert = expert - first
        routed = (expert >= 0) & (expert < held)
    key = jnp.where(routed, expert, held).reshape(-1)   # `held` sorts last
    order = jnp.argsort(key, stable=True)
    rows = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    return routed, order, rows


def _small_blocks(w):
    """Whether the TPU compiler's grouped matmul would walk `w` (G, K, N)
    in blocks under 512 a side: it takes for each side the largest power
    of two up to 512 that divides it (`ragged_dot_tiling="tm,tk,tn"`), and
    a side that is a multiple of 128 and of nothing larger gives blocks of
    32 KB whose grid steps, not whose bytes, set the time
    (`kernels/grouped_matmul.py`)."""
    return any(side % 512 for side in w.shape[1:])


def dropless_experts(x, expert, weight, w_gate, w_up, w_down, first=0,
                     num_experts=None, up_transposed=False, use_pallas=False,
                     interpret=False):
    """Every assignment to an expert held here computed, none dropped,
    over the step's flat rows. The experts are gated,
    `down(silu(gate x) * up x)` (three grouped products), or, where
    `w_gate` is None, plain with a squared ReLU, `down(relu(up x)^2)`
    (two; `models/nemotron_h.py`).

    x (T, H); expert (T, k) i32, the expert each of a row's k assignments
    goes to, numbered over ALL the layer's experts (negative: routes
    nowhere, a slack row); weight (T, k) f32, what its result is weighted
    by. w_gate / w_up (E, H, F) and w_down (E, F, H): the experts HELD,
    the layer's experts `first .. first + E` of the `num_experts` the
    router chose among (None: E, the whole layer, `models/laguna.py`). A
    chip that holds a share (`models/glm_dsa.py`: 16 of 256) routes over
    all of them all the same and computes its own: an assignment to an
    absent expert sorts past the end, as a slack row's does, and adds
    nothing here; the chips that hold the others add theirs, and the
    shares' sums over every chip are the whole layer's
    (tests/test_glm_dsa_serving.py).

    Rows are sorted by expert, the grouped products run over the
    sorted rows that can hold an assignment, and each row gets the
    weighted sum of its assignments back. A whole layer's are all T k;
    a share's are four times its mean (T k E / num_experts) when they
    hold every held assignment, as they all but always do, and all T k
    in a step where they do not (`lax.cond`: a router that sends every
    row to the held experts is slow, not wrong). The products are
    LAUNCHED over `tiled_rows` of those: the buffer's length is what
    gives the compiler's kernel its row tile (`grouped_product`), and
    the rows added are nobody's, like every row past the experts' sum.
    `up_transposed`: w_gate / w_up are (E, F, H), a matrix's rows its
    outputs, as a checkpoint keeps a linear layer (a width F that is not
    whole lane tiles then lies where the device keeps it anyway).
    Where the compiler's kernel would walk a matrix in small blocks
    (`_small_blocks`: a width that 512 does not divide) and the matrix
    fits fast memory, the product is `kernels/grouped_matmul`, which
    fetches a touched expert's matrix whole and once, if the caller hands
    on its engine's `use_pallas` / `interpret` pair (as it does to every
    kernel of its step); the compiler's kernel everywhere else, so a model
    whose widths 512 divides compiles to what it did.

    A whole layer spreads its T k product rows back over the assignments
    and sums a row's k. A share never makes that (T k, H) array: a
    conditional's result is a buffer in memory, so it would be written
    whole, copied out of the branch and read again by the sum, six
    passes over 75 MB a layer of which the few rows hold anything
    (PERF.md, Findings PR 47). Each branch weights its product rows
    where they lie (sorted row i is assignment `order[i]`) and adds them
    to their rows of the (T, H) sum it returns.

    This is the serving steps' form; a training step, whose rows are
    thousands an expert and which is differentiated, takes
    `dropless_experts_blocked`.
    -> (out (T, H) f32, rows (E,) i32: the rows each held expert got).
    """
    T, k = expert.shape
    E = w_up.shape[0]
    few = _product_rows(T * k, E, num_experts)
    routed, order, rows = _sorted_by_expert(expert, E, first, num_experts)

    def product(n, lhs, w, transposed=False):
        if (use_pallas or interpret) and _small_blocks(w) \
                and grouped_matmul.fits(w):
            return grouped_matmul.grouped_matmul(
                lhs, w, rows, row_tile(n, E), transposed, interpret)
        return grouped_product(
            lhs, jnp.swapaxes(w, 1, 2) if transposed else w, rows)

    def products(n):
        """(n, H) f32: the first n sorted rows through their experts."""
        # the gather builds the buffer at the length the products are
        # launched over; the rows it adds (row 0's) are nobody's
        xs = x[jnp.pad(order[:n], (0, tiled_rows(n, E) - n)) // k]
        up = functools.partial(product, n, xs, transposed=up_transposed)
        if w_gate is None:
            h = jnp.square(jax.nn.relu(up(w_up)))
        else:
            h = jax.nn.silu(up(w_gate)) * up(w_up)
        y = product(n, h.astype(x.dtype), w_down)[:n]
        # rows past the experts' are nobody's: whatever is there, drop it
        return jnp.where((jnp.arange(n) < jnp.sum(rows))[:, None], y, 0.0)

    if few < T * k:
        w = jnp.where(routed, weight, 0.0).astype(jnp.float32).reshape(-1)

        def summed(n):
            """() -> (T, H): the first n sorted rows' products, each
            weighted by its assignment's weight (0 past the held ones,
            and what the products left there is dropped BEFORE the
            weight meets it) and added to its token's row."""
            at = order[:n]
            return lambda: _sum_by_row(products(n) * w[at][:, None],
                                       at // k, T)
        return jax.lax.cond(jnp.sum(rows) <= few, summed(few),
                            summed(T * k)), rows
    back = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))         # assignment -> sorted
    y = products(few)[back]
    w = jnp.where(routed, weight, 0.0).astype(jnp.float32)
    out = jnp.einsum("tkh,tk->th", y.reshape(T, k, -1), w)
    return out, rows


def _sum_by_row(y, row, num_rows):
    """y (n, H) f32, row (n,) the row each belongs to -> (num_rows, H)
    f32, the sum of each row's: a product with the (num_rows, n) matrix
    of ones where `row` says, exact at `Precision.HIGHEST` (one operand
    is 0 or 1) and the MXU's, where a scatter-add visits a row at a time
    (0.11 and 0.26 ms a layer over the three products against 0.23 and
    0.37: PERF.md, Findings PR 47). 0 x NaN is NaN in a product, so a
    y that is not finite is taken out first and its row made NaN after:
    what one request's row holds stays that request's."""
    hot = row[None, :] == jnp.arange(num_rows)[:, None]
    fine = jnp.all(jnp.isfinite(y), -1)
    out = jnp.dot(hot.astype(y.dtype), jnp.where(fine[:, None], y, 0.0),
                  precision=jax.lax.Precision.HIGHEST)
    return jnp.where(jnp.any(hot & ~fine, -1)[:, None], jnp.nan, out)


# sorted rows a block of `dropless_experts_blocked` holds: 26 kB a row of
# intermediates at Moonlight's widths, 105 MB a block, six or seven blocks
# walked at 3,072 rows a held expert. Same seed and batches on one chip (my
# chip run, PR 45): 8,192 rows 28,609 tokens/s, 2,048 rows 28,759 (the
# float32 sums of the weights' gradients are added to once a block), 4,096
# rows 29,134; and a layer whose held rows lie just over a block's edge pays
# a smaller block. 4,608 (nine 512-row tiles, so that 23,041-27,648 held rows
# are always six blocks) is 3.6% slower over the same steps: a block then
# costs 7.5 ms where one of 4,096 costs 4.5
ROW_BLOCK = 4096


def dropless_experts_blocked(x, expert, weight, w_gate, w_up, w_down,
                             first=0, num_experts=None):
    """`dropless_experts` for a step that is differentiated at thousands
    of rows an expert (`models/deepseek_spmd.py`): the same arguments and
    the same result, every assignment to a held expert computed in forward
    and backward under any routing, an assignment to an absent expert
    adding nothing and passing no gradient.

    The sorted rows go through their experts a block of ROW_BLOCK at a
    time, each block's weighted results added to its rows' output, and
    the blocks past the held assignments are never entered; what is alive
    at once is one block's products, whatever the routing, forward and
    backward (`_blocked_experts`, which brings its own backward pass).
    -> (out (T, H) f32, rows (E,) i32: the rows each held expert got).
    """
    k = expert.shape[1]
    routed, order, rows = _sorted_by_expert(expert, w_gate.shape[0], first,
                                            num_experts)
    w = jnp.where(routed, weight, 0.0).astype(jnp.float32).reshape(-1)
    return _blocked_experts(x, w, w_gate, w_up, w_down, order, rows, k,
                            ROW_BLOCK), rows


def _block_products(xs, wa, w_gate, w_up, w_down, sizes, live):
    """One block of sorted rows through their experts: xs (R, H) the
    rows, wa (R,) f32 their assignments' weights, sizes (E,) the part of
    each expert's run that lies in the block, live (R,) which rows are
    held assignments at all. -> (R, H) f32, weighted; 0 where not live.
    Rows past the experts' sum are nobody's and the TPU's grouped matmul
    leaves whatever was in memory there, in a product AND in the
    products its transposes make in the backward pass: they are dropped
    BEFORE the weight meets them (0 x garbage is not 0), and
    `_blocked_bwd` drops their gradient by x again."""
    h = jax.nn.silu(grouped_product(xs, w_gate, sizes)) \
        * grouped_product(xs, w_up, sizes)
    y = grouped_product(h.astype(xs.dtype), w_down, sizes)
    return jnp.where(live[:, None], y, 0.0) * wa[:, None]


def _block_plan(order, rows, k, row_block):
    """How `_blocked_experts` walks the sorted rows -> (live blocks,
    at(i)): as many blocks of R = `row_block` rows as hold the held
    assignments; `at(i)` gives block i's assignments (R,), its rows'
    tokens, the experts' sizes in it and which of its rows are live."""
    n = order.shape[0]
    R = min(int(row_block), -(-n // ROW_TILE_MIN) * ROW_TILE_MIN)
    held = jnp.sum(rows)
    end = jnp.cumsum(rows)
    start = end - rows
    order = jnp.pad(order, (0, -n % R))

    def at(i):
        lo = i * R
        idx = jax.lax.dynamic_slice(order, (lo,), (R,))
        sizes = jnp.clip(end - lo, 0, R) - jnp.clip(start - lo, 0, R)
        return idx, idx // k, sizes, lo + jnp.arange(R) < held
    return -(-held // R), at


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _blocked_experts(x, weight, w_gate, w_up, w_down, order, rows, k,
                     row_block):
    """`dropless_experts` under `jax.grad` at a training step's size.
    x (T, H); weight (T k,) f32 by assignment; order (T k,) sorted row ->
    assignment; rows (E,) the rows each held expert owns, the held
    assignments being the first `sum(rows)` sorted rows. -> (T, H) f32.

    Forward and backward each walk the LIVE blocks of `row_block` sorted
    rows, as many as hold the held assignments (a loop whose trip count
    the routing decides: every row to the held experts is slow, not
    wrong, and a usual step walks an eighth of the layer's assignments).
    A block gathers its rows of x, runs the three grouped products over
    the part of each expert's run that lies in it, and adds each row's
    weighted result to its token's output. The backward is written here,
    not derived: reverse mode through a loop of blocks keeps a copy of
    every loop-invariant operand a block (x and the three matrices, 6 GB
    at the cell's size), so each block's products are made again from
    the layer's inputs and differentiated on their own; what is alive at
    once is one block's intermediates, 26 kB a sorted row, and the
    float32 sums of the gradients."""
    live_blocks, at = _block_plan(order, rows, k, row_block)

    def body(i, out):
        idx, tok, sizes, live = at(i)
        return out.at[tok].add(_block_products(
            x[tok], weight[idx], w_gate, w_up, w_down, sizes, live))
    return jax.lax.fori_loop(0, live_blocks, body,
                             jnp.zeros(x.shape, jnp.float32))


def _blocked_fwd(x, weight, w_gate, w_up, w_down, order, rows, k, row_block):
    out = _blocked_experts(x, weight, w_gate, w_up, w_down, order, rows, k,
                           row_block)
    return out, (x, weight, w_gate, w_up, w_down, order, rows)


def _blocked_bwd(k, row_block, res, d_out):
    x, weight, w_gate, w_up, w_down, order, rows = res
    live_blocks, at = _block_plan(order, rows, k, row_block)
    f32 = lambda a: jnp.zeros(a.shape, jnp.float32)

    def body(i, acc):
        idx, tok, sizes, live = at(i)
        _, pull = jax.vjp(
            lambda xs, wa, *w: _block_products(xs, wa, *w, sizes, live),
            x[tok], weight[idx], w_gate, w_up, w_down)
        d_xs, d_wa, *d_w = pull(d_out[tok])
        # a row nobody owns has no gradient; the transposed products
        # leave there what they found (PERF.md, Findings PR 45)
        d_xs = jnp.where(live[:, None], d_xs, 0)
        dx, dweight, *dw = acc
        return (dx.at[tok].add(d_xs.astype(jnp.float32)),
                dweight.at[idx].add(d_wa),
                *(a + b.astype(jnp.float32) for a, b in zip(dw, d_w)))
    dx, dweight, *dw = jax.lax.fori_loop(
        0, live_blocks, body,
        (f32(x), f32(weight), f32(w_gate), f32(w_up), f32(w_down)))
    ints = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dx.astype(x.dtype), dweight,
            *(a.astype(w.dtype) for a, w in zip(dw, (w_gate, w_up, w_down))),
            ints(order), ints(rows))


_blocked_experts.defvjp(_blocked_fwd, _blocked_bwd)
