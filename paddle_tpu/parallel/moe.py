"""Expert parallelism / MoE (reference: python/paddle/incubate/nn/layer/
fused_moe + fleet EP groups over NCCL alltoall).

TPU-native GShard-style dense dispatch: top-k gating → capacity-bounded
one-hot dispatch tensors → two einsums. With the expert axis sharded
over 'ep' on the mesh, GSPMD lowers the dispatch einsums to all_to_all
over ICI — the NCCL alltoall of the reference, derived not hand-written.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .._core.tensor import Tensor, apply
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


def dropless_experts(x, expert, weight, w_gate, w_up, w_down, first=0,
                     num_experts=None):
    """Every assignment to an expert held here computed, none dropped:
    SwiGLU experts over the step's flat rows.

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

    Rows are sorted by expert, three grouped products run over the
    sorted rows that can hold an assignment, and each row gets the
    weighted sum of its assignments back. A whole layer's are all T k;
    a share's are four times its mean (T k E / num_experts) when they
    hold every held assignment, as they all but always do, and all T k
    in a step where they do not (`lax.cond`: a router that sends every
    row to the held experts is slow, not wrong). The products are
    LAUNCHED over `tiled_rows` of those: the buffer's length is what
    gives the compiler's kernel its row tile (`grouped_product`), and
    the rows added are nobody's, like every row past the experts' sum.
    -> (out (T, H) f32, rows (E,) i32: the rows each held expert got).
    """
    T, k = expert.shape
    E = w_gate.shape[0]
    few = _product_rows(T * k, E, num_experts)
    if num_experts in (None, E):
        routed = expert >= 0
    else:
        expert = expert - first
        routed = (expert >= 0) & (expert < E)
    key = jnp.where(routed, expert, E).reshape(-1)    # E sorts last
    order = jnp.argsort(key, stable=True)           # sorted -> assignment
    rows = jnp.zeros((E + 1,), jnp.int32).at[key].add(1)[:E]
    back = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))         # assignment -> sorted

    def products(n):
        """(n, H) f32: the first n sorted rows through their experts."""
        # the gather builds the buffer at the length the products are
        # launched over; the rows it adds (row 0's) are nobody's
        xs = x[jnp.pad(order[:n], (0, tiled_rows(n, E) - n)) // k]
        h = jax.nn.silu(grouped_product(xs, w_gate, rows)) \
            * grouped_product(xs, w_up, rows)
        y = grouped_product(h.astype(x.dtype), w_down, rows)[:n]
        # rows past the experts' are nobody's: whatever is there, drop it
        return jnp.where((jnp.arange(n) < jnp.sum(rows))[:, None], y, 0.0)

    if few == T * k:
        y = products(few)[back]
    else:
        def gathered(n):
            """() -> (T*k, H): each assignment's row of `products(n)`,
            zero for one that sorted past them (not held)."""
            return lambda: jnp.where(
                (back < n)[:, None], products(n)[jnp.minimum(back, n - 1)],
                0.0)
        y = jax.lax.cond(jnp.sum(rows) <= few, gathered(few),
                         gathered(T * k))
    w = jnp.where(routed, weight, 0.0).astype(jnp.float32)
    out = jnp.einsum("tkh,tk->th", y.reshape(T, k, -1), w)
    return out, rows
