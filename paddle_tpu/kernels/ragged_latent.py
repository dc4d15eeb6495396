"""Learned sparse attention over a latent paged cache, on the ragged step's
descriptors: three siblings of `ragged_paged_attention` that share its
runs (`ragged_runs`) and its walk through the page table.

A layer of this kind (`models/glm_dsa.py`) keeps a token two rows on one
page table: a latent row shared by every head (`rank` values the heads
read as keys AND values, then the rotary part of the key) and an index
key. A row of the step's flat buffer then goes through three parts:

  * `ragged_index_scores`: I(t, s) = sum_j w[t, j] ReLU(q[t, j] . k[s])
    for every cached position s <= t of the row's slot, the index keys
    fetched by pages, a run's rows meeting a block of keys together.
    Scores come back in the blocks the walk made them in,
    `(blocks, rows, block)`: column `b * block + i` is position that.
  * `dsa_select`: the row's k-th largest score as a THRESHOLD, exact,
    ties to the lower position: a bisection over the 32 bits of the
    scores' order-preserving integer keys (`score_keys`), 32 counts over
    the row's live columns in fast memory and no sort. A row with no more
    than k columns selects them all.
  * `ragged_sparse_latent_attention`: every head over the positions the
    selection names, softmax over exactly those. The kernel walks the
    run's latent pages as its siblings do and masks by the threshold, so
    it READS the run's whole context where an ideal one would fetch the
    k selected rows: a selected row is 1,152 bytes, and a fetch of that
    size costs a DMA descriptor each (half a million a layer at 256
    rows). What it reads beyond the selection earns no credit in the
    benchmark's roofline share.

A layer that attends its WHOLE context (`models/longcat_flash.py`) has no
index key, no scores and no selection: `ragged_latent_attention` is the
third part alone, on the same walk and the same online softmax
(`_attend`), masked by the causal limit only.

Both attention kernels are `_attention_walk`, which has two bodies and
picks one a run by the run's row count: a run that fills its q block of
`ATTN_ROWS` rows (`whole`: a prompt's chunk) is one product a trip; any
other (`row`: a decode row; `piece`: the 2-15 rows of a chunk at a q
block's edge) is walked a row at a time by ONE body entered at the run's
row, whose trip holds only what changes with the trip (the engine counts
the kinds: `pt_latent_runs`, `pt_latent_trips`).

Each has the plain `jax.numpy` path the CPU tests run (gathers a row's
whole context, so only for small shapes) and a Pallas kernel, tested
against it under `interpret=True`. Pools are one layer's, `(1, pages,
page, row)`: one row a token for all heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.flash_attention import _fit_lanes
from ..ops.paged_attention import F0, F1, LANES, NEG_INF, Z, _on_tpu
from .ragged_paged_attention import ragged_runs

__all__ = ["ragged_index_scores", "dsa_select", "score_keys",
           "ragged_sparse_latent_attention", "ragged_latent_attention",
           "latent_block_pages", "INDEX_ROWS", "ATTN_ROWS"]

INDEX_ROWS, SELECT_ROWS, ATTN_ROWS = 8, 8, 16   # buffer rows a program
_BLOCK_TOKENS = 512                             # columns a trip of the walk
_INT_MIN = np.int32(-2 ** 31)
_LOW31 = np.int32(2 ** 31 - 1)
_NO_SCORE = np.float32(-np.inf)
_VMEM = 96 * 1024 * 1024
_ONE = np.int32(1)


def latent_block_pages(page_size, n_pages, block_pages=None):
    """Pages a trip of the walk fetches: given, else 512 tokens' worth."""
    return min(int(block_pages or max(1, _BLOCK_TOKENS // page_size)),
               n_pages)


def score_keys(scores):
    """float32 -> int32 with the same order (-inf lowest, above only
    INT_MIN, which no score maps to): the sign bit kept, the rest flipped
    where it is set."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ ((bits >> 31) & _LOW31)


def _walk(runs_ref, qb_ref, ptab_ref, pools, bufs, sem, *, page_size,
          block_pages, visit, passes=None):
    """`_ragged_kernel`'s walk: program j visits the runs of q block j
    and, a run, its KV blocks of `block_pages` pages, each page fetched
    through the page table into the buffer the NEXT trip reads (the
    prefetch crosses from a run's last block to the next walk's first).
    A page the run does not own is neither fetched nor waited for.

    A run's context is walked `passes(r)` times (once where None).
    `visit(r, i, trips)` is called once a walk, pass i of run r: what is
    the walk's and not a trip's is computed there, and it calls
    `trips(body, init)` exactly ONCE on the path it takes, which runs
    `body(b, slot_, carry) -> carry` on what has arrived, a block b
    (`slot_`: the buffer's half it lies in), and returns the last
    carry."""
    j = pl.program_id(0)
    r_lo, r_hi = qb_ref[j], qb_ref[j + 1]
    blk = np.int32(block_pages * page_size)

    def for_pages(r, b, slot_, op):
        seq = runs_ref[2, r]
        owned = pl.cdiv(runs_ref[3, r], np.int32(page_size))
        for p in range(block_pages):
            ordinal = b * np.int32(block_pages) + np.int32(p)

            @pl.when(ordinal < owned)
            def _(p=p, ordinal=ordinal):
                page = ptab_ref[seq, ordinal]
                for n, (pool, buf) in enumerate(zip(pools, bufs)):
                    getattr(pltpu.make_async_copy(
                        pool.at[:, page], buf.at[slot_, :, np.int32(p)],
                        sem.at[np.int32(n), slot_]), op)()

    @pl.when(r_lo < r_hi)
    def _first_fetch():
        for_pages(r_lo, Z, Z, "start")

    def run(r, slot0):
        n_blocks = pl.cdiv(runs_ref[3, r], blk)

        def walk(i, slot0, again):
            def trips(body, init):
                def trip(b, carry):
                    slot_ = (slot0 + b) & _ONE
                    last = b + _ONE >= n_blocks
                    r_next = jnp.where(last & ~again, r + _ONE, r)
                    b_next = jnp.where(last, Z, b + _ONE)

                    @pl.when(r_next < r_hi)
                    def _prefetch():
                        for_pages(r_next, b_next, _ONE - slot_, "start")

                    for_pages(r, b, slot_, "wait")
                    return body(b, slot_, carry)

                return jax.lax.fori_loop(Z, n_blocks, trip, init)

            visit(r, i, trips)
            return (slot0 + n_blocks) & _ONE

        if passes is None:
            return walk(Z, slot0, np.bool_(False))
        n_passes = passes(r)
        return jax.lax.fori_loop(
            Z, n_passes, lambda i, slot0: walk(i, slot0, i + _ONE < n_passes),
            slot0)

    jax.lax.fori_loop(r_lo, r_hi, run, Z)


def _row_limits(runs_ref, r, row):
    """(whether buffer row `row` lies in run r, its causal limit)."""
    first, n_rows, kv_len = runs_ref[0, r], runs_ref[1, r], runs_ref[3, r]
    return ((row >= first) & (row < first + n_rows),
            kv_len - (first + n_rows) + row + _ONE)


def _cols(b, blk):
    return b * np.int32(blk) + jax.lax.broadcasted_iota(
        jnp.int32, (1, blk), 1)


def _weighted_relu(s, w):
    """sum over heads of w ReLU(s) in float32: s (heads, n) as the product
    left it, w (heads, n). A zero comes back +0: -0 and +0 are one score
    and would be two keys."""
    return jnp.sum(jnp.maximum(s, F0) * w, axis=0, keepdims=True) + F0


# -- index scores -----------------------------------------------------------
def _index_kernel(runs_ref, qb_ref, ptab_ref, q_ref, w_ref, pool, o_ref,
                  buf, sem, *, page_size, block_pages, heads):
    """Program j: q block j's rows x index heads (rows * heads, D) against
    every key block of the runs in it. One product a trip for the whole q
    block, then a live row's heads weighted and summed into its columns
    of `o_ref` (blocks, rows, block); what no row reaches stays -inf. Keys
    kept in a narrower type than the queries' (float8) are widened to it
    in fast memory."""
    rows = q_ref.shape[0] // heads
    blk = block_pages * page_size
    j = pl.program_id(0)
    o_ref[...] = jnp.full_like(o_ref, _NO_SCORE)

    def visit(r, _, trips):
        def block(b, slot_, carry):
            k = buf[slot_, 0].reshape(blk, buf.shape[-1]).astype(q_ref.dtype)
            s = jax.lax.dot_general(q_ref[...], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            col = _cols(b, blk)
            for i in range(rows):
                mine, lim = _row_limits(runs_ref, r, j * np.int32(rows)
                                        + np.int32(i))

                @pl.when(mine)
                def _(i=i, lim=lim):
                    tot = _weighted_relu(s[i * heads:(i + 1) * heads],
                                         _fit_lanes(w_ref[i], blk))
                    o_ref[b, i:i + 1, :] = jnp.where(col < lim, tot,
                                                     _NO_SCORE)
            return carry

        trips(block, Z)

    _walk(runs_ref, qb_ref, ptab_ref, (pool,), (buf,), sem,
          page_size=page_size, block_pages=block_pages, visit=visit)


def _context(pages_, page_table, tok_slot, n_pages):
    """Each row's whole paged context, `n_pages` pages of it (the table
    padded where the walk's last block overhangs it) -> (T, C, row)."""
    pages = jnp.pad(page_table[tok_slot],
                    ((0, 0), (0, n_pages - page_table.shape[1])))
    return pages_[0][pages].reshape(tok_slot.shape[0],
                                    n_pages * pages_.shape[-2], -1)


def _index_reference(q, w, key_pages, page_table, tok_slot, tok_pos, bp):
    t = q.shape[0]
    blk = bp * key_pages.shape[-2]
    nb = -(-page_table.shape[1] // bp)
    keys = _context(key_pages, page_table, tok_slot, nb * bp)   # (T, C, D)
    s = jnp.einsum("thd,tcd->thc", q, keys.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    tot = jax.vmap(lambda s_, w_: _weighted_relu(
        s_, jnp.broadcast_to(w_[:, None], s_.shape))[0])(s, w)
    tot = jnp.where(jnp.arange(nb * blk)[None, :] <= tok_pos[:, None], tot,
                    _NO_SCORE)
    return tot.reshape(t, nb, blk).swapaxes(0, 1)


def ragged_index_scores(q, w, key_pages, page_table, tok_slot, tok_pos, *,
                        use_pallas=None, interpret=False, block_pages=None,
                        runs=None):
    """q (T, heads, D) index queries, w (T, heads) f32 their weights;
    key_pages (1, P, page, D) one layer's index keys, in the queries' type
    or a narrower one (float8_e4m3fn); page_table (S,
    pages a sequence); tok_slot / tok_pos (T,) the step's descriptors
    (pos -1: no row). -> (blocks, T, block) f32: row t's I(t, s) at
    [s // block, t, s % block] for s <= tok_pos[t], -inf elsewhere;
    block = `latent_block_pages` pages. `runs`: `ragged_runs(tok_slot,
    tok_pos, heads, INDEX_ROWS)` from a caller that derives it once."""
    t, heads, d = q.shape
    page = key_pages.shape[-2]
    bp = latent_block_pages(page, page_table.shape[1], block_pages)
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas and not interpret:
        return _index_reference(q, w, key_pages, page_table, tok_slot,
                                tok_pos, bp)
    if runs is None:
        runs = ragged_runs(tok_slot, tok_pos, heads, INDEX_ROWS)
    runs, qb_first = runs
    n_qb = qb_first.shape[0] - 1
    t_pad = n_qb * INDEX_ROWS
    blk, nb = bp * page, -(-page_table.shape[1] // bp)
    q2 = jnp.pad(q, ((0, t_pad - t), (0, 0), (0, 0))).reshape(
        t_pad * heads, d)
    w3 = jnp.broadcast_to(jnp.pad(w.astype(jnp.float32), (
        (0, t_pad - t), (0, 0)))[:, :, None], (t_pad, heads, LANES))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(n_qb,),
        in_specs=[
            pl.BlockSpec((INDEX_ROWS * heads, d), lambda j, *_: (j, Z)),
            pl.BlockSpec((INDEX_ROWS, heads, LANES), lambda j, *_: (j, Z, Z)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((nb, INDEX_ROWS, blk),
                               lambda j, *_: (Z, j, Z)),
        scratch_shapes=[
            pltpu.VMEM((2, 1, bp) + key_pages.shape[-2:], key_pages.dtype),
            pltpu.SemaphoreType.DMA((1, 2))])
    out = pl.pallas_call(
        functools.partial(_index_kernel, page_size=page, block_pages=bp,
                          heads=heads),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, t_pad, blk), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="ragged_index_scores",
    )(runs, qb_first, page_table.astype(jnp.int32), q2, w3, key_pages)
    return out[:, :t]


# -- the selection ----------------------------------------------------------
def _select_kernel(nb_ref, sc_ref, thr_ref, cnt_ref, key_ref, *, k):
    """Program i: SELECT_ROWS rows' scores (blocks, rows, block), of which
    the first `nb_ref[i]` blocks hold a live column. The k-th largest
    key a row, built from the top bit down: a bit stays where at least k
    keys lie at or above the candidate. Fewer than k keys above -inf
    leave INT_MIN, under every key."""
    nb = nb_ref[pl.program_id(0)]
    rows = sc_ref.shape[1]

    def to_key(b, c):
        bits = pltpu.bitcast(sc_ref[b], jnp.int32)
        key_ref[b] = bits ^ ((bits >> 31) & _LOW31)
        return c
    jax.lax.fori_loop(Z, nb, to_key, Z)

    def count_ge(cand):
        # lane by lane over the blocks, ONE reduction across lanes a count
        n = jax.lax.fori_loop(
            Z, nb, lambda b, n: n + (key_ref[b] >= cand).astype(jnp.int32),
            jnp.zeros(key_ref.shape[1:], jnp.int32))
        return jnp.sum(n, axis=1, keepdims=True, dtype=jnp.int32)

    ans = jnp.zeros((rows, 1), jnp.int32)   # offset binary: INT_MIN is 0
    for bit in range(31, -1, -1):
        cand = ans | np.int32(-2 ** 31 if bit == 31 else 1 << bit)
        ans = jnp.where(count_ge(cand ^ _INT_MIN) >= np.int32(k), cand, ans)
    thr = ans ^ _INT_MIN
    thr_ref[...] = jnp.broadcast_to(thr, thr_ref.shape)
    cnt_ref[...] = jnp.broadcast_to(count_ge(thr), cnt_ref.shape)


def _tie_position(scores, thr, k):
    """Where more keys lie at the threshold than the selection has room
    for, the position of the last one it takes (the lower positions
    first). scores (blocks, T, block), thr (T,) -> (T,) i32."""
    nb, t, blk = scores.shape
    key = score_keys(scores).swapaxes(0, 1).reshape(t, nb * blk)
    need = np.int32(k) - jnp.sum(key > thr[:, None], axis=1)
    at = key == thr[:, None]
    taken = at & (jnp.cumsum(at, axis=1) <= need[:, None])
    return jnp.max(jnp.where(taken, jnp.arange(nb * blk, dtype=jnp.int32),
                             -1), axis=1).astype(jnp.int32)


def dsa_select(scores, tok_pos, k, *, use_pallas=None, interpret=False):
    """scores (blocks, T, block) f32 as `ragged_index_scores` leaves them,
    tok_pos (T,), k static. -> (thr, at) both (T,) i32: row t selects the
    positions whose `score_keys` lie above thr[t], and those AT it up to
    position at[t]: its k largest, ties to the lower position; all of
    them where it has no more than k (thr INT_MIN)."""
    nb, t, blk = scores.shape
    every = jnp.full((t,), nb * blk, jnp.int32)
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas and not interpret:
        key = score_keys(scores).swapaxes(0, 1).reshape(t, nb * blk)
        if nb * blk <= k:
            return jnp.full((t,), _INT_MIN, jnp.int32), every
        kth = jax.lax.top_k(key, k)[0][:, -1]
        thr = jnp.where(tok_pos + 1 > k, kth, _INT_MIN)
        return thr, jnp.where(tok_pos + 1 > k,
                              _tie_position(scores, thr, k), every)
    t_pad = -(-t // SELECT_ROWS) * SELECT_ROWS
    sc = jnp.pad(scores, ((0, 0), (0, t_pad - t), (0, 0)),
                 constant_values=_NO_SCORE)
    lim = jnp.pad(tok_pos.astype(jnp.int32) + 1, (0, t_pad - t))
    live = -(-jnp.max(lim.reshape(-1, SELECT_ROWS), axis=1) // np.int32(blk))
    out = jax.ShapeDtypeStruct((t_pad, LANES), jnp.int32)
    spec = pl.BlockSpec((SELECT_ROWS, LANES), lambda i, *_: (i, Z))
    thr, cnt = pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(t_pad // SELECT_ROWS,),
            in_specs=[pl.BlockSpec((nb, SELECT_ROWS, blk),
                                   lambda i, *_: (Z, i, Z))],
            out_specs=[spec, spec],
            scratch_shapes=[pltpu.VMEM((nb, SELECT_ROWS, blk), jnp.int32)]),
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="dsa_select",
    )(live.astype(jnp.int32), sc)
    thr, cnt = thr[:t, 0], cnt[:t, 0]
    # more keys at the threshold than room: exact ties, which float32
    # scores of distinct positions all but never make; the cumulative
    # count that settles them runs only in a step that has one
    tied = (cnt > k) & (thr != _INT_MIN)
    at = jax.lax.cond(jnp.any(tied),
                      lambda: jnp.where(tied, _tie_position(scores, thr, k),
                                        every),
                      lambda: every)
    return thr, at


# -- what both attention kernels do to a block ---------------------------------
def _begin(j, buf, m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j == 0)
    def _finite_buffer():
        # the tail of a run's last block is never fetched: what P = 0
        # multiplies there must be finite, and stale pages are
        buf[...] = jnp.zeros_like(buf)


def _attend(q, kv, seen, m_prev, l_prev, acc_prev, *, scale, blk, rank):
    """One block of the online softmax of q's rows (rows x heads, row):
    scores on the whole latent row, values its first `rank`; the rows'
    running maximum, sum and accumulator in, the new ones out."""
    s = jax.lax.dot_general(
        q, kv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = jnp.where(seen, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(seen, jnp.exp(s - _fit_lanes(m_new, blk)),
                  jnp.zeros_like(s))
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_prev * _fit_lanes(alpha, rank) + jax.lax.dot_general(
        p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def _attention_walk(runs_ref, qb_ref, ptab_ref, q_ref, pool, o_ref, buf, sem,
                    m_ref, l_ref, acc_ref, *, scale, page_size, block_pages,
                    heads, rank, whole_seen, row_seen):
    """What both attention kernels are. Program j: q block j's rows x
    heads (rows * heads, row) over the latent pages of the runs in it, by
    one of TWO bodies, chosen a run by its row count:

      * a run that fills the q block (a prompt's chunk) walks its context
        once, all its rows against a block as ONE product; the mask is
        `whole_seen(r, row0, b, col)` (rows * heads, blk), `row0` the q
        block's first buffer row; the state is the q block's, in `m_ref`,
        `l_ref`, `acc_ref`;
      * any other run (a decode row; the 2-15 rows of a chunk's piece at
        a q block's edge) walks it once a ROW, so that no product is made
        for rows of other runs. What is the row's and not the trip's is
        taken before its first trip: its heads' queries, and
        `row_seen(at, lim)` for the q block's row `at` under its causal
        limit, which returns the trip's `seen(b, col)` (1, blk). Maximum,
        sum and accumulator start as a fresh row's, are carried through
        the trips as values and stored when the walk ends.

    Latent rows kept in a narrower type than the queries' (float8) are
    widened to it in fast memory."""
    rows = q_ref.shape[0] // heads
    blk = block_pages * page_size
    j = pl.program_id(0)
    row0 = j * np.int32(rows)
    _begin(j, buf, m_ref, l_ref, acc_ref)
    attend = functools.partial(_attend, scale=scale, blk=blk, rank=rank)

    def block_of(slot_):
        return buf[slot_, 0].reshape(blk, buf.shape[-1]).astype(q_ref.dtype)

    def visit(r, i, trips):
        first, n_rows, kv_len = runs_ref[0, r], runs_ref[1, r], runs_ref[3, r]
        whole = n_rows == np.int32(rows)

        @pl.when(whole)
        def _every_row_at_once():
            def block(b, slot_, carry):
                m, l, acc = attend(
                    q_ref[...], block_of(slot_),
                    whole_seen(r, row0, b, _cols(b, blk)), m_ref[...],
                    l_ref[...], acc_ref[...])
                m_ref[...] = m
                l_ref[...] = l
                acc_ref[...] = acc
                return carry

            trips(block, Z)

        @pl.when(~whole)
        def _a_row_by_itself():
            at = first - row0 + i
            hs = pl.ds(pl.multiple_of(at * np.int32(heads), heads), heads)
            q = q_ref[hs, :]
            seen = row_seen(at, kv_len - n_rows + i + _ONE)

            def block(b, slot_, carry):
                return attend(q, block_of(slot_), seen(b, _cols(b, blk)),
                              *carry)

            _, l, acc = trips(block, (
                jnp.full((heads, LANES), NEG_INF, jnp.float32),
                jnp.zeros((heads, LANES), jnp.float32),
                jnp.zeros((heads, rank), jnp.float32)))
            l_ref[hs, :] = l
            acc_ref[hs, :] = acc

    _walk(runs_ref, qb_ref, ptab_ref, (pool,), (buf,), sem,
          page_size=page_size, block_pages=block_pages, visit=visit,
          passes=lambda r: jnp.where(runs_ref[1, r] == np.int32(rows), _ONE,
                                     runs_ref[1, r]))
    _finish(o_ref, l_ref, acc_ref, rank)


def _finish(o_ref, l_ref, acc_ref, rank):
    l = l_ref[...]
    l_safe = jnp.where(l == F0, F1, l)          # rows of no run: 0 / 1
    o_ref[...] = (acc_ref[...] / _fit_lanes(l_safe, rank)).astype(o_ref.dtype)


# -- attention over the selected rows ---------------------------------------
def _latent_kernel(runs_ref, qb_ref, ptab_ref, q_ref, sc_ref, thr_ref, at_ref,
                   pool, o_ref, buf, sem, m_ref, l_ref, acc_ref, *, heads,
                   **kw):
    """`_attention_walk` under a selection: a row sees what its index
    scores of the block (`sc_ref`, (blocks, rows, blk)) keep under its
    threshold, and under its causal limit."""
    rows = q_ref.shape[0] // heads
    blk = sc_ref.shape[-1]

    def row_seen(at, lim):
        me = pl.ds(at, 1)
        thr = _fit_lanes(thr_ref[me, :], blk)
        upto = _fit_lanes(at_ref[me, :], blk)

        def seen(b, col):
            bits = pltpu.bitcast(sc_ref[b, me, :], jnp.int32)
            key = bits ^ ((bits >> 31) & _LOW31)
            return ((key > thr) | ((key == thr) & (col <= upto))) & (
                col < lim)
        return seen

    def whole_seen(r, row0, b, col):
        # row x head -> its row's mask: each row's spread over its heads
        return jnp.concatenate([jnp.broadcast_to(row_seen(i, _row_limits(
            runs_ref, r, row0 + np.int32(i))[1])(b, col), (heads, blk))
            for i in range(rows)], axis=0)

    _attention_walk(runs_ref, qb_ref, ptab_ref, q_ref, pool, o_ref, buf, sem,
                    m_ref, l_ref, acc_ref, heads=heads, whole_seen=whole_seen,
                    row_seen=row_seen, **kw)


def _selected(scores, thr, at):
    """(blocks, T, block) scores -> (T, C) bool: what `dsa_select`'s
    threshold names."""
    nb, t, blk = scores.shape
    key = score_keys(scores).swapaxes(0, 1).reshape(t, nb * blk)
    cols = jnp.arange(nb * blk, dtype=jnp.int32)[None, :]
    return (key > thr[:, None]) | ((key == thr[:, None])
                                   & (cols <= at[:, None]))


def _softmax_over(s, seen, kv, rank, dtype):
    """The `jax.numpy` paths' softmax: scores s (T, heads, C) over each
    row's context kv (T, C, row) f32 where `seen` (T, C) says so, values
    the context's first `rank`; a row that sees nothing comes back zero."""
    s = jnp.where(seen[:, None, :], s, NEG_INF)
    p = jnp.where(seen[:, None, :],
                  jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    o = jnp.einsum("thc,tcr->thr", p / jnp.where(l == 0, 1.0, l),
                   kv[..., :rank])
    return o.astype(dtype)


def _latent_reference(q, pages_, scores, thr, at, page_table, tok_slot,
                      tok_pos, rank, scale):
    nb, t, blk = scores.shape
    kv = _context(pages_, page_table, tok_slot,
                  nb * blk // pages_.shape[-2]).astype(jnp.float32)
    s = jnp.einsum("thr,tcr->thc", q.astype(jnp.float32), kv) * scale
    seen = _selected(scores, thr, at) & (
        jnp.arange(nb * blk)[None, :] <= tok_pos[:, None])
    return _softmax_over(s, seen, kv, rank, q.dtype)


def ragged_sparse_latent_attention(q, latent_pages, scores, thr, at,
                                   page_table, tok_slot, tok_pos, *, rank,
                                   sm_scale, use_pallas=None, interpret=False,
                                   runs=None):
    """q (T, heads, row): each head's query against the cached latent row
    (the key's up-projection absorbed into it, then its rotary part, zeros
    in the lanes the pool pads); latent_pages (1, P, page, row) one
    layer's; scores / thr / at: `ragged_index_scores` and `dsa_select` of
    the same rows, whose block is this walk's. -> (T, heads, rank): the
    softmax over the selected positions s <= tok_pos[t] of their first
    `rank` values (the caller up-projects them a head). Rows with pos -1
    come back zero. `runs`: `ragged_runs(tok_slot, tok_pos, heads,
    ATTN_ROWS)`."""
    t, heads, row = q.shape
    nb, _, blk = scores.shape
    page = latent_pages.shape[-2]
    bp = blk // page
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas and not interpret:
        return _latent_reference(q, latent_pages, scores, thr, at,
                                 page_table, tok_slot, tok_pos, rank,
                                 sm_scale)
    if runs is None:
        runs = ragged_runs(tok_slot, tok_pos, heads, ATTN_ROWS)
    runs, qb_first = runs
    n_qb = qb_first.shape[0] - 1
    t_pad = n_qb * ATTN_ROWS
    q2 = jnp.pad(q, ((0, t_pad - t), (0, 0), (0, 0))).reshape(
        t_pad * heads, row)
    sc = jnp.pad(scores, ((0, 0), (0, t_pad - t), (0, 0)),
                 constant_values=_NO_SCORE)

    def lanes(x):
        return jnp.broadcast_to(jnp.pad(x.astype(jnp.int32), (
            0, t_pad - t))[:, None], (t_pad, LANES))
    stat = pl.BlockSpec((ATTN_ROWS, LANES), lambda j, *_: (j, Z))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(n_qb,),
        in_specs=[
            pl.BlockSpec((ATTN_ROWS * heads, row), lambda j, *_: (j, Z)),
            pl.BlockSpec((nb, ATTN_ROWS, blk), lambda j, *_: (Z, j, Z)),
            stat, stat,
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ATTN_ROWS * heads, rank),
                               lambda j, *_: (j, Z)),
        scratch_shapes=[
            pltpu.VMEM((2, 1, bp) + latent_pages.shape[-2:],
                       latent_pages.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.VMEM((ATTN_ROWS * heads, LANES), jnp.float32),
            pltpu.VMEM((ATTN_ROWS * heads, LANES), jnp.float32),
            pltpu.VMEM((ATTN_ROWS * heads, rank), jnp.float32)])
    o = pl.pallas_call(
        functools.partial(_latent_kernel, scale=np.float32(sm_scale),
                          page_size=page, block_pages=bp, heads=heads,
                          rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad * heads, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="ragged_sparse_latent_attention",
    )(runs, qb_first, page_table.astype(jnp.int32), q2, sc, lanes(thr),
      lanes(at), latent_pages)
    return o.reshape(t_pad, heads, rank)[:t]


# -- attention over the whole context ---------------------------------------
def _dense_latent_kernel(runs_ref, qb_ref, ptab_ref, q_ref, rowid_ref, pool,
                         o_ref, buf, sem, m_ref, l_ref, acc_ref, *,
                         page_size, block_pages, **kw):
    """`_attention_walk` without a selection: a row sees every column
    under its causal limit; a whole q block's rows' limits from
    `rowid_ref` (rows * heads, LANES), the row each row x head belongs
    to."""
    blk = block_pages * page_size

    def whole_seen(r, row0, b, col):
        return col < _row_limits(runs_ref, r, row0)[1] + _fit_lanes(
            rowid_ref[...], blk)

    _attention_walk(runs_ref, qb_ref, ptab_ref, q_ref, pool, o_ref, buf, sem,
                    m_ref, l_ref, acc_ref, page_size=page_size,
                    block_pages=block_pages, whole_seen=whole_seen,
                    row_seen=lambda at, lim: lambda b, col: col < lim, **kw)


def _dense_latent_reference(q, pages_, page_table, tok_slot, tok_pos, rank,
                            scale):
    """`_latent_reference` with every column under the causal limit seen."""
    kv = _context(pages_, page_table, tok_slot,
                  page_table.shape[1]).astype(jnp.float32)
    s = jnp.einsum("thr,tcr->thc", q.astype(jnp.float32), kv) * scale
    seen = jnp.arange(kv.shape[1])[None, :] <= tok_pos[:, None]
    return _softmax_over(s, seen, kv, rank, q.dtype)


def ragged_latent_attention(q, latent_pages, page_table, tok_slot, tok_pos,
                            *, rank, sm_scale, use_pallas=None,
                            interpret=False, block_pages=None, runs=None):
    """`ragged_sparse_latent_attention` for a layer that selects nothing:
    q (T, heads, row) against latent_pages (1, P, page, row), in the
    queries' type or a narrower one (float8_e4m3fn) -> (T, heads, rank),
    the softmax over EVERY position s <= tok_pos[t] of its first `rank`
    values. Rows with pos -1 come back zero. `runs`: `ragged_runs(
    tok_slot, tok_pos, heads, ATTN_ROWS)`."""
    t, heads, row = q.shape
    page = latent_pages.shape[-2]
    bp = latent_block_pages(page, page_table.shape[1], block_pages)
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas and not interpret:
        return _dense_latent_reference(q, latent_pages, page_table, tok_slot,
                                       tok_pos, rank, sm_scale)
    if runs is None:
        runs = ragged_runs(tok_slot, tok_pos, heads, ATTN_ROWS)
    runs, qb_first = runs
    n_qb = qb_first.shape[0] - 1
    t_pad = n_qb * ATTN_ROWS
    q2 = jnp.pad(q, ((0, t_pad - t), (0, 0), (0, 0))).reshape(
        t_pad * heads, row)
    rowid = jnp.broadcast_to(
        (jnp.arange(ATTN_ROWS * heads, dtype=jnp.int32) // heads)[:, None],
        (ATTN_ROWS * heads, LANES))
    stat = pltpu.VMEM((ATTN_ROWS * heads, LANES), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(n_qb,),
        in_specs=[
            pl.BlockSpec((ATTN_ROWS * heads, row), lambda j, *_: (j, Z)),
            pl.BlockSpec((ATTN_ROWS * heads, LANES), lambda j, *_: (Z, Z)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ATTN_ROWS * heads, rank),
                               lambda j, *_: (j, Z)),
        scratch_shapes=[
            pltpu.VMEM((2, 1, bp) + latent_pages.shape[-2:],
                       latent_pages.dtype),
            pltpu.SemaphoreType.DMA((1, 2)), stat, stat,
            pltpu.VMEM((ATTN_ROWS * heads, rank), jnp.float32)])
    o = pl.pallas_call(
        functools.partial(_dense_latent_kernel, scale=np.float32(sm_scale),
                          page_size=page, block_pages=bp, heads=heads,
                          rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad * heads, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="ragged_latent_attention",
    )(runs, qb_first, page_table.astype(jnp.int32), q2, rowid, latent_pages)
    return o.reshape(t_pad, heads, rank)[:t]
