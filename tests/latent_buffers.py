"""Flat buffers for the latent attention kernels' interpreted tests
(`test_longcat_flash_serving.py`, `test_glm_dsa_serving.py`): each a way
a step's rows can lie in the kernels' q blocks of 16, so that every kind
of run (`kernels/ragged_latent._attention_walk`) is met at its edges.
Pages of 4 and walks of 2 pages a trip: a block is 8 tokens, a slot holds
32."""
import numpy as np

import jax.numpy as jnp

PAGE, N_PAGES = 4, 8


def _mixed():
    """The buffer the kernels' first tests drew: a decode row 20 deep,
    one 3 deep, a chunk of 13 rows from position 5 on, a slack row, and a
    chunk of 16 from position 7 on that fills the second q block."""
    slot, pos = np.zeros(32, np.int32), np.full(32, -1, np.int32)
    slot[0], pos[0] = 0, 20
    slot[1], pos[1] = 2, 3
    slot[2:15], pos[2:15] = 1, 5 + np.arange(13)
    slot[16:32], pos[16:32] = 3, 7 + np.arange(16)
    return slot, pos


def _decode_only():
    """Twenty decode rows of twenty slots, 1 to 32 deep, and slack."""
    slot, pos = np.zeros(32, np.int32), np.full(32, -1, np.int32)
    slot[:20] = np.arange(20)
    pos[:20] = (np.arange(20) * 13) % 32
    return slot, pos


def _chunk_inside():
    """One chunk of 32 rows from buffer row 13 on: a piece of 3 rows, a
    whole q block, a piece of 13; a decode row before it."""
    slot, pos = np.zeros(48, np.int32), np.full(48, -1, np.int32)
    slot[0], pos[0] = 1, 9
    slot[13:45], pos[13:45] = 0, np.arange(32)
    return slot, pos


def _one_page_tail():
    """Runs whose last block owns ONE of its two pages: a decode row at
    position 18 (19 tokens: five pages), a whole chunk that ends at 19,
    a piece of 2 that ends at 16."""
    slot, pos = np.zeros(32, np.int32), np.full(32, -1, np.int32)
    slot[0], pos[0] = 0, 18
    slot[1:3], pos[1:3] = 1, (15, 16)
    slot[16:32], pos[16:32] = 2, 4 + np.arange(16)
    return slot, pos


def _gaps():
    """Rows of no run between runs, and a q block of none at all."""
    slot, pos = np.zeros(48, np.int32), np.full(48, -1, np.int32)
    slot[0], pos[0] = 0, 11
    slot[2], pos[2] = 1, 30
    slot[5:10], pos[5:10] = 2, 3 + np.arange(5)
    slot[11], pos[11] = 3, 0
    slot[15], pos[15] = 4, 8
    slot[33:36], pos[33:36] = 5, 20 + np.arange(3)
    slot[47], pos[47] = 6, 15
    return slot, pos


def _sixteen_slots():
    """A q block whose 16 rows are 16 slots of different lengths, one of
    them shorter than a block, one a whole slot deep."""
    slot = np.arange(16, dtype=np.int32)
    pos = np.asarray([2, 31, 8, 7, 16, 23, 0, 9, 15, 24, 5, 12, 30, 17, 3,
                      21], np.int32)
    return slot, pos


BUFFERS = dict(mixed=_mixed, decode_only=_decode_only,
               chunk_inside=_chunk_inside, one_page_tail=_one_page_tail,
               gaps=_gaps, sixteen_slots=_sixteen_slots)


def rows(buffer, seed=0, dtype=jnp.float32, heads=4, width=24):
    """The buffer's descriptors with a page table drawn apart, a pool of
    latent rows in `dtype` and the rows' queries."""
    slot, pos = BUFFERS[buffer]()
    slots = int(slot.max()) + 1
    pool = slots * N_PAGES + 8
    rng = np.random.default_rng(seed)
    table = rng.permutation(pool - 1)[:slots * N_PAGES].reshape(
        slots, N_PAGES)
    draw = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return dict(table=jnp.asarray(table, jnp.int32), slot=jnp.asarray(slot),
                pos=jnp.asarray(pos),
                latent=draw(1, pool, PAGE, width).astype(dtype),
                q=draw(len(pos), heads, width))


def by_definition(d, t, seen=None, rank=16, scale=0.2):
    """Row t's output by the definition: softmax over the positions up to
    its own (of those, the ones `seen` (C,) keeps) of their first `rank`
    values, in numpy."""
    n = int(d["pos"][t]) + 1
    lat = np.asarray(d["latent"].astype(jnp.float32))[0]
    ctx = lat[np.asarray(d["table"])[int(d["slot"][t])]].reshape(
        -1, lat.shape[-1])[:n]
    if seen is not None:
        ctx = ctx[np.asarray(seen)[:n]]
    s = np.asarray(d["q"])[t] @ ctx.T * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ ctx[:, :rank]
