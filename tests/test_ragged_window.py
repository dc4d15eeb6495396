"""The ragged paged-attention kernel under a static window, and with query
groups of 6 and 8: the Pallas kernel (TPU interpret mode) against its jnp
reference and both against dense masked attention; page-table entries
wholly behind the window point at a poisoned page, as the engine leaves
them once it has given those pages back, so a read of one shows."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.kernels.ragged_paged_attention import (
    ragged_paged_attention, ragged_paged_attention_reference)

PAGE, KVH, D = 4, 2, 16
PAGES_PER_SEQ, SLOTS, T = 16, 4, 24            # 64 tokens a sequence
WINDOW = 8


def _rows(*runs):
    slots, poss = [], []
    for slot, first, n in runs:
        slots += [slot] * n
        poss += list(range(first, first + n))
    return slots, poss


MIXES = {
    # contexts under, at and far over the window
    "decode_only": _rows((0, 40, 1), (1, 3, 1), (2, 63, 1), (3, 7, 1)),
    "decode_at_edges": _rows((0, 8, 1), (1, 9, 1), (2, 15, 1), (3, 16, 1)),
    # a chunk longer than the window, from the start and deep in
    "long_prefill": _rows((1, 0, 21)),
    "deep_chunk": _rows((2, 30, 13), (0, 50, 1)),
    "mixed_wave": _rows((0, 33, 1), (2, 8, 1), (1, 5, 9), (3, 0, 6)),
    "run_over_q_blocks": _rows((3, 2, 1), (0, 10, 12)),
    "empty": ([], []),
}


def _problem(mix, group, quant=False, seed=0, poison=True):
    rng = np.random.default_rng(seed)
    slots, poss = MIXES[mix]
    n = len(slots)
    num_pages = SLOTS * PAGES_PER_SEQ + 1
    shape = (KVH, num_pages, PAGE, D)
    q = jnp.asarray(rng.standard_normal((T, KVH * group, D)), jnp.float32)
    kw = {}
    if quant:
        k = rng.integers(-127, 128, shape).astype(np.int8)
        v = rng.integers(-127, 128, shape).astype(np.int8)
        for name in ("k_scale", "v_scale"):
            kw[name] = jnp.asarray(rng.uniform(
                0.01, 0.1, shape[:3] + (1,)), jnp.float32)
    else:
        k = rng.standard_normal(shape).astype(np.float32)
        v = rng.standard_normal(shape).astype(np.float32)
    ptab = rng.permutation(num_pages - 1).reshape(
        SLOTS, PAGES_PER_SEQ).astype(np.int32)
    dense_tab = ptab.copy()
    if poison:
        # what the engine does: a page wholly behind the first row's
        # window goes back to the pool and its entry to the trash page
        trash = num_pages - 1
        k[:, trash], v[:, trash] = (100, 100) if quant else (1e4, 1e4)
        for s in range(SLOTS):
            firsts = [p for sl, p in zip(slots, poss) if sl == s]
            if firsts:
                ptab[s, :max(0, min(firsts) - (WINDOW - 1)) // PAGE] = trash
    slot = jnp.asarray(slots + [0] * (T - n), jnp.int32)
    pos = jnp.asarray(poss + [-1] * (T - n), jnp.int32)
    args = (q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(ptab), slot, pos)
    return n, args, kw, dense_tab


def _dense(args, kw, table, window):
    """Plain masked softmax attention over the gathered context."""
    q, k, v, _, slot, pos = (np.asarray(a) for a in args)
    k, v = k.astype(np.float32), v.astype(np.float32)
    if kw:
        k = k * np.asarray(kw["k_scale"])
        v = v * np.asarray(kw["v_scale"])
    out = np.zeros(q.shape, np.float32)
    group = q.shape[1] // KVH
    for i in range(q.shape[0]):
        if pos[i] < 0:
            continue
        lo = 0 if window is None else max(0, pos[i] - window + 1)
        cols = np.arange(lo, pos[i] + 1)
        pages, offs = table[slot[i], cols // PAGE], cols % PAGE
        for h in range(q.shape[1]):
            kk, vv = k[h // group, pages, offs], v[h // group, pages, offs]
            s = kk @ q[i, h] * D ** -0.5
            p = np.exp(s - s.max())
            out[i, h] = (p / p.sum()) @ vv
    return out


def _close(got, want, rel=2e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.abs(got - want).max()
    assert err <= rel * max(np.abs(want).max(), 1.0), f"max |delta| = {err}"


@pytest.mark.parametrize("group", [6, 8])
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_window_kernel_reference_and_dense_agree(mix, group):
    n, args, kw, table = _problem(mix, group)
    ref = ragged_paged_attention_reference(*args, window=WINDOW)
    ker = ragged_paged_attention(*args, use_pallas=True, interpret=True,
                                 block_pages=2, window=WINDOW)
    want = _dense(args, kw, table, WINDOW)
    _close(ref, want)
    _close(ker, want)
    assert not np.asarray(ker)[n:].any()        # slack rows come back zero


@pytest.mark.parametrize("mix", ["decode_only", "deep_chunk", "mixed_wave"])
def test_window_kernel_int8_pages(mix):
    n, args, kw, table = _problem(mix, 8, quant=True)
    want = _dense(args, kw, table, WINDOW)
    _close(ragged_paged_attention_reference(*args, window=WINDOW, **kw), want)
    _close(ragged_paged_attention(*args, use_pallas=True, interpret=True,
                                  block_pages=2, window=WINDOW, **kw), want)


@pytest.mark.parametrize("block_pages", [1, 2, 4])
def test_window_walk_starts_at_the_windows_block_for_any_tile(block_pages):
    n, args, kw, table = _problem("deep_chunk", 6)
    ker = ragged_paged_attention(*args, use_pallas=True, interpret=True,
                                 block_pages=block_pages, window=WINDOW)
    _close(ker, _dense(args, kw, table, WINDOW))


@pytest.mark.parametrize("group", [6, 8])
def test_no_window_is_the_full_context(group):
    """`window=None` is today's kernel: every column up to the row's own."""
    n, args, kw, table = _problem("mixed_wave", group, poison=False)
    want = _dense(args, kw, table, None)
    _close(ragged_paged_attention_reference(*args), want)
    _close(ragged_paged_attention(*args, use_pallas=True, interpret=True,
                                  block_pages=2), want)
    # and a window wider than every context changes nothing
    _close(ragged_paged_attention(*args, use_pallas=True, interpret=True,
                                  block_pages=2, window=64), want)
