"""FlashMask pallas kernel vs dense reference (VERDICT r2 item 4).

The kernel path never materializes the (S, S) mask; these tests pin it
against the dense flashmask_reference in interpret mode, fwd + bwd,
across every supported (causal, n) mask flavor, ragged shapes, and the
block-skip edge cases (fully-masked rows/blocks)."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.flashmask_attention import (_live_ranges,
                                                derived_blocks,
                                                flashmask_attention_bhsd,
                                                flashmask_live_blocks,
                                                flashmask_reference)


def _qkv(b, h, s, d, seed=0, dtype=jnp.float32, d_v=None):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, h, s, d), dtype) * 0.3,
            jnp.asarray(rng.randn(b, h, s, d), dtype) * 0.3,
            jnp.asarray(rng.randn(b, h, s, d_v or d), dtype) * 0.3)


def _close(a, b, tol=2e-3):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) < tol, np.max(np.abs(a - b))


def _grads(fn, *args):
    loss = lambda *a: (fn(*a) * a[2]).sum()
    return jax.value_and_grad(loss, (0, 1, 2))(*args)


def _doc_sri(lens, s=None):
    """(1, 1, S, 1) causal n = 1 document mask: every key column masks
    the rows from its document's end on."""
    ends = np.repeat(np.cumsum(lens), lens)
    s = s or len(ends)
    return jnp.asarray(ends[:s, None][None, None], jnp.int32)


# packed documents the live walk has to get right: boundaries off every
# block edge, one document filling the sequence, runs of one-token
# documents, S not a multiple of the block, q blocks whose range is dead
_PACKED = {
    "off_edges": ([100, 57, 171, 56], 128),
    "one_document": ([384], 128),
    "one_token_runs": ([1] * 130 + [120, 1, 1, 1, 131], 128),
    "ragged_tail": ([90, 130, 100], 128),           # S = 320
    "derived_blocks": ([300, 1, 83], None),
    "rectangular_blocks": ([200, 3, 181], (256, 128)),
}


_MODES = ["causal_n1", "causal_n2", "noncausal_n2", "noncausal_n4"]


def _mode_sri(mode, s, seed=0):
    """(1, 2, S, n): head 0 a structured mask that kills whole blocks
    (documents of uneven length, bands), head 1 random indices."""
    rng = np.random.RandomState(seed)
    lens = []
    while sum(lens) < s:
        lens.append(int(rng.choice([1, 40, 130, 300, 700])))
    ends = np.repeat(np.cumsum(lens), lens)[:s]
    starts = ends - np.repeat(lens, lens)[:s]
    ends = np.minimum(ends, s)
    r = lambda lo, hi: rng.randint(lo, hi, s)
    if mode == "causal_n1":
        cols = [[ends], [r(1, s + 1)]]
    elif mode == "causal_n2":       # masked: start <= row < end
        cols = [[ends, np.minimum(ends + 300, s)],
                [r(0, s), np.minimum(r(0, s) + r(0, s // 2), s)]]
    elif mode == "noncausal_n2":    # masked: row >= start or row < end
        cols = [[ends, starts], [r(s // 2, s + 1), r(0, s // 2)]]
    else:                           # two masked bands
        cols = [[np.maximum(starts - 200, 0), starts, ends,
                 np.minimum(ends + 200, s)],
                [r(0, s // 4), r(s // 4, s // 2), r(s // 2, s),
                 np.full(s, s)]]
    sri = np.stack([np.stack(c, -1) for c in cols])[None]
    return jnp.asarray(sri, jnp.int32)


_DENSE = {}


def _dense_keep(mode, window, s):
    """The dense (2, S, S) keep mask by flashmask_reference's own rule:
    with zero scores its probabilities are uniform over the kept keys,
    and V = identity hands them out as the output."""
    key = (mode, window, s)
    if key not in _DENSE:
        sri = _mode_sri(mode, s)
        z = jnp.zeros((1, 2, s, s), jnp.float32)
        v = jnp.broadcast_to(jnp.eye(s, dtype=jnp.float32), (1, 2, s, s))
        o, _ = flashmask_reference(z, z, v, sri, mode.startswith("causal"),
                                   window)
        _DENSE[key] = (sri, np.asarray(o)[0] > 0)
    return _DENSE[key]


def _block_any(keep, block_q, block_k):
    """(bh, n_q, n_k): the block holds an unmasked pair."""
    bh, sq, sk = keep.shape
    n_q, n_k = -(-sq // block_q), -(-sk // block_k)
    pad = np.zeros((bh, n_q * block_q, n_k * block_k), bool)
    pad[:, :sq, :sk] = keep
    return pad.reshape(bh, n_q, block_q, n_k, block_k).any((2, 4))


class TestLiveRanges:
    @pytest.mark.parametrize("block", [128, 256, 512])
    @pytest.mark.parametrize("window", [None, (200, 64)])
    @pytest.mark.parametrize("mode", _MODES)
    def test_no_unmasked_pair_outside_the_range(self, mode, window, block):
        s = 1500                    # not a multiple of any block
        causal = mode.startswith("causal")
        sri, keep = _dense_keep(mode, window, s)
        srir = jnp.swapaxes(sri, -1, -2).reshape(2, -1, s)
        k_range, q_range = _live_ranges(srir, 2, causal, window, block,
                                        block, s, s)
        live = _block_any(keep, block, block)
        n = live.shape[1]
        idx = np.arange(n)
        for (first, last), lv in ((k_range, live),
                                  (q_range, live.swapaxes(1, 2))):
            first = np.asarray(first).reshape(2, n, 1)
            last = np.asarray(last).reshape(2, n, 1)
            inside = (idx >= first) & (idx <= last)
            assert not (lv & ~inside).any()
            # the envelope is tight: its ends are live blocks, and a
            # line with no live block has the empty range (0, -1)
            has = lv.any(-1)
            assert (np.take_along_axis(lv, first, -1)[..., 0] == has).all()
            assert (last[..., 0][~has] == -1).all()
            assert (first[..., 0][~has] == 0).all()
        if mode == "causal_n1" and window is None:
            # documents: the range is exact, and the public count is it
            inside = (idx >= np.asarray(k_range[0]).reshape(2, n, 1)) & \
                (idx <= np.asarray(k_range[1]).reshape(2, n, 1))
            assert (inside[0] == live[0]).all()
            # ... and so is what the kernels launch: a line's trips are
            # its live blocks, and no block of the (bh, n, n) rectangle
            # outside them costs a step
            got, grid = flashmask_live_blocks(sri, True, None, block, block)
            assert grid == got == int(inside.sum())
            assert grid < 2 * n * n

    @pytest.mark.parametrize("s,median", [(8192, 1400), (4096, 700)])
    def test_packed_documents_launch_their_live_blocks_only(self, s, median):
        """The training cells' masks (four sequences of lognormal
        documents, sixteen heads) at the 512 x 512 blocks they run at:
        the kernels launch the live blocks and none beside them, well
        under the rectangle the grid used to hold."""
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "flashmask_bench", os.path.join(os.path.dirname(__file__), "..",
                                            "tools", "flashmask_bench.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        ends = bench.doc_ends(4, s, median, seed=s)
        sri = jnp.broadcast_to(jnp.asarray(ends)[:, None, :, None],
                               (4, 16, s, 1))
        live, grid = flashmask_live_blocks(sri, True, None, 512, 512)
        whole = 64 * (s // 512) ** 2
        assert grid - live == 0
        assert 64 * (s // 512) <= grid < 0.5 * whole
        # a general mask's range is an envelope: the holes are counted
        cols = jnp.arange(s, dtype=jnp.int32)[None, None]
        band = jnp.stack([cols + 512, cols + 2048], -1)     # (1, 1, s, 2)
        live, grid = flashmask_live_blocks(band, True, None, 512, 512)
        n = s // 512
        assert grid - live > 0 and grid <= n * (n + 1) // 2

    def test_no_mask_is_the_causal_triangle(self):
        (first, last), (qf, ql) = _live_ranges(None, 3, True, None, 128,
                                               256, 512, 512)
        assert np.asarray(first).tolist() == [0] * 12
        assert np.asarray(last).tolist() == [0, 0, 1, 1] * 3
        assert np.asarray(qf).tolist() == [0, 2] * 3
        assert np.asarray(ql).tolist() == [3, 3] * 3

    @pytest.mark.parametrize("shape,want", [
        ((4096, 4096, 128, jnp.bfloat16), (512, 512)),   # pretrain_4k
        ((4096, 4096, 128, jnp.float32), (512, 512)),
        ((192, 192, 64, jnp.float32), (128, 128)),       # pads least
        ((64, 64, 64, jnp.float32), (128, 128)),         # then min(., S)
        ((300, 1024, 128, jnp.bfloat16), (128, 512)),
        ((8192, 8192, 256, jnp.float32), (512, 512)),
    ])
    def test_blocks_derived_from_the_shapes(self, shape, want):
        assert derived_blocks(*shape) == want

    def test_environment_and_arguments_still_override(self, monkeypatch):
        from paddle_tpu.ops.flashmask_attention import _blocks
        args = (4096, 4096, 128, jnp.bfloat16)
        assert _blocks(None, None, *args) == (512, 512)
        assert _blocks(256, None, *args) == (256, 512)
        monkeypatch.setenv("PT_FLASH_BLOCK_Q", "128")
        assert _blocks(None, None, *args) == (128, 512)
        monkeypatch.setenv("PT_FLASH_BLOCK_K", "256")
        assert _blocks(None, None, *args) == (128, 256)
        assert _blocks(512, 512, *args) == (512, 512)
        assert _blocks(None, None, 64, 64, 64, jnp.float32) == (64, 64)


class TestFlashMaskKernel:
    def _check(self, sri, causal, s=256, window=None, seed=0, b=2, h=2,
               d=64, block=128):
        """block: an int, a (block_q, block_k) pair, or None for the
        blocks the entry derives."""
        bq, bk = block if isinstance(block, tuple) else (block, block)
        q, k, v = _qkv(b, h, s, d, seed)
        o_ref, _ = flashmask_reference(q, k, v, sri, causal, window)
        o_ker = flashmask_attention_bhsd(
            q, k, v, sri, causal=causal, window=window, use_pallas=True,
            interpret=True, block_q=bq, block_k=bk)
        _close(o_ker, o_ref)
        # backward
        ref_fn = lambda q_, k_, v_: flashmask_reference(
            q_, k_, v_, sri, causal, window)[0]
        ker_fn = lambda q_, k_, v_: flashmask_attention_bhsd(
            q_, k_, v_, sri, causal=causal, window=window, use_pallas=True,
            interpret=True, block_q=bq, block_k=bk)
        _, g_ref = _grads(ref_fn, q, k, v)
        _, g_ker = _grads(ker_fn, q, k, v)
        for a, b_ in zip(g_ker, g_ref):
            _close(a, b_, tol=5e-3)

    def test_causal_n1_lt_start(self):
        """n=1: rows >= start_j masked (e.g. document-causal cutoff)."""
        s = 256
        rng = np.random.RandomState(1)
        sri = jnp.asarray(rng.randint(1, s + 1, (2, 2, s, 1)), jnp.int32)
        self._check(sri, causal=True, s=s)

    def test_causal_n2_band(self):
        s = 256
        rng = np.random.RandomState(2)
        start = rng.randint(0, s, (2, 2, s, 1))
        end = start + rng.randint(0, s // 2, (2, 2, s, 1))
        sri = jnp.asarray(np.concatenate([start, np.minimum(end, s)], -1),
                          jnp.int32)
        self._check(sri, causal=True, s=s)

    def test_noncausal_n2(self):
        s = 256
        rng = np.random.RandomState(3)
        start = rng.randint(s // 2, s + 1, (2, 2, s, 1))
        end = rng.randint(0, s // 2, (2, 2, s, 1))
        sri = jnp.asarray(np.concatenate([start, end], -1), jnp.int32)
        self._check(sri, causal=False, s=s)

    def test_noncausal_n4_two_bands(self):
        s = 256
        rng = np.random.RandomState(4)
        s0 = rng.randint(0, s // 4, (2, 2, s, 1))
        e0 = s0 + rng.randint(0, s // 4, (2, 2, s, 1))
        s1 = rng.randint(s // 2, s, (2, 2, s, 1))
        e1 = s1 + rng.randint(0, s // 4, (2, 2, s, 1))
        sri = jnp.asarray(np.concatenate(
            [s0, e0, s1, np.minimum(e1, s)], -1), jnp.int32)
        self._check(sri, causal=False, s=s)

    def test_sliding_window_no_sri(self):
        self._check(None, causal=True, s=256, window=(64, 0))

    def test_window_plus_sri(self):
        s = 256
        rng = np.random.RandomState(5)
        sri = jnp.asarray(rng.randint(1, s + 1, (2, 2, s, 1)), jnp.int32)
        self._check(sri, causal=True, s=s, window=(96, 0))

    def test_ragged_tail_blocks(self):
        """S not a multiple of the block: padding lanes must weaken, not
        falsify, the skip predicate."""
        s = 192  # 1.5 blocks of 128
        rng = np.random.RandomState(6)
        sri = jnp.asarray(rng.randint(1, s + 1, (1, 2, s, 1)), jnp.int32)
        self._check(sri, causal=True, s=s, b=1)

    def test_fully_masked_rows_zero(self):
        """Rows masked for every key must produce zeros (both paths)."""
        s = 128
        sri = jnp.full((1, 1, s, 1), 1, jnp.int32)  # mask all rows >= 1
        q, k, v = _qkv(1, 1, s, 64, seed=7)
        o_ker = flashmask_attention_bhsd(q, k, v, sri, causal=True,
                                         use_pallas=True, interpret=True)
        # row 0 attends to col 0 only; every other row fully masked -> 0
        assert np.allclose(np.asarray(o_ker)[0, 0, 1:], 0.0, atol=1e-6)
        o_ref, _ = flashmask_reference(q, k, v, sri, True, None)
        _close(o_ker, o_ref)

    def test_block_skip_equals_no_skip(self):
        """A mask that kills entire blocks (shared document boundary at
        a block edge) — the skip fast-path must not change results."""
        s = 512
        # every column masks rows >= 256: the bottom half of the matrix
        # is entirely masked -> whole k-blocks skipped for q-blocks >= 2
        sri = jnp.full((1, 2, s, 1), 256, jnp.int32)
        self._check(sri, causal=True, s=s, b=1)

    @pytest.mark.parametrize("case", sorted(_PACKED))
    def test_packed_documents(self, case):
        """Forward and all three gradients on packed documents: a
        line's loop walks its range and nothing else, and what it
        leaves out must be exactly what the mask kills."""
        lens, block = _PACKED[case]
        s = sum(lens)
        sri = jnp.broadcast_to(_doc_sri(lens), (1, 2, s, 1))
        self._check(sri, causal=True, s=s, b=1, block=block)

    @pytest.mark.parametrize("block", [128, (256, 128)])
    def test_dead_q_blocks_give_zeros_and_a_finite_lse(self, block):
        """Every key column masks the rows from 100 on: the q blocks
        past the first have an empty range (no step of theirs runs the
        body), and must still return zeros and a finite lse."""
        from paddle_tpu.ops.flashmask_attention import _fwd_pallas
        s = 512
        bq, bk = block if isinstance(block, tuple) else (block, block)
        sri = jnp.full((1, 2, s, 1), 100, jnp.int32)
        srir = jnp.swapaxes(sri, -1, -2).reshape(2, 1, s)
        (first, last), _ = _live_ranges(srir, 2, True, None, bq, bk, s, s)
        assert (np.asarray(last).reshape(2, -1)[:, 1:] == -1).all()
        q, k, v = _qkv(1, 2, s, 64, seed=21)
        o, lse = _fwd_pallas(q, k, v, sri, True, None, 0.125, bq, bk, True)
        assert np.all(np.asarray(o)[0, :, 100:] == 0.0)
        assert np.isfinite(np.asarray(lse)).all()
        self._check(sri, causal=True, s=s, b=1, block=block, seed=21)

    @pytest.mark.parametrize("block", [128, (256, 128)])
    def test_lines_a_band_hides_give_zeros_and_zero_gradients(self, block):
        """n = 2, causal: every key column masks the rows 128 .. 255 (a
        q block with an empty range: its loop makes no trip), and the
        columns 256 .. 383 mask every row (a k block with an empty
        range in dK/dV's grid). Zeros, a finite lse and zero gradients
        there; everywhere the dense reference's numbers."""
        from paddle_tpu.ops.flashmask_attention import _fwd_pallas
        s = 512
        bq, bk = block if isinstance(block, tuple) else (block, block)
        cols = np.arange(s)
        hidden = (cols >= 256) & (cols < 384)
        start = np.where(hidden, 0, 128)
        end = np.where(hidden, s, 256)
        sri = jnp.broadcast_to(jnp.asarray(
            np.stack([start, end], -1)[None, None], jnp.int32), (1, 2, s, 2))
        srir = jnp.swapaxes(sri, -1, -2).reshape(2, 2, s)
        k_range, q_range = _live_ranges(srir, 2, True, None, bq, bk, s, s)
        q_dead = np.asarray(k_range[1]).reshape(2, -1) < 0
        k_dead = np.asarray(q_range[1]).reshape(2, -1) < 0
        assert q_dead[:, 128 // bq].all() == (bq == 128)
        assert k_dead[:, 256 // bk].all()
        q, k, v = _qkv(1, 2, s, 64, seed=22)
        o, lse = _fwd_pallas(q, k, v, sri, True, None, 0.125, bq, bk, True)
        assert np.all(np.asarray(o)[0, :, 128:256] == 0.0)
        assert np.isfinite(np.asarray(lse)).all()
        ker_fn = lambda q_, k_, v_: flashmask_attention_bhsd(
            q_, k_, v_, sri, causal=True, use_pallas=True, interpret=True,
            block_q=bq, block_k=bk)
        dq, dk, dv = jax.grad(lambda *a: (ker_fn(*a) * q).sum(), (0, 1, 2))(
            k, q, v)                    # weights that are no operand
        assert np.all(np.asarray(dq)[0, :, 128:256] == 0.0)
        assert np.all(np.asarray(dk)[0, :, 256:384] == 0.0)
        assert np.all(np.asarray(dv)[0, :, 256:384] == 0.0)
        self._check(sri, causal=True, s=s, b=1, block=block, seed=22)

    @pytest.mark.parametrize("sq,sk,mode,window,block", [
        (300, 520, "causal_n1", (100, 0), 128),
        (520, 300, "noncausal_n2", (64, 32), (256, 128)),
        (333, 333, None, (50, 0), 128),
        (700, 450, "noncausal_n4", None, (128, 256)),
        (100, 100, "causal_n2", (30, 0), None),
    ])
    def test_rectangles_and_lengths_no_block_divides(self, sq, sk, mode,
                                                     window, block):
        """`sq != sk`, lengths off every block edge and a window: the
        sequences grow to whole blocks outside the kernels and the mask
        keeps the growth out. Forward and all three gradients."""
        bq, bk = block if isinstance(block, tuple) else (block, block)
        causal = mode is None or mode.startswith("causal")
        sri = None if mode is None else _mode_sri(mode, sk)
        rng = np.random.RandomState(sq)
        q = jnp.asarray(rng.randn(1, 2, sq, 64), jnp.float32) * 0.3
        k, v = (jnp.asarray(rng.randn(1, 2, sk, 64), jnp.float32) * 0.3
                for _ in range(2))
        w = jnp.asarray(rng.randn(1, 2, sq, 64), jnp.float32)
        ref_fn = lambda *a: flashmask_reference(*a, sri, causal, window)[0]
        ker_fn = lambda *a: flashmask_attention_bhsd(
            *a, sri, causal=causal, window=window, use_pallas=True,
            interpret=True, block_q=bq, block_k=bk)
        _close(ker_fn(q, k, v), ref_fn(q, k, v))
        loss = lambda fn: (lambda *a: (fn(*a) * w).sum())
        g_ref = jax.grad(loss(ref_fn), (0, 1, 2))(q, k, v)
        g_ker = jax.grad(loss(ker_fn), (0, 1, 2))(q, k, v)
        for a, b_, like in zip(g_ker, g_ref, (q, k, v)):
            assert a.shape == like.shape
            _close(a, b_, tol=5e-3)

    def test_bf16_gradients(self):
        """bfloat16 operands enter the MXU as they are and the computed
        tiles (p, ds) are cast to them; float32 accumulation. Against
        the float32 reference on the same (rounded) inputs."""
        lens = [100, 57, 99]
        s = sum(lens)
        sri = jnp.broadcast_to(_doc_sri(lens), (1, 2, s, 1))
        q, k, v = _qkv(1, 2, s, 64, seed=31, dtype=jnp.bfloat16)
        f32 = [t.astype(jnp.float32) for t in (q, k, v)]
        ref_fn = lambda q_, k_, v_: flashmask_reference(
            q_, k_, v_, sri, True, None)[0]
        ker_fn = lambda q_, k_, v_: flashmask_attention_bhsd(
            q_, k_, v_, sri, causal=True, use_pallas=True, interpret=True,
            block_q=128, block_k=128).astype(jnp.float32)
        _, g_ref = _grads(ref_fn, *f32)
        _, g_ker = _grads(ker_fn, q, k, v)
        for a, b_ in zip(g_ker, g_ref):
            assert a.dtype == jnp.bfloat16
            _close(a, b_, tol=3e-2)
            a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
            assert np.linalg.norm(a - b_) / np.linalg.norm(b_) < 1e-2

    def test_bf16(self):
        s = 256
        rng = np.random.RandomState(8)
        sri = jnp.asarray(rng.randint(1, s + 1, (2, 2, s, 1)), jnp.int32)
        q, k, v = _qkv(2, 2, s, 64, seed=8, dtype=jnp.bfloat16)
        o_ref, _ = flashmask_reference(q, k, v, sri, True, None)
        o_ker = flashmask_attention_bhsd(q, k, v, sri, causal=True,
                                         use_pallas=True, interpret=True)
        _close(o_ker, o_ref, tol=2e-2)

    def test_sparse_attention_under_jit(self):
        """CSR sparse_attention must trace under jit with a static
        max_nnz and match eager + dense-causal (regression: it used to
        host-compute gather indices from concrete offsets)."""
        import paddle_tpu.nn.functional as F
        from paddle_tpu.ops.flash_attention import mha_reference
        rng = np.random.RandomState(0)
        B, H, S, D = 1, 2, 16, 8
        q = rng.randn(B, H, S, D).astype(np.float32)
        off = np.zeros((B, H, S + 1), np.int32)
        cols = []
        for i in range(S):
            cols += list(range(i + 1))
            off[..., i + 1] = len(cols)
        col = np.tile(np.asarray(cols, np.int32), (B, H, 1))
        eager = np.asarray(F.sparse_attention(q, q, q, off, col).numpy())
        jitted = np.asarray(jax.jit(
            lambda a, o, c: F.sparse_attention(a, a, a, o, c,
                                               max_nnz=S))(q, off, col))
        assert np.allclose(eager, jitted, atol=1e-5)
        ref, _ = mha_reference(jnp.asarray(q), jnp.asarray(q),
                               jnp.asarray(q), None, True,
                               1.0 / math.sqrt(D))
        assert np.allclose(eager, np.asarray(ref), atol=1e-4)
        with pytest.raises(ValueError, match="max_nnz"):
            jax.jit(lambda a, o, c: F.sparse_attention(a, a, a, o, c))(
                q, off, col)

    def test_causal_scalar_window_off_tpu(self):
        """Regression: causal + int window_size through the public
        wrapper must not crash on the off-TPU reference path."""
        import paddle_tpu as pt
        import paddle_tpu.nn.functional as F
        rng = np.random.RandomState(10)
        q = pt.to_tensor(rng.randn(1, 128, 2, 64).astype(np.float32))
        out = F.flashmask_attention(q, q, q, causal=True, window_size=32)
        o = np.asarray(out.numpy())
        assert o.shape == (1, 128, 2, 64) and np.isfinite(o).all()

    def test_training_dropout_actually_drops(self):
        """dropout>0 + training must change the result (reference
        semantics: probabilities dropped), not silently no-op."""
        import paddle_tpu as pt
        import paddle_tpu.nn.functional as F
        rng = np.random.RandomState(11)
        s = 128
        q = pt.to_tensor(rng.randn(1, s, 2, 64).astype(np.float32) * 0.3)
        sri = pt.to_tensor(rng.randint(1, s + 1, (1, 2, s, 1))
                           .astype(np.int32))
        pt.seed(7)
        o_drop = np.asarray(F.flashmask_attention(
            q, q, q, startend_row_indices=sri, causal=True, dropout=0.5,
            training=True).numpy())
        o_plain = np.asarray(F.flashmask_attention(
            q, q, q, startend_row_indices=sri, causal=True).numpy())
        assert np.isfinite(o_drop).all()
        assert np.max(np.abs(o_drop - o_plain)) > 1e-3
        # eval mode ignores dropout
        o_eval = np.asarray(F.flashmask_attention(
            q, q, q, startend_row_indices=sri, causal=True, dropout=0.5,
            training=False).numpy())
        assert np.allclose(o_eval, o_plain, atol=2e-3)

    @pytest.mark.parametrize("s,block_q,block_k", [(256, 128, 128),
                                                   (512, 256, 256),
                                                   (512, 128, 256)])
    def test_dropout_kernel_matches_reference_same_seed(self, s, block_q,
                                                        block_k):
        """VERDICT r4 item 5: in-kernel counter-based dropout. The
        dense reference regenerates the identical mask from
        (seed, coords), so kernel fwd AND grads must match it exactly
        (not just statistically) — including through the hand-seeded
        backward kernels that re-derive the mask. The mask is keyed by
        absolute coordinates, so no block size may change it."""
        seed = 12345
        rng = np.random.RandomState(3)
        q, k, v = _qkv(2, 2, s, 64, seed=3)
        sri = jnp.asarray(rng.randint(1, s + 1, (2, 2, s, 1)), jnp.int32)
        for rate in (0.1, 0.5):
            ref_fn = lambda q_, k_, v_: flashmask_reference(
                q_, k_, v_, sri, True, None, dropout=rate,
                dropout_seed=seed)[0]
            ker_fn = lambda q_, k_, v_: flashmask_attention_bhsd(
                q_, k_, v_, sri, causal=True, use_pallas=True,
                interpret=True, block_q=block_q, block_k=block_k,
                dropout=rate, dropout_seed=seed)
            _close(ker_fn(q, k, v), ref_fn(q, k, v))
            _, g_ref = _grads(ref_fn, q, k, v)
            _, g_ker = _grads(ker_fn, q, k, v)
            for a, b_ in zip(g_ker, g_ref):
                _close(a, b_, tol=5e-3)

    def test_dropout_rate_statistics_8k(self):
        """The hash mask's empirical drop rate over an 8k x 2k grid
        must sit within 1% of the requested rate, and differ by seed."""
        from paddle_tpu.ops.flashmask_attention import dropout_keep_mask
        rows = jnp.arange(8192)[:, None]
        cols = jnp.arange(2048)[None, :]
        for rate in (0.1, 0.5, 0.9):
            keep = np.asarray(dropout_keep_mask(rows, cols, 0, 42, rate))
            got = 1.0 - keep.mean()
            assert abs(got - rate) < 0.01, (rate, got)
        a = np.asarray(dropout_keep_mask(rows, cols, 0, 1, 0.5))
        b = np.asarray(dropout_keep_mask(rows, cols, 0, 2, 0.5))
        assert 0.4 < (a ^ b).mean() < 0.6  # independent-ish by seed
        c = np.asarray(dropout_keep_mask(rows, cols, 1, 1, 0.5))
        assert 0.4 < (a ^ c).mean() < 0.6  # and by batch*head

    def test_dropout_lse_and_masking_invariants(self):
        """lse excludes dropout (probabilities are dropped AFTER
        normalization), and dropout never un-masks masked pairs —
        fully-masked rows stay exactly zero."""
        from paddle_tpu.ops.flashmask_attention import _fwd_pallas
        s = 256
        rng = np.random.RandomState(5)
        q, k, v = _qkv(1, 2, s, 64, seed=5)
        # rows in [64, 128) fully masked: every column start <= 64
        sri = jnp.asarray(np.where(np.arange(s)[None, None, :, None] < 999,
                                   64, 64).astype(np.int32))
        sri = jnp.broadcast_to(sri, (1, 2, s, 1))
        o0, lse0 = _fwd_pallas(q, k, v, sri, True, None, 0.125, 128, 128,
                               True)
        od, lsed = _fwd_pallas(q, k, v, sri, True, None, 0.125, 128, 128,
                               True, dropout=0.5, seed=jnp.asarray([9]))
        assert np.allclose(np.asarray(lse0), np.asarray(lsed), atol=1e-5)
        # rows >= 64 attend nowhere (start=64 masks r >= 64 for all
        # cols, causal triangle masks the rest): zero with or without
        # dropout
        assert np.allclose(np.asarray(od)[0, :, 65:], 0.0)
        assert np.allclose(np.asarray(o0)[0, :, 65:], 0.0)

    @pytest.mark.slow
    def test_dropout_8k_in_kernel(self):
        """S=8k packed-doc config with dropout through the kernel path —
        no (S, S) materialization on any flashmask config (the dense
        fallback is gone). Spot rows checked against an O(S)-per-row
        reference applying the SAME hash mask."""
        from paddle_tpu.ops.flashmask_attention import dropout_keep_mask
        s, d, rate, seed = 8192, 64, 0.2, 77
        q, k, v = _qkv(1, 1, s, d, seed=13)
        doc = np.arange(s) // 1024
        sri = jnp.asarray(((doc + 1) * 1024)[None, None, :, None],
                          jnp.int32)
        o = flashmask_attention_bhsd(q, k, v, sri, causal=True,
                                     use_pallas=True, interpret=True,
                                     block_q=512, block_k=512,
                                     dropout=rate, dropout_seed=seed)
        o = np.asarray(o)
        assert np.isfinite(o).all()
        qn, kn, vn = (np.asarray(t, np.float32) for t in (q, k, v))
        for r in (0, 1024, 5000, 8191):
            lo = (r // 1024) * 1024
            cols = np.arange(lo, r + 1)
            sc = qn[0, 0, r] @ kn[0, 0, cols].T / math.sqrt(d)
            p = np.exp(sc - sc.max())
            p /= p.sum()
            keep = np.asarray(dropout_keep_mask(
                jnp.asarray([r])[:, None], jnp.asarray(cols)[None, :],
                0, seed, rate))[0]
            p = np.where(keep, p / (1 - rate), 0.0)
            exp = p @ vn[0, 0, cols]
            assert np.allclose(o[0, 0, r], exp, atol=2e-3), r

    @pytest.mark.slow
    def test_long_context_8k_no_dense_mask(self):
        """VERDICT 'Done' bar: S=8k through the kernel path (O(S·block)
        memory — a dense f32 mask would be 256 MB/head). Spot-checks a
        handful of rows against an O(S)-per-row reference."""
        s, d = 8192, 64
        rng = np.random.RandomState(9)
        q, k, v = _qkv(1, 1, s, d, seed=9)
        # document-mask: tokens attend only within their 1k-doc —
        # each key column masks every row >= its doc's end boundary
        doc = np.arange(s) // 1024
        sri = jnp.asarray(((doc + 1) * 1024)[None, None, :, None],
                          jnp.int32)
        o = flashmask_attention_bhsd(q, k, v, sri, causal=True,
                                     use_pallas=True, interpret=True,
                                     block_q=512, block_k=512)
        o = np.asarray(o)
        assert np.isfinite(o).all()
        qn = np.asarray(q, np.float32)
        kn = np.asarray(k, np.float32)
        vn = np.asarray(v, np.float32)
        for r in (0, 700, 1024, 5000, 8191):
            lo = (r // 1024) * 1024
            cols = np.arange(lo, r + 1)  # in-doc causal window
            sc = qn[0, 0, r] @ kn[0, 0, cols].T / math.sqrt(d)
            p = np.exp(sc - sc.max())
            p /= p.sum()
            exp = p @ vn[0, 0, cols]
            assert np.allclose(o[0, 0, r], exp, atol=2e-3), r


# ---------------------------------------------------------------------------
# A value width of its own (latent attention up-projected: keys 192, values
# 128): v, o, dO and dV at the values' width, q, k, dQ and dK at the keys'
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("d,d_v,lens,blocks", [
    (24, 16, [100, 57, 171, 56], (128, 128)),
    (24, 16, [90, 130, 100], (128, 128)),           # S = 320: a ragged tail
    (16, 24, [200, 3, 181], (256, 128)),            # values the wider
    (192, 128, [150, 106], (128, 128)),             # the published widths
])
def test_value_width_differs_from_key_width(d, d_v, lens, blocks):
    """Forward and all three gradients against the dense reference,
    causal with document ends; the scale is the keys' 1/sqrt(d)."""
    s = sum(lens)
    q, k, v = _qkv(1, 2, s, d, seed=d, d_v=d_v)
    sri = jnp.broadcast_to(_doc_sri(lens), (1, 2, s, 1))
    w = jnp.asarray(np.random.RandomState(1).randn(1, 2, s, d_v), jnp.float32)

    def kernel(q, k, v):
        return flashmask_attention_bhsd(
            q, k, v, sri, causal=True, block_q=blocks[0], block_k=blocks[1],
            use_pallas=True, interpret=True)

    def dense(q, k, v):
        return flashmask_reference(q, k, v, sri, causal=True,
                                   sm_scale=1.0 / math.sqrt(d))[0]
    loss = lambda fn: (lambda *a: (fn(*a) * w).sum())
    got, g_got = jax.value_and_grad(loss(kernel), (0, 1, 2))(q, k, v)
    want, g_want = jax.value_and_grad(loss(dense), (0, 1, 2))(q, k, v)
    assert kernel(q, k, v).shape == (1, 2, s, d_v)
    _close(kernel(q, k, v), dense(q, k, v))
    _close(got, want, tol=2e-2)
    for a, b, like in zip(g_got, g_want, (q, k, v)):
        assert a.shape == like.shape
        _close(a, b)


def test_equal_widths_derive_the_blocks_they_did():
    """`d_v` equal to `d` (or not given) changes nothing the dense
    decoder's cell runs at; 192 / 128 still fits 512 x 512."""
    assert derived_blocks(4096, 4096, 128, jnp.bfloat16) == \
        derived_blocks(4096, 4096, 128, jnp.bfloat16, d_v=128) == (512, 512)
    assert derived_blocks(8192, 8192, 192, jnp.bfloat16, d_v=128) == (512, 512)
