"""Unified ragged step (ISSUE 11): ONE jitted `unified_step` serves an
arbitrary mix of prefill chunks, suffix prefills, spec-verify grids and
decodes from a flat token buffer. Acceptance asserted here:

  * the pallas ragged-paged-attention kernel (TPU interpret mode) agrees
    with the pure-jnp reference on CPU by tolerance (1e-5 on float32
    inputs: the kernel sums in blocks of pages, the reference page by
    page), fp32 and int8, over every mix of runs the engine lays down
    and over buffers that break its layout;
  * ragged engines are token-identical to the bucketed entry points
    across every mode (plain / int8 / prefix / tier / spec / chunked /
    preemption), under both the sync and the pipelined pump;
  * changing the prefill/decode mix between waves triggers ZERO
    retraces of `serving.unified_step`;
  * pad-waste telemetry: a ragged run books no `pt_pad_tokens` and a
    growing `pt_ragged_tokens`; the bucketed run pads;
  * a PT_FAULTS `step_launch` crash mid-run warm-restarts, requeues,
    and still yields token-identical outputs through the scheduler.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels import (ragged_paged_attention,
                                ragged_paged_attention_reference)
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama_serving import Request, ServingEngine
from paddle_tpu.serving.metrics import MetricsRegistry
from paddle_tpu.serving.scheduler import RequestScheduler

CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Kernel vs reference: by tolerance on CPU (TPU interpret mode)
# ---------------------------------------------------------------------------
def _close(ker, ref, rel=1e-5):
    """Within `rel` of the reference's largest value (float32 inputs:
    only the order of summation differs)."""
    ker, ref = np.asarray(ker, np.float32), np.asarray(ref, np.float32)
    assert ker.shape == ref.shape
    err = np.abs(ker - ref).max()
    assert err <= rel * max(np.abs(ref).max(), 1.0), f"max |delta| = {err}"


class TestKernelBitEquivalence:
    PAGE = 8
    KVH = 2
    QH = 4
    D = 16
    PAGES_PER_SEQ = 4
    NUM_PAGES = 12
    SLOTS = 3

    def _problem(self, seed=0, quant=False):
        rng = np.random.default_rng(seed)
        q = rng.standard_normal((10, self.QH, self.D)).astype(np.float32)
        shape = (self.KVH, self.NUM_PAGES, self.PAGE, self.D)
        if quant:
            k_pages = rng.integers(-127, 128, shape).astype(np.int8)
            v_pages = rng.integers(-127, 128, shape).astype(np.int8)
            ks = rng.uniform(0.01, 0.1, shape[:3] + (1,)).astype(np.float32)
            vs = rng.uniform(0.01, 0.1, shape[:3] + (1,)).astype(np.float32)
        else:
            k_pages = rng.standard_normal(shape).astype(np.float32)
            v_pages = rng.standard_normal(shape).astype(np.float32)
            ks = vs = None
        ptab = rng.permutation(self.NUM_PAGES)[
            :self.SLOTS * self.PAGES_PER_SEQ].reshape(
            self.SLOTS, self.PAGES_PER_SEQ).astype(np.int32)
        # the mix: a 5-token prefill run on slot 0, two decodes, and
        # three inactive slack rows (pos -1) — one wave, one call
        tok_slot = np.array([0, 0, 0, 0, 0, 1, 2, 0, 0, 0], np.int32)
        tok_pos = np.array([0, 1, 2, 3, 4, 15, 9, -1, -1, -1], np.int32)
        return (jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
                jnp.asarray(ptab), jnp.asarray(tok_slot),
                jnp.asarray(tok_pos), ks, vs)

    @pytest.mark.parametrize("quant", [False, True],
                             ids=["fp32", "int8"])
    def test_pallas_interpret_bit_identical(self, quant):
        q, k, v, ptab, slot, pos, ks, vs = self._problem(quant=quant)
        kw = {}
        if quant:
            kw = {"k_scale": jnp.asarray(ks), "v_scale": jnp.asarray(vs)}
        ref = ragged_paged_attention(q, k, v, ptab, slot, pos,
                                     use_pallas=False, **kw)
        ker = ragged_paged_attention(q, k, v, ptab, slot, pos,
                                     use_pallas=True, interpret=True, **kw)
        ref = np.asarray(ref)
        ker = np.asarray(ker)
        assert ref.shape == ker.shape == (10, self.QH, self.D)
        # by tolerance: the kernel sums a KV block of pages at a time,
        # the reference a page at a time (bit identity went with the
        # grid kernel, ISSUE 26)
        _close(ker, ref)
        # inactive slack rows (pos -1) produce exact zeros, both ways
        assert ref[:7].any() and ker[:7].any()
        assert np.array_equal(ref[7:], np.zeros_like(ref[7:]))
        assert np.array_equal(ker[7:], np.zeros_like(ker[7:]))

    def test_reference_entry_point_is_the_dispatch_target(self):
        """CPU default (use_pallas unset, no TPU) must route to the
        reference — tier-1 never imports a TPU-only path."""
        q, k, v, ptab, slot, pos, _, _ = self._problem()
        via_dispatch = ragged_paged_attention(q, k, v, ptab, slot, pos)
        direct = ragged_paged_attention_reference(q, k, v, ptab, slot, pos)
        assert np.array_equal(np.asarray(via_dispatch), np.asarray(direct))

    def test_causality_prefill_rows_ignore_future(self):
        """Row at pos p must see exactly columns <= p: rerunning with
        later-position KV overwritten cannot change earlier rows."""
        q, k, v, ptab, slot, pos, _, _ = self._problem()
        base = np.asarray(ragged_paged_attention(q, k, v, ptab, slot, pos))
        k2 = np.asarray(k).copy()
        v2 = np.asarray(v).copy()
        # clobber slot 0's column 4 (page ord 0, offset 4): only the
        # prefill row AT pos 4 may change, rows 0..3 must not
        pg = int(np.asarray(ptab)[0, 0])
        k2[:, pg, 4] = 99.0
        v2[:, pg, 4] = -99.0
        out = np.asarray(ragged_paged_attention(
            q, jnp.asarray(k2), jnp.asarray(v2), ptab, slot, pos))
        assert np.array_equal(base[:4], out[:4])
        assert not np.array_equal(base[4], out[4])


# ---------------------------------------------------------------------------
# Tunable kernel tiling (ISSUE 12): a tile choice never changes a bit
# ---------------------------------------------------------------------------
class TestKernelTiling:
    """`block_q` x `block_pages` (q rows a block x pages a KV block)
    is a STATIC tuning knob: every legal tile agrees with the
    reference by the same tolerance, fp32 and int8 — a tile changes
    the order of summation and nothing else, so the autotuner
    (tools/tune_ragged.py) may pick any of them."""
    # the test problem's GQA group is 2 (4q/2kv), so q rows come in
    # fours; (8, 2) cuts the 10-row buffer into two q blocks;
    # PAGES_PER_SEQ=4 bounds block_pages
    TILES = [(8, 2), (16, 1), (16, 4)]

    @pytest.mark.parametrize("quant", [False, True], ids=["fp32", "int8"])
    def test_every_legal_tile_bit_identical(self, quant):
        prob = TestKernelBitEquivalence()
        q, k, v, ptab, slot, pos, ks, vs = prob._problem(quant=quant)
        kw = {}
        if quant:
            kw = {"k_scale": jnp.asarray(ks), "v_scale": jnp.asarray(vs)}
        ref = np.asarray(ragged_paged_attention(
            q, k, v, ptab, slot, pos, use_pallas=False, **kw))
        for bq, bp in self.TILES:
            out = np.asarray(ragged_paged_attention(
                q, k, v, ptab, slot, pos, use_pallas=True, interpret=True,
                block_q=bq, block_pages=bp, **kw))
            _close(out, ref)
            assert not out[7:].any(), \
                f"tile (block_q={bq}, block_pages={bp}): slack rows"

    def test_reference_honors_block_q_too(self):
        """use_pallas=False with a tuned block_q: the tile is the
        kernel's alone, so a CPU engine constructed on a tile file
        computes the reference's own bits."""
        prob = TestKernelBitEquivalence()
        q, k, v, ptab, slot, pos, _, _ = prob._problem()
        base = np.asarray(ragged_paged_attention(
            q, k, v, ptab, slot, pos, use_pallas=False))
        out = np.asarray(ragged_paged_attention(
            q, k, v, ptab, slot, pos, use_pallas=False, block_q=16))
        assert np.array_equal(base, out)

    def test_illegal_tiles_rejected_loudly(self):
        prob = TestKernelBitEquivalence()
        q, k, v, ptab, slot, pos, _, _ = prob._problem()
        with pytest.raises(ValueError, match="block_q"):
            ragged_paged_attention(q, k, v, ptab, slot, pos,
                                   use_pallas=True, interpret=True,
                                   block_q=6)   # 6 rows x group 2: not
                                                # whole sublane tiles
        with pytest.raises(ValueError, match="block_pages"):
            ragged_paged_attention(q, k, v, ptab, slot, pos,
                                   use_pallas=True, interpret=True,
                                   block_pages=-1)


# ---------------------------------------------------------------------------
# Every mix of runs the engine lays down, and buffers that break its
# layout (ISSUE 26): one program per run, KV blocks from the lengths
# ---------------------------------------------------------------------------
def _rows(*runs):
    """[(slot, first position, rows)] -> flat (slot, pos) lists."""
    slots, poss = [], []
    for slot, first, n in runs:
        slots += [slot] * n
        poss += list(range(first, first + n))
    return slots, poss


class TestKernelMixes:
    PAGE, KVH, QH, D = 8, 2, 4, 16
    PAGES_PER_SEQ, SLOTS, T = 8, 4, 24          # 64 tokens a sequence
    # a KV block is 2 pages = 16 tokens; a q block 8 rows (3 a buffer)
    TILE = dict(block_q=8, block_pages=2)
    MIXES = {
        # one row a slot, contexts from one token to a full sequence
        "decode_only": _rows((0, 40, 1), (1, 0, 1), (2, 63, 1), (3, 17, 1)),
        # one prompt's first chunk, longer than two q blocks
        "long_prefill": _rows((1, 0, 21)),
        # _ragged_plan's wave: decode rows first, then the chunks
        "mixed_wave": _rows((0, 33, 1), (2, 8, 1), (1, 5, 9), (3, 0, 6)),
        # prefix-cache suffix tail: KV length 3 pages more than its rows
        "suffix_tail": _rows((2, 24, 7), (0, 12, 1)),
        # spec-verify grids of G=4 on three slots
        "verify_grid_g4": _rows((0, 20, 4), (1, 7, 4), (3, 44, 4)),
        # a run that crosses two q-block edges beside a decode row
        "run_over_q_blocks": _rows((3, 2, 1), (0, 10, 19)),
        # KV lengths at a KV block's edge (32), one under, one over
        "kv_block_edge": _rows((0, 31, 1), (1, 30, 1), (2, 32, 1),
                               (3, 28, 4)),
        # broken layout: slot 1's rows split in two places, and one
        # slot's positions not consecutive — shorter runs, same answer
        "split_slot": (
            [1, 1, 0, 1, 1, 1, 2, 1, 3, 3],
            [4, 5, 9, 6, 7, 8, 3, 9, 20, 22]),
        # an empty buffer: every row inactive
        "empty": ([], []),
    }

    def _problem(self, mix, dtype, quant=False, seed=0):
        rng = np.random.default_rng(seed)
        slots, poss = self.MIXES[mix]
        n = len(slots)
        num_pages = self.SLOTS * self.PAGES_PER_SEQ + 1
        shape = (self.KVH, num_pages, self.PAGE, self.D)
        q = jnp.asarray(rng.standard_normal((self.T, self.QH, self.D)),
                        dtype)
        kw = {}
        if quant:
            k = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
            v = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
            for name in ("k_scale", "v_scale"):
                kw[name] = jnp.asarray(rng.uniform(
                    0.01, 0.1, shape[:3] + (1,)), jnp.float32)
        else:
            k = jnp.asarray(rng.standard_normal(shape), dtype)
            v = jnp.asarray(rng.standard_normal(shape), dtype)
        ptab = jnp.asarray(rng.permutation(num_pages - 1).reshape(
            self.SLOTS, self.PAGES_PER_SEQ), jnp.int32)
        slot = jnp.asarray(slots + [0] * (self.T - n), jnp.int32)
        pos = jnp.asarray(poss + [-1] * (self.T - n), jnp.int32)
        return n, (q, k, v, ptab, slot, pos), kw

    @pytest.mark.parametrize("mix", sorted(MIXES))
    def test_mix_matches_reference(self, mix):
        n, args, kw = self._problem(mix, jnp.float32)
        ref = ragged_paged_attention_reference(*args)
        ker = ragged_paged_attention(*args, use_pallas=True, interpret=True,
                                     **self.TILE)
        _close(ker, ref)
        assert np.asarray(ker)[:n].any() == (n > 0)
        assert not np.asarray(ker)[n:].any()

    @pytest.mark.parametrize("mix", ["mixed_wave", "kv_block_edge"])
    def test_mix_matches_reference_int8(self, mix):
        n, args, kw = self._problem(mix, jnp.float32, quant=True)
        ref = ragged_paged_attention_reference(*args, **kw)
        ker = ragged_paged_attention(*args, use_pallas=True, interpret=True,
                                     **self.TILE, **kw)
        _close(ker, ref)
        assert not np.asarray(ker)[n:].any()

    @pytest.mark.parametrize("mix", ["mixed_wave", "run_over_q_blocks"])
    def test_bfloat16_pools(self, mix):
        """bf16 q and pools, as on the chip: QK^T on the stored
        operands, float32 sums; one bf16 ulp of the output is 2^-8."""
        n, args, kw = self._problem(mix, jnp.bfloat16)
        ref = ragged_paged_attention_reference(*args)
        ker = ragged_paged_attention(*args, use_pallas=True, interpret=True,
                                     **self.TILE)
        assert ker.dtype == jnp.bfloat16
        _close(ker, ref, rel=2.0 ** -7)
        assert not np.asarray(ker, np.float32)[n:].any()

    def test_derived_tile_and_given_runs(self):
        """The derived tile (one q block here), and `runs=` handed in by
        a caller that derived them once (the layer scan)."""
        from paddle_tpu.kernels import ragged_runs
        n, args, kw = self._problem("mixed_wave", jnp.float32)
        ref = ragged_paged_attention_reference(*args)
        runs = ragged_runs(args[4], args[5], self.QH // self.KVH)
        assert runs[1].shape == (2,)            # one q block: [0, n_runs]
        assert int(runs[1][1]) == 4
        ker = ragged_paged_attention(*args, use_pallas=True, interpret=True,
                                     runs=runs)
        _close(ker, ref)


def _runs_numpy(slot, pos, block_q):
    """The run derivation as a loop: [(first, rows, slot, kv_len)] and
    the first run of each q block."""
    runs, i, t = [], 0, len(pos)
    while i < t:
        if pos[i] < 0:
            i += 1
            continue
        j = i + 1
        while (j < t and j % block_q and pos[j] >= 0
               and slot[j] == slot[i] and pos[j] == pos[j - 1] + 1):
            j += 1
        runs.append((i, j - i, int(slot[i]), int(pos[j - 1]) + 1))
        i = j
    edges = [e * block_q for e in range(-(-t // block_q) + 1)]
    return runs, [sum(r[0] < e for r in runs) for e in edges]


def test_run_derivation_matches_a_numpy_loop():
    """`ragged_runs` (a few integer ops inside the jitted step) against
    the loop, over the engine's own layout and over buffers that break
    it: random slots, gaps, repeated and descending positions."""
    from paddle_tpu.kernels import ragged_runs
    rng = np.random.default_rng(7)
    t, group = 32, 2
    for trial in range(40):
        legal = trial % 2 == 0
        if legal:      # decode rows, then chunks, then slack
            order = rng.permutation(8)
            n_dec = int(rng.integers(0, 5))
            slots = list(order[:n_dec])
            poss = list(rng.integers(0, 200, n_dec))
            for s in order[n_dec:n_dec + int(rng.integers(0, 4))]:
                n = int(rng.integers(1, 12))
                if len(slots) + n > t:
                    break
                first = int(rng.integers(0, 100))
                slots += [s] * n
                poss += list(range(first, first + n))
            pad = t - len(slots)
            slot = np.array(slots + [0] * pad, np.int32)
            pos = np.array(poss + [-1] * pad, np.int32)
        else:
            slot = rng.integers(0, 3, t).astype(np.int32)
            pos = rng.integers(-1, 4, t).astype(np.int32)
        for block_q in (4, 12, 32):
            want, want_first = _runs_numpy(slot, pos, block_q)
            runs, qb_first = ragged_runs(jnp.asarray(slot), jnp.asarray(pos),
                                         group, block_q)
            runs, qb_first = np.asarray(runs), np.asarray(qb_first)
            assert runs.dtype == qb_first.dtype == np.int32
            assert qb_first.tolist() == want_first
            got = [tuple(int(x) for x in runs[:, r])
                   for r in range(qb_first[-1])]
            assert got == want, (trial, block_q)
            # every live row lies in exactly one run
            covered = np.zeros(t, int)
            for first, n, _, _ in got:
                covered[first:first + n] += 1
            assert np.array_equal(covered, (pos >= 0).astype(int))


# ---------------------------------------------------------------------------
# The stack written and read where it lies (ISSUE 31, [donate-pools])
# ---------------------------------------------------------------------------
def _random_stacks(rng, shape, quant):
    """(k, v, k_scale, v_scale) stacks of `shape` = (layers, kv heads,
    pages, page, head_dim): bfloat16 and no scales, or int8 with a
    float32 scale a row."""
    if quant:
        return tuple(
            [jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
             for _ in "kv"] +
            [jnp.asarray(rng.uniform(0.01, 0.1, shape[:4] + (1,)),
                         jnp.float32) for _ in "kv"])
    return tuple([jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                  for _ in "kv"] + [None, None])


@pytest.mark.parametrize("cache", ["bf16", "int8"])
def test_stacked_scatter_and_layer_indexed_read_give_the_per_layer_bits(
        cache):
    """`unified_step`'s scan writes a layer's rows flat into the carried
    5-D stack and hands the kernel the stack and a layer index. Both are
    the per-layer path's bits: the stack after `_scatter_kv_stacked` is
    the stack after `_scatter_kv`'s three-index form, the trash page's
    rows and the int8 scales included, and attention through `layer=`
    (the interpreted kernel and the reference) is attention over the
    layer sliced out."""
    from paddle_tpu.models.llama_serving import (_scatter_kv,
                                                 _scatter_kv_stacked)
    L, kvh, qh, pages, page, d, t = 3, 2, 4, 12, 8, 16, 10
    quant = cache == "int8"
    rng = np.random.default_rng(31)
    kp, vp, ksp, vsp = _random_stacks(rng, (L, kvh, pages, page, d), quant)
    ptab = jnp.asarray(rng.permutation(pages - 1)[:8].reshape(2, 4),
                       jnp.int32)
    # a 5-row prefill chunk, two decode rows, three slack rows that all
    # land on the trash page's first row
    slot = jnp.asarray([0, 0, 0, 0, 0, 1, 0, 0, 0, 0], jnp.int32)
    pos = jnp.asarray([9, 10, 11, 12, 13, 21, 14, -1, -1, -1], jnp.int32)
    on = pos >= 0
    page_ids = jnp.where(on, ptab[slot, jnp.maximum(pos, 0) // page],
                         pages - 1)
    off = jnp.maximum(pos, 0) % page
    q = jnp.asarray(rng.standard_normal((t, qh, d)), jnp.bfloat16)
    stacked, each = (kp, vp, ksp, vsp), (kp, vp, ksp, vsp)
    for li in range(L):
        kt = jnp.asarray(rng.standard_normal((kvh, t, d)), jnp.bfloat16)
        vt = jnp.asarray(rng.standard_normal((kvh, t, d)), jnp.bfloat16)
        stacked = _scatter_kv_stacked(*stacked, jnp.int32(li), page_ids,
                                      off, kt, vt, quant)
        *each, kl, vl, ksl, vsl = _scatter_kv(*each, li, page_ids, off, kt,
                                              vt, quant)
        for a, b in zip(stacked, each):
            assert (a is None and b is None) or np.array_equal(
                np.asarray(a), np.asarray(b))
        for kernel in (dict(use_pallas=False),
                       dict(use_pallas=True, interpret=True)):
            kw = dict(kernel, block_pages=2)
            by_index = ragged_paged_attention(
                q, stacked[0], stacked[1], ptab, slot, pos,
                k_scale=stacked[2], v_scale=stacked[3],
                layer=jnp.int32(li), **kw)
            sliced = ragged_paged_attention(
                q, kl, vl, ptab, slot, pos, k_scale=ksl, v_scale=vsl, **kw)
            assert np.asarray(by_index[:7]).any()
            assert np.array_equal(np.asarray(by_index), np.asarray(sliced))


# ---------------------------------------------------------------------------
# The q, k and v products end where the step says (ISSUE 51,
# [weight-slices]): same values, same weights, no second copy
# ---------------------------------------------------------------------------
def _plain_step(params, kp, vp, ksp, vsp, ptab, tokens, slot, pos, page,
                need, quant):
    """`unified_step`'s arithmetic written out a layer at a time: three
    plain products reshaped to heads as they come, the per-layer scatter,
    the attention reference over the layer sliced out."""
    import jax
    from paddle_tpu.models.llama_serving import (_rms, _scatter_kv,
                                                 apply_rotary_emb,
                                                 rope_cos_sin)
    c = CFG
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    hd, t = c.hidden_size // nh, tokens.shape[0]
    on, at = pos >= 0, jnp.maximum(pos, 0)
    cos, sin = rope_cos_sin(None, hd, base=c.rope_theta, position_ids=at)
    page_ids = jnp.where(on, ptab[slot, at // page], kp.shape[2] - 1)
    h = params["embed"][tokens]
    for li in range(c.num_hidden_layers):
        lp = {name: w[li] for name, w in params["layers"].items()}
        x = _rms(h, lp["ln1"], c.rms_norm_eps)
        q = (x @ lp["wq"]).reshape(t, nh, hd)
        k = (x @ lp["wk"]).reshape(t, nkv, hd)
        v = (x @ lp["wv"]).reshape(t, nkv, hd)
        q, k = apply_rotary_emb(q, k, cos[:, None], sin[:, None])
        kp, vp, ksp, vsp, kl, vl, ksl, vsl = _scatter_kv(
            kp, vp, ksp, vsp, li, page_ids, at % page, k.swapaxes(0, 1),
            v.swapaxes(0, 1), quant)
        o = ragged_paged_attention(q, kl, vl, ptab, slot, pos,
                                   use_pallas=False, k_scale=ksl,
                                   v_scale=vsl)
        h = h + o.reshape(t, -1).astype(h.dtype) @ lp["wo"]
        x = _rms(h, lp["ln2"], c.rms_norm_eps)
        h = h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) \
            @ lp["w_down"]
    h = _rms(h, params["final_norm"], c.rms_norm_eps)[need]
    return kp, vp, ksp, vsp, h @ params["lm_head"]


@pytest.mark.parametrize("cache", ["bf16", "int8"])
def test_unified_step_gives_what_three_plain_products_give(cache):
    """The barrier behind a layer's q, k and v products moves no value:
    over bfloat16 weights `unified_step` gives the logits, the greedy
    tokens and the pools of the same layers written out with `x @ wq`,
    `x @ wk`, `x @ wv` reshaped as they come (a prefill chunk, two decode
    rows and slack rows in one wave, both cache types)."""
    from paddle_tpu.models.llama_serving import unified_step
    L, kvh, hd = CFG.num_hidden_layers, CFG.num_key_value_heads, \
        CFG.hidden_size // CFG.num_attention_heads
    pages, page, slots = 12, 8, 3
    quant = cache == "int8"
    weights = M.init_params(CFG, seed=51, dtype=jnp.bfloat16)
    rng = np.random.default_rng(51)

    def pools():
        # made anew for each side: the step donates what it is given
        return _random_stacks(np.random.default_rng(5151),
                              (L, kvh, pages, page, hd), quant)
    ptab = jnp.asarray(rng.permutation(pages - 1)[:9].reshape(slots, 3),
                       jnp.int32)
    tokens = jnp.asarray(rng.integers(1, CFG.vocab_size, 10), jnp.int32)
    slot = jnp.asarray([0, 0, 0, 0, 0, 1, 2, 0, 0, 0], jnp.int32)
    pos = jnp.asarray([3, 4, 5, 6, 7, 17, 9, -1, -1, -1], jnp.int32)
    need = jnp.asarray([4, 5, 6], jnp.int32)
    sample = {"temp": jnp.zeros((slots,), jnp.float32),
              "top_k": jnp.zeros((slots,), jnp.int32),
              "top_p": jnp.ones((slots,), jnp.float32),
              "key": jnp.zeros((slots, 2), jnp.uint32)}
    kp, vp, ksp, vsp = pools()
    want = _plain_step(weights, kp, vp, ksp, vsp, ptab, tokens, slot, pos,
                       page, need, quant)
    kp, vp, ksp, vsp = pools()
    *got, rec = unified_step(weights, kp, vp, ptab, tokens, slot, pos, CFG,
                             page, need_rows=need, k_scale=ksp,
                             v_scale=vsp, sample=sample)
    logits, ref = (np.asarray(a, np.float32) for a in (got[4], want[4]))
    # a bf16 ulp of the largest logit: the products are the same
    # products, summed in float32 and rounded once
    assert np.abs(logits - ref).max() <= 2.0 ** -8 * np.abs(ref).max()
    assert np.array_equal(np.asarray(rec[0]), ref.argmax(-1))
    for a, b in zip(got[:4], want[:4]):
        if a is None:
            assert b is None
            continue
        # an int8 value may round one step apart where its row's scale
        # does; everything else within a bf16 ulp of the largest
        step = a.dtype == jnp.int8
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= (1.0 if step
                                       else 2.0 ** -7 * np.abs(b).max())


@pytest.mark.parametrize("ragged", [True, False])
def test_engine_steps_over_the_weights_it_was_given(params, ragged):
    """No engine lays a second copy of a weight beside the caller's: the
    tree the step gets holds the caller's `wq`, `wk` and `wv` (and no
    stack made of them) on the engine's device, ragged or bucketed."""
    eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                        page_size=8, use_pallas=False, ragged=ragged)
    assert sorted(eng.params["layers"]) == sorted(params["layers"])
    for name in ("wq", "wk", "wv"):
        mine, theirs = eng.params["layers"][name], params["layers"][name]
        assert mine.devices() == {eng.device}
        assert mine.unsafe_buffer_pointer() == theirs.unsafe_buffer_pointer()
    eng.submit(Request("a", [1, 5, 9, 3, 7], max_new_tokens=4))
    assert len(eng.run()[0].output) == 4


# ---------------------------------------------------------------------------
# Token identity: ragged == bucketed, every mode, both pumps
# ---------------------------------------------------------------------------
def _submit_mixed(eng, max_new=8):
    eng.submit(Request("g0", [1, 5, 9, 3, 7], max_new_tokens=max_new))
    eng.submit(Request("s0", [2, 4, 6], max_new_tokens=max_new,
                       temperature=0.8, top_k=8, top_p=0.9, seed=123))
    eng.submit(Request("g1", [9, 9, 2], max_new_tokens=max_new,
                       logprobs=True))
    eng.submit(Request("s1", [7, 1], max_new_tokens=max_new,
                       temperature=1.1, seed=7, logprobs=True))


def _outputs(done):
    return {r.rid: (list(r.output), None if r.logprobs is None
                    else [round(v, 5) for v in r.logprobs])
            for r in done}


MODES = {
    "plain": {},
    "int8": {"cache_dtype": "int8"},
    "prefix": {"prefix_cache": True},
    "tier": {"prefix_cache": True, "host_tier_bytes": 1 << 20},
    "spec": {"spec_decode": 4},
    "chunked": {"spec_decode": 4, "chunked_prefill": True},
}
# the tier-1 budget carries one composition per distinct ragged code
# path (plain carry, quantized scatter, shared-page suffix prefill,
# spec verify-grid) under the sync pump plus the plain pipelined pump;
# the heavier compositions and remaining pump crosses run in the slow
# lane
_FAST = {("plain", False), ("plain", True), ("int8", False),
         ("prefix", False), ("spec", False)}
_PARAMS = [pytest.param(m, p, marks=()
                        if (m, p) in _FAST else pytest.mark.slow,
                        id=f"{m}-{'pipelined' if p else 'sync'}")
           for m in sorted(MODES) for p in (False, True)]


class TestTokenIdentity:
    """ragged=True == ragged=False, token for token and logprob for
    logprob, under the same pump."""

    @pytest.mark.parametrize("mode,pipelined", _PARAMS)
    def test_ragged_equals_bucketed(self, params, mode, pipelined):
        kw = MODES[mode]
        outs = []
        for ragged in (False, True):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=8, use_pallas=False,
                                ragged=ragged, **kw)
            _submit_mixed(eng)
            done = eng.run_pipelined() if pipelined else eng.run()
            assert len(done) == 4
            outs.append(_outputs(done))
        for rid, (toks, lps) in outs[0].items():
            r_toks, r_lps = outs[1][rid]
            # TOKEN identity is the contract, every mode
            assert toks == r_toks, f"mode {mode} rid {rid} diverged"
            if lps is None:
                assert r_lps is None
            elif mode == "int8":
                # int8 dequantizes inside the ragged attention kernel
                # but ahead of it in the bucketed one — same tokens,
                # logprobs drift at float rounding
                assert np.allclose(lps, r_lps, atol=1e-3), rid
            else:
                assert lps == r_lps, f"mode {mode} rid {rid} logprobs"

    @pytest.mark.parametrize("pipelined", [False, True],
                             ids=["sync", "pipelined"])
    def test_ragged_under_preemption(self, params, pipelined):
        """An oversubscribed pool forces preemption mid-run: the
        ragged engine must stall/preempt exactly like the bucketed one
        and emit the same tokens."""
        outs = []
        for ragged in (False, True):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=32,
                                page_size=8, num_pages=6,
                                use_pallas=False, ragged=ragged)
            eng.submit(Request("s", [3, 7, 2, 9], max_new_tokens=20,
                               temperature=0.8, top_k=8, seed=123))
            eng.submit(Request("g", [1, 4, 6, 2], max_new_tokens=20))
            done = eng.run_pipelined(max_steps=500) if pipelined \
                else eng.run(max_steps=500)
            assert eng.preemptions > 0
            outs.append({r.rid: r.output for r in done})
        assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# Zero retrace across mix changes + pad-waste telemetry
# ---------------------------------------------------------------------------
class TestRaggedTelemetry:
    def test_mix_change_zero_retrace(self, params):
        """Acceptance: prefill-heavy wave, mixed wave, decode-only
        wave, chunk-tail wave — ONE `serving.unified_step` trace
        serves them all; a mix change never retraces."""
        from paddle_tpu.observability.compile_telemetry import REGISTRY
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, ragged=True)
        eng.submit(Request("warm", [1, 2, 3], max_new_tokens=2))
        eng.run()
        fns = REGISTRY.snapshot()
        fns = fns.get("functions", fns)
        before = fns["serving.unified_step"]["compiles"]
        assert before >= 1
        # wildly different mixes: long prefill + short, staggered
        # admissions (prefill rows next to decode rows), sampled +
        # greedy, lengths crossing page boundaries
        eng.submit(Request("a", list(range(1, 20)), max_new_tokens=6))
        eng.submit(Request("b", [5], max_new_tokens=9,
                           temperature=0.7, top_k=4, seed=3))
        eng.submit(Request("c", [8, 8, 8, 8, 8, 8, 8], max_new_tokens=4))
        eng.run()
        fns = REGISTRY.snapshot()
        fns = fns.get("functions", fns)
        assert fns["serving.unified_step"]["compiles"] == before, \
            "mix change retraced unified_step"

    @pytest.mark.parametrize("mode", ["plain", "int8", "prefix"])
    def test_one_program_serves_all_of_a_modes_traffic(self, params,
                                                       monkeypatch, mode):
        """One serving step (ISSUE 37): whatever a ragged engine is
        asked to serve, every wave calls `unified_step` with ONE
        signature, arrays' shapes and static arguments alike, so it
        compiles exactly one program. Seen at the call, not in the
        process-wide registry, which other tests' engines share."""
        from paddle_tpu.models import llama_serving
        from paddle_tpu.observability.compile_telemetry import signature_of
        real, sigs = llama_serving.unified_step, []

        def spy(*a, **kw):
            sigs.append(signature_of(a, kw))
            return real(*a, **kw)
        monkeypatch.setattr(llama_serving, "unified_step", spy)
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, **MODES[mode])
        assert eng.ragged and eng.tok_buf is not None
        head = list(range(1, 18))           # two whole pages to share
        eng.submit(Request("a", head + [20], max_new_tokens=6))
        eng.submit(Request("b", [5], max_new_tokens=9, temperature=0.7,
                           top_k=4, seed=3, logprobs=True))
        eng.run_pipelined()
        eng.submit(Request("c", head + [21, 22], max_new_tokens=4))
        eng.submit(Request("d", [8] * 7, max_new_tokens=3, logprobs=True))
        eng.submit(Request("e", [3, 1], max_new_tokens=12))
        done = eng.run()
        assert len(done) == 5 and len(sigs) > 12
        assert len(set(sigs)) == 1, set(sigs)
        if mode == "prefix":
            assert eng.prefix_cache.tokens_reused >= 16

    def test_pad_counters(self, params):
        """ragged: zero pad tokens ever booked, ragged rows counted;
        bucketed: the same workload pads. Counters surface through
        EngineMetrics with the `_total` rendering."""
        books = {}
        for ragged in (False, True):
            eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                                page_size=8, use_pallas=False,
                                ragged=ragged)
            reg = MetricsRegistry()
            sched = RequestScheduler(eng, max_queue=8, metrics=reg)
            hs = [sched.submit([1 + i, 5, 9], rid=f"r{i}",
                               max_new_tokens=5) for i in range(3)]
            for h in hs:
                h.result(timeout=60)
            sched.shutdown(drain=True, timeout=30)
            snap = reg.snapshot()
            books[ragged] = (eng.pad_tokens, eng.ragged_tokens,
                             snap["pt_pad_tokens"]["value"],
                             snap["pt_ragged_tokens"]["value"],
                             reg.render_prometheus())
        pad, rag, m_pad, m_rag, text = books[True]
        assert pad == 0 and m_pad == 0
        assert rag > 0 and m_rag == rag
        assert "pt_ragged_tokens_total" in text
        assert "pt_pad_tokens_total 0" in text
        b_pad, b_rag, b_m_pad, _, _ = books[False]
        assert b_pad > 0 and b_m_pad == b_pad
        assert b_rag == 0


    def test_run_and_kv_block_counters_reach_metrics(self, params,
                                                     monkeypatch):
        """`pt_ragged_runs` / `pt_ragged_kv_blocks` (ISSUE 26) carry
        what the waves' own descriptors imply: two decode rows and a
        26-token prompt cut 14 + 12 by a 16-row buffer, KV blocks of
        one page (8 tokens), q blocks of 8 rows."""
        from paddle_tpu.models import llama_serving
        from paddle_tpu.serving.metrics import EngineMetrics
        waves = []
        real = llama_serving.unified_step

        def spy(params_, k, v, page_table, tokens, tok_slot, tok_pos,
                *a, **kw):
            waves.append((np.asarray(tok_slot), np.asarray(tok_pos)))
            return real(params_, k, v, page_table, tokens, tok_slot,
                        tok_pos, *a, **kw)
        monkeypatch.setattr(llama_serving, "unified_step", spy)
        eng = ServingEngine(params, CFG, max_seqs=4, max_seq_len=64,
                            page_size=8, use_pallas=False, ragged=True,
                            ragged_tokens=16, block_q=8, block_pages=1)
        reg = MetricsRegistry()
        eng.metrics = EngineMetrics(reg)
        eng.submit(Request("d0", [1, 2, 3], max_new_tokens=12))
        eng.submit(Request("d1", [4, 5, 6, 7], max_new_tokens=12))
        for _ in range(3):
            eng.step()
        eng.submit(Request("p0", list(range(1, 27)), max_new_tokens=4))
        eng.run()
        runs = blocks = 0
        chunk_runs = []
        for tok_slot, tok_pos in waves:
            whole, _ = _runs_numpy(tok_slot, tok_pos, len(tok_pos))
            pieces, _ = _runs_numpy(tok_slot, tok_pos, 8)
            runs += len(whole)
            blocks += sum(-(-kv_len // 8) for _, _, _, kv_len in pieces)
            chunk_runs += [r for r in whole if r[1] > 4]
        # the hand-built wave: the prompt's two chunks, one run each
        assert [(r[1], r[3]) for r in chunk_runs] == [(14, 14), (12, 26)]
        assert eng.ragged_runs == runs > len(waves)
        assert eng.ragged_kv_blocks == blocks > runs
        snap = reg.snapshot()
        assert snap["pt_ragged_runs"]["value"] == runs
        assert snap["pt_ragged_kv_blocks"]["value"] == blocks
        text = reg.render_prometheus()
        assert f"pt_ragged_runs_total {runs}" in text
        assert f"pt_ragged_kv_blocks_total {blocks}" in text


# ---------------------------------------------------------------------------
# PT_FAULTS crash-recovery drill: step_launch crash under ragged
# ---------------------------------------------------------------------------
class TestFaultDrill:
    N = 4

    def _drill(self, params, pipelined):
        # the pump follows the engine: bucketed -> synchronous, ragged
        # -> one step deep
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False,
                            ragged=pipelined)
        sched = RequestScheduler(eng, max_queue=16,
                                 metrics=MetricsRegistry())
        assert sched._pipeline is pipelined
        sched.pause()
        hs = [sched.submit([1 + i, 5, 9, 3], rid=f"r{i}",
                           max_new_tokens=8) for i in range(self.N)]
        sched.resume()
        outs = {h.rid: h.result(timeout=90) for h in hs}
        st = sched.stats()
        sched.shutdown(drain=True, timeout=30)
        c = eng.pool.counts()
        assert c["free"] + c["cached"] + c["live"] == eng.num_pages - 1
        return outs, st

    @pytest.mark.parametrize("pipelined",
                             [False, pytest.param(True,
                                                  marks=pytest.mark.slow)],
                             ids=["sync", "pipelined"])
    def test_step_launch_crash_recovers_token_identical(
            self, params, pipelined, monkeypatch):
        monkeypatch.delenv("PT_FAULTS", raising=False)
        base, st = self._drill(params, pipelined)
        assert st["recovery"]["restarts"] == 0
        # a transient device-program crash on the 3rd launched wave:
        # warm restart + requeue, nobody fails, tokens identical
        monkeypatch.setenv("PT_FAULTS", "step_launch:raise@3")
        outs, st = self._drill(params, pipelined)
        assert outs == base
        assert st["recovery"]["restarts"] >= 1
        assert st["requests"]["failed"] == 0
        assert st["requests"]["completed"] == self.N


# ---------------------------------------------------------------------------
# The row-sparse lm_head epilogue (ISSUE 12)
# ---------------------------------------------------------------------------
class TestEpilogue:
    """`need_rows` is the ragged step's epilogue: tokens AND logprobs
    equal the bucketed entry points', which unembed every row they are
    given (`decode_step`, `verify_step`: an independent full-logits
    implementation); the program holds no (T, vocab) buffer; the
    skipped unembed rows are booked in pt_logit_rows(_skipped)."""

    def _run(self, params, ragged, kw, pipelined, spec_workload):
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, ragged=ragged,
                            **kw)
        if spec_workload:
            # n-gram repeats, so the greedy verify path really drafts
            eng.submit(Request("g0", [1, 5, 1, 5, 1, 5], max_new_tokens=8,
                               logprobs=True))
            eng.submit(Request("g1", [9, 9, 9, 2], max_new_tokens=8,
                               logprobs=True))
            eng.submit(Request("g2", [2, 4, 2, 4, 2], max_new_tokens=8,
                               logprobs=True))
        else:
            # `_submit_mixed`'s requests, every one with its logprobs
            eng.submit(Request("g0", [1, 5, 9, 3, 7], max_new_tokens=8,
                               logprobs=True))
            eng.submit(Request("s0", [2, 4, 6], max_new_tokens=8,
                               temperature=0.8, top_k=8, top_p=0.9,
                               seed=123, logprobs=True))
            eng.submit(Request("g1", [9, 9, 2], max_new_tokens=8,
                               logprobs=True))
            eng.submit(Request("s1", [7, 1], max_new_tokens=8,
                               temperature=1.1, seed=7, logprobs=True))
        done = eng.run_pipelined() if pipelined else eng.run()
        return eng, _outputs(done)

    @pytest.mark.parametrize("mode,pipelined", _PARAMS)
    def test_need_rows_equal_the_bucketed_full_logits(self, params, mode,
                                                      pipelined):
        kw = MODES[mode]
        spec_workload = bool(kw.get("spec_decode"))
        full_eng, full = self._run(params, False, kw, pipelined,
                                   spec_workload)
        eng, sparse = self._run(params, True, kw, pipelined, spec_workload)
        assert eng.logit_rows_skipped > 0
        if spec_workload:
            assert eng.spec_accepted > 0
        elif mode == "plain":
            # bucketed decode is a row a slot: it has none to skip
            assert full_eng.logit_rows_skipped == 0
        assert sorted(full) == sorted(sparse) and len(full) >= 3
        for rid, (toks, lps) in full.items():
            s_toks, s_lps = sparse[rid]
            assert toks == s_toks, f"mode {mode} rid {rid} diverged"
            assert len(s_lps) == len(toks)
            if mode == "int8":
                # int8 dequantizes inside the ragged attention kernel
                # but ahead of it in the bucketed one
                assert np.allclose(lps, s_lps, atol=1e-3), rid
            else:
                assert lps == s_lps, f"mode {mode} rid {rid} logprobs"

    def test_step_program_holds_no_full_logits(self):
        """An absolute statement about the one program: what
        `unified_step` returns as logits is `(need_buf, vocab)`, and no
        value anywhere in its jaxpr is `(T, vocab)`, with T the flat
        buffer's rows, over twice `need_buf` here."""
        import jax
        from paddle_tpu.models import llama_serving
        # a vocabulary no other width of the model shares
        cfg = LlamaConfig.tiny(vocab=96, hidden=32, layers=2, heads=4,
                               kv_heads=2, ffn=64, seq=128)
        eng = ServingEngine(M.init_params(cfg, seed=0, dtype=jnp.float32),
                            cfg, max_seqs=2, max_seq_len=64, page_size=8,
                            use_pallas=False, ragged=True, ragged_tokens=16)
        T, N, V = eng.ragged_buf, eng.need_buf, cfg.vocab_size
        assert (T, N) == (16, 2)
        real, seen = eng.model.step, []

        def step(*a, **kw):
            seen.append((a, kw))
            return real(*a, **kw)
        object.__setattr__(eng.model, "step", step)
        _submit_mixed(eng)
        eng.step()
        (params_, caches, tables, *rows, config, page_size), kw = seen[0]
        assert kw["need_rows"].shape == (N,)
        ((k, v, ks, vs),), = caches
        spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
        static = {n: kw.pop(n) for n in ("use_pallas", "interpret",
                                         "block_q", "block_pages")}
        jaxpr, out = jax.make_jaxpr(
            lambda *a, **kws: llama_serving.unified_step.__wrapped__
            .__wrapped__(*a, config, page_size, k_scale=ks, v_scale=vs,
                         **static, **kws), return_shape=True)(
            *jax.tree_util.tree_map(spec, (params_, k, v, tables[0], *rows)),
            **jax.tree_util.tree_map(spec, kw))
        assert out[4].shape == (N, V)
        text = str(jaxpr)       # nested jaxprs (the layer scan) included
        assert f"[{T},{cfg.hidden_size}]" in text      # the flat rows
        assert f"[{N},{V}]" in text and f"[{T},{V}]" not in text

    def test_row_ledger_reaches_metrics(self, params):
        """pt_logit_rows / pt_logit_rows_skipped mirror the engine's
        counters through EngineMetrics and render with the counter
        `_total` suffix."""
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, ragged=True)
        reg = MetricsRegistry()
        sched = RequestScheduler(eng, max_queue=8, metrics=reg)
        hs = [sched.submit([1 + i, 5, 9], rid=f"r{i}",
                           max_new_tokens=5) for i in range(3)]
        for h in hs:
            h.result(timeout=60)
        sched.shutdown(drain=True, timeout=30)
        snap = reg.snapshot()
        assert eng.logit_rows > 0
        assert eng.logit_rows_skipped > 0
        assert snap["pt_logit_rows"]["value"] == eng.logit_rows
        assert snap["pt_logit_rows_skipped"]["value"] == \
            eng.logit_rows_skipped
        text = reg.render_prometheus()
        assert "pt_logit_rows_total" in text
        assert "pt_logit_rows_skipped_total" in text

    def test_need_rows_zero_retrace(self, params):
        """The need descriptor is a fixed-shape (max_seqs * G,) operand:
        waves with wildly different needed-row counts reuse ONE
        `serving.unified_step` trace."""
        from paddle_tpu.observability.compile_telemetry import REGISTRY
        eng = ServingEngine(params, CFG, max_seqs=2, max_seq_len=64,
                            page_size=8, use_pallas=False, ragged=True)
        eng.submit(Request("warm", [1, 2, 3], max_new_tokens=2))
        eng.run()
        before = REGISTRY.snapshot()["serving.unified_step"]["compiles"]
        assert before >= 1
        # one long prefill (1 needed row), then a full decode batch
        # (max_seqs needed rows), then staggered admissions
        eng.submit(Request("a", list(range(1, 20)), max_new_tokens=6))
        eng.run()
        eng.submit(Request("b", [5], max_new_tokens=9))
        eng.submit(Request("c", [8, 8, 8], max_new_tokens=4))
        eng.run()
        after = REGISTRY.snapshot()["serving.unified_step"]["compiles"]
        assert after == before, "need_rows churn retraced unified_step"
