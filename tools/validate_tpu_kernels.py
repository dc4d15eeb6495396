"""Pallas kernels compiled by Mosaic (no interpret mode) vs their jnp
references, at the serving shapes: head_dim 128, GQA group 4, page 16,
bf16 activations, bf16 and int8 cache.

tests/ run the kernels interpreted on the CPU; this is the part only a
chip can answer — does Mosaic compile the kernel, and does it compute
the reference's numbers. `chip_smoke.py` imports `CHECKS` and runs them
as its kernels phase; standalone, on a machine with a TPU:

    python tools/validate_tpu_kernels.py      # exit 0 iff every family passes

Each check takes `interpret` and `small`: `chip_smoke.py --dry-run-cpu`
runs the same code interpreted at a tiny size so the command is debugged
before it is sent to a chip.

Tolerances. Inputs are bf16 values scaled by 0.3, so outputs are O(0.3)
and one bf16 ulp of an output is ~1e-3. Kernel and reference differ by
accumulation order and by where a product is rounded to bf16 (a
DEFAULT-precision f32 dot on the TPU runs as bf16 passes on BOTH sides),
which is a few ulps: TOL_BF16 = 2e-2 absolute on outputs, and on
gradients relative to the largest reference gradient. int8 results carry
the same bound against the reference fed the same int8 pages.

The ragged kernel sums a KV block of pages at a time in float32 where its
reference sums a page at a time, and rounds once, to bf16, at the end:
the two differ by at most the output's last bf16 bit. Read on a v5e (my
chip run, PR 26, all eight mixes x cache types): 9.8e-4 (one ulp of an
output in [0.125, 0.25)) on six, 1.2e-4 on the suffix tail's two. The
bound stays TOL_BF16, twenty times that: it is there to catch a wrong
mask, page or scale (errors of the outputs' own size, 0.1-0.3), not to
track the rounding.
"""
from __future__ import annotations

import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOL_BF16 = 2e-2
D, GROUP, PAGE = 128, 4, 16


def max_err(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float32) -
                               np.asarray(b, np.float32))))


def _randn(rng, *shape):
    import jax.numpy as jnp
    return jnp.asarray(rng.randn(*shape) * 0.3, jnp.bfloat16)


def _fwd_bwd_errs(loss_kernel, loss_ref, args):
    """(fwd abs err, bwd err relative to the largest reference grad) of
    two `(loss, out)` functions differentiated w.r.t. all of `args`."""
    import jax
    argnums = tuple(range(len(args)))
    (_, o_k), g_k = jax.value_and_grad(loss_kernel, argnums,
                                       has_aux=True)(*args)
    (_, o_r), g_r = jax.value_and_grad(loss_ref, argnums,
                                       has_aux=True)(*args)
    gmag = max(float(np.abs(np.asarray(g, np.float32)).max()) for g in g_r)
    eg = max(max_err(a, b) for a, b in zip(g_k, g_r)) / max(gmag, 1.0)
    return max_err(o_k, o_r), eg


def _assert_close(name, *errs):
    bad = [e for e in errs if not e < TOL_BF16]     # catches NaN too
    assert not bad, f"{name}: err {errs} exceeds {TOL_BF16}"
    return [round(e, 5) for e in errs]


def flash_fwd_bwd(interpret=False, small=False):
    import jax.numpy as jnp
    from paddle_tpu.ops.flash_attention import (flash_attention_bhsd,
                                                mha_reference)
    rng = np.random.RandomState(0)
    # the train phase's attention shape, and a ragged tail block
    shapes = [(1, 2, 256, D), (1, 2, 200, D)] if small else \
        [(2, 4, 2048, D), (1, 4, 1000, D)]
    out = {}
    for b, h, s, d in shapes:
        q, k, v = (_randn(rng, b, h, s, d) for _ in range(3))

        def loss_k(q, k, v):
            o = flash_attention_bhsd(q, k, v, causal=True, use_pallas=True,
                                     interpret=interpret)
            return (o * v).astype(jnp.float32).sum(), o

        def loss_r(q, k, v):
            o, _ = mha_reference(q, k, v, None, True, 1.0 / math.sqrt(d))
            return (o * v).astype(jnp.float32).sum(), o

        out[f"{b}x{h}x{s}x{d}"] = _assert_close(
            f"flash s={s}", *_fwd_bwd_errs(loss_k, loss_r, (q, k, v)))
    return out


def varlen_fwd_bwd(interpret=False, small=False):
    import jax.numpy as jnp
    from paddle_tpu.ops.varlen_attention import (flash_attn_unpadded,
                                                 seg_ids_from_cu_seqlens,
                                                 varlen_reference)
    rng = np.random.RandomState(1)
    h = 2 if small else 8
    lens = [100, 56, 92, 8] if small else [700, 56, 1100, 8]
    cu = jnp.asarray(np.cumsum([0] + lens), jnp.int32)
    total = int(cu[-1])
    q, k, v = (_randn(rng, total, h, D) for _ in range(3))
    seg = seg_ids_from_cu_seqlens(cu, total)

    def loss_k(q, k, v):
        o, _ = flash_attn_unpadded(q, k, v, cu, cu, causal=True,
                                   use_pallas=True, interpret=interpret)
        return (o * v).astype(jnp.float32).sum(), o

    def loss_r(q, k, v):
        o, _ = varlen_reference(*(jnp.swapaxes(t, 0, 1) for t in (q, k, v)),
                                seg, seg, True, 1.0 / math.sqrt(D))
        o = jnp.swapaxes(o, 0, 1).astype(q.dtype)
        return (o * v).astype(jnp.float32).sum(), o

    return {f"packed{total}": _assert_close(
        "varlen", *_fwd_bwd_errs(loss_k, loss_r, (q, k, v)))}


def _paged_case(rng, small):
    """A page pool with a shuffled page table: (q heads, kv heads,
    k/v pages bf16, table, pages_per_seq)."""
    import jax.numpy as jnp
    kvh = 2 if small else 8
    b = 4
    pages_per_seq = 8 if small else 136      # 2176-token context
    num_pages = b * pages_per_seq + 1
    k_pages = _randn(rng, kvh, num_pages, PAGE, D)
    v_pages = _randn(rng, kvh, num_pages, PAGE, D)
    table = jnp.asarray(rng.permutation(num_pages - 1)[:b * pages_per_seq]
                        .reshape(b, pages_per_seq), jnp.int32)
    return kvh, b, pages_per_seq, k_pages, v_pages, table


def _quantized(k_pages, v_pages):
    from paddle_tpu.ops.paged_attention import quantize_kv
    kq, ks = quantize_kv(k_pages)
    vq, vs = quantize_kv(v_pages)
    return kq, vq, ks, vs


def paged_decode_and_verify(interpret=False, small=False):
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import (
        paged_attention, paged_attention_reference, paged_verify_attention,
        paged_verify_reference)
    rng = np.random.RandomState(2)
    kvh, b, pps, k_pages, v_pages, table = _paged_case(rng, small)
    cap = pps * PAGE
    lengths = jnp.asarray([cap - 28, 17, cap, cap // 2], jnp.int32)
    q = _randn(rng, b, kvh * GROUP, D)
    kq, vq, ks, vs = _quantized(k_pages, v_pages)
    out = {}
    for tag, kp, vp, sc in (("bf16", k_pages, v_pages, {}),
                            ("int8", kq, vq,
                             dict(k_scale=ks, v_scale=vs))):
        o_k = paged_attention(q, kp, vp, table, lengths, use_pallas=True,
                              interpret=interpret, **sc)
        o_r = paged_attention_reference(q, kp, vp, table, lengths,
                                        D ** -0.5, *sc.values())
        out[f"decode_{tag}"] = _assert_close(f"paged decode {tag}",
                                             max_err(o_k, o_r))
        # verify chunk: G tokens per sequence, per-row causal limit;
        # G=3 exercises the row-padding path
        base = jnp.asarray([cap - 40, 10, cap - 8, cap // 2], jnp.int32)
        for G in (4, 3):
            qv = _randn(rng, b, kvh * GROUP, G, D)
            ov_k = paged_verify_attention(qv, kp, vp, table, base,
                                          use_pallas=True,
                                          interpret=interpret, **sc)
            ov_r = paged_verify_reference(qv, kp, vp, table, base, **sc)
            out[f"verify_g{G}_{tag}"] = _assert_close(
                f"verify chunk G={G} {tag}", max_err(ov_k, ov_r))
    return out


def flashmask_fwd_bwd(interpret=False, small=False):
    """Every mask mode the kernels take (n = 1 / 2 / 4, causal or not),
    a window, in-kernel dropout, keys wider than values (latent
    attention's 192 / 128), a head of 64, and a rectangle whose lengths
    no block divides."""
    import jax.numpy as jnp
    from paddle_tpu.ops.flashmask_attention import (flashmask_attention_bhsd,
                                                    flashmask_reference)
    rng = np.random.RandomState(3)
    b, h, s = (1, 2, 256) if small else (2, 4, 1024)

    def sri_of(causal, n, sk):
        r = lambda lo, hi: rng.randint(lo, hi, (b, h, sk, 1))
        if causal and n == 1:       # document-causal cutoff
            cols = [r(1, sk + 1)]
        elif causal:                # masked: start <= row < end
            start = r(0, sk)
            cols = [start, np.minimum(start + r(0, sk // 2), sk)]
        elif n == 2:                # masked: row >= start or row < end
            cols = [r(sk // 2, sk + 1), r(0, sk // 2)]
        else:                       # two masked bands
            cols = [r(0, sk // 4), r(sk // 4, sk // 2), r(sk // 2, sk),
                    np.full((b, h, sk, 1), sk)]
        return jnp.asarray(np.concatenate(cols, -1), jnp.int32)

    # key: (causal, n, window, dropout, sq, sk, d, d_v)
    odd = (200, 150) if small else (1000, 700)
    cases = {
        "cn1": (True, 1, None, 0.0, s, s, D, D),
        "cn2": (True, 2, None, 0.0, s, s, D, D),
        "bn2": (False, 2, None, 0.0, s, s, D, D),
        "bn4": (False, 4, None, 0.0, s, s, D, D),
        "cn1_drop0.3": (True, 1, None, 0.3, s, s, D, D),
        "cn1_window": (True, 1, (s // 4, 0), 0.0, s, s, D, D),
        "bn2_window": (False, 2, (s // 4, s // 8), 0.0, s, s, D, D),
        "cn1_192_128": (True, 1, None, 0.0, s, s, 192, D),
        "cn1_head64": (True, 1, None, 0.0, s, s, 64, 64),
        "cn1_odd_lengths": (True, 1, (odd[1] // 2, 0), 0.0, *odd, D, D),
    }
    out = {}
    for key, (causal, n, window, drop, sq, sk, d, d_v) in cases.items():
        q, k, v = (_randn(rng, b, h, sq, d), _randn(rng, b, h, sk, d),
                   _randn(rng, b, h, sk, d_v))
        w = _randn(rng, b, h, sq, d_v)
        sri = sri_of(causal, n, sk)
        kw = dict(dropout=drop, dropout_seed=123) if drop else {}

        def loss_k(q, k, v):
            o = flashmask_attention_bhsd(q, k, v, sri, causal=causal,
                                         window=window, use_pallas=True,
                                         interpret=interpret, **kw)
            return (o * w).astype(jnp.float32).sum(), o

        def loss_r(q, k, v):
            o, _ = flashmask_reference(q, k, v, sri, causal, window, **kw)
            return (o * w).astype(jnp.float32).sum(), o

        out[key] = _assert_close(
            f"flashmask {key}", *_fwd_bwd_errs(loss_k, loss_r, (q, k, v)))
    return out


def ragged(interpret=False, small=False):
    """The serving step's kernel on the mixes the engine produces: a
    decode-only wave (one row per slot, slack rows inactive); a wave
    where one slot's prefill chunk spans several pages beside decodes; a
    prefix-cache suffix tail (a run whose KV length is far more than its
    rows); and a run cut by q blocks of 16 rows, each piece walking the
    context up to its own last row."""
    import jax.numpy as jnp
    from paddle_tpu.kernels.ragged_paged_attention import (
        ragged_paged_attention, ragged_paged_attention_reference)
    rng = np.random.RandomState(4)
    kvh, b, pps, k_pages, v_pages, table = _paged_case(rng, small)
    cap = pps * PAGE
    t = 32 if small else 64
    # the chunk starts and ends mid-page and covers whole pages between
    chunk, start = (PAGE + 5, 9) if small else (3 * PAGE - 5, PAGE + 7)
    tail, long_run = (6, 20) if small else (20, 40)
    mixes = {
        "decode_only": ([0, 1, 2, 3], [cap - 28, 16, cap - 1, 0], {}),
        "prefill_chunk": ([1] * chunk + [0, 2],
                          list(range(start, start + chunk))
                          + [cap // 2, 15], {}),
        "suffix_tail": ([2, 3] + [0] * tail,
                        [7, cap - 3]
                        + list(range(cap // 2 + 3, cap // 2 + 3 + tail)), {}),
        "long_run": ([3] + [1] * long_run,
                     [PAGE] + list(range(5, 5 + long_run)),
                     dict(block_q=16)),
    }
    kq, vq, ks, vs = _quantized(k_pages, v_pages)
    out = {}
    for mix, (slots, poss, tile) in mixes.items():
        n = len(slots)
        assert n <= t
        slot = jnp.asarray(slots + [0] * (t - n), jnp.int32)
        pos = jnp.asarray(poss + [-1] * (t - n), jnp.int32)
        q = _randn(rng, t, kvh * GROUP, D)
        for tag, kp, vp, sc in (("bf16", k_pages, v_pages, {}),
                                ("int8", kq, vq,
                                 dict(k_scale=ks, v_scale=vs))):
            o_k = ragged_paged_attention(q, kp, vp, table, slot, pos,
                                         use_pallas=True,
                                         interpret=interpret, **tile, **sc)
            o_r = ragged_paged_attention_reference(q, kp, vp, table, slot,
                                                   pos, **sc)
            assert not np.asarray(o_k[n:], np.float32).any(), \
                f"ragged {mix} {tag}: inactive rows not zero"
            out[f"{mix}_{tag}"] = _assert_close(f"ragged {mix} {tag}",
                                                max_err(o_k, o_r))
    return out


CHECKS = [
    ("flash fwd+bwd", flash_fwd_bwd),
    ("varlen fwd+bwd", varlen_fwd_bwd),
    ("paged decode + verify chunk", paged_decode_and_verify),
    ("flashmask fwd+bwd", flashmask_fwd_bwd),
    ("ragged paged attention", ragged),
]


def main():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"validate_tpu_kernels: platform is {dev.platform!r}, "
                 "not 'tpu' — nothing to validate")
    print(f"validating on {dev.device_kind} (jax {jax.__version__})",
          flush=True)
    failed = []
    for name, fn in CHECKS:
        t0 = time.perf_counter()
        try:
            detail = fn()
        except Exception as e:  # noqa: BLE001 — report every family, then fail
            detail = f"{type(e).__name__}: {e}"
            failed.append(name)
        print(f"[{'FAIL' if name in failed else 'PASS'}] {name} "
              f"({time.perf_counter() - t0:.1f}s): {detail}", flush=True)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
