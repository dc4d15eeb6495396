"""Offline autotuner for the serving ragged-paged-attention kernel tile
(ISSUE 12; re-cut by ISSUE 26 for the kernel that walks runs).

Sweeps legal (block_q, block_pages) tiles — q rows a block x pages a KV
block — of `paddle_tpu.kernels.ragged_paged_attention` on the attached
backend over a serving-shaped wave (decode rows and a prefill chunk at
the benchmark cell's shape: 32 rows, 32 / 8 heads of 128, pages of 16,
256 pages a sequence), checks every candidate against the jnp reference
by tolerance (a tile changes the order of summation, nothing else), and
persists the per-TPU-generation winner into TUNED.kernels.json via
`_tuning_defaults.save_ragged_tile`. The tile derived from the shapes
(0, 0) leads the grid and stays the winner unless another is more than
2% quicker. The serving engine loads that file ONCE at construction
(`load_ragged_tile(device_generation())`), so a tuned tile is a static
jit arg — it never retraces a live trace.

Run on a live chip:   python tools/tune_ragged.py
Re-tune a new chip generation: same command on that chip — winners key
by generation, so v5e and v6e entries coexist in one file.

Smoke mode (no hardware): --smoke (or PT_TUNE_SMOKE=1) runs the sweep
on CPU (interpret-mode pallas, tiny problem) and writes to
TUNED.kernels.smoke.json — never the file the engine reads — proving
the sweep/verify/persist/reload loop before a run on a chip. Docs: docs/tuning.md § Serving kernel autotune.

Env knobs:
  PT_TUNE_OUT            — output path override
  PT_RAGGED_TILE_FILE    — engine-side file override (tests point both
                           here for the roundtrip check)
  PT_TUNE_RAGGED_ITERS   — timed iterations per config (default 20)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def _load_defaults():
    import importlib.util
    p = os.path.join(ROOT, "paddle_tpu", "_tuning_defaults.py")
    spec = importlib.util.spec_from_file_location("_tuning_defaults", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_TD = _load_defaults()


# a tile has to beat the derived one by more than this to be kept
MIN_GAIN = 0.02
# kernel calls inside one timed program, as in the layer scan: a single
# call is shorter than the host's dispatch of it
CALLS = 16


def make_problem(smoke, seed=0):
    """A serving-shaped wave: decode rows first, one a slot, then a
    prefill chunk, then slack rows — `_ragged_plan`'s layout. The full
    problem is the benchmark's serving cell (7 decoding contexts of a
    few hundred tokens and a 24-row chunk deep in its prompt: about
    4,000 tokens of keys and values); smoke keeps every dim tiny
    (interpret-mode pallas multiplies cost ~100x)."""
    import numpy as np
    import jax.numpy as jnp

    if smoke:
        qh, kvh, d, page, pages_per_seq, slots, t = 4, 2, 16, 8, 4, 3, 16
        dtype, decodes, chunk = jnp.float32, [30, 17], (3, 9)
    else:
        qh, kvh, d, page, pages_per_seq, slots, t = 32, 8, 128, 16, 256, 32, 32
        dtype = jnp.bfloat16
        decodes, chunk = [388, 512, 1130, 201, 640, 455, 300], (24, 410)
    num_pages = slots * pages_per_seq + 1
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((t, qh, d)) * 0.3
    kshape = (kvh, num_pages, page, d)
    k_pages = rng.standard_normal(kshape) * 0.3
    v_pages = rng.standard_normal(kshape) * 0.3
    ptab = rng.permutation(num_pages - 1).astype(np.int32).reshape(
        slots, pages_per_seq)
    tok_slot = np.zeros((t,), np.int32)
    tok_pos = np.full((t,), -1, np.int32)
    n_dec = len(decodes)
    tok_slot[:n_dec] = np.arange(n_dec)
    tok_pos[:n_dec] = decodes
    n_pf, first = chunk
    tok_slot[n_dec:n_dec + n_pf] = n_dec
    tok_pos[n_dec:n_dec + n_pf] = first + np.arange(n_pf)
    return (jnp.asarray(q, dtype), jnp.asarray(k_pages, dtype),
            jnp.asarray(v_pages, dtype), jnp.asarray(ptab),
            jnp.asarray(tok_slot), jnp.asarray(tok_pos))


def candidate_tiles(t, group, page_size, n_pages, smoke):
    """Legal (block_q, block_pages) grid around the derived tile
    (0, 0), which always leads: q rows halved and quartered (a long
    run then re-reads its context once a q block), KV blocks of half
    to four times the lane width."""
    from paddle_tpu.kernels import ragged_tile

    bq, bp = ragged_tile(None, None, t, group, page_size, n_pages)
    if smoke:
        return [(0, 0), (bq // 2, 0), (0, 1)]
    qs = [0, bq // 2, bq // 4]
    ps = [0, bp // 2, bp * 2, bp * 4]
    return [(q, p) for q in qs for p in ps if p <= n_pages]


def time_config(fn, iters):
    import jax
    out = fn()                      # compile + correctness sample
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return out, times[len(times) // 2]   # median


def sweep(smoke, iters, use_pallas=None, interpret=None):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import (ragged_paged_attention,
                                    ragged_paged_attention_reference)

    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu" or smoke
    if interpret is None:
        interpret = backend != "tpu"
    q, k, v, ptab, slot, pos = make_problem(smoke)
    group = q.shape[1] // k.shape[0]
    ref = np.asarray(ragged_paged_attention_reference(
        q, k, v, ptab, slot, pos), np.float32)
    # float32: the order of summation alone; bf16: an output ulp or two
    tol = (1e-5 if q.dtype == jnp.float32 else 2e-2) * max(
        float(np.abs(ref).max()), 1.0)
    calls = 1 if smoke else CALLS
    rows = []
    for bq, bp in candidate_tiles(q.shape[0], group, k.shape[2],
                                  ptab.shape[1], smoke):
        cfg = {"block_q": bq, "block_pages": bp}

        @jax.jit
        def run(q, bq=bq, bp=bp):
            def one(qc, _):
                o = ragged_paged_attention(
                    qc, k, v, ptab, slot, pos, use_pallas=use_pallas,
                    interpret=interpret, block_q=bq or None,
                    block_pages=bp or None)
                return q + o * 1e-3, o      # each call feeds the next
            return jax.lax.scan(one, q, None, length=calls)[1][0]
        try:
            out, t = time_config(lambda: run(q), iters)
        except Exception as e:   # Mosaic rejection on a real chip
            print(f"  tile {cfg} FAILED: {e}", flush=True)
            rows.append(dict(cfg, time_s=None, close=False,
                             error=str(e)[:200]))
            continue
        err = float(np.abs(np.asarray(out, np.float32) - ref).max())
        close = err <= tol
        rows.append(dict(cfg, time_s=t / calls, close=close, err=err))
        print(f"  tile {cfg}: {t / calls * 1e6:.1f} us/call, "
              f"|delta| {err:.2e}"
              f"{'' if close else '  OVER TOLERANCE — rejected'}",
              flush=True)
    ok = [r for r in rows if r["time_s"] is not None and r["close"]]
    if not ok:
        raise RuntimeError("every tile config failed or diverged")
    best = min(ok, key=lambda r: r["time_s"])
    derived = rows[0]
    if derived in ok and \
            best["time_s"] > derived["time_s"] * (1.0 - MIN_GAIN):
        best = derived
    return best, rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    default=os.environ.get("PT_TUNE_SMOKE") == "1",
                    help="CPU interpret-mode sweep; writes the smoke "
                         "file, never TUNED.kernels.json")
    ap.add_argument("--out", default=None, help="tile-file override")
    ap.add_argument("--iters", type=int, default=int(
        os.environ.get("PT_TUNE_RAGGED_ITERS", "20")))
    args = ap.parse_args(argv)

    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    backend = jax.default_backend()
    if not args.smoke and backend != "tpu":
        print("tune_ragged: TPU unreachable; not tuning (use --smoke "
              "for the CPU harness check)", file=sys.stderr)
        return 1
    out_path = args.out or os.environ.get("PT_TUNE_OUT") or (
        os.path.join(ROOT, "TUNED.kernels.smoke.json") if args.smoke
        else _TD.RAGGED_TILE_FILE)

    from paddle_tpu.observability.device_telemetry import device_generation
    gen = device_generation()
    print(f"tune_ragged: backend={backend} generation={gen} "
          f"out={os.path.basename(out_path)}"
          f"{' (SMOKE)' if args.smoke else ''}", flush=True)
    best, rows = sweep(args.smoke, args.iters)
    entry = _TD.save_ragged_tile(
        gen, best["block_q"], best["block_pages"], path=out_path,
        extra={"time_us": round(best["time_s"] * 1e6, 2),
               "smoke": args.smoke, "ts": time.time(),
               "trials": [{k: r.get(k) for k in
                           ("block_q", "block_pages", "time_s", "close")}
                          for r in rows]})
    # reload through the engine's own loader: what we persisted is
    # exactly what a ServingEngine on this generation will pick up
    got = _TD.load_ragged_tile(gen, path=out_path)
    assert got == (best["block_q"], best["block_pages"]), got
    print(f"{os.path.basename(out_path)}[{_TD.generation_key(gen)}] <- "
          f"{entry}", flush=True)
    print(json.dumps({"generation": _TD.generation_key(gen),
                      "best": {"block_q": best["block_q"],
                               "block_pages": best["block_pages"]},
                      "time_us": round(best["time_s"] * 1e6, 2),
                      "n_trials": len(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
