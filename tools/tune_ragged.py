"""Offline autotuner for the serving ragged-paged-attention kernel tile
(ISSUE 12; ROADMAP item-1 follow-on — "real-TPU tile-size tuning for
the kernel").

Sweeps legal (block_q, block_pages) tile configs of
`paddle_tpu.kernels.ragged_paged_attention` on the attached backend
over a serving-shaped problem (a decode+prefill wave), verifies every
candidate is BIT-identical to the default tile (the kernel's contract
— a tile choice must never change a sampled token), and persists the
per-TPU-generation winner into TUNED.kernels.json via
`_tuning_defaults.save_ragged_tile`. The serving engine loads that
file ONCE at construction (`load_ragged_tile(device_generation())`),
so a tuned tile is a static jit arg — it never retraces a live trace.

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


def make_problem(smoke, seed=0):
    """A serving-shaped wave: prefill run + decodes + slack rows, GQA
    q/kv heads, paged KV. Smoke keeps every dim tiny (interpret-mode
    pallas multiplies cost ~100x)."""
    import numpy as np
    import jax.numpy as jnp

    if smoke:
        qh, kvh, d, page, pages_per_seq, slots, t = 4, 2, 16, 8, 4, 3, 16
    else:
        qh, kvh, d, page, pages_per_seq, slots, t = 32, 8, 128, 16, 32, 8, 64
    num_pages = slots * pages_per_seq + 1
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((t, qh, d)).astype(np.float32)
    kshape = (kvh, num_pages, page, d)
    k_pages = rng.standard_normal(kshape).astype(np.float32)
    v_pages = rng.standard_normal(kshape).astype(np.float32)
    ptab = np.arange(slots * pages_per_seq, dtype=np.int32).reshape(
        slots, pages_per_seq)
    # slot 0: a prefill run filling half the buffer; remaining slots:
    # deep decodes (max pages in play — the config that tiling moves);
    # tail: inactive slack rows, the kernel's early-exit path
    n_pf = t // 2
    tok_slot = np.zeros((t,), np.int32)
    tok_pos = np.full((t,), -1, np.int32)
    tok_pos[:n_pf] = np.arange(n_pf, dtype=np.int32)
    depth = pages_per_seq * page - 1
    for i, s in enumerate(range(1, slots)):
        row = n_pf + i
        if row >= t:
            break
        tok_slot[row] = s
        tok_pos[row] = depth - i
    return (jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(ptab), jnp.asarray(tok_slot), jnp.asarray(tok_pos))


def candidate_tiles(group, n_pages, smoke):
    """Legal (block_q, block_pages) grid: block_q sublane-aligned and
    >= the GQA group (0 = derive the seed shape), block_pages within
    the page-table depth. The seed tile (0, 1) always leads — it is
    the verified baseline every other config must bit-match."""
    from paddle_tpu.ops.paged_attention import MIN_GROUP

    gp_min = group + (-group) % MIN_GROUP
    qs = [0] + [gp_min * m for m in (2, 4)]
    ps = [1, 2, 4, 8]
    if smoke:
        qs, ps = [0, gp_min * 2], [1, 2]
    return [(bq, bp) for bq in qs for bp in ps
            if bp <= max(n_pages, 1)]


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
    from paddle_tpu.kernels import ragged_paged_attention

    backend = jax.default_backend()
    if use_pallas is None:
        use_pallas = backend == "tpu" or smoke
    if interpret is None:
        interpret = backend != "tpu"
    q, k, v, ptab, slot, pos = make_problem(smoke)
    group = q.shape[1] // k.shape[0]
    n_pages = ptab.shape[1]
    rows = []
    base_out = None
    for bq, bp in candidate_tiles(group, n_pages, smoke):
        cfg = {"block_q": bq, "block_pages": bp}

        def run(bq=bq, bp=bp):
            return ragged_paged_attention(
                q, k, v, ptab, slot, pos, use_pallas=use_pallas,
                interpret=interpret, block_q=bq or None,
                block_pages=bp or None)
        try:
            out, t = time_config(run, iters)
        except Exception as e:   # Mosaic rejection on a real chip
            print(f"  tile {cfg} FAILED: {e}", flush=True)
            rows.append(dict(cfg, time_s=None, exact=False,
                             error=str(e)[:200]))
            continue
        out = np.asarray(out)
        if base_out is None:
            base_out = out           # the seed tile leads the grid
        exact = bool(np.array_equal(base_out, out))
        rows.append(dict(cfg, time_s=t, exact=exact))
        print(f"  tile {cfg}: {t * 1e6:.1f} us/call"
              f"{'' if exact else '  NOT BIT-IDENTICAL — rejected'}",
              flush=True)
    ok = [r for r in rows if r["time_s"] is not None and r["exact"]]
    if not ok:
        raise RuntimeError("every tile config failed or diverged")
    best = min(ok, key=lambda r: r["time_s"])
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
                           ("block_q", "block_pages", "time_s", "exact")}
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
