"""Stub bench child for autotune.py's smoke mode (PT_TUNE_SMOKE=1).

Reads the same PT_BENCH_* / PT_FLASH_* env knobs a real bench.py child
would, and answers with a deterministic fake tok/s landscape that has a
single known peak — so tests can assert the staged search actually
finds it.  Fault injection via PT_SMOKE_FAULT exercises every guard in
run_trial():

  cpu     — emit backend:"cpu" (the child ran bench.py's CPU smoke)
  crash   — exit non-zero with noise on stderr
  garbage — exit 0 but print no parseable JSON line
  hang    — sleep past the trial timeout

PT_SMOKE_FAULT_BLOCK_Q, if set, applies the fault only to trials at
that block_q — lets a test poison one stage-B config while the rest of
the search proceeds.
"""
import json
import os
import sys
import time


def main():
    batch = int(os.environ.get("PT_BENCH_BATCH", "16"))
    seq = int(os.environ.get("PT_BENCH_SEQ", "2048"))
    remat = os.environ.get("PT_BENCH_REMAT", "true")
    bq = int(os.environ.get("PT_FLASH_BLOCK_Q", "128"))
    bk = int(os.environ.get("PT_FLASH_BLOCK_K", "128"))
    nm = int(os.environ.get("PT_BENCH_NMICRO", "0"))
    fce = os.environ.get("PT_FUSED_CE", "0") == "1"

    fault = os.environ.get("PT_SMOKE_FAULT", "")
    only_bq = os.environ.get("PT_SMOKE_FAULT_BLOCK_Q")
    if fault and (only_bq is None or int(only_bq) == bq):
        if fault == "hang":
            time.sleep(3600)
        if fault == "crash":
            print("fake Mosaic OOM: exhausted VMEM", file=sys.stderr)
            sys.exit(7)
        if fault == "garbage":
            print("no json here, just vibes")
            return
        extra = {"backend": "cpu", "mfu": 0.01}
        print(json.dumps({"metric": "smoke", "value": 1.0, "unit": "tok/s",
                          "vs_baseline": 0.0, "extra": extra}))
        return

    # Deterministic landscape, peaked at batch=64, remat=true,
    # fused_ce=True, n_micro=2, (block_q, block_k)=(256, 512) — the
    # shape the first honest on-chip stage-A pass suggested (2026-08-01:
    # full-remat MFU climbs with batch, dots disappoints, the grad-accum
    # corner wins at the HBM wall).  Tests assert the staged search
    # lands exactly there.
    v = 10_000.0
    v += {8: 100, 16: 500, 24: 1400, 32: 1500, 40: 1700,
          48: 2000, 64: 2200}.get(batch, 0)
    v += {"true": 800, "dots": 600, "false": 400}.get(remat, 0)
    v += 1200 if fce else 0
    v += {(128, 128): 0, (256, 256): 600, (256, 512): 900,
          (512, 256): 300, (512, 512): 500}.get((bq, bk), 0)
    v += {0: 0, 2: 250, 4: -400}.get(nm, 0)
    mfu = round(v / 58_000.0, 4)
    print(json.dumps({
        "metric": f"smoke llama-{seq}x{batch}", "value": v,
        "unit": "tok/s", "vs_baseline": 0.0,
        "extra": {"backend": "tpu", "mfu": mfu,
                  "mfu_legacy": round(mfu * 1.13, 4)}}))


if __name__ == "__main__":
    main()
