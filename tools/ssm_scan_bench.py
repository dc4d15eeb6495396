"""One-chip timing of the state-space kernels ALONE, at their benchmark
cell's shapes (`kernels/ragged_ssm.py`; ISSUE 48).

The benchmark's trace has one name a kernel and one mix of rows, so it
cannot say what a decode row's pass over its slot's state costs and what a
prompt row costs beside it. This tool calls `ragged_scan` and `ragged_conv`
by themselves on buffers of one kind (decode rows only, one run a slot; a
prompt's chunk only; the cell's mix: every other slot decoding, a chunk
behind them) and prints the host's clock around the calls (the median of
`--reps`, each ended by `block_until_ready`) beside the least time the
state's bytes take. The mix's values are compared with the `jax.numpy`
path first: what Mosaic compiled against the recurrence row by row.

    chiprun -- python tools/ssm_scan_bench.py
    JAX_PLATFORMS=cpu python tools/ssm_scan_bench.py --smoke

`--smoke`: tiny shapes, kernels interpreted: it debugs the command and
proves nothing. Without `--smoke` a missing TPU is an error.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# nemotron-3-nano-30b-a3b.serve1: rows a step, slots, Mamba layers held,
# heads x head size, groups, state size, convolution taps
CELL = dict(rows=256, slots=128, layers=4, heads=64, p=64, g=8, n=128, k=4,
            chunk=128)
SMOKE = dict(rows=24, slots=6, layers=2, heads=4, p=8, g=2, n=16, k=4,
             chunk=8)
HBM = 819e9


def buffers(c):
    """name -> (tok_slot, tok_pos) numpy."""
    import numpy as np
    t, s = c["rows"], c["slots"]

    def lay(decode, chunk):
        slot, pos = np.zeros(t, np.int32), np.full(t, -1, np.int32)
        slot[:decode] = np.arange(decode)
        pos[:decode] = 300 + 7 * np.arange(decode)
        if chunk:
            slot[decode:decode + chunk] = s - 1
            pos[decode:decode + chunk] = 256 + np.arange(chunk)
        return slot, pos
    return {"decode": lay(s, 0), "chunk": lay(0, c["chunk"]),
            "mix": lay(s - 1, c["chunk"])}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--state", default="float32")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.kernels import ragged_ssm
    if not args.smoke and jax.default_backend() != "tpu":
        sys.exit("ssm_scan_bench: no TPU (use --smoke on the CPU)")
    c = SMOKE if args.smoke else CELL
    kw = dict(interpret=True) if args.smoke else dict(use_pallas=True)
    t, heads, p, g, n, k = (c[x] for x in ("rows", "heads", "p", "g", "n", "k"))
    chans, conv_dim = heads * p, heads * p + 2 * g * n
    width = ragged_ssm.conv_tile(conv_dim)
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, b, cc = f(t, heads, p), f(t, g, n), f(t, g, n)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (t, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (heads,)), jnp.float32)
    # rounded as the carried rows are kept (the model hands them over so)
    u = f(t, conv_dim).astype(jnp.bfloat16).astype(jnp.float32)
    w, bias = f(k, conv_dim), f(conv_dim)
    state0 = f(c["layers"], c["slots"], n, chans).astype(args.state)
    conv0 = f(c["layers"], c["slots"], k - 1, conv_dim // width,
              width).astype(jnp.bfloat16)
    print(f"device: {jax.devices()[0].device_kind}; rows {t}, slots "
          f"{c['slots']}, state {state0.dtype} {state0.nbytes / 1e9:.3f} GB")

    def scan(state, slot, pos, **how):
        return ragged_ssm.ragged_scan(x, dt, a, b, cc, state, 1, slot, pos,
                                      **how)

    def conv(state, slot, pos, **how):
        return ragged_ssm.ragged_conv(u, state, 1, w, bias, slot, pos, **how)

    for name, (slot, pos) in buffers(c).items():
        slot, pos = jnp.asarray(slot), jnp.asarray(pos)
        runs = int(ragged_ssm.ssm_runs(slot, pos, c["slots"])[1])
        rows = int((pos >= 0).sum())
        for op, fn, start in (("scan", scan, state0), ("conv", conv, conv0)):
            fast = jax.jit(lambda s, fn=fn: fn(s, slot, pos, **kw),
                           donate_argnums=0)
            if name == "mix":
                y0, s0 = jax.jit(lambda s, fn=fn: fn(s, slot, pos))(start)
                y1, s1 = fast(jnp.copy(start))
                print(f"  {op} against the jax.numpy path: y "
                      f"{float(jnp.abs(y0 - y1).max()):.3g} of "
                      f"{float(jnp.abs(y0).max()):.3g}, state "
                      f"{float(jnp.abs(s0.astype(jnp.float32) - s1.astype(jnp.float32)).max()):.3g}")
            state = jnp.copy(start)
            times = []
            for _ in range(args.reps + 2):
                t0 = time.perf_counter()
                y, state = fast(state)
                jax.block_until_ready((y, state))
                times.append(time.perf_counter() - t0)
            ms = 1e3 * float(np.median(times[2:]))
            block = start[0, 0].nbytes
            least = 1e3 * runs * 2 * block / HBM
            line = f"{name:7s} {op}: {runs:3d} runs {rows:3d} rows " \
                   f"bytes {least:.3f} ms"
            print(line if args.smoke else line + f"  host clock {ms:.3f} ms "
                  f"= {100 * least / ms:.1f}% of the bytes' time")
    return 0


if __name__ == "__main__":
    sys.exit(main())
