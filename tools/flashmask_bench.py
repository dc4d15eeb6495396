"""One-chip timing of the three flashmask kernels ALONE, at the two
training cells' per-chip shapes (ISSUE 49).

`ops/flashmask_attention.py` runs a forward, a dQ and a dK/dV kernel over
the blocks a mask leaves live. A training step's trace has them under one
or two names beside everything else, so it cannot say what a block costs,
what a block that does nothing costs, or which block size wins. This tool
calls each kernel by itself, at

  * `pretrain_4k`:        (4, 16, 4096, keys 128 / values 128), documents
                          of median 700 tokens;
  * `sparse_pretrain_8k`: (4, 16, 8192, keys 192 / values 128), documents
                          of median 1,400;

bfloat16, document lengths drawn as those cells' traffic describes them
(lognormal, sigma 1.0, clipped to 16 .. the sequence, packed until the
sequence is full) from `--seed`, at each of 512 / 256 blocks a side,
beside an all-dead mask (every pair masked: what is left is what a call
costs for launching its blocks) and one full-length document (plain
causal). A line gives ms a call of each kernel from the device's own
times in a profiler trace of the forward's program and of the backward's
(`fwd_ms`, `dq_ms`, `dkv_ms`, their sum `three_ms`), the time of whatever
else ran in either program (`fwd_beside_ms`, `bwd_beside_ms`: the pads,
the ranges, the row sums), every custom call the traces held
(`*_custom_calls`: name or result count -> [events, median ms]), and
`flashmask_live_blocks`' `(live, grid)`. One (batch, head) of the packed
mask is compared with the dense reference first, forward and gradients.

    chiprun -- python tools/flashmask_bench.py              # both shapes
    python tools/flashmask_bench.py --tree .chip_scratch/parent
    python tools/flashmask_bench.py --shapes sparse_pretrain_8k --blocks 512x512
    JAX_PLATFORMS=cpu python tools/flashmask_bench.py --smoke

`--tree`: the checkout whose `paddle_tpu` is timed (default: this one),
so one chip call can read a parent commit and a change, a process each.
`--smoke`: tiny shapes, kernels interpreted, no trace: it debugs the
command and proves nothing. Without `--smoke` a missing TPU is an error.
"""
from __future__ import annotations

import argparse
import functools
import glob
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a cell's per-chip attention call and its traffic's documents
SHAPES = {
    "pretrain_4k": dict(b=4, h=16, s=4096, d=128, d_v=128, median=700),
    "sparse_pretrain_8k": dict(b=4, h=16, s=8192, d=192, d_v=128, median=1400),
}
SMOKE = dict(b=1, h=2, s=512, d=24, d_v=16, median=90)
SIGMA, SHORTEST = 1.0, 16


def doc_ends(b, s, median, seed):
    """(b, s) int32: for every key column the end of its document, the
    sequences packed from seeded lognormal lengths until full."""
    import numpy as np
    rng = np.random.default_rng(seed)
    ends = np.zeros((b, s), np.int32)
    for row in ends:
        pos = 0
        while pos < s:
            n = int(np.clip(np.rint(rng.lognormal(math.log(median), SIGMA)),
                            SHORTEST, s))
            row[pos:pos + n] = min(pos + n, s)
            pos += n
    return ends


def masks(c, seed):
    """name -> (b, h, s, 1) int32 start rows of a causal n = 1 mask."""
    import numpy as np
    b, h, s = c["b"], c["h"], c["s"]
    per_row = {"packed": doc_ends(b, s, c["median"], seed),
               "all_dead": np.zeros((b, s), np.int32),
               "one_document": np.full((b, s), s, np.int32)}
    return {k: np.broadcast_to(v[:, None, :, None], (b, h, s, 1))
            for k, v in per_row.items()}


def _device_ops(trace_dir):
    """[(name, start ns, duration ns)] of the newest trace's device
    operations, by start."""
    import jax
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out += [(e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events]
    return sorted(out, key=lambda e: e[1])


def _kernels(ops, shape, iters):
    """One program's trace -> (name -> median ms a call of each Pallas
    kernel in it, ms a call of everything else, what was seen). A
    kernel is a custom call whose results are `shape`-led arrays
    (batch x heads, rows, ...): two of them for the forward (o, lse) and
    for dK/dV, one for dQ. Whatever else the trace names a custom call
    is counted beside the kernels and listed in `seen`, so a trace
    that does not look as expected is reported and not refused."""
    import re
    lead = "[%d,%d," % shape
    groups = {}
    for name, _, ns in ops:
        head = name.split(" custom-call(")[0]
        if "custom-call" not in name:
            key = None
        else:
            arrays = re.findall(r"\w+\[[\d,]*\]", head)
            mine = arrays and all(lead in a for a in arrays)
            key = len(arrays) if mine else re.sub(r"[.\d]+ = .*", "", head)
        groups.setdefault(key, []).append(ns)
    median = lambda ns: sorted(ns)[len(ns) // 2] / 1e6
    seen = {str(k): [len(v), median(v)] for k, v in groups.items()
            if k is not None}
    beside = sum(sum(v) for k, v in groups.items()
                 if not isinstance(k, int)) / iters / 1e6
    return ({k: median(v) for k, v in groups.items() if isinstance(k, int)},
            beside, seen)


def _check(fm, q, k, v, sri, do, scale, blocks, interpret):
    """Max gaps of the three kernels against the dense reference on the
    first (batch, head), float32."""
    import jax
    import jax.numpy as jnp
    one = [x[:1, :1] for x in (q, k, v, sri, do)]
    q1, k1, v1, sri1, do1 = one
    o, lse = fm._fwd_pallas(q1, k1, v1, sri1, True, None, scale, *blocks,
                            interpret)
    got = (o,) + fm._bwd_pallas(q1, k1, v1, sri1, o, lse, do1, True, None,
                                scale, *blocks, interpret)
    f32 = lambda x: x.astype(jnp.float32)
    ref, vjp = jax.vjp(lambda *a: fm.flashmask_reference(
        *a, sri1, True, None, scale)[0], f32(q1), f32(k1), f32(v1))
    want = (ref,) + vjp(f32(do1))
    return {name: float(jnp.max(jnp.abs(f32(g) - w)))
            for name, g, w in zip(("o", "dq", "dk", "dv"), got, want)}


def bench_shape(name, c, fm, *, smoke, iters, seed, blocks):
    import jax
    import jax.numpy as jnp
    import numpy as np
    b, h, s, d, d_v = (c[x] for x in ("b", "h", "s", "d", "d_v"))
    dtype = jnp.float32 if smoke else jnp.bfloat16
    rng = np.random.default_rng(seed)
    q, k = (jnp.asarray(rng.normal(size=(b, h, s, d)) * 0.5, dtype)
            for _ in range(2))
    v, do = (jnp.asarray(rng.normal(size=(b, h, s, d_v)) * 0.5, dtype)
             for _ in range(2))
    scale = 1.0 / math.sqrt(d)
    derived = fm.derived_blocks(s, s, d, dtype, d_v)
    lines = []
    for mask, sri in masks(c, seed).items():
        sri = jnp.asarray(sri)
        for bq, bk in (dict.fromkeys([tuple(derived), *blocks])
                       if mask == "packed" else [derived]):
            fwd = jax.jit(lambda q, k, v, sri: fm._fwd_pallas(
                q, k, v, sri, True, None, scale, bq, bk, smoke))
            bwd = jax.jit(lambda q, k, v, sri, o, lse, do: fm._bwd_pallas(
                q, k, v, sri, o, lse, do, True, None, scale, bq, bk, smoke))
            o, lse = jax.block_until_ready(fwd(q, k, v, sri))
            runs = {"fwd": functools.partial(fwd, q, k, v, sri),
                    "bwd": functools.partial(bwd, q, k, v, sri, o, lse, do)}
            live, grid = fm.flashmask_live_blocks(sri, True, None, bq, bk)
            line = dict(shape=name, mask=mask, block_q=bq, block_k=bk,
                        derived=(bq, bk) == tuple(derived), live=live,
                        grid=grid, blocks_in_all=b * h * -(-s // bq) * -(-s // bk))
            if mask == "packed" and (bq, bk) == tuple(derived):
                line["max_gap_vs_dense"] = _check(fm, q, k, v, sri, do, scale,
                                                  (bq, bk), smoke)

            for kind, fn in runs.items():
                jax.block_until_ready(fn())             # compiles
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = fn()
                jax.block_until_ready(out)
                line[f"{kind}_host_ms"] = \
                    (time.perf_counter() - t0) / iters * 1e3
                if smoke:
                    continue
                with tempfile.TemporaryDirectory() as tmp:
                    jax.profiler.start_trace(tmp)
                    for _ in range(iters):
                        out = fn()
                    jax.block_until_ready(out)
                    jax.profiler.stop_trace()
                    ms, beside, seen = _kernels(
                        _device_ops(tmp), (b * h, -(-s // 128) * 128), iters)
                line[f"{kind}_beside_ms"] = beside
                line[f"{kind}_custom_calls"] = seen
                names = {"fwd": {2: "fwd_ms"},
                         "bwd": {1: "dq_ms", 2: "dkv_ms"}}[kind]
                line.update({names[n]: v for n, v in ms.items() if n in names})
            if all(k in line for k in ("fwd_ms", "dq_ms", "dkv_ms")):
                line["three_ms"] = line["fwd_ms"] + line["dq_ms"] + line["dkv_ms"]
            lines.append(line)
            print(json.dumps(line), flush=True)
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=ROOT,
                    help="checkout whose paddle_tpu is timed")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--blocks", default="512x512,512x256,256x512,256x256",
                    help="block_q x block_k, the packed mask at each")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=2147400049)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None,
                    help="JSON of every line (default: chiprun_out/"
                         "flashmask_bench.<tree's name>.json)")
    args = ap.parse_args(argv)
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    import jax
    from paddle_tpu.ops import flashmask_attention as fm
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.smoke:
        raise SystemExit(f"no TPU here (platform={dev.platform}): a time "
                         "comes from a chip run; --smoke debugs the command")
    blocks = [tuple(int(x) for x in pair.split("x"))
              for pair in args.blocks.split(",")]
    head = dict(tree=tree, smoke=args.smoke, platform=dev.platform,
                device_kind=dev.device_kind, iters=args.iters, seed=args.seed,
                module=os.path.abspath(fm.__file__))
    print(json.dumps(head), flush=True)
    lines = []
    for name in args.shapes.split(","):
        c = dict(SHAPES[name], **(SMOKE if args.smoke else {}))
        lines += bench_shape(
            name, c, fm, smoke=args.smoke, iters=2 if args.smoke else args.iters,
            seed=args.seed, blocks=[(128, 128), (256, 128)] if args.smoke
            else blocks)
    out = args.out or os.path.join(
        ROOT, "chiprun_out", f"flashmask_bench.{os.path.basename(tree)}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(dict(head, lines=lines), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
