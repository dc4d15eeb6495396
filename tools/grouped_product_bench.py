"""One-chip timing of the experts' grouped products ALONE, at the three
MoE cells' shapes and group sizes (ISSUE 44).

`parallel/moe.grouped_product` is `jax.lax.ragged_dot`, which the TPU
compiler lowers to a grouped matmul whose ROW TILE it derives from the
length of the row buffer (the largest power of two, at most 512, that
divides it: `ragged_dot_tiling="tm,tk,tn"` in the compiled text). Every
(expert, row tile) visit multiplies a whole `tm x tk x tn` block, masked
rows and all, so at decode's three to five rows an expert the tile
decides whether a call is bound by the weights' bytes or by products of
rows nobody owns. The benchmark's trace has one name for all of a
step's products; this tool times one product by itself:

  * `raw@<length>`: `lax.ragged_dot` over a row buffer of that length
    (the program's own, and the next `tile x odd` for tiles 8 to 256),
    the same rows and group sizes in each: what the compiler's kernel
    costs at each tile;
  * `tree@<length>`: the timed checkout's `grouped_product` at the
    length the program hands it (its `tiled_rows`; before PR 44 the
    rows themselves);
  * `layer`: the timed checkout's whole `dropless_experts` (the sort,
    the gathers, three products, the weighted sum) on a step's rows;

and reads the custom call's own device time from a profiler trace,
median of `--iters` calls, beside the time the touched experts' bytes
take at the chip's 819 GB/s. A handful of rows are compared with a
matmul a row first.

    chiprun -- python tools/grouped_product_bench.py         # every shape
    python tools/grouped_product_bench.py --tree .chip_scratch/parent
    python tools/grouped_product_bench.py --shapes laguna_gate_up,glm_down
    JAX_PLATFORMS=cpu python tools/grouped_product_bench.py --smoke

`--tree`: the checkout whose `paddle_tpu` is timed (default: this one),
so one call reads a parent commit and a change on the same chip.
`--smoke`: tiny shapes, no trace: it debugs the command and proves
nothing. Without `--smoke` a missing TPU is an error.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HBM_BYTES_A_SECOND = 819e9        # v5e (benchmarks/peaks.json)
TILES = (8, 16, 32, 64, 128, 256)
# a product as a cell launches it: the rows of the buffer, K, N, the
# groups, and the rows that belong to a group (the cells' traced runs,
# PERF.md section 5: 880 assignments over 256 experts a Laguna layer,
# about 78 and 59 to the 16 held of GLM-5's and LongCat's)
SHAPES = {
    "laguna_gate_up": dict(rows=1024, k=2048, n=512, groups=256, real=880),
    "laguna_down": dict(rows=1024, k=512, n=2048, groups=256, real=880),
    "glm_gate_up": dict(rows=1024, k=6144, n=2048, groups=16, real=78),
    "glm_down": dict(rows=1024, k=2048, n=6144, groups=16, real=78),
    "longcat_gate_up": dict(rows=384, k=6144, n=2048, groups=16, real=59),
    "longcat_down": dict(rows=384, k=2048, n=6144, groups=16, real=59),
}
# a layer as a cell's step runs it: rows of the flat buffer and how many
# hold a token, picks a row, hidden and expert width, experts held of
# the experts routed over (`picks_over`: LongCat's router is 768 wide)
LAYERS = {
    "laguna": dict(t=128, live=110, k=8, h=2048, f=512, held=256,
                   experts=256, picks_over=256),
    "glm": dict(t=512, live=156, k=8, h=6144, f=2048, held=16,
                experts=256, picks_over=256),
    "longcat": dict(t=256, live=235, k=12, h=6144, f=2048, held=16,
                    experts=512, picks_over=768),
}
SMOKE_SHAPE = dict(rows=48, k=32, n=16, groups=6, real=20)
# a share whose few sorted rows (128) are fewer than its assignments
# (512), so that the smoke goes through `dropless_experts`' conditional
SMOKE_LAYER = dict(t=64, live=50, k=8, h=32, f=16, held=4, experts=64,
                   picks_over=64)


def tile_lengths(n, tiles=TILES):
    """`n` itself and, a tile, the next `tile x odd` at or past `n`: the
    lengths the compiler gives that tile."""
    return sorted({n} | {(-(-n // t) | 1) * t for t in tiles})


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else None


def _timed(fn, args, *, iters, smoke, module):
    """`fn(*args)` compiled once under the name `module` and called
    `iters` times: {its `ragged_dot_tiling`s (the TPU compiler's; none
    on the CPU); host ms a call; from the trace, read as the benchmark
    reads it (`benchmarks/xplane.py`): the products' median ms, all of a
    call's summed, their count a call, the metadata kernel's ms and the
    module's} and the first call's result."""
    import jax
    from benchmarks import xplane

    def named(*a):
        return fn(*a)
    named.__name__ = named.__qualname__ = module    # the jit's module
    call = jax.jit(named).lower(*args).compile()
    line = {"tiling": sorted(set(re.findall(
        r'ragged_dot_tiling="([^"]*)"', call.as_text())))}
    first = jax.block_until_ready(call(*args))

    def run():
        for _ in range(iters):
            out = call(*args)
        jax.block_until_ready(out)

    t0 = time.perf_counter()
    run()
    line["host_ms_a_call"] = (time.perf_counter() - t0) / iters * 1e3
    if smoke:
        return line, first
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        run()
        jax.profiler.stop_trace()
        dev, = xplane.load(xplane.newest_xplane(d))["devices"].values()
    ops = [(label, ns) for label, _, ns in dev["ops"]
           if label.startswith("ragged-dot")]
    meta = [ns for label, ns in ops if label.startswith("ragged-dot-metadata")]
    dots = [ns for label, ns in ops
            if not label.startswith("ragged-dot-metadata")]
    mods = [ns for name, _, ns in dev["modules"] if module in name]
    if len(dots) < iters or len(dots) % iters or len(mods) < iters:
        raise SystemExit(f"{len(dots)} ragged-dot events and {len(mods)} "
                         f"modules named {module} in the trace of {iters} "
                         "calls")
    line.update(
        products_a_call=len(dots) // iters,
        product_ms=_median(dots) / 1e6,
        product_ms_min_max=[min(dots) / 1e6, max(dots) / 1e6],
        products_ms_a_call=sum(dots) / iters / 1e6,
        metadata_ms_a_call=sum(meta) / iters / 1e6,
        module_ms_a_call=_median(mods) / 1e6)
    return line, first


def bench_shape(name, s, moe, *, smoke, iters, seed):
    """A product alone: `raw@` every length, `tree@` the program's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    dtype = jnp.float32 if smoke else jnp.bfloat16
    sizes = rng.multinomial(s["real"], np.full(
        s["groups"], 1.0 / s["groups"])).astype(np.int32)
    touched = int((sizes > 0).sum())
    rhs = (jax.random.normal(key, (s["groups"], s["k"], s["n"]), jnp.float32)
           * 0.05).astype(dtype)
    lengths = tile_lengths(s["rows"], (4, 8) if smoke else TILES)
    # what the timed checkout launches this product over (before PR 44:
    # the rows themselves)
    own = getattr(moe, "tiled_rows", lambda rows, groups: rows)(
        s["rows"], s["groups"])
    longest = max(lengths + [own])
    rows = (jax.random.normal(jax.random.fold_in(key, 1),
                              (longest, s["k"]), jnp.float32)).astype(dtype)
    sizes_d = jnp.asarray(sizes)
    group_of = np.repeat(np.arange(s["groups"]), sizes)
    some = np.unique(np.concatenate([
        np.arange(min(3, s["real"])), [s["real"] // 2], [s["real"] - 1]]))
    want = jnp.einsum("mk,mkn->mn", rows[some].astype(jnp.float32),
                      rhs[group_of[some]].astype(jnp.float32),
                      precision="highest")
    weight_bytes = touched * s["k"] * s["n"] * rhs.dtype.itemsize
    head = dict(shape=name, k=s["k"], n=s["n"], groups=s["groups"],
                real_rows=s["real"], experts_touched=touched,
                rows_max_expert=int(sizes.max()),
                bytes_ms=(weight_bytes + s["real"] * (s["k"] * 2 + s["n"] * 4))
                / HBM_BYTES_A_SECOND * 1e3)

    def raw(lhs, rhs, sizes):
        return jax.lax.ragged_dot(lhs, rhs, sizes,
                                  preferred_element_type=jnp.float32)

    lines = []
    for what, fn, todo in (("raw", raw, lengths),
                           ("tree", moe.grouped_product, [own])):
        for length in todo:
            timed, got = _timed(fn, (rows[:length], rhs, sizes_d),
                                iters=iters, smoke=smoke,
                                module=f"{what}_{name}_{length}")
            line = dict(head, buffer=f"{what}@{length}",
                        out_shape=list(got.shape),
                        max_gap_vs_matmul=float(jnp.max(jnp.abs(
                            got[some] - want))), **timed)
            if "product_ms" in line:
                line["bytes_share_pct"] = (
                    100 * head["bytes_ms"] / line["products_ms_a_call"])
            lines.append(line)
            print(json.dumps(line), flush=True)
    return lines


def bench_layer(name, c, moe, *, smoke, iters, seed):
    """The whole `dropless_experts` of the timed checkout on a step's
    rows: (three products' ms, the module's ms) a call."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed + 1)
    key = jax.random.PRNGKey((seed + 1) % (2 ** 31))
    dtype = jnp.float32 if smoke else jnp.bfloat16
    t, k = c["t"], c["k"]
    picks = np.argsort(rng.random((t, c["picks_over"])), axis=1)[:, :k]
    picks[c["live"]:] = -1                          # slack rows
    weight = rng.random((t, k)).astype(np.float32)
    ws = [(jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
           * 0.05).astype(dtype)
          for i, shape in enumerate([(c["held"], c["h"], c["f"])] * 2
                                    + [(c["held"], c["f"], c["h"])])]
    x = jax.random.normal(jax.random.fold_in(key, 9), (t, c["h"]),
                          jnp.float32).astype(dtype)
    share = {} if c["held"] == c["experts"] else dict(
        first=0, num_experts=c["experts"])
    timed, (out, got) = _timed(
        lambda x, e, w, *ws: moe.dropless_experts(x, e, w, *ws, **share),
        (x, jnp.asarray(picks, jnp.int32), jnp.asarray(weight), *ws),
        iters=iters, smoke=smoke, module=f"layer_{name}")
    got = np.asarray(got)
    # a matmul a held assignment, for three rows
    xf = np.asarray(x, np.float32)
    wf = [np.asarray(w, np.float32) for w in ws]
    gap = 0.0
    for r in (0, c["live"] // 2, c["live"] - 1):
        ref = np.zeros(c["h"], np.float32)
        for j in range(k):
            e = picks[r, j]
            if 0 <= e < c["held"]:
                a = xf[r] @ wf[0][e]
                hid = (a / (1 + np.exp(-a))) * (xf[r] @ wf[1][e])
                hid = np.asarray(jnp.asarray(hid).astype(dtype), np.float32)
                ref += weight[r, j] * (hid @ wf[2][e])
        gap = max(gap, float(np.max(np.abs(ref - np.asarray(out[r])))))
    touched = int((got > 0).sum())
    expert_bytes = 3 * c["h"] * c["f"] * ws[0].dtype.itemsize
    line = dict(layer=name, buffer="layer", rows=t, live_rows=c["live"],
                assignments=int(got.sum()), experts_touched=touched,
                rows_max_expert=int(got.max()),
                bytes_ms=touched * expert_bytes / HBM_BYTES_A_SECOND * 1e3,
                max_gap_vs_matmul=gap, **timed)
    print(json.dumps(line), flush=True)
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=ROOT,
                    help="checkout whose paddle_tpu is timed")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--layers", default=",".join(LAYERS))
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2147400044)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None,
                    help="JSON of every line (default: chiprun_out/"
                         "grouped_product_bench.<tree's name>.json)")
    args = ap.parse_args(argv)
    tree = os.path.abspath(args.tree)
    sys.path[:0] = [tree, ROOT]         # `benchmarks.xplane` is in either
    import jax
    from paddle_tpu.parallel import moe
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.smoke:
        raise SystemExit(f"no TPU here (platform={dev.platform}): a time "
                         "comes from a chip run; --smoke debugs the command")
    head = dict(tree=tree, smoke=args.smoke, platform=dev.platform,
                device_kind=dev.device_kind, iters=args.iters, seed=args.seed,
                module=os.path.abspath(moe.__file__))
    print(json.dumps(head), flush=True)
    how = dict(smoke=args.smoke, iters=2 if args.smoke else args.iters,
               seed=args.seed)
    lines = []
    for name in filter(None, args.shapes.split(",")):
        lines += bench_shape(name, SMOKE_SHAPE if args.smoke else SHAPES[name],
                             moe, **how)
    for name in filter(None, args.layers.split(",")):
        lines.append(bench_layer(
            name, SMOKE_LAYER if args.smoke else LAYERS[name], moe, **how))
    out = args.out or os.path.join(
        ROOT, "chiprun_out",
        f"grouped_product_bench.{os.path.basename(tree)}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(dict(head, lines=lines), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
