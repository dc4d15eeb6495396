"""One-chip timing of the two latent attention kernels ALONE, at their
benchmark cells' shapes (ISSUE 42).

`kernels/ragged_latent.py` walks a step's runs: a trip is one 512-token
block of a run's context met by the run's rows. The benchmark's trace
has one name a kernel, so it cannot say what a trip costs and what a run
costs beside its trips. This tool can: it calls each kernel by itself on
buffers of one kind,

  * decode rows only, every run one row, at several context lengths
    (whole blocks, so trips = length / 512): a line through the times
    gives us a trip (slope) and us a run (intercept);
  * a prompt's chunk only (whole q blocks: one product a trip);
  * the cell's mix (decode rows first, the chunk behind them, as the
    engine lays a step out),

and reads the custom call's own device time from a profiler trace (the
host's clock around the calls is printed beside it). A handful of the
mix's rows are compared with the `jax.numpy` path first.

    chiprun -- python tools/latent_walk_bench.py            # both cells
    python tools/latent_walk_bench.py --tree .chip_scratch/parent
    python tools/latent_walk_bench.py --cells agent --buffers decode@1536,mix
    JAX_PLATFORMS=cpu python tools/latent_walk_bench.py --smoke

`--tree`: the checkout whose `paddle_tpu` is timed (default: this one),
so one call reads a parent commit and a change on the same chip.
`--smoke`: tiny shapes, kernels interpreted, no trace: it debugs the
command and proves nothing. Without `--smoke` a missing TPU is an error.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HEADS, ROW, RANK, PAGE = 64, 640, 512, 128
# a cell's kernel at the cell's buffer: rows a step, slots, pool and table,
# decode runs, the decode lengths the line is fitted through (whole
# blocks), the cell's own decode length and its prompt chunk
CELLS = {
    "agent": dict(kernel="dense", rows=256, slots=96, pages=3072,
                  seq_pages=96, decode=94, fit=(512, 1536, 3072),
                  depth=2700, chunk=160),
    "longctx": dict(kernel="sparse", rows=512, slots=48, pages=5632,
                    seq_pages=352, decode=48, fit=(2560, 12800, 25600),
                    depth=25000, chunk=464, topk=2048),
}
SMOKE = dict(rows=32, slots=6, pages=40, seq_pages=8, decode=5,
             fit=(8, 16, 24), depth=21, chunk=19, topk=6)


def _buffers(c):
    """name -> (tok_slot, tok_pos) numpy: the buffers described above."""
    import numpy as np

    def empty():
        return np.zeros(c["rows"], np.int32), np.full(c["rows"], -1, np.int32)

    def decode(n, depth):
        slot, pos = empty()
        slot[:n], pos[:n] = np.arange(n), depth - 1
        return slot, pos

    out = {f"decode@{d}": decode(c["slots"], d) for d in c["fit"]}
    out[f"decode@{c['depth']}"] = decode(c["slots"], c["depth"])
    slot, pos = empty()
    n = c["chunk"] // 16 * 16
    slot[:n], pos[:n] = c["slots"] - 1, c["depth"] - n + np.arange(n)
    out["chunk"] = (slot, pos)
    slot, pos = decode(c["decode"], c["depth"])
    n = min(c["chunk"], c["rows"] - c["decode"])
    slot[c["decode"]:c["decode"] + n] = c["slots"] - 1
    pos[c["decode"]:c["decode"] + n] = c["depth"] - n + np.arange(n)
    out["mix"] = (slot, pos)
    return out


def _shape_of(slot, pos, block):
    """(runs, trips, whole-q-block runs, pairs) of a buffer, as the kernel
    cuts it: a run ends at a q block's edge; a trip is a block of a run's
    context, counted ONCE a run on either side of PR 42 (since then the
    rows of a chunk's piece each walk it)."""
    import numpy as np
    on = pos >= 0
    i = np.arange(len(pos))
    cont = (on[1:] & on[:-1] & (slot[1:] == slot[:-1])
            & (pos[1:] == pos[:-1] + 1) & (i[1:] % 16 != 0))
    start = on & ~np.append(False, cont)
    end = on & ~np.append(cont, False)
    first, last = np.nonzero(start)[0], np.nonzero(end)[0]
    trips = -(-(pos[last] + 1) // block)
    whole = (last - first + 1) == 16
    return dict(runs=int(len(first)), trips=int(trips.sum()),
                whole_runs=int(whole.sum()), whole_trips=int(trips[whole].sum()),
                pairs=int((pos[on] + 1).sum()))


def _kernel_ns(trace_dir, needle):
    """Device durations (ns) of the custom calls whose name holds
    `needle`, from the newest trace under `trace_dir`."""
    import jax
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for plane in pd.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                out += [float(e.duration_ns) for e in line.events
                        if needle in e.name]
    return out


def _fit(points):
    """Least squares of t = runs * (a + trips_per_run * b) through
    [(runs, trips, seconds)] -> (us a run, us a trip)."""
    import numpy as np
    x = np.asarray([[r, t] for r, t, _ in points], np.float64)
    y = np.asarray([s for _, _, s in points], np.float64)
    (a, b), *_ = np.linalg.lstsq(x, y, rcond=None)
    return a * 1e6, b * 1e6


def bench_cell(name, c, rl, *, smoke, iters, seed, only=()):
    import jax
    import jax.numpy as jnp
    import numpy as np
    page = 4 if smoke else PAGE
    heads, row, rank = (4, 24, 16) if smoke else (HEADS, ROW, RANK)
    block = rl.latent_block_pages(page, c["seq_pages"]) * page
    dtype = jnp.float32 if smoke else jnp.bfloat16
    sparse = c["kernel"] == "sparse"
    needle = ("ragged_sparse_latent_attention" if sparse
              else "ragged_latent_attention")
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    # every slot its own pages while the pool lasts, then the pool again:
    # the kernel only reads, and HBM has no cache to flatter a shared page
    table = jnp.asarray((np.arange(c["slots"] * c["seq_pages"]).reshape(
        c["slots"], c["seq_pages"])) % c["pages"], jnp.int32)
    latent = (jax.random.normal(key, (1, c["pages"], page, row), jnp.float32)
              * 0.5).astype(dtype)
    q = jnp.asarray(rng.normal(size=(c["rows"], heads, row)) * 0.5, dtype)
    how = dict(interpret=True) if smoke else dict(use_pallas=True)
    kw = dict(rank=rank, sm_scale=np.float32(1 / 16), **how)
    nb = -(-c["seq_pages"] * page // block)

    def call(slot, pos):
        """(jitted kernel call on device arguments, the reference's view)"""
        slot_d, pos_d = jnp.asarray(slot), jnp.asarray(pos)
        runs = jax.jit(lambda s, p: rl.ragged_runs(s, p, heads, rl.ATTN_ROWS))(
            slot_d, pos_d)
        if not sparse:
            fn = jax.jit(lambda q, lat, runs: rl.ragged_latent_attention(
                q, lat, table, slot_d, pos_d, runs=runs, **kw))
            return (lambda: fn(q, latent, runs)), None
        cols = jnp.arange(nb * block, dtype=jnp.int32)
        scores = jax.jit(lambda k: jnp.where(
            cols[None, :] <= pos_d[:, None],
            jax.random.normal(k, (c["rows"], nb * block), jnp.float32),
            -jnp.inf).reshape(c["rows"], nb, block).swapaxes(0, 1))(key)
        thr, at = jax.jit(lambda s, p: rl.dsa_select(
            s, p, c["topk"], **how))(scores, pos_d)
        fn = jax.jit(lambda q, lat, sc, thr, at, runs:
                     rl.ragged_sparse_latent_attention(
                         q, lat, sc, thr, at, table, slot_d, pos_d,
                         runs=runs, **kw))
        return (lambda: fn(q, latent, scores, thr, at, runs)), (scores, thr, at)

    def check(slot, pos, got, sel):
        """A handful of rows (decode, the chunk's edges) against the
        `jax.numpy` path, which gathers a row's whole context."""
        on = np.nonzero(pos >= 0)[0]
        idx = np.unique(np.concatenate([on[:3], on[-3:], on[len(on) // 2:][:2]]))
        ref_kw = dict(rank=rank, sm_scale=kw["sm_scale"], use_pallas=False)
        a = (table, jnp.asarray(slot[idx]), jnp.asarray(pos[idx]))
        if sparse:
            sc, thr, at = sel
            ref = rl.ragged_sparse_latent_attention(
                q[idx], latent, sc[:, idx], thr[idx], at[idx], *a, **ref_kw)
        else:
            ref = rl.ragged_latent_attention(q[idx], latent, *a, **ref_kw)
        gap = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                    - got[idx].astype(jnp.float32))))
        off = np.nonzero(pos < 0)[0]
        return gap, bool(off.size == 0 or not bool(jnp.any(got[off] != 0)))

    rows, points = [], []
    for label, (slot, pos) in _buffers(c).items():
        if only and label not in only:
            continue
        fn, sel = call(slot, pos)
        got = jax.block_until_ready(fn())           # compiles
        line = dict(cell=name, kernel=needle, buffer=label,
                    **_shape_of(slot, pos, block))
        if label == "mix":
            line["max_gap_vs_jnp"], line["slack_rows_zero"] = check(
                slot, pos, got, sel)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out)
        line["host_ms_a_call"] = (time.perf_counter() - t0) / iters * 1e3
        if not smoke:
            with tempfile.TemporaryDirectory() as d:
                jax.profiler.start_trace(d)
                for _ in range(iters):
                    out = fn()
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                ns = sorted(_kernel_ns(d, needle))
            if len(ns) < iters:
                raise SystemExit(f"{len(ns)} events named {needle} in the "
                                 f"trace of {iters} calls")
            line["device_ms_a_call"] = ns[len(ns) // 2] / 1e6
            line["device_ms_min_max"] = [ns[0] / 1e6, ns[-1] / 1e6]
            line["us_a_trip_all_in"] = ns[len(ns) // 2] / 1e3 / line["trips"]
            if label.startswith("decode@") and int(label[7:]) in c["fit"]:
                points.append((line["runs"], line["trips"],
                               ns[len(ns) // 2] / 1e9))
        rows.append(line)
        print(json.dumps(line), flush=True)
    fit = None
    if points:
        a, b = _fit(points)
        fit = dict(cell=name, kernel=needle, decode_us_a_run=a,
                   decode_us_a_trip=b, through=[
                       dict(runs=r, trips=t, ms=s * 1e3) for r, t, s in points])
        print(json.dumps(fit), flush=True)
    return rows, fit


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=ROOT,
                    help="checkout whose paddle_tpu is timed")
    ap.add_argument("--cells", default="agent,longctx")
    ap.add_argument("--buffers", default="",
                    help="only these buffers, by name (decode@1536,mix)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2147400042)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None,
                    help="JSON of every line (default: chiprun_out/"
                         "latent_walk_bench.<tree's name>.json)")
    args = ap.parse_args(argv)
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    import jax
    from paddle_tpu.kernels import ragged_latent as rl
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.smoke:
        raise SystemExit(f"no TPU here (platform={dev.platform}): a time "
                         "comes from a chip run; --smoke debugs the command")
    head = dict(tree=tree, smoke=args.smoke, platform=dev.platform,
                device_kind=dev.device_kind, iters=args.iters, seed=args.seed,
                module=os.path.abspath(rl.__file__))
    print(json.dumps(head), flush=True)
    lines, fits = [], []
    for name in args.cells.split(","):
        c = dict(CELLS[name], **(SMOKE if args.smoke else {}))
        rows, fit = bench_cell(name, c, rl, smoke=args.smoke,
                               iters=2 if args.smoke else args.iters,
                               seed=args.seed, only=tuple(
                                   filter(None, args.buffers.split(","))))
        lines += rows
        fits += [fit] if fit else []
    out = args.out or os.path.join(
        ROOT, "chiprun_out", f"latent_walk_bench.{os.path.basename(tree)}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(dict(head, lines=lines, fits=fits), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
