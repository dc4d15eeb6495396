"""Driver of the `train_steps` traffic kind: the compiled step with its
state is built once in set-up, driven from the seed through its first
steps (which the reference follows), and that same object is handed to the
window. Steps are fed from the host each step and dispatched one ahead of
the step being waited for, as a training loop does."""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks import costs, harness, traffic_gen

now = time.monotonic
CHECK_STEPS = 3


def _host(tree):
    import jax
    return jax.tree_util.tree_map(lambda x: float(np.asarray(x)), tree)


def _worst_leaf(got, ref):
    """Worst leaf of |norm_got - norm_ref| over max(norm_ref of that leaf,
    norm_ref of the median leaf): some gradients are all but zero."""
    import jax
    g = np.array(jax.tree_util.tree_leaves(got))
    r = np.array(jax.tree_util.tree_leaves(ref))
    return float(np.max(np.abs(g - r) / np.maximum(r, np.median(r))))


def _reference(cfg, seed, batches, devices, precision=None):
    """The first steps by the plain reference, float32 at `highest`, spread
    over the chips by GSPMD so that it fits; nothing of the program in it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    ref = harness.load_module("reference", cfg["family"])
    model = harness.load_module("models", cfg["family"])
    mesh = Mesh(np.asarray(devices), ("x",))

    def spec(axis, shape):
        parts = [None] * len(shape)
        if axis is not None:
            parts[axis] = "x"
        return NamedSharding(mesh, P(*parts))
    shard = jax.tree_util.tree_map(
        spec, ref.SHARD_AXIS, model.shapes(cfg["model"]),
        is_leaf=lambda x: x is None or isinstance(x, int))
    stored = model.init_weights(cfg["model"], seed, jnp.bfloat16, shard)
    repl = NamedSharding(mesh, P())
    batches = [tuple(jax.device_put(jnp.asarray(a), repl) for a in b)
               for b in batches]
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, bs: ref.adamw_steps(
            jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p), bs,
            cfg["model"], cfg["optimizer"], precision=precision))(
                stored, batches)
    got = {"losses": [float(x) for x in np.asarray(out["losses"])],
           "grad_norms": _host(out["grad_norms"]),
           "delta_norms": _host(out["delta_norms"])}
    del stored, out
    return got


def _first_steps(step, params, state, feed, model, cfg, seed, shardings):
    """The step's first CHECK_STEPS steps, through the window's own call and
    feed -> (params, state, what the reference is compared with): each loss,
    the per-leaf norm of the first gradient as the optimizer got it (from
    the first moment after one step), the per-leaf norm of the parameters'
    change (master weights against the seeded ones, made again)."""
    import jax
    import jax.numpy as jnp
    b1 = cfg["optimizer"]["b1"]
    slot = lambda key: (lambda x: isinstance(x, dict) and key in x)  # noqa: E731
    prog = {"losses": []}
    for i in range(CHECK_STEPS):
        params, state, loss = step(params, state, jnp.asarray(i), feed(i))
        prog["losses"].append(float(loss))
        if i == 0:
            prog["grad_norms"] = _host(jax.jit(lambda s: jax.tree_util.tree_map(
                lambda l: jnp.sqrt(jnp.sum(jnp.square(l["m"]))) / (1 - b1),
                s, is_leaf=slot("m")))(state))
    p0 = model.init_weights(cfg["model"], seed, params["embed"].dtype, shardings)
    prog["delta_norms"] = _host(jax.jit(lambda s, p: jax.tree_util.tree_map(
        lambda l, q: jnp.sqrt(jnp.sum(jnp.square(
            l["master"] - q.astype(jnp.float32)))), s, p,
        is_leaf=slot("master")))(state, p0))
    return params, state, prog


def _window(ctx, step, params, state, feed, annotate, tracer):
    """Steps until the window closes, each dispatched before the one before
    it is waited for. -> (completion times, last loss)."""
    import jax.numpy as jnp
    t_open = now()
    ctx.window_opened(t_open)
    t_close = t_open + ctx.args.seconds
    if tracer:
        tracer.start()
    i, pending, done_at = CHECK_STEPS, None, []

    def wait():
        pending.block_until_ready()
        done_at.append(now())

    while now() < t_close:
        with annotate("bench.train_step"):
            params, state, loss = step(params, state, jnp.asarray(i), feed(i))
        if pending is not None:
            wait()
        pending, i = loss, i + 1
        if tracer and not tracer.stopped and \
                now() - t_open >= tracer.seconds:
            wait()                      # the trace ends on a step's end
            pending = None
            tracer.stop()
    if pending is not None:
        wait()
    if tracer and not tracer.stopped:
        tracer.stop()
    return t_open, done_at, float(loss)


def run(ctx):
    import jax
    args, cfg, spec = ctx.args, ctx.config, ctx.traffic
    model = harness.load_module("models", cfg["family"])
    devices = jax.devices()[:ctx.cell["chips"]]
    pool = traffic_gen.packed_batches(
        spec, args.seed, max(spec["distinct_batches"], CHECK_STEPS),
        cfg["model"]["vocab_size"])
    tokens_per_step = int(pool[0][0].size)
    pairs = float(np.mean([sum(costs.attended_pairs(
        b[0].shape[1], b[2][r] if len(b) > 2 else None)
        for r in range(b[0].shape[0])) for b in pool]))
    ctx.facts.update(tokens_per_step=tokens_per_step, pairs_per_step=pairs,
                     batches=len(pool))
    checks, e2e, steps_done, tracer = [], {}, 0, None
    if ctx.control:
        # the control stands in the program's place; no window is needed
        ctx.window_opened(now())
        prog = _reference(cfg, args.seed, pool[:CHECK_STEPS], devices,
                          precision=ctx.control)
    else:
        t = model.build_trainer(cfg, args.seed, devices)

        def feed(i):
            return tuple(jax.device_put(a, t["batch_sharding"])
                         for a in pool[i % len(pool)])
        params, state, prog = _first_steps(
            t["step"], t.pop("params"), t.pop("opt_state"), feed, model, cfg,
            args.seed, t["param_shardings"])
        compiles = harness.CompileCounter()
        tracer = harness.Tracer(ctx, spec) if args.trace else None
        t_open, done_at, last_loss = _window(
            ctx, t["step"], params, state, feed,
            harness.annotator(args.trace), tracer)
        steps_done = len(done_at)
        e2e["train_tokens_per_s"] = \
            steps_done * tokens_per_step / (done_at[-1] - t_open)
        ctx.say(f"train: {steps_done} steps of {tokens_per_step} tokens in "
                f"the window; last loss {last_loss:.4f}; first losses "
                f"{prog['losses']}")
        ctx.say_time(f"train: window of {done_at[-1] - t_open:.3f} s")
        checks = [("compiles_in_window", compiles.since_mark(), 0),
                  ("last_loss_not_finite", 0 if np.isfinite(last_loss) else 1, 0)]
        del params, state, t
        gc.collect()
    peak = harness.memory_peak_bytes()

    t_ref = now()
    ref = _reference(cfg, args.seed, pool[:CHECK_STEPS], devices)
    ctx.say_time(f"reference: {CHECK_STEPS} steps took {now() - t_ref:.1f} s")
    ctx.say(f"reference: losses {ref['losses']}")
    ctx.facts.update(steps_in_window=steps_done)
    tol = cfg["tolerance"]
    checks = [
        ("loss_gap", max(abs(a - b) for a, b in zip(prog["losses"],
                                                    ref["losses"])),
         tol["loss_gap"]),
        ("first_grad_norm_gap_worst_leaf",
         _worst_leaf(prog["grad_norms"], ref["grad_norms"]),
         tol["first_grad_norm_gap_worst_leaf"]),
        ("param_change_norm_gap_worst_leaf",
         _worst_leaf(prog["delta_norms"], ref["delta_norms"]),
         tol["param_change_norm_gap_worst_leaf"])] + checks
    return harness.Outcome(checks=checks, attempted=max(steps_done, CHECK_STEPS),
                           failed=0, end_to_end=e2e, memory_peak_bytes=peak,
                           tracer=tracer)
