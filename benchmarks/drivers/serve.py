"""Driver of the serving traffic kinds (`open_loop`, `backlog`): one engine
behind the scheduler, in process; an open-loop generator that times every
request from when it was DUE; one reader per request on the token stream.

Clock: `time.monotonic()` for everything, the scheduler's stamps included.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmarks import harness, stats, traffic_gen

now = time.monotonic


class Served:
    """What the benchmark saw of one offered request."""

    def __init__(self, offered, due_abs):
        self.o = offered
        self.due = due_abs
        self.t_submit = None
        self.handle = None
        self.tokens = []
        self.t_tokens = []
        self.error = None
        self.ended = False
        self.thread = None

    @property
    def finished(self):
        return self.ended and self.error is None and \
            len(self.tokens) == self.o.n_out


def _read(served, annotate):
    """Reader thread: stamp every token as the stream hands it over."""
    try:
        for chunk in served.handle.stream():
            with annotate("bench.stream_read"):
                t = now()
                served.tokens.extend(chunk)
                served.t_tokens.extend([t] * len(chunk))
    except Exception as e:  # noqa: BLE001 - a failed request is a result
        served.error = e
    served.ended = True


def _submit(sched, served, annotate):
    with annotate("bench.submit"):
        served.t_submit = now()
        served.handle = sched.submit(
            served.o.prompt, max_new_tokens=served.o.n_out, eos_id=None,
            rid=f"b{served.o.idx}")
    served.thread = threading.Thread(
        target=_read, args=(served, annotate), daemon=True,
        name=f"bench-read-{served.o.idx}")
    served.thread.start()


def _generate(sched, plan, t_close, annotate, stop):
    """Generator thread: offer each request when it is due, never earlier,
    whatever the system has completed. Offering stops with the window."""
    for served in plan:
        while not stop.is_set():
            wait = served.due - now()
            if wait <= 0:
                break
            time.sleep(min(wait, 0.05))
        if stop.is_set() or served.due >= t_close:
            return
        try:
            _submit(sched, served, annotate)
        except Exception as e:  # noqa: BLE001 - a refused request misses
            served.error = e
            served.ended = True


def _warm_up(sched, spec, vocab, annotate):
    """Every program the cell's traffic uses, before the ramp. The step
    program has one shape, but the engine builds small programs the first
    time k prefills end in the same step, other ones when rows are
    decoding beside them: so bursts of 1, 2, 3, ... short prompts, each
    admitted as one wave, first alone and then beside a decoding request."""
    rng = np.random.default_rng(0)
    w = spec["warmup"]

    def offer(n_prompt, n_out):
        o = traffic_gen.Offered(-1, "warmup", 0.0,
                                rng.integers(1, vocab, n_prompt).tolist(), n_out)
        s = Served(o, now())
        _submit(sched, s, annotate)
        return s

    def finish(batch):
        for s in batch:
            s.thread.join(timeout=600)
            if not s.finished:
                raise RuntimeError(f"warm-up request did not finish: {s.error}")

    def burst(lengths):
        sched.pause()           # the burst is admitted as one wave
        batch = [offer(n, w["new_tokens"]) for n in lengths]
        sched.resume()
        return batch

    for lengths in w["bursts"]:
        finish(burst(lengths))
    decoder = offer(w["bursts"][0][0], w["decoder_tokens"])
    while not decoder.t_tokens and not decoder.ended:
        time.sleep(0.005)
    for lengths in w["bursts"]:
        finish(burst(lengths))
    finish([decoder])


def _check_sample(plan, seed, budget, n_max):
    """The requests whose served tokens are compared: the longest request
    the run finished, then the others that were served a token (finished or
    still in flight when the run shut down: at today's speed a window
    finishes about ten), in an order drawn from the seed, until they hold
    `budget` served tokens, at most `n_max` of one request."""
    done = [s for s in plan if s.finished]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.o.prompt) + s.o.n_out)
    rest = [s for s in plan if s is not longest and s.tokens
            and s.error is None]
    rng = np.random.default_rng([int(seed) & ((1 << 63) - 1), 7])
    sample, held = [longest], min(len(longest.tokens), n_max)
    for i in rng.permutation(len(rest)):
        if held >= budget:
            break
        sample.append(rest[i])
        held += min(len(rest[i].tokens), n_max)
    return sample


def _mean_live_tokens(plan, t_open, t_close, points=200):
    """Tokens of context alive in the engine, averaged over the window, from
    the run's own request log: a request holds nothing before admission, a
    growing share of its prompt until its first token, then its prompt and
    the tokens emitted so far, and nothing after its last token."""
    grid = np.linspace(t_open, t_close, points)
    total = np.zeros(points)
    for s in plan:
        if s.handle is None or s.handle.t_admitted is None or not s.t_tokens:
            continue
        a, t = s.handle.t_admitted, np.asarray(s.t_tokens)
        n_prompt = len(s.o.prompt)
        ended = t[-1] if s.ended else np.inf
        prefill = n_prompt * np.clip((grid - a) / max(t[0] - a, 1e-9), 0, 1)
        live = prefill + np.searchsorted(t, grid, side="right")
        total += np.where((grid >= a) & (grid <= ended), live, 0.0)
    return float(total.mean())


def _window_samples(plan, due_in, t_open, t_close):
    """-> (TTFT of every request due in the window that got a first token,
    the gaps whose later token fell inside the window, the tokens emitted
    inside it, the generator's lateness per submit, the wait from due time
    to the scheduler's admission stamp), times in ms."""
    ttft = [(s.t_tokens[0] - s.due) * 1e3 for s in due_in if s.t_tokens]
    itl, emitted = [], 0
    for s in plan:
        t = np.asarray(s.t_tokens)
        emitted += int(np.sum((t >= t_open) & (t < t_close)))
        if t.size > 1:
            gaps, later = np.diff(t) * 1e3, t[1:]
            itl.extend(gaps[(later >= t_open) & (later < t_close)].tolist())
    late = [(s.t_submit - s.due) * 1e3 for s in plan if s.t_submit is not None]
    waits = [(s.handle.t_admitted - s.due) * 1e3 for s in due_in
             if s.handle is not None and s.handle.t_admitted is not None]
    return ttft, itl, emitted, late, waits


_ROW_GAPS = {}      # one compiled reference per padded length, kept


def reference_gaps(cfg, params, sample):
    """For every compared token, the gap by which its logit lies below the
    reference's best at its position -> the widest, the mean and the mean
    square of those gaps. One request a call, padded to the shortest of the
    configuration's `check.buckets` that holds it."""
    import jax
    import jax.numpy as jnp
    ref = harness.load_module("reference", cfg["family"])
    m, n_max = cfg["model"], cfg["check"]["max_served_tokens"]
    raw = []
    with jax.default_matmul_precision("highest"):
        for s in sample:
            seq = s.o.prompt + list(s.tokens)
            S = min(b for b in cfg["check"]["buckets"] if b >= len(seq))
            key = (cfg["name"], S)
            if key not in _ROW_GAPS:
                _ROW_GAPS[key] = jax.jit(lambda p, t, f, c: ref.served_gaps(
                    p, t, f, c, m, n_max=n_max)[0])
            count = min(len(s.tokens), n_max)
            toks = np.zeros((S,), np.int32)
            toks[:len(seq)] = seq
            gaps = _ROW_GAPS[key](params, jnp.asarray(toks),
                                  jnp.int32(len(s.o.prompt)), jnp.int32(count))
            raw.append(np.asarray(gaps)[:count])
    gaps = np.concatenate(raw)
    return {"served_gap": float(gaps.max()),
            "served_gap_mean": float(gaps.mean()),
            "served_gap_sq_mean": float(np.mean(gaps ** 2)),
            "served_not_first_share": float(np.mean(gaps > 0)),
            "served_tokens_compared": int(gaps.size)}


def run(ctx):
    import jax
    args, cfg, spec = ctx.args, ctx.config, ctx.traffic
    model = harness.load_module("models", cfg["family"])
    annotate = harness.annotator(args.trace)
    vocab = cfg["model"]["vocab_size"]
    ramp_s, offered = traffic_gen.serving_traffic(spec, args.seed,
                                                  args.seconds, vocab)
    params = model.init_weights(cfg["model"], args.seed,
                                model.DTYPES[cfg["precision"]["weights"]])
    jax.block_until_ready(params)
    control = ctx.control
    cache_dtype = cfg["controls"][control].get("cache_dtype") \
        if control else None
    engine, sched = model.build_server(cfg, params, cache_dtype=cache_dtype)
    stop = threading.Event()
    try:
        _warm_up(sched, spec, vocab, annotate)
        compiles = harness.CompileCounter()
        t_gen = now()
        t_open, t_close = t_gen + ramp_s, t_gen + ramp_s + args.seconds
        plan = [Served(o, t_gen + o.due_s) for o in offered]
        gen = threading.Thread(target=_generate, name="bench-generator",
                               args=(sched, plan, t_close, annotate, stop),
                               daemon=True)
        gen.start()
        harness.sleep_until(t_open)
        ctx.window_opened(now())
        ctx.say("window: open")
        c_open = harness.counters(sched.registry)
        compiles.mark()
        in_flight = sum(1 for s in plan if s.handle is not None and not s.ended)
        tracer = harness.Tracer(ctx, spec) if args.trace else None
        if tracer:
            tracer.start()
            harness.sleep_until(min(now() + tracer.seconds, t_close))
            tracer.stop()
        harness.sleep_until(t_close)
        c_close = harness.counters(sched.registry)
        in_flight_close = sum(1 for s in plan
                              if s.handle is not None and not s.ended)
        in_window_compiles = compiles.since_mark()
        if in_window_compiles:
            ctx.say(f"compiled in the window: {compiles.names}")
        gen.join(timeout=5)
        # drain: first tokens of everything due in the window, and enough
        # served tokens to compare with the reference (a run at the cell's
        # own length has them when its window closes), one finished
        # request among them
        due_in = [s for s in plan if t_open <= s.due < t_close]
        t_limit = t_close + spec["drain_s"]
        check = cfg["check"]
        while now() < t_limit:
            firsts = all(s.t_tokens or s.ended for s in due_in if s.handle)
            served = sum(min(len(s.tokens), check["max_served_tokens"])
                         for s in plan)
            going = any(s.handle is not None and not s.ended for s in plan)
            enough = served >= check["served_tokens"] and \
                any(s.finished for s in plan)
            if firsts and (enough or not going):
                break
            time.sleep(0.05)
    finally:
        stop.set()
        sched.shutdown(drain=False, timeout=120)
    for s in plan:
        if s.thread is not None:
            s.thread.join(timeout=30)
    peak = harness.memory_peak_bytes()
    sample = _check_sample(plan, args.seed, check["served_tokens"],
                           check["max_served_tokens"])
    stats_line = sched.stats()
    del engine, sched
    gc.collect()

    ttft, itl, emitted, late, waits = _window_samples(plan, due_in, t_open,
                                                      t_close)
    missing = len(due_in) - len(ttft)
    errors = sum(1 for s in plan if s.error is not None)
    short = sum(1 for s in plan if s.ended and s.error is None and s.handle
                and s.handle.state == "done" and len(s.tokens) != s.o.n_out)
    deltas = {k: c_close.get(k, 0.0) - c_open.get(k, 0.0) for k in c_close}
    ctx.say(f"traffic: kind={spec['kind']} offered={len(plan)} "
            f"submitted={sum(s.handle is not None for s in plan)} "
            f"due_in_window={len(due_in)} in_flight_at_open={in_flight} "
            f"in_flight_at_close={in_flight_close} "
            f"ramp_s={ramp_s:g} finished={sum(s.finished for s in plan)}")
    ctx.say(f"samples: ttft={len(ttft)} (missing {missing}) itl={len(itl)} "
            f"tokens_in_window={emitted}")
    if itl:
        ctx.say_time("itl_ms " + " ".join(
            f"p{int(q * 100)}={stats.percentile(itl, q):.2f}"
            for q in (0.5, 0.9, 0.95, 0.98, 0.99, 1.0)))
    if ttft:
        ctx.say_time("ttft_ms sorted " + " ".join(f"{x:.0f}" for x in sorted(ttft)))
    ctx.say_time(f"ttft_sample_median_ms={np.median(ttft) if ttft else None}; "
                 f"generator lateness_ms max={max(late) if late else 0:.3f} "
                 f"mean={np.mean(late) if late else 0:.3f} over {len(late)} "
                 "submits")
    ctx.say(f"scheduler: {stats_line.get('requests')} preemptions="
            f"{deltas.get('pt_serving_preemptions', 0):g} "
            f"device_steps_in_window={deltas.get('pt_serving_device_steps', 0):g}")

    e2e = {}
    if emitted:
        e2e["serve_tokens_per_s"] = emitted / args.seconds
    if ttft:
        e2e["ttft_p50_ms"] = stats.harrell_davis(ttft)
    if itl:
        e2e["itl_p99_ms"] = stats.percentile(itl, 0.99)

    ctx.facts.update(
        counters=deltas, queue_wait_ms=waits, tokens_in_window=emitted,
        window_s=args.seconds,
        mean_live_tokens=_mean_live_tokens(plan, t_open, t_close))

    # ---- correct ----------------------------------------------------------
    checks = [("compiles_in_window", in_window_compiles, 0),
              ("requests_failed_or_missing", errors + missing, 0),
              ("finished_with_wrong_length", short, 0)]
    if sample:
        got = reference_gaps(cfg, params, sample)
        ctx.say(f"check: {got['served_tokens_compared']} served tokens of "
                f"{len(sample)} requests ({sum(s.finished for s in sample)} "
                f"finished, the longest of them "
                f"{len(sample[0].o.prompt) + sample[0].o.n_out} tokens)")
        ctx.say("check: " + " ".join(f"{k}={v:.6g}" for k, v in got.items()))
        checks += [(k, got[k], cfg["tolerance"][k])
                   for k in ("served_gap", "served_gap_sq_mean")]
    else:
        checks.append(("finished_requests_to_compare", 0, None))
    return harness.Outcome(
        checks=checks, attempted=len([s for s in plan if s.handle or s.error]),
        failed=errors + missing, end_to_end=e2e, memory_peak_bytes=peak,
        tracer=tracer)
