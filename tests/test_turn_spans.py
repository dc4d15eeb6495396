"""One turn of the serving pump as nested spans (ISSUE 25): the span
names and their nesting under both pumps, the `pt_serving_turn_seconds`
parts against the turns' wall time, the ragged kernel's row counters
against a brute-force count from the descriptors, the parts on the
`serving.step` record and on an injected stall's `anomaly.step_stall`,
and no flight-recorder event per part."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import llama_serving
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models.llama_serving import Request, ServingEngine
from paddle_tpu.observability import flight_recorder
from paddle_tpu.profiler import TURN, record_span
from paddle_tpu.serving.faults import FaultPlan
from paddle_tpu.serving.metrics import (TURN_PARTS, EngineMetrics,
                                        MetricsRegistry)
from paddle_tpu.serving.scheduler import RequestScheduler

CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)

# span -> part, as docs/observability.md § A turn of the pump has them
SPAN_PART = {
    "serving.sched_feed": "admit", "serving.admit": "admit",
    "serving.plan": "plan", "serving.stage": "dispatch",
    "serving.unified_step": "dispatch", "serving.seed_gather": "dispatch",
    "pt.track_jit": "telemetry", "serving.fetch": "fetch",
    "serving.consume": "consume", "serving.telemetry": "telemetry",
    "serving.publish": "publish"}


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


def _engine(params, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 8)
    return ServingEngine(params, CFG, use_pallas=False, **kw)


class Annotations:
    """A stub in `jax.profiler.TraceAnnotation`'s place: every span of
    every thread as (thread, name, depth, t_enter, t_exit, metadata)."""

    def __init__(self):
        self.events = []
        self._depth = threading.local()
        outer = self

        class Stub:
            def __init__(self, name, **kw):
                self.name, self.meta = name, dict(kw)

            def __enter__(self):
                d = getattr(outer._depth, "d", 0)
                outer._depth.d = d + 1
                self.rec = [threading.get_ident(), self.name, d,
                            time.monotonic(), None, self.meta]
                outer.events.append(self.rec)
                return self

            def __exit__(self, *exc):
                outer._depth.d -= 1
                self.rec[4] = time.monotonic()

            def set_metadata(self, **kw):
                self.meta.update(kw)

        self.Stub = Stub

    def turns(self):
        """[(turn event, [events nested under it])] of the thread that
        opened `serving.turn`, in order."""
        out = []
        for ev in self.events:
            if ev[1] == "serving.turn":
                out.append((ev, []))
            elif out and ev[0] == out[-1][0][0] and ev[2] > 0 and \
                    out[-1][0][4] is not None and ev[3] < out[-1][0][4]:
                out[-1][1].append(ev)
        return out


@pytest.fixture
def annotations(monkeypatch):
    a = Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", a.Stub)
    return a


def _serve(params, pipeline, n_req=3, max_new=6, **eng_kw):
    """The pump follows the engine: a ragged one is driven one step
    deep, a bucketed one (`pipeline` false) synchronously."""
    sched = RequestScheduler(_engine(params, ragged=pipeline, **eng_kw),
                             max_queue=8, metrics=MetricsRegistry())
    assert sched._pipeline is pipeline
    try:
        t0 = time.monotonic()
        handles = [sched.submit([1 + i, 5, 9, 3, 7, 2, 8, 4, 6],
                                max_new_tokens=max_new)
                   for i in range(n_req)]
        outs = [h.result(timeout=180) for h in handles]
        assert all(len(o) == max_new for o in outs)
        wall = time.monotonic() - t0
    finally:
        sched.shutdown(drain=True, timeout=60)
    return sched, wall


@pytest.mark.parametrize("pipeline", [False, True],
                         ids=["sync", "pipelined"])
def test_every_turn_yields_the_spans_well_nested(params, annotations,
                                                 pipeline):
    sched, _ = _serve(params, pipeline)
    turns = annotations.turns()
    stepped = [(t, kids) for t, kids in turns if "step" in t[5]]
    # (a bucketed prefill seeds at admission: six tokens, five turns)
    assert len(stepped) >= (6 if pipeline else 5)
    seen = set()
    for turn, kids in stepped:
        names = [k[1] for k in kids]
        seen.update(names)
        # (the bucketed entry points' dispatch spans belong to no part)
        assert set(names) <= set(SPAN_PART) | {"serving.parked"} | (
            set() if pipeline else {"serving.prefill",
                                    "serving.decode_step"}), names
        assert turn[2] == 0 and turn[4] is not None
        # well nested: a span lies inside the nearest open span above it
        stack = [turn]
        for k in kids:
            while stack[-1][2] >= k[2]:
                stack.pop()
            assert stack[-1][3] <= k[3] and k[4] <= stack[-1][4], k[1]
            assert k[2] == stack[-1][2] + 1
            stack.append(k)
        # the order of a turn: feed, then the engine, then the planes'
        # block, then publish
        top = [k[1] for k in kids if k[2] == 1]
        assert top[0] == "serving.sched_feed"
        assert top[-2:] == ["serving.telemetry", "serving.publish"]
        # (a pump that parked did so after its first feed, under ONE
        # `serving.parked` however often it polled, and fed again under
        # it at every poll and on waking)
        engine = [n for n in top[:-2]
                  if n not in ("serving.sched_feed", "serving.parked")]
        assert top[:len(top) - 2 - len(engine)] in (
            ["serving.sched_feed"],
            ["serving.sched_feed", "serving.parked"])
        for p in (k for k in kids if k[1] == "serving.parked"):
            assert {k[1] for k in kids if k[2] == 2 and
                    p[3] <= k[3] and k[4] <= p[4]} <= {"serving.sched_feed"}
        if pipeline:
            # launch parts, then fetch and consume of the step before
            assert engine[:3] == ["serving.admit", "serving.plan",
                                  "serving.stage"] or \
                engine[:2] == ["serving.admit", "serving.plan"]
        else:
            # the bucketed turn has no plan, stage, fetch or consume
            # span: what it shares with the ragged one ends here
            assert engine[0] == "serving.admit"
            assert turn[5]["step"] >= 1
            continue
        if "serving.fetch" in engine:
            i = engine.index("serving.fetch")
            assert engine[i + 1] == "serving.consume"
            assert "serving.plan" in engine[:i]
        if "serving.unified_step" in engine:
            i = engine.index("serving.unified_step")
            assert engine[i - 1] == "serving.stage"
            # track_jit's two short spans sit inside the dispatch span,
            # around the call
            inner = [k[1] for k in kids if k[2] == 2]
            assert inner.count("pt.track_jit") >= 2
        assert turn[5]["step"] >= 1
    if pipeline:
        assert set(SPAN_PART) <= seen
    # a request that finished was finalized under publish, with the
    # planes' share as a nested serving.telemetry
    nested = [k for _, kids in stepped for k in kids
              if k[1] == "serving.telemetry" and k[2] == 2]
    assert nested
    if pipeline:
        # the turn's arguments: rows of the wave it launched
        assert any(t[5]["prefill_rows"] > 0 for t, _ in stepped)
        assert any(t[5]["decode_rows"] > 0 for t, _ in stepped)
    assert sched.metrics_snapshot()["pt_serving_device_steps"]["value"] \
        == max(t[5]["step"] for t, _ in stepped)


@pytest.mark.parametrize("pipeline", [False, True],
                         ids=["sync", "pipelined"])
def test_turn_seconds_add_up_to_no_more_than_the_turns(params, annotations,
                                                       pipeline):
    sched, wall = _serve(params, pipeline)
    snap = sched.metrics_snapshot()
    parts = {p: snap[f'pt_serving_turn_seconds{{part="{p}"}}']["value"]
             for p in TURN_PARTS}
    # (a bucketed turn runs its step under no part's span)
    assert all(parts[p] > 0 for p in (
        TURN_PARTS if pipeline else ("admit", "telemetry", "publish"))), \
        parts
    turn_wall = sum(t[4] - t[3] for t, _ in annotations.turns()
                    if "step" in t[5])
    assert sum(parts.values()) <= turn_wall <= wall + 60
    if pipeline:
        # the parts are self times: almost all of a turn is under some
        # part
        assert sum(parts.values()) > 0.5 * turn_wall
    # the documented host gap is still taken, at the dispatch
    assert snap["pt_step_host_gap_seconds"]["count"] >= 1


def test_self_time_is_duration_less_children():
    """Against what each sleep really took: on a loaded machine a sleep
    of 10 ms can take 100."""
    took = {}

    def nap(name, s):
        t = time.perf_counter()
        time.sleep(s)
        took[name] = time.perf_counter() - t

    with record_span("serving.turn", part=TURN) as turn:
        with record_span("a", part="plan"):
            nap("a", 0.02)
            with record_span("b", part="telemetry"):
                nap("b", 0.03)
                with record_span("c", part="plan"):
                    nap("c", 0.01)
        nap("turn", 0.01)
    assert set(turn.parts) == {"plan", "telemetry"}
    assert turn.parts["plan"] == pytest.approx(took["a"] + took["c"],
                                               abs=0.005)
    assert turn.parts["telemetry"] == pytest.approx(took["b"], abs=0.005)
    assert sum(turn.parts.values()) <= turn.dur_s - took["turn"] + 0.001
    # outside a turn a part span still annotates and keeps no books
    with record_span("a", part="plan") as lone:
        pass
    assert lone.parts is None and lone.dur_s >= 0


def test_row_counters_equal_a_brute_force_count(params, monkeypatch):
    """Decode rows beside a prefill chunk that is split over two steps:
    the counters against a count from the descriptors the device got."""
    waves = []
    real = llama_serving.unified_step

    def spy(params_, k, v, page_table, tokens, tok_slot, tok_pos, *a, **kw):
        waves.append((np.asarray(tok_slot), np.asarray(tok_pos)))
        return real(params_, k, v, page_table, tokens, tok_slot, tok_pos,
                    *a, **kw)
    monkeypatch.setattr(llama_serving, "unified_step", spy)
    eng = _engine(params, max_seqs=4, ragged_tokens=16)
    reg = MetricsRegistry()
    eng.metrics = EngineMetrics(reg)
    eng.submit(Request("d0", [1, 2, 3], max_new_tokens=12))
    eng.submit(Request("d1", [4, 5, 6, 7], max_new_tokens=12))
    for _ in range(3):
        eng.step()
    # 26 prompt tokens into a 16-row buffer beside two decode rows: the
    # chunk is split 14 + 12 over two steps
    eng.submit(Request("p0", list(range(1, 27)), max_new_tokens=4))
    eng.run()
    mixed = [w for w in waves
             if len({s for s, p in zip(*w) if p >= 0}) >= 3]
    assert len(mixed) >= 2, "no wave mixed decode rows with the chunk"
    pairs = kv = rows = 0
    for tok_slot, tok_pos in waves:
        live = tok_pos >= 0
        rows += int(live.sum())
        for s, p in zip(tok_slot[live], tok_pos[live]):
            pairs += int(p) + 1
        for s in set(tok_slot[live].tolist()):
            kv += int(tok_pos[live][tok_slot[live] == s].max()) + 1
    assert eng.ragged_tokens == rows
    assert eng.ragged_attn_pairs == pairs > kv == eng.ragged_kv_tokens > 0
    snap = reg.snapshot()
    assert snap["pt_ragged_attn_pairs"]["value"] == pairs
    assert snap["pt_ragged_kv_tokens"]["value"] == kv
    assert snap["pt_ragged_tokens"]["value"] == rows


def test_step_record_and_stall_carry_the_parts_and_the_ring_gains_nothing(
        params):
    flight_recorder.RECORDER.clear()
    # 2 s, not half a second: on a loaded machine the steps before it
    # swing so widely that the sentinel's band passes a short stall
    sched = RequestScheduler(
        _engine(params, faults=FaultPlan("step_launch:delay@30:delay=2.0")),
        max_queue=4, metrics=MetricsRegistry())
    try:
        out = sched.submit([1, 2, 3, 4], max_new_tokens=45).result(
            timeout=180)
        assert len(out) == 45
        snap = sched.metrics_snapshot()       # the scan runs on scrape
        steps = int(snap["pt_serving_device_steps"]["value"])
    finally:
        sched.shutdown(drain=False, timeout=30)
    evs = flight_recorder.snapshot()["events"]
    records = [e for e in evs if e.get("kind") == "log"
               and e.get("event") == "serving.step"]
    assert records and all(set(r["parts"]) == set(TURN_PARTS)
                           for r in records)
    # a record's parts tile the time since the record before: the step
    # itself and the publish of the turn before. On a loaded machine the
    # pump can lose the CPU between two spans, in time no part owns: a
    # turn in ten may
    tiled = [sum(r["parts"].values()) >= 0.5 * r["step_s"]
             for r in records[1:]]
    assert sum(tiled) >= 0.9 * len(tiled)
    assert any(r["parts"]["publish"] > 0 for r in records[1:])
    stalls = [e for e in evs if e.get("kind") == "anomaly.step_stall"]
    assert stalls
    a = stalls[-1]
    # the injected delay sits at the `step_launch` fault point, at the
    # end of planning: the part furthest over its baseline names it
    assert a["stalled_part"] == "plan" and a["stalled_over_s"] > 0.4
    assert a["parts"]["plan"] > 0.4 and a["largest_part"] in TURN_PARTS
    # the ring: one span (`serving.unified_step`) and one log record a
    # turn as before, nothing per part (a turn of the one-step-deep pump,
    # which this engine runs unasked, may finish a step and launch none)
    names = {e.get("name") for e in evs if e.get("kind") == "span"}
    assert "serving.unified_step" in names
    assert not names & (set(SPAN_PART) - {"serving.unified_step"})
    assert "serving.turn" not in names
    per_turn = [e for e in evs if e.get("kind") in ("span", "log")
                and (e.get("name") or e.get("event", "")).startswith(
                    "serving.")]
    assert steps <= len(records) and len(per_turn) <= 2 * len(records)
