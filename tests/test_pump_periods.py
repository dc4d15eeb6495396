"""The pump's time tiled into periods and parked stretches (ISSUE 43):
each period under what the step it FETCHED carried, a park in
`pt_serving_parked_seconds` and in neither kind, the three counters
against the pump's wall time, under both pumps; on a fake engine whose
steps last what the test says, by a clock that moves only when told to,
and on the real engine against the host's clock."""
import collections
import time

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models.llama_serving import ServingEngine
from paddle_tpu.observability import flight_recorder
from paddle_tpu.serving import scheduler as scheduler_mod
from paddle_tpu.serving.metrics import (CARRIED, TURN_PARTS,
                                        MetricsRegistry)
from paddle_tpu.serving.scheduler import RequestScheduler

DECODE, PROMPT = (3, 0), (3, 5)     # (decode rows, prompt rows) of a wave
PUMPS = pytest.mark.parametrize("pipeline", [False, True],
                                ids=["sync", "pipelined"])


class Clock:
    """In `time`'s place in the scheduler: it moves only when told to."""

    def __init__(self):
        self.t = 1000.0

    def monotonic(self):
        return self.t

    perf_counter = time = monotonic

    def advance(self, s):
        self.t += s


class Ticket:
    def __init__(self, rows, seconds):
        self.rows, self.seconds = rows, seconds
        self.slots, self.t_fetched = [0], None


class FakeEngine:
    """One slot; a request is served by the next `max_new_tokens` waves
    of `script`, [(rows, seconds)], a token a wave. A wave's seconds pass
    on the clock while its results are fetched, as the device's do under
    a pump that has the next step queued. `last_rows` is the wave
    LAUNCHED, as the real engine's."""

    spec_decode = 0

    def __init__(self, clock, script, ragged):
        self.clock, self.script, self.ragged = clock, collections.deque(
            script), ragged
        self._slots, self._waiting = [None], collections.deque()
        self.finished, self.metrics = [], None
        self.device_steps = self.prefill_tokens = self._launched = 0
        self.last_rows = (0, 0)

    def validate(self, req):
        pass

    def submit(self, req):
        self._waiting.append(req)

    def _prefilling(self, req):
        return False

    def step_launch(self, carry=None):
        if self._slots[0] is None and self._waiting:
            self._slots[0], self._launched = self._waiting.popleft(), 0
        req = self._slots[0]
        if req is None or self._launched == req.max_new_tokens:
            return None
        rows, seconds = self.script.popleft()
        self._launched += 1
        self.device_steps += 1
        self.prefill_tokens += rows[1]
        self.last_rows = rows
        return Ticket(rows, seconds)

    def step_finish(self, ticket, inflight=None):
        self.clock.advance(ticket.seconds)
        ticket.t_fetched = self.clock.monotonic()
        req = self._slots[0]
        req.output.append(7)
        if len(req.output) == req.max_new_tokens:
            self.finished.append(req)
            self._slots[0] = None
        return 1

    def step(self):
        ticket = self.step_launch()
        return 0 if ticket is None else self.step_finish(ticket)


class Spans:
    """Every `record_span` of the scheduler as (name, part, ring), and a
    stub in `jax.profiler.TraceAnnotation`'s place that keeps each span's
    metadata."""

    def __init__(self, monkeypatch):
        self.calls, self.events = [], []
        outer, real = self, scheduler_mod.record_span

        def spy(name, args=None, part=None, ring=None):
            outer.calls.append((name, part, ring))
            return real(name, args=args, part=part, ring=ring)

        class Stub:
            def __init__(self, name, **kw):
                self.rec = (name, dict(kw))

            def __enter__(self):
                outer.events.append(self.rec)
                return self

            def __exit__(self, *exc):
                pass

            def set_metadata(self, **kw):
                self.rec[1].update(kw)

        monkeypatch.setattr(scheduler_mod, "record_span", spy)
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Stub)

    def count(self, name):
        return sum(1 for n, _ in list(self.events) if n == name)

    def wait_for(self, name, n):
        """Until the pump has opened its n-th span of this name."""
        deadline = time.monotonic() + 30
        while self.count(name) < n:
            assert time.monotonic() < deadline, (name, n)
            time.sleep(0.002)


@pytest.fixture
def rig(monkeypatch):
    """-> make(script, pipeline) -> (clock, spans, scheduler), the pump
    parked for the first time; every scheduler is shut down at the end."""
    made = []

    def make(script, pipeline):
        clock, spans = Clock(), Spans(monkeypatch)
        monkeypatch.setattr(scheduler_mod, "time", clock)
        sched = RequestScheduler(FakeEngine(clock, script, ragged=pipeline),
                                 max_queue=8, metrics=MetricsRegistry())
        made.append(sched)
        assert sched._pipeline is pipeline
        spans.wait_for("serving.parked", 1)
        return clock, spans, sched

    yield make
    for sched in made:
        sched.shutdown(drain=False, timeout=30)


def _booked(sched):
    snap = sched.metrics_snapshot()
    seconds = {c: snap[f'pt_serving_period_seconds{{carried="{c}"}}']["value"]
               for c in CARRIED}
    count = {c: snap[f'pt_serving_periods{{carried="{c}"}}']["value"]
             for c in CARRIED}
    return seconds, count, snap["pt_serving_parked_seconds"]["value"]


def _burst(sched, n_waves):
    out = sched.submit([1, 2, 3], max_new_tokens=n_waves).result(timeout=60)
    assert len(out) == n_waves


# decode waves of 1, 2, 4 s between prompt waves of 10, 20, 40: whichever
# way a booking is off by one wave, the sums differ
ALTERNATION = [(DECODE, 1.0), (PROMPT, 10.0), (DECODE, 2.0), (PROMPT, 20.0),
               (DECODE, 4.0), (PROMPT, 40.0)]


@PUMPS
def test_a_period_is_booked_under_the_step_it_fetched(rig, pipeline):
    """Under the one-step-deep pump `engine.last_rows` is the NEXT wave's
    by the time a step is fetched: read there, every prompt wave's
    seconds would land under `decode`."""
    clock, spans, sched = rig(ALTERNATION, pipeline)
    _burst(sched, len(ALTERNATION))
    seconds, count, _ = _booked(sched)
    assert seconds == {"decode": 7.0, "prompt": 70.0}
    assert count == {"decode": 3, "prompt": 3}


@PUMPS
def test_a_park_lands_in_parked_and_in_neither_kind(rig, pipeline):
    clock, spans, sched = rig(ALTERNATION, pipeline)
    clock.advance(3.0)                  # parked since the pump began
    _burst(sched, 2)
    spans.wait_for("serving.parked", 2)
    before = _booked(sched)
    assert before[2] == 3.0             # booked on waking, not before
    clock.advance(500.0)
    # a parked pump polls (idle_poll_s); the park stays one span
    time.sleep(0.1)
    assert spans.count("serving.parked") == 2
    _burst(sched, 2)
    seconds, count, parked = _booked(sched)
    assert parked == 503.0
    assert seconds == {"decode": 3.0, "prompt": 30.0}
    assert count == {"decode": 2, "prompt": 2}


@PUMPS
def test_the_three_counters_tile_the_pumps_time(rig, pipeline):
    clock, spans, sched = rig(ALTERNATION, pipeline)
    t0 = clock.t
    for i, (n_waves, park) in enumerate(((1, 0.25), (3, 8.0), (2, 0.5))):
        clock.advance(park)
        _burst(sched, n_waves)
        spans.wait_for("serving.parked", i + 2)
    clock.advance(1.5)
    sched.shutdown(drain=True, timeout=30)      # the last park is booked
    seconds, count, parked = _booked(sched)
    assert parked == 0.25 + 8.0 + 0.5 + 1.5
    assert sum(seconds.values()) == 77.0 and sum(count.values()) == 6
    assert sum(seconds.values()) + parked == clock.t - t0


@PUMPS
def test_the_turn_names_the_wave_it_launched_and_the_step_it_fetched(
        rig, pipeline):
    clock, spans, sched = rig(ALTERNATION, pipeline)
    _burst(sched, 4)
    spans.wait_for("serving.parked", 2)
    turns = [meta for name, meta in spans.events
             if name == "serving.turn" and "step" in meta]
    fetched = [t for t in turns if "carried" in t]
    assert [(t["carried"], t["fetched_prompt_rows"]) for t in fetched] == \
        [("decode", 0), ("prompt", 5)] * 2
    for t in fetched:
        assert t["fetched_decode_rows"] == (3 if pipeline else 1)
    if pipeline:
        # one step deep: the turn that fetched wave N launched wave N+1
        # (the last launched none, and `last_rows` stays wave N's)
        assert [(t["decode_rows"], t["prefill_rows"]) for t in fetched] == \
            [PROMPT, DECODE, PROMPT, PROMPT]
        assert len(turns) == len(fetched) + 1   # the first fetched none
    else:
        assert all((t["decode_rows"], t["prefill_rows"]) ==
                   (3, t["fetched_prompt_rows"]) for t in fetched)


def test_parked_is_a_span_of_no_part_and_out_of_the_ring(rig):
    flight_recorder.RECORDER.clear()
    clock, spans, sched = rig(ALTERNATION, True)
    time.sleep(1.0)                     # parked, on the host's clock too
    _burst(sched, 2)
    spans.wait_for("serving.parked", 2)
    assert {c for c in spans.calls if c[0] == "serving.parked"} == \
        {("serving.parked", None, False)}
    snap = sched.metrics_snapshot()
    by_part = {k: v["value"] for k, v in snap.items()
               if k.startswith("pt_serving_turn_seconds")}
    assert set(by_part) == {f'pt_serving_turn_seconds{{part="{p}"}}'
                            for p in TURN_PARTS}
    # the parts are timed on the host's own clock: three turns of a fake
    # engine and the polls' feeds, and none of the second parked
    assert sum(by_part.values()) < 0.5
    names = {e.get("name") for e in flight_recorder.snapshot()["events"]
             if e.get("kind") == "span"}
    assert "serving.parked" not in names


# ---- the real engine, the host's clock ----------------------------------
CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2,
                       ffn=64, seq=128)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, seed=0, dtype=jnp.float32)


@PUMPS
def test_the_engines_tickets_carry_their_rows_and_their_fetch(params,
                                                              pipeline):
    eng = ServingEngine(params, CFG, use_pallas=False, max_seqs=4,
                        max_seq_len=64, page_size=8, ragged=pipeline,
                        **({"ragged_tokens": 16} if pipeline else {}))
    t0 = time.monotonic()
    sched = RequestScheduler(eng, max_queue=8, metrics=MetricsRegistry())
    try:
        first = sched.submit([1, 5, 9, 3, 7, 2, 8, 4, 6], max_new_tokens=8)
        assert len(first.result(timeout=180)) == 8
        time.sleep(0.3)
        # 26 prompt rows into a 16-row wave: two prompt steps at least
        late = [sched.submit(list(range(1, 27)), max_new_tokens=6),
                sched.submit([4, 5, 6], max_new_tokens=12)]
        assert [len(h.result(timeout=180)) for h in late] == [6, 12]
    finally:
        sched.shutdown(drain=True, timeout=60)
    wall = time.monotonic() - t0
    seconds, count, parked = _booked(sched)
    snap = sched.metrics_snapshot()
    assert count["prompt"] >= (3 if pipeline else 2)
    assert count["decode"] >= 10
    assert all(seconds[c] > 0 for c in CARRIED) and parked >= 0.3
    if pipeline:
        assert sum(count.values()) == \
            snap["pt_serving_device_steps"]["value"]
    # the tiling: everything from the scheduler's making to the pump's
    # last park, but for the thread's start and its exit
    assert sum(seconds.values()) + parked == pytest.approx(wall, abs=0.25)
    assert sum(seconds.values()) + parked <= wall


def test_a_drained_step_is_booked_too(params):
    """A cancel drains the step in flight at the top of a turn: that
    fetch ends a period like any other."""
    eng = ServingEngine(params, CFG, use_pallas=False, max_seqs=4,
                        max_seq_len=64, page_size=8)
    sched = RequestScheduler(eng, max_queue=8, metrics=MetricsRegistry())
    try:
        h = sched.submit([1, 2, 3, 4], max_new_tokens=40)
        for _ in h.stream(timeout=60):
            break
        h.cancel()
        h._done.wait(timeout=60)
    finally:
        sched.shutdown(drain=True, timeout=60)
    _, count, _ = _booked(sched)
    assert sum(count.values()) == \
        sched.metrics_snapshot()["pt_serving_device_steps"]["value"]
