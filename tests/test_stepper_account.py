"""The stepper's own books (``telemetry.ledger.StepperAccount``): an always-on
phase clock for the host path of a decode round, the slot-seconds the streams
stood still for a prefill, a record of every stall, the collector's pauses and
the streaming handlers' CPU.

No model and no compile: the scheduler is driven through its seam by
``tests/test_engine_scheduler.py``'s scripted executor (next token = t + 1),
and the clocks are injected, so every second asserted here was written by the
test.
"""

import gc
import http.client
import json
import logging
import threading

import pytest

from dlti_tpu.config import MODEL_PRESETS
from dlti_tpu.data.tokenizer import ByteTokenizer
from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams
from dlti_tpu.serving import engine as engine_module
from dlti_tpu.serving.server import ServerConfig, make_server
from dlti_tpu.serving import server as server_module
from dlti_tpu.telemetry import (
    GC_METRIC_NAMES, STEPPER_METRIC_NAMES, NullStepperAccount,
    RequestTelemetry, StepperAccount, configure_tracer, get_tracer,
    install_gc_hook, remove_gc_hook, request_breakdown,
)
from dlti_tpu.telemetry import ledger, startup
from dlti_tpu.telemetry.ledger import DEVICE_WAIT, WAIT
from dlti_tpu.telemetry.tracer import SpanTracer
from test_engine_scheduler import ScriptedExecutor

CFG = MODEL_PRESETS["llama_tiny"]
TICK = 2.0 ** -10   # of the injected clocks: sums of it are exact, no stall
PREP_PARTS = ("engine/decode_plan", "engine/decode_assemble",
              "engine/decode_stage")


class Ticks:
    """A clock that reads ``step`` later every time it is read, and counts
    its reads; ``jump`` moves it on between two reads."""

    def __init__(self, step=1.0):
        self.now, self.step, self.reads = 0.0, step, 0

    def __call__(self):
        self.reads += 1
        self.now += self.step
        return self.now

    def jump(self, seconds):
        self.now += seconds


@pytest.fixture()
def scripted(monkeypatch):
    monkeypatch.setattr(engine_module, "EngineExecutor", ScriptedExecutor)


@pytest.fixture()
def tracer():
    tr = configure_tracer(enabled=True, capacity=8192)
    tr.clear()
    yield tr
    configure_tracer(enabled=False)
    tr.clear()


def _engine(telemetry=None, **over):
    kw = dict(max_seqs=4, block_size=4, num_blocks=64, max_model_len=64,
              eos_token_id=-1, memory_ledger=False)
    kw.update(over)
    return InferenceEngine(CFG, None, EngineConfig(**kw),
                           telemetry=telemetry)


def _ticking_engine(**over):
    """An engine whose account reads injected clocks (wall and CPU)."""
    tel = RequestTelemetry()
    wall, cpu = Ticks(TICK), Ticks(TICK / 4)
    tel.stepper = StepperAccount(tel.tracer, clock=wall, cpu_clock=cpu)
    return _engine(tel, **over), tel.stepper, wall, cpu


# -- the clock ----------------------------------------------------------------

def test_phases_sum_to_the_threads_wall_exactly_over_a_nested_step():
    clock = Ticks()
    acct = StepperAccount(SpanTracer(), clock=clock, cpu_clock=Ticks(0.0))
    phase = acct.phase
    for _ in range(3):
        with phase("server/lock_wait"):
            clock.jump(0.5)
        with phase("server/step", step=True):
            clock.jump(2.0)
            with phase("engine/decode_prep", "engine"):
                with phase("engine/decode_plan", "engine"):
                    clock.jump(3.0)
                clock.jump(0.125)
                with phase("engine/decode_stage", "engine"):
                    clock.jump(4.0)
            with phase("engine/decode_wait", "engine", DEVICE_WAIT):
                clock.jump(0.25)
        clock.jump(7.0)   # the loop's own time, inside no phase
    got = acct.seconds()
    # (the stretch of the phase still open is booked when it ends)
    assert sum(got.values()) == acct.wall() == clock.now - 7.0 - acct.start
    # the innermost open phase gets the time: an inner one suspends its outer
    assert got["engine/decode_plan"] == 3 * (3.0 + 1.0)
    assert got["engine/decode_stage"] == 3 * (4.0 + 1.0)
    assert got["engine/decode_prep"] == 3 * (0.125 + 3 * 1.0)
    assert got["server/step"] == 3 * (2.0 + 3 * 1.0)
    assert got["server/lock_wait"] == 3 * 1.5
    # (before the first phase, between lock_wait and step, after the step)
    assert got[ledger.STEPPER_BASE_PHASE] == 1.0 + 3 * 1.0 + 2 * (7.0 + 1.0)
    assert acct.entries() == {name: 3 for name in got
                              if name != ledger.STEPPER_BASE_PHASE}


def test_the_threads_cpu_is_read_in_one_step_of_sixteen_as_running_totals():
    every = ledger.STEPPER_CPU_MARK_EVERY
    cpu, clock = Ticks(0.25), Ticks(0.125)   # (under the stall's limit)
    acct = StepperAccount(SpanTracer(), clock=clock, cpu_clock=cpu)
    done = [100]
    acct.steps_done = lambda: done[0]
    acct.bind()                # reads the clock once: the thread's base

    def step():
        with acct.phase("server/lock_wait"):
            pass
        with acct.phase("server/step", step=True):
            with acct.phase("engine/admit", "engine"):
                with acct.phase("engine/prefill_wait", "engine",
                                DEVICE_WAIT):
                    pass
            with acct.phase("engine/decode_wait", "engine", DEVICE_WAIT):
                done[0] += 1
        with acct.phase("server/drain_events"):
            pass

    before = cpu.reads
    step()   # the first step is a marked one: its entry, round its two waits
    assert cpu.reads - before == 1 + 2 + 2
    # three running totals as of the step's entry: the thread's CPU since
    # it was bound (one read later), the wall of its host phases (the lock
    # wait's one stretch), the engine's decode steps
    assert acct.cpu_seconds == 0.25
    assert acct.marked_host_seconds == 0.125
    assert acct.marked_decode_steps == 100
    # what the two waits took, for the fifteen steps not measured too
    assert acct.device_wait_cpu_seconds == every * 2 * 0.25
    for _ in range(every - 1):
        step()
    assert cpu.reads - before == 5   # fifteen steps read nothing
    assert acct.marked_decode_steps == 100
    step()
    assert cpu.reads - before == 10
    assert acct.cpu_seconds == 6 * 0.25   # a running total: nothing lost
    assert acct.marked_decode_steps == 100 + every
    # sixteen whole steps of host wall since the first mark: of a step's
    # twelve stretches the two inside the waits and the three outside every
    # phase (before the lock, the step and the drain) are not host time
    assert acct.marked_host_seconds == (1 + every * 7) * 0.125
    assert acct.device_wait_cpu_seconds == 2 * every * 2 * 0.25
    assert every == 16


def test_another_thread_gets_the_tracers_span_and_books_nothing():
    tr = SpanTracer(enabled=True)
    acct = StepperAccount(tr, clock=Ticks(TICK), cpu_clock=Ticks(0.0))
    with acct.phase("server/step"):
        pass   # this thread owns the account now
    booked = dict(acct.entries())

    def foreign():
        with acct.phase("engine/admit", "engine"):
            pass

    t = threading.Thread(target=foreign)
    t.start()
    t.join()
    assert acct.entries() == booked
    spans = [(e["name"], e["tid"]) for e in tr.events()]
    assert [n for n, _ in spans] == ["server/step", "engine/admit"]
    assert spans[0][1] != spans[1][1]


# -- the engine's phases and the tracer's spans --------------------------------

def _inside(kid, parent):
    return (parent["ts"] <= kid["ts"]
            and kid["ts"] + kid["dur"] <= parent["ts"] + parent["dur"])


def test_the_account_books_with_the_tracer_off_and_the_spans_match_it_on(
        scripted):
    assert not get_tracer().enabled
    get_tracer().clear()
    eng = _engine()
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    eng.generate([[5, 6, 7], [9, 10]], sp)
    assert len(get_tracer()) == 0
    off = eng.telemetry.stepper.entries()
    assert {"engine/admit", "engine/decode_prep", *PREP_PARTS,
            "engine/decode_launch", "engine/decode_wait",
            "engine/decode_emit", "engine/prefill_launch",
            "engine/prefill_wait"} <= set(off)
    assert off["engine/decode_stage"] == off["engine/decode_launch"]
    assert off["engine/decode_plan"] == off["engine/decode_prep"]
    assert "engine/prefill_group" not in off      # the tracer's alone
    assert all(s > 0 for s in eng.telemetry.stepper.seconds().values())

    tr = configure_tracer(enabled=True, capacity=8192)
    try:
        eng2 = _engine()
        eng2.generate([[5, 6, 7], [9, 10]], sp)
        events = [e for e in tr.events() if e.get("ph") == "X"
                  and e["name"].startswith(("engine/", "server/"))]
    finally:
        configure_tracer(enabled=False)
        tr.clear()
    # the same run books the same entries, and every entry is one span
    on = eng2.telemetry.stepper.entries()
    assert on == off
    by_name = {}
    for e in events:
        by_name[e["name"]] = by_name.get(e["name"], 0) + 1
    assert by_name.pop("engine/prefill_group") == on["engine/prefill_launch"]
    assert by_name == on
    preps = [e for e in events if e["name"] == "engine/decode_prep"]
    for part in PREP_PARTS:
        for kid in (e for e in events if e["name"] == part):
            assert sum(_inside(kid, p) for p in preps) == 1, part
    assert not {e["name"] for e in events} & {"engine/decode_dispatch",
                                               "engine/decode_sync"}
    # no span of the step's path reads the thread's CPU clock
    assert not [e["name"] for e in events if "cpu_us" in e.get("args", {})]


def test_only_the_steps_span_carries_cpu_us():
    """``server/step`` is the one span whose ``cpu_us`` a metric reads
    (``stepper_cpu_share``): the one whose site asks for the CPU clock."""
    tr = SpanTracer(enabled=True)
    acct = StepperAccount(tr, clock=Ticks(TICK), cpu_clock=Ticks(0.0))
    with acct.phase("server/lock_wait"):
        pass
    with acct.phase("server/step", step=True):
        with acct.phase("engine/decode_prep", "engine"):
            with acct.phase("engine/decode_stage", "engine"):
                pass
        with acct.phase("engine/decode_wait", "engine", DEVICE_WAIT):
            pass
    with tr.span("engine/prefill_group", cat="engine", rows=2):
        pass
    with tr.span("train/step_dispatch", cat="train", cpu=True):
        pass
    got = {e["name"]: e.get("args", {}) for e in tr.events()}
    assert [n for n, a in got.items() if "cpu_us" in a] == [
        "server/step", "train/step_dispatch"]
    assert got["server/step"]["cpu_us"] >= 0
    assert got["engine/prefill_group"] == {"rows": 2}


def test_a_round_reads_the_clocks_a_fixed_number_of_times(scripted):
    """The hot path's cost, pinned without timing anything: a steady decode
    round of the engine's real ``step()`` is eight phases, two wall-clock
    reads each, and inside the server's ``server/step`` three reads of the
    thread's CPU clock in sixteen rounds, all in the marked one (none
    without a server)."""
    eng, acct, wall, cpu = _ticking_engine()
    for prompt in ([5, 6, 7], [9, 10], [11, 12, 13]):
        eng.submit(prompt, SamplingParams(max_tokens=40, temperature=0.0))
    for _ in range(4):
        eng.step()   # admitted, prefilled, one round in flight
    for _ in range(5):
        steps, w0, c0 = eng.stats["decode_steps"], wall.reads, cpu.reads
        eng.step()
        assert eng.stats["decode_steps"] == steps + 1
        assert wall.reads - w0 <= 16
        assert cpu.reads - c0 == 0
    c0 = cpu.reads
    for _ in range(ledger.STEPPER_CPU_MARK_EVERY):
        w0 = wall.reads
        with acct.phase("server/step", step=True):
            eng.step()
        assert wall.reads - w0 <= 18
    # one marked step: its entry and the two ends of its one device wait
    assert cpu.reads - c0 == 3
    assert sum(acct.seconds().values()) == acct.wall()


# -- the clock changes no output ------------------------------------------------

@pytest.fixture(scope="module")
def tiny_params():
    import jax
    import jax.numpy as jnp

    from dlti_tpu.models import LlamaForCausalLM

    return LlamaForCausalLM(CFG, None).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


# step of the loop -> the requests submitted before it: admissions between
# rounds, into free slots and into slots just freed; a greedy row among
# seeded ones at temperature 1
ARRIVALS = {
    0: [([5, 6, 7, 8, 9], dict(temperature=1.0, seed=11, max_tokens=14)),
        ([9, 10], dict(temperature=0.0, max_tokens=9))],
    3: [([3, 1, 4, 1, 5, 9, 2, 6], dict(temperature=1.0, seed=12,
                                        max_tokens=7))],
    7: [([2, 7, 1, 8], dict(temperature=1.0, seed=13, max_tokens=11)),
        ([6, 6, 7], dict(temperature=0.0, max_tokens=5))],
}


def _shapes(value):
    if hasattr(value, "shape"):
        return tuple(value.shape)
    if isinstance(value, dict):
        return {k: _shapes(v) for k, v in sorted(value.items())}
    if isinstance(value, (list, tuple)):
        return tuple(_shapes(v) for v in value)
    return value if isinstance(value, (int, bool, type(None))) else "-"


def _drive(params, telemetry):
    """One seeded tiny engine driven as ``AsyncEngine._run`` drives it:
    the streams (tokens, log-probs, why each ended) and the executor's
    calls (name, the shapes of what it was given), in order."""
    eng = InferenceEngine(
        CFG, params, EngineConfig(max_seqs=3, block_size=4, num_blocks=64,
                                  max_model_len=64, cache_dtype="float32",
                                  eos_token_id=-1, memory_ledger=False),
        telemetry=telemetry)
    calls = []

    def recorded(name, real):
        def call(*args, **kwargs):
            calls.append((name, _shapes(args), _shapes(kwargs)))
            return real(*args, **kwargs)
        return call

    for name in ("prefill", "stage_decode", "launch_decode", "fetch"):
        setattr(eng.executor, name, recorded(name, getattr(eng.executor,
                                                            name)))
    acct = eng.telemetry.stepper
    acct.bind()
    phase, reqs, i = acct.phase, [], 0
    while i <= max(ARRIVALS) or eng.has_work:
        for prompt, sp in ARRIVALS.get(i, ()):
            reqs.append(eng.submit(prompt, SamplingParams(**sp)))
        with phase("server/lock_wait"):
            pass
        with phase("server/step", step=True):
            eng.step()
        with phase("server/lock_wait"):
            with phase("server/drain_events"):
                pass
        i += 1
    assert eng.stats["decode_rounds_launched_ahead"] > 0
    return [(r.output_token_ids, r.output_logprobs, r.finish_reason)
            for r in reqs], calls


def test_the_account_the_null_account_and_the_ring_give_the_same_streams(
        tiny_params):
    """The proof that the books cannot change an output: the same seeded
    traffic gives bit-equal tokens and log-probs and the same sequence of
    executor calls with the account booking, with an account that books
    nothing, and with the ring on."""
    assert not get_tracer().enabled
    booked = RequestTelemetry()
    want, want_calls = _drive(tiny_params, booked)
    assert len(want) == 5 and all(len(t) == len(lp) for t, lp, _ in want)
    assert {r[2] for r in want} == {"length"}
    assert booked.stepper.entries()["engine/decode_stage"] > 10
    assert booked.stepper.doing().startswith("newest prefill call ")
    assert booked.stepper.doing().endswith(" blocks a row")
    assert len([c for c in want_calls if c[0] == "prefill"]) >= 3

    null = RequestTelemetry()
    null.stepper = NullStepperAccount()
    got, got_calls = _drive(tiny_params, null)
    assert got == want
    assert got_calls == want_calls

    tr = configure_tracer(enabled=True, capacity=8192)
    try:
        tr.clear()
        ringed, ringed_calls = _drive(tiny_params, RequestTelemetry())
        spans = {e["name"] for e in tr.events() if e.get("ph") == "X"}
    finally:
        configure_tracer(enabled=False)
        tr.clear()
    assert {"server/step", "engine/decode_stage", "engine/prefill_launch",
            "engine/decode_wait"} <= spans
    assert ringed == want
    assert ringed_calls == want_calls


def test_a_handler_reads_its_cpu_clock_at_one_event_in_64(monkeypatch):
    """An event costs the handler one add; the thread's CPU clock is read
    at every 64th event of the thread and nowhere else, and what is booked
    is the thread's running total since its last read, for those 64 events
    (never scaled: the clock ticks in steps of 10 ms on the chip's host).
    One meter a thread, kept across its responses."""
    reads = []

    def thread_time():
        reads.append(len(reads))
        return 0.5 * len(reads)

    monkeypatch.setattr(server_module.time, "thread_time", thread_time)
    cpu0 = server_module.sse_handler_cpu_seconds_total.value
    events0 = server_module.sse_events_total.value
    booked = []

    def handler():
        meter = server_module._HandlerMeter.of_this_thread()
        for _ in range(63):
            meter.event()
        booked.append((len(reads), server_module.sse_events_total.value))
        meter.event()                 # the 64th: one read, one block booked
        booked.append((len(reads), server_module.sse_events_total.value))
        for _ in range(40):           # the response ends short of a block
            meter.event()
        # the thread's next response goes on where this one stopped
        again = server_module._HandlerMeter.of_this_thread()
        assert again is meter
        for _ in range(24):
            again.event()
        booked.append((len(reads), server_module.sse_events_total.value))

    t = threading.Thread(target=handler)
    t.start()
    t.join()
    assert booked == [(0, events0), (1, events0 + 64), (2, events0 + 128)]
    # the first block from the thread's start (0), the second from the
    # first read: 0.5 + 0.5, nothing times 64
    assert server_module.sse_handler_cpu_seconds_total.value - cpu0 == 1.0
    # another thread has a meter of its own
    assert server_module._HandlerMeter.of_this_thread().events == 0


# -- a stall leaves a record ----------------------------------------------------

@pytest.mark.parametrize("name, kind, stalls", [
    ("engine/decode_emit", ledger.HOST, 1),
    ("engine/decode_wait", DEVICE_WAIT, 0),
    ("server/wait_work", WAIT, 0), ("engine/prefill_launch", ledger.HOST, 1)])
def test_a_host_phase_that_stood_still_leaves_one_record(
        name, kind, stalls, engine_log):
    clock = Ticks(0.001)
    tr = SpanTracer(enabled=True)
    acct = StepperAccount(tr, clock=clock, cpu_clock=Ticks(0.0))
    acct.describe = lambda: {"live_slots": 31, "waiting": 7}
    with engine_log.at_level(logging.INFO, logger="dlti_tpu"):
        with acct.phase("server/step", step=True):
            with acct.phase(name, "engine", kind):
                clock.jump(0.3)
    assert {k: v for k, v in acct.stalls().items() if v} == (
        {name: 1} if stalls else {})
    assert acct.stall_seconds() == pytest.approx(0.301 * stalls)
    instants = [e for e in tr.events() if e["name"] == "server/stall"]
    records = [r for r in engine_log.records if "stepper" in r.getMessage()]
    assert len(instants) == len(records) == stalls
    if stalls:
        assert instants[0]["ph"] == "i"
        assert instants[0]["args"]["phase"] == name
        text = records[0].getMessage()
        assert records[0].levelno == logging.WARNING
        assert name in text and "0.301 s" in text
        assert "live slots 31, waiting 7" in text
        assert "gc pause" in text and "process cpu" in text
        # what the deltas are over: from the step's mark to the record
        assert "in the 0.302 s since the last CPU mark" in text


def test_a_phase_that_built_a_program_did_not_stand_still(engine_log):
    """A first call of a shape compiles inside ``engine/prefill_launch``:
    host time over the limit, and no stall. One INFO line that names the
    programs, nothing booked, and the next phase over the limit that built
    nothing is a stall again."""
    clock = Ticks(0.001)
    tr = SpanTracer(enabled=True)
    acct = StepperAccount(tr, clock=clock, cpu_clock=Ticks(0.0))
    with engine_log.at_level(logging.INFO, logger="dlti_tpu"):
        with acct.phase("server/step", step=True):
            with acct.phase("engine/prefill_launch", "engine"):
                clock.jump(4.0)
                # the listener hears a compilation, and a fetch (JAX sends
                # the fetch's own event first, then a compile duration)
                startup._on_duration(startup._COMPILE_EVENT, 3.5,
                                     fun_name="jit_prefill")
                startup._on_duration(startup._FETCH_EVENT, 0.005)
                startup._on_duration(startup._COMPILE_EVENT, 0.006,
                                     fun_name="jit__apply_rows")
            with acct.phase("engine/decode_emit", "engine"):
                clock.jump(0.3)  # and this one compiled nothing
    assert {k: v for k, v in acct.stalls().items() if v} == {
        "engine/decode_emit": 1}
    assert acct.stall_seconds() == pytest.approx(0.301)
    lines = [(r.levelno, r.getMessage()) for r in engine_log.records
             if "stepper" in r.getMessage()
             and "compiled after ready" not in r.getMessage()]
    assert [lv for lv, _ in lines] == [logging.INFO, logging.WARNING]
    assert "4.001 s in engine/prefill_launch, 2 program(s)" in lines[0][1]
    assert "jit_prefill (compiled, 3.500 s), " \
           "jit__apply_rows (fetched, 0.006 s)" in lines[0][1]
    assert len([e for e in tr.events() if e["name"] == "server/stall"]) == 1


def test_a_program_built_after_ready_is_logged_by_name(
        engine_log, monkeypatch):
    """``telemetry.startup``'s listener: silent until the ``ready`` phase
    is marked, then one INFO line a program with its name, whether it was
    compiled or fetched, and the stepper's open phase. No new counter: the
    four that were there count what they counted."""
    monkeypatch.setattr(startup, "_ready", False)
    acct = StepperAccount(SpanTracer(), clock=Ticks(TICK),
                          cpu_clock=Ticks(0.0))
    acct.bind()
    counted = startup.compile_scalars()

    def late(record):
        return "compiled after ready" in record.getMessage()

    with engine_log.at_level(logging.INFO, logger="dlti_tpu"):
        startup._on_duration(startup._COMPILE_EVENT, 1.5, fun_name="jit_warm")
        assert not [r for r in engine_log.records if late(r)]
        monkeypatch.setattr(startup, "_ready", True)
        with acct.phase("server/step", step=True):
            with acct.phase("engine/prefill_launch", "engine"):
                # the engine says what it was last asked to run: the shape
                # a program built in here was built for
                acct.doing = lambda: ("newest prefill call 8 rows x 1024 "
                                      "tokens x 64 blocks a row")
                startup._on_duration(startup._COMPILE_EVENT, 11.8,
                                     fun_name="jit(prefill)")
                acct.doing = None
            with acct.phase("engine/decode_stage", "engine"):
                startup._on_duration(startup._COMPILE_EVENT, 0.25,
                                     fun_name="jit__apply_rows")
        startup._on_duration(startup._FETCH_EVENT, 0.004)
        startup._on_duration(startup._COMPILE_EVENT, 0.005,
                             fun_name="jit_fold_in")
    lines = [r.getMessage() for r in engine_log.records if late(r)]
    assert lines == [
        "compiled after ready: jit(prefill), 11.800 s, compiled; stepper in "
        "engine/prefill_launch (newest prefill call 8 rows x 1024 tokens x "
        "64 blocks a row)",
        "compiled after ready: jit__apply_rows, 0.250 s, compiled; "
        "stepper in engine/decode_stage",
        "compiled after ready: jit_fold_in, 0.005 s, fetched; "
        f"stepper in {ledger.STEPPER_BASE_PHASE}"]
    assert all(r.levelno == logging.INFO for r in engine_log.records
               if late(r))
    now = startup.compile_scalars()
    assert now["compilations"] == counted["compilations"] + 3
    assert now["compile_cache_hits"] == counted["compile_cache_hits"] + 1
    assert [p[0] for p in startup.recent_programs(3)] == [
        "jit(prefill)", "jit__apply_rows", "jit_fold_in"]


def test_the_callers_time_between_two_steps_is_no_stall(scripted, engine_log):
    """An engine driven without a server: what its caller does between two
    steps is outside every phase, which is a wait and not the round's."""
    eng, acct, wall, _ = _ticking_engine()
    eng.submit([5, 6, 7], SamplingParams(max_tokens=8, temperature=0.0))
    with engine_log.at_level(logging.INFO, logger="dlti_tpu"):
        eng.step()
        wall.jump(3.0)
        eng.step()
    assert acct.seconds()[ledger.STEPPER_BASE_PHASE] > 3.0
    assert acct.stall_seconds() == 0.0 and not any(acct.stalls().values())
    assert ledger.STEPPER_BASE_PHASE not in acct.stalls()
    assert not [r for r in engine_log.records if "stepper" in r.getMessage()]


# -- what a stream lost to a prefill -------------------------------------------

def test_a_prefill_of_wall_w_with_n_decoding_slots_adds_n_times_w(scripted):
    eng, acct, wall, _ = _ticking_engine()
    sp = SamplingParams(max_tokens=30, temperature=0.0)
    first = [eng.submit(p, sp) for p in ([5, 6, 7], [9, 10])]
    eng.step()   # both admitted in one call: nobody was decoding yet
    assert eng.stats["decode_stream_stall_seconds_prefill"] == 0.0
    # launch in, launch out, wait in, wait out: the call's wall is 3 ticks
    assert eng._prefill_wall_s == 3 * TICK
    eng.step()
    late = eng.submit([20, 21, 22], sp)
    eng.step()   # its prefill call makes two decoding streams wait
    assert eng._prefill_wall_s == 6 * TICK
    assert eng.stats["decode_stream_stall_seconds_prefill"] == 2 * 3 * TICK
    while eng.has_work:
        eng.step()
    # each of the two first requests stood still for the late one's call;
    # the late one for nobody's
    assert [r.prefill_stall_s for r in first] == [3 * TICK, 3 * TICK]
    assert late.prefill_stall_s == 0.0
    assert all(r._prefill_stall_mark is None for r in (*first, late))


def test_decode_prefill_stall_plus_decode_is_the_old_decode():
    from dlti_tpu.serving.engine import Request

    def req(stall):
        r = Request("r", [1, 2, 3], arrival_time=10.0)
        r.admitted_time, r.first_token_time, r.finish_time = 10.5, 11.0, 15.0
        r.finish_reason = "length"
        r.prefill_stall_s = stall
        return r

    old = request_breakdown(req(0.0))["phases"]
    assert "decode_prefill_stall" not in old and old["decode"] == 4.0
    new = request_breakdown(req(1.25))["phases"]
    assert new["decode_prefill_stall"] == 1.25
    assert new["decode_prefill_stall"] + new["decode"] == old["decode"]
    assert sum(new.values()) == sum(old.values()) == 5.0
    # never more than the decode there was
    capped = request_breakdown(req(9.0))["phases"]
    assert capped["decode_prefill_stall"] == 4.0 and capped["decode"] == 0.0
    assert "decode_prefill_stall" in ledger.REQUEST_PHASES


# -- the collector --------------------------------------------------------------

def test_the_gc_hook_books_a_forced_collection_and_is_gone_after_shutdown():
    tr = SpanTracer(enabled=True)
    pause0, count0 = ledger.gc_totals()
    install_gc_hook(tr)
    install_gc_hook(tr)   # idempotent
    try:
        assert gc.callbacks.count(ledger._GC_BOOK) == 1
        gc.collect(2)
    finally:
        remove_gc_hook()
    assert ledger._GC_BOOK not in gc.callbacks
    pause, count = ledger.gc_totals()
    assert count[2] == count0[2] + 1 and pause[2] > pause0[2]
    # (the spans were parked by the hook and reached the ring at its removal)
    spans = [e for e in tr.events() if e["name"] == "gc/collect"
             and e["args"]["generation"] == 2]
    assert len(spans) == 1 and "collected" in spans[0]["args"]
    assert 'generation="2"' in ledger.gc_pause_seconds_total.samples()[-1][1]
    gc.collect(2)   # unhooked: nothing more is booked
    assert ledger.gc_totals()[1][2] == count[2]
    assert (ledger.gc_pause_seconds_total.name,
            ledger.gc_collections_total.name) == GC_METRIC_NAMES


def test_a_collection_inside_the_rings_lock_waits_for_nothing():
    """A collection starts at any bytecode boundary, also on a thread that
    holds the ring's lock (inside ``SpanTracer._append``): the hook must
    take no lock there. (One ring-on chip run in nine stood still for good
    in its warm-up before this held.)"""
    tr = SpanTracer(enabled=True)
    install_gc_hook(tr)
    done = []

    def collect_under_the_lock():
        with tr._lock:
            gc.collect(0)
        done.append(True)

    try:
        t = threading.Thread(target=collect_under_the_lock, daemon=True)
        t.start()
        t.join(timeout=20)
        assert done, "the hook waited for the ring's lock its thread held"
        assert not [e for e in tr.events() if e["name"] == "gc/collect"]
        assert ledger._GC_BOOK.parked
        # the stepper's account moves them into the ring at a marked step
        acct = StepperAccount(tr, clock=Ticks(TICK), cpu_clock=Ticks(0.0))
        with acct.phase("server/step", step=True):
            pass
        assert not ledger._GC_BOOK.parked
        spans = [e for e in tr.events() if e["name"] == "gc/collect"]
        assert spans and spans[0]["tid"] == t.ident & 0x7FFFFFFF
    finally:
        remove_gc_hook()


# -- the profiler's start mark --------------------------------------------------

def test_profiler_start_carries_the_steppers_open_phase(monkeypatch):
    import jax.profiler

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda *a, **k: None)
    tr = SpanTracer()
    clock = Ticks()
    acct = StepperAccount(tr, clock=clock, cpu_clock=Ticks(0.0))
    acct.bind()
    with acct.phase("server/step"):
        with acct.phase("engine/decode_wait", "engine", DEVICE_WAIT):
            began = acct.last
            tr.start_capture("unused")
            tr.stop_capture()
    start = tr.events()[0]
    assert start["name"] == "profiler/start"
    assert start["args"] == {"stepper_phase": "engine/decode_wait",
                             "stepper_phase_since_us": began * 1e6}
    # the phases open at that moment began as no-ops: they are in no trace
    assert [e["name"] for e in tr.events()] == ["profiler/start",
                                                "profiler/stop"]


# -- on /metrics, with the tracer off -------------------------------------------

def _scrape(port):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    out = {}
    for line in text.splitlines():
        if not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def _stream(port, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read().decode()
    conn.close()
    return resp.status, data


def test_every_new_series_is_on_metrics_with_the_tracer_disabled(scripted):
    assert not get_tracer().enabled
    engine = _engine(max_model_len=128)
    httpd, async_engine = make_server(
        engine, ByteTokenizer(),
        ServerConfig(host="127.0.0.1", port=0,
                     default_params=SamplingParams(max_tokens=8)))
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    install_gc_hook(get_tracer())
    try:
        before = _scrape(port)
        status, data = _stream(port, {"prompt": "hello", "max_tokens": 80,
                                      "temperature": 0.0, "stream": True})
        assert status == 200 and "[DONE]" in data
        gc.collect(2)
        after = _scrape(port)
        slow = json.loads(_get_json(port, "/debug/slow"))
    finally:
        remove_gc_hook()
        httpd.shutdown()
        async_engine.shutdown()
        httpd.server_close()
    phases = {k.split('"')[1]: v for k, v in after.items()
              if k.startswith(STEPPER_METRIC_NAMES[0] + "{")}
    assert {"server/lock_wait", "server/wait_work", "server/step",
            "server/drain_events", ledger.STEPPER_BASE_PHASE,
            "engine/admit", "engine/prefill_launch", "engine/prefill_wait",
            "engine/decode_prep", *PREP_PARTS, "engine/decode_launch",
            "engine/decode_wait", "engine/decode_emit"} <= set(phases)
    assert (f'{STEPPER_METRIC_NAMES[1]}'
            f'{{phase="engine/decode_stage",kind="host"}}') in after
    # what each phase is to the thread is on its series, as declared where
    # it is entered: a reader sums by the label and keeps no list of names
    kinds = {k.split('"')[1]: k.split('"')[3] for k in after
             if k.startswith(STEPPER_METRIC_NAMES[0] + "{")}
    waits = {"server/wait_work": "wait", ledger.STEPPER_BASE_PHASE: "wait",
             "engine/decode_wait": "device_wait",
             "engine/prefill_wait": "device_wait"}
    assert kinds == {name: waits.get(name, "host") for name in kinds}
    for name in (*STEPPER_METRIC_NAMES[2:6], STEPPER_METRIC_NAMES[7],
                 "dlti_sse_handler_cpu_seconds_total", "dlti_sse_events_total",
                 "dlti_decode_stream_stall_seconds_prefill",
                 f'{GC_METRIC_NAMES[0]}{{generation="2"}}',
                 f'{GC_METRIC_NAMES[1]}{{generation="2"}}'):
        assert name in after, name
    # every host phase entered so far is there (at 0 unless this machine
    # stood still), the waits never
    stalls = {k: v for k, v in after.items()
              if k.startswith(STEPPER_METRIC_NAMES[6] + "{")}
    assert f'{STEPPER_METRIC_NAMES[6]}{{phase="engine/decode_emit"}}' in stalls
    assert not [k for k in stalls if "decode_wait" in k or "wait_work" in k]
    assert not [k for k in after if "host_prep" in k]
    # 80 tokens and the end went over the handler's queue: one whole block
    # of 64 is booked, with the thread's CPU up to its 64th event
    assert after["dlti_sse_events_total"] \
        - before["dlti_sse_events_total"] == 64
    assert after["dlti_sse_handler_cpu_seconds_total"] \
        > before["dlti_sse_handler_cpu_seconds_total"]
    assert after["dlti_stepper_cpu_seconds_total"] > 0
    # as of the last marked step's entry: behind the live totals
    assert 0 < after["dlti_stepper_marked_host_seconds_total"] <= sum(
        v for k, v in after.items()
        if k.startswith(STEPPER_METRIC_NAMES[0] + "{") and 'kind="host"' in k)
    assert 0 < after["dlti_stepper_marked_decode_steps_total"] \
        <= after["dlti_decode_steps"]
    # conservation, as scraped: the phases' growth over the window is the
    # stepper's wall over it (to the phase open at either scrape)
    acct = async_engine.account
    assert sum(acct.seconds().values()) == pytest.approx(acct.wall())
    assert sum(phases.values()) > sum(
        v for k, v in before.items()
        if k.startswith(STEPPER_METRIC_NAMES[0] + "{"))
    # and the request ledger's phase catalog names the new phase
    assert "decode_prefill_stall" in slow["phases"]
    assert slow["retained"] == 1


def _get_json(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    data = conn.getresponse().read().decode()
    conn.close()
    return data


# -- the trainer's loop ---------------------------------------------------------

def test_the_trainers_bookkeeping_is_a_span_between_sync_and_the_next_fetch(
        tracer):
    import numpy as np

    from dlti_tpu.config import (
        CheckpointConfig, Config, DataConfig, LoRAConfig, TrainConfig)
    from dlti_tpu.training import Trainer

    cfg = Config(
        model=CFG, lora=LoRAConfig(enabled=False),
        data=DataConfig(max_seq_len=16),
        checkpoint=CheckpointConfig(save_strategy="no"),
        train=TrainConfig(num_epochs=1, micro_batch_size=2,
                          grad_accum_steps=1, max_steps=3, logging_steps=1))
    rng = np.random.default_rng(0)
    ids = [rng.integers(1, 500, (1, 2, 16), dtype=np.int32) for _ in range(4)]
    Trainer(cfg).train(
        batches_per_epoch=[{"input_ids": a, "labels": a} for a in ids])
    order = [e["name"] for e in sorted(
        (e for e in tracer.events() if e.get("ph") == "X"),
        key=lambda e: e["ts"])
        if e["name"] in ("train/device_sync", "train/bookkeep",
                         "train/batch_fetch")]
    assert order.count("train/bookkeep") == 3
    for i, name in enumerate(order):
        if name == "train/bookkeep":
            assert order[i - 1] == "train/device_sync"
            assert order[i + 1] == "train/batch_fetch"
